"""``shard_flop_imbalance`` on a hand-made partition of the 4x4 matrix of
``test_work.py`` (row flop 4, 3, 2, 0)."""
import importlib.util
import os

import numpy as np

import workload

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Run:
    def __init__(self, wl):
        self.workload = wl


def _workload(row_starts):
    wl = object.__new__(workload.Workload)
    wl.graphs = [workload.Graph(np.array([0, 2, 4, 5, 5]),
                                np.array([0, 1, 0, 2, 1]), 4)]
    wl.shards = len(row_starts) - 1
    wl.row_starts = row_starts
    return wl


def test_two_shards():
    # shards hold 4 + 3 = 7 and 2 + 0 = 2 partial products: 7 / 4.5
    read = _reader("shard_flop_imbalance")
    assert abs(read(_Run(_workload((0, 2, 4)))) - 7 / 4.5) < 1e-12


def test_balanced_partition_reads_one():
    read = _reader("shard_flop_imbalance")
    # rows {0}, {1, 2, 3}: 4 and 5 -> 5 / 4.5; rows {0}, {1}, {2, 3}:
    # 4, 3, 2 -> 4 / 3
    assert abs(read(_Run(_workload((0, 1, 4)))) - 5 / 4.5) < 1e-12
    assert abs(read(_Run(_workload((0, 1, 2, 4)))) - 4 / 3) < 1e-12


def test_one_chip_reads_nothing():
    read = _reader("shard_flop_imbalance")
    assert read(_Run(_workload((0, 4)))) is None
