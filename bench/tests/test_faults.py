"""``correct`` comes out false when the timed path is broken underneath,
and true when it is not.

Each case drives a whole run of a cell on the CPU at R-MAT scale 9
(``drive.py``) in a process of its own, so the four-chip cell can have
four virtual devices.  The faults are the ones these cells can have: the
previous product's answer returned (state unchanged), half of the rows
left out, one shard's rows never gathered (the exchange between chips
left out), and one value altered by a relative 1e-3 where it is
produced.  The control is the same run handed bfloat16 values.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SCALE = "9"

ONE_CHIP = ("stale", "half", "altered")
CASES = ([(c, f) for c in ("g500_s13.repeat", "er_s15.repeat",
                           "g500_s13.oneshot") for f in ONE_CHIP]
         + [("g500_s14_rows4.repeat", f)
            for f in ("stale", "half", "exchange", "altered")])
CELLS = ("g500_s13.repeat", "er_s15.repeat", "g500_s13.oneshot",
         "g500_s14_rows4.repeat")


def drive(cell, fault, dtype=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if "rows4" in cell:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    cmd = [sys.executable, os.path.join(HERE, "drive.py"), cell, SCALE,
           fault] + ([dtype] if dtype else [])
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = drive(cell, "none")
    assert r["correct"], r
    assert r["checks"]["rows_off"]["value"] == 0


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(cell, fault):
    r = drive(cell, fault)
    assert not r["correct"], r
    assert r["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell):
    r = drive(cell, "none", "bfloat16")
    assert not r["correct"], r
    c = r["checks"]["value_rel_err"]
    assert c["value"] > c["limit"]
