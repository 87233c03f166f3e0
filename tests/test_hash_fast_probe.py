"""The numeric hash kernel's two-row insert and its per-row fallback,
against a plain scan of the table the probe loop builds.

The kernel inserts each partial product into the two table rows its probe
visits first, on the vector unit, and redoes a row by the probe loop
where some insert needed a third.  Either way its raw ``(cols, vals)``
must equal, bit for bit, the probe loop's table scanned in slot order
(``test_hash_flush._scan``).  Schedules are made by hand: keys are chosen
by the slot they hash to, and keys equal modulo a table size share a slot
(and a chunk) in it.  Values are dyadic, so every sum is exact.

Cases: tables of one row, narrower than a row and a whole row, whose
probes wrap to lanes before the hash slot (the second row *is* the first,
so the two writes must merge), with hits there; a two-row table near
full, where linear probing runs past both rows; a fallback row as a bin's
last row, and as the last row the stream pushes, with B rows that refill
the window while the row is redone; and a ``vmap``ped batch whose members
fall back in different rows.  The counted kernel counts the rows redone.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.spgemm_hash import kernel as K
from repro.kernels.spgemm_hash import ops as hash_ops
from test_hash_flush import _key, _operands, _scan
from test_kernel_counters import _probe


def _same(slot, tsz, n, stride=1 << 12):
    """``n`` distinct keys that all hash to ``slot`` of a ``tsz``-slot
    table (and of every table up to ``stride`` slots whose slot it is)."""
    return [_key(slot, tsz) + stride * j for j in range(n)]


def _spread(n, start=0):
    """``n`` distinct keys spread over the table."""
    return [3 * (start + t) + 1 for t in range(n)]


# case: bins, each (tsz, rows of A, each a list of B rows of keys)
CASES = {
    # five keys of slot 62 of 64 take 62, 63 and wrap to lanes 0-2; a
    # later key of slot 0 lands behind them, and a second B row hits the
    # wrapped lanes
    "one_row_wrap_64": [(64, [[_same(62, 64, 5) + [_key(0, 64)],
                               _same(62, 64, 5)[2:] + [_key(0, 64)]]])],
    "one_row_wrap_128": [(128, [[_same(126, 128, 6) + [_key(1, 128)],
                                 _same(126, 128, 6)[::-1]],
                                [_same(127, 128, 3)]])],
    # 200 keys of slot 100 fill 100..255 and wrap: linear probing takes a
    # third step (row 0 again, lanes before 100); the row after is plain
    "two_rows_near_full": [(256, [[_same(100, 256, 200),
                                   _same(100, 256, 200)[150:]],
                                  [_spread(50)]])],
    # 300 keys of one slot outgrow two rows under both probings
    "fallback_last_in_bin": [(512, [[_spread(200)], [_same(5, 512, 300)]]),
                             (128, [[_spread(60, 7)]])],
    # the stream's last push is a fallback row whose B rows straddle a
    # B window (1,024 entries), so the redo reads them in again
    "fallback_last_pushed": [(1024, [[_spread(450)],
                                     [_spread(400, 500),
                                      _same(9, 1024, 300)]])],
}

#: rows of A whose probes need a third step, per case and probing
#: (``hash``, ``hash_vector``): where the kernel must fall back
FALLS_BACK = {
    "one_row_wrap_64": ((), ()),
    "one_row_wrap_128": ((), ()),
    "two_rows_near_full": ((0,), ()),
    "fallback_last_in_bin": ((1,), (1,)),
    "fallback_last_pushed": ((1,), (1,)),
}


def _third_steps(bins, ops, vector):
    """Rows of A where the probe loop takes more than two steps."""
    _, _, ip_a, bs, bl, _, b_idx, _ = ops
    rows, i = [], 0
    for tsz, a_rows in bins:
        for _ in a_rows:
            table = [[K.EMPTY] * K.LANES for _ in range(max(tsz >> 7, 1))]
            steps = [_probe(table, int(b_idx[t]), tsz, vector)
                     for j in range(ip_a[i], ip_a[i + 1])
                     for t in range(bs[j], bs[j] + bl[j])]
            if max(steps) > 2:
                rows.append(i)
            i += 1
    return tuple(rows)


def _want(bins, ops, vector):
    want = [e for t in _scan(bins, ops, vector) for row in t for e in row]
    return (np.array([k for _, k, _ in want], np.int32),
            np.array([v for _, _, v in want], np.float32))


def _call(bins, ops, vector, cap_c, counters=False):
    _, bin_tsize, ip_a, bs, _, _, b_idx, _ = ops
    return hash_ops._entry(True, len(bins), len(ip_a) - 1, len(bs),
                           len(b_idx), cap_c, int(bin_tsize.max()), vector,
                           True, counters)


PARAMS = [(c, v) for c in sorted(CASES) for v in (False, True)]


@pytest.mark.parametrize(
    "case,vector", PARAMS,
    ids=[f"{c}-{'hash_vector' if v else 'hash'}" for c, v in PARAMS])
def test_two_row_insert_equals_the_probe_loop(case, vector):
    bins = CASES[case]
    ops = _operands(bins)
    assert _third_steps(bins, ops, vector) == FALLS_BACK[case][vector]
    cols, vals = _want(bins, ops, vector)
    got_c, got_v = _call(bins, ops, vector, len(cols))(*ops)
    assert np.asarray(got_c).tobytes() == cols.tobytes()
    assert np.asarray(got_v).tobytes() == vals.tobytes()
    # the counted kernel redoes the same rows and writes the same bits
    c_c, c_v, cnt = _call(bins, ops, vector, len(cols), True)(*ops)
    assert np.asarray(c_c).tobytes() == cols.tobytes()
    assert np.asarray(c_v).tobytes() == vals.tobytes()
    redo = int(np.asarray(cnt)[K.COUNTERS.index("redo_rows")])
    assert redo == len(FALLS_BACK[case][vector])


@pytest.mark.parametrize("tsz,slot", [(64, 62), (128, 126)])
def test_one_row_table_wraps_before_the_hash_slot(tsz, slot):
    """The wrap cases hold what they are named for: linear probing puts
    keys of ``slot`` on lanes before it, in a table of one row."""
    bins = CASES[f"one_row_wrap_{tsz}"]
    ops = _operands(bins)
    tables = _scan(bins, ops, False)
    lanes = [ln for t in tables for row in t for ln, k, _ in row
             if (k * K.HASH_CONST) % 2 ** 32 % tsz == slot]
    assert any(ln < slot for ln in lanes) and slot in lanes


@pytest.mark.parametrize("vector", (False, True),
                         ids=("hash", "hash_vector"))
def test_batched_members_fall_back_in_different_rows(vector):
    """Two members of the batched grid share A and differ in B's keys:
    member 0 falls back in row 0, member 1 in row 1."""
    same, spread = _same(5, 512, 300), _spread(300)
    members = [[(512, [[same], [spread]])], [(512, [[spread], [same]])]]
    ops = [_operands(bins) for bins in members]
    for e, (bins, o) in enumerate(zip(members, ops)):
        assert _third_steps(bins, o, vector) == (e,)
    want = [_want(bins, o, vector) for bins, o in zip(members, ops)]
    offsets, bin_tsize, ip_a, bs, bl, a_val, _, b_val = ops[0]
    call = _call(members[0], ops[0], vector, len(want[0][0]))
    b_idx = jnp.asarray(np.stack([o[6] for o in ops]))
    got_c, got_v = jax.vmap(
        lambda bi: call(offsets, bin_tsize, ip_a, bs, bl, a_val, bi,
                        b_val))(b_idx)
    for e, (cols, vals) in enumerate(want):
        assert np.asarray(got_c[e]).tobytes() == cols.tobytes()
        assert np.asarray(got_v[e]).tobytes() == vals.tobytes()
