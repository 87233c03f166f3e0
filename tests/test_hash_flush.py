"""The numeric hash kernel's flush, against a plain scan of its table.

The kernel writes each row of C as its hash table holds it: slots in
order, row by row, lanes in order.  Here the table is rebuilt in Python
(the same multiply hash and probe order as the kernel,
``test_kernel_counters._probe``) and scanned slot by slot; the kernel's
``(cols, vals)`` must equal that scan bit for bit.  Schedules are made by
hand, so each case places its entries where it wants them: a key is
chosen by the linear-probing slot it hashes to.  Values are dyadic, so
every sum is exact whatever order or fusion the backend uses.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.spgemm_hash import kernel as K
from repro.kernels.spgemm_hash import ops as hash_ops
from test_kernel_counters import _probe

#: the multiply hash's inverse: key ``slot * _INV % tsz`` hashes to ``slot``
_INV = pow(K.HASH_CONST % 2 ** 32, -1, 2 ** 32)


def _key(slot, tsz):
    return slot * _INV % tsz


def _keys(i, n, tsz=K.LANES):
    """``n`` distinct keys of a ``tsz``-slot table, varied by row ``i``."""
    return [(i * 37 + t) % tsz for t in range(n)]


def _overlapping(keys):
    """One row of A as two B rows that share the middle of ``keys``, so
    some slots sum two products."""
    h = len(keys) // 2
    return [keys[:h + len(keys) // 4], keys[h - len(keys) // 4:]]


# case: bins, each (tsz, rows of A, each a list of B rows of keys)
CASES = {
    "full_row": [(128, [_overlapping(_keys(0, 128))])],
    "lane_127_alone": [(1024, [[[_key(127, 1024), _key(3 * 128 + 5, 1024)]]])],
    # the second bin's table is one row, over rows the first bin filled
    "empty_rows_between": [
        (2048, [[[_key(s, 2048) for s in (0, 5 * 128 + 5, 15 * 128 + 15,
                                          5 * 128 + 6)]]]),
        (128, [_overlapping(_keys(1, 40)), [_keys(2, 30)]])],
    # 100 entries a row: the 21st row's table row crosses the first stage
    # boundary and is the stream's last, so it closes from the spare row
    "straddles_stage": [(128, [_overlapping(_keys(i, 100))
                               for i in range(21)])],
    # 16 full rows fill the stage exactly, then a row opens the next one
    "fills_stage_exactly": [(128, [[_keys(i, 128)] for i in range(16)]
                             + [[_keys(16, 5)]])],
}


def _operands(bins):
    """The kernel's flat operands for ``bins``: schedule, A entries
    pre-gathered to the B rows they select, B entries."""
    ip_a, bs, bl, b_idx = [0], [], [], []
    for _, rows in bins:
        for b_rows in rows:
            for keys in b_rows:
                bs.append(len(b_idx))
                bl.append(len(keys))
                b_idx.extend(keys)
            ip_a.append(len(bs))
    offsets = np.cumsum([0] + [len(rows) for _, rows in bins])
    a_val = (np.arange(len(bs)) % 3 + 1) / 2
    b_val = (np.arange(len(b_idx)) % 7 + 1) / 4
    i32 = lambda x: np.asarray(x, np.int32)          # noqa: E731
    return (i32(offsets), i32([t for t, _ in bins]), i32(ip_a), i32(bs),
            i32(bl), np.float32(a_val), i32(b_idx), np.float32(b_val))


def _scan(bins, ops, vector):
    """Per row of A, its table as ``[(lane, key, value), ...]`` per table
    row, built by the kernel's probing and scanned in slot order."""
    _, _, ip_a, bs, bl, a_val, b_idx, b_val = ops
    tables, i = [], 0
    for tsz, rows in bins:
        n_rows = max(tsz // K.LANES, 1)
        for _ in rows:
            keys = [[K.EMPTY] * K.LANES for _ in range(n_rows)]
            vals = np.zeros((n_rows, K.LANES), np.float32)
            for j in range(ip_a[i], ip_a[i + 1]):
                for t in range(bs[j], bs[j] + bl[j]):
                    key = int(b_idx[t])
                    _probe(keys, key, tsz, vector)
                    r = next(r for r in range(n_rows) if key in keys[r])
                    vals[r, keys[r].index(key)] += a_val[j] * b_val[t]
            tables.append([[(ln, k, vals[r, ln]) for ln, k in enumerate(row)
                            if k != K.EMPTY] for r, row in enumerate(keys)])
            i += 1
    return tables


def _holds(case, tables):
    """The layout ``case`` is named for is in the scanned tables."""
    flat = [row for t in tables for row in t]
    ends = np.cumsum([len(row) for row in flat if row])
    starts = ends - [len(row) for row in flat if row]
    if case == "full_row":
        return any(len(row) == K.LANES for row in flat)
    if case == "lane_127_alone":
        return any([ln for ln, _, _ in row] == [K.LANES - 1] for row in flat)
    if case == "empty_rows_between":
        return any(not t[r] and any(t[:r]) and any(t[r + 1:])
                   for t in tables for r in range(len(t)))
    if case == "straddles_stage":
        return starts[-1] < K.WIN_OUT < ends[-1]
    assert case == "fills_stage_exactly"
    return K.WIN_OUT in ends and ends[-1] > K.WIN_OUT


#: (case, vector, batched): every case under both probings, but chunked
#: probing fills a row from lane 0, so lane 127 alone is linear's; and a
#: long stream cut in the batched grid
PARAMS = [(c, v, False) for c in sorted(CASES) for v in (False, True)
          if not (c == "lane_127_alone" and v)] + \
    [("straddles_stage", v, True) for v in (False, True)]


@pytest.mark.parametrize(
    "case,vector,batched", PARAMS,
    ids=[f"{c}-{'hash_vector' if v else 'hash'}{'-cut' if b else ''}"
         for c, v, b in PARAMS])
def test_flush_writes_the_table_in_slot_order(case, vector, batched):
    """``cut``: three members of the batched grid, each with its own
    values, whose streams are cut at ``end`` one stage into the next."""
    bins = CASES[case]
    ops = _operands(bins)
    tables = _scan(bins, ops, vector)
    assert _holds(case, tables), case
    want = [e for t in tables for row in t for e in row]
    cols = np.array([k for _, k, _ in want], np.int32)
    vals = np.array([v for _, _, v in want], np.float32)
    offsets, bin_tsize, ip_a, bs, bl, a_val, b_idx, b_val = ops
    table_size = int(bin_tsize.max())
    cap_c = K.WIN_OUT if batched else len(want)
    call = hash_ops._entry(True, len(bins), len(ip_a) - 1, len(bs),
                           len(b_idx), cap_c, table_size, vector, True)
    run = lambda av: call(offsets, bin_tsize, ip_a, bs, bl, av,  # noqa: E731
                          b_idx, b_val)
    if not batched:
        got_c, got_v = run(jnp.asarray(a_val))
        assert np.asarray(got_c).tobytes() == cols.tobytes()
        assert np.asarray(got_v).tobytes() == vals.tobytes()
        return
    assert len(want) > cap_c
    scale = np.float32([1, 2, 4])
    got_c, got_v = jax.vmap(run)(jnp.asarray(scale[:, None] * a_val))
    for e in range(len(scale)):
        assert np.asarray(got_c[e]).tobytes() == cols[:cap_c].tobytes()
        assert np.asarray(got_v[e]).tobytes() == \
            (scale[e] * vals[:cap_c]).tobytes()
