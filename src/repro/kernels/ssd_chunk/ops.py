"""Jit'd wrapper for the SSD chunk kernel."""
from __future__ import annotations

from repro.kernels import _compat
from . import kernel as K


def ssd_pallas(xd, log_a, Bm, Cm, chunk: int, *,
               interpret: bool | None = None):
    """Same contract as repro.models.ssm.ssd_chunked.

    xd: (b, s, nh, hp) inputs pre-scaled by dt; log_a: (b, s, nh);
    Bm/Cm: (b, s, g, n).  Returns (y (b, s, nh, hp), hT (b, nh, n, hp))
    -- note hT is (n, hp)-ordered; transpose to match SSMCache.h's
    (hp, n) if feeding the decode path.
    """
    interpret = _compat.resolve_interpret(interpret)
    b, s, nh, hp = xd.shape
    g, n = Bm.shape[2], Bm.shape[3]
    call = K.ssd_call(b, s, nh, hp, g, n, min(chunk, s), xd.dtype,
                      interpret)
    return call(xd, log_a, Bm, Cm)
