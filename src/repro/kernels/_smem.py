"""SMEM windows over HBM operands, for kernels that walk CSR arrays.

A TPU kernel may read and write single scalars only in SMEM, and SMEM is
1 MiB on v5e, so a CSR operand of real size stays in HBM
(``memory_space=pl.ANY``) and the kernel sees it through a small SMEM
window that a DMA refills when an index leaves it.  Output that is
produced one scalar at a time goes out the same way: through an SMEM
staging window that is DMA'd to HBM when full (:class:`Stream`).  Output
produced a 128-lane vector at a time goes out through a VMEM stage of
whole rows instead (:class:`RowStream`), so its entries are never taken
out of the vector one scalar at a time.

1-D int32/f32 arrays in HBM are tiled by ``ALIGN`` words, so every DMA
here starts at a multiple of ``ALIGN`` and moves a whole number of tiles;
callers pad operands with :func:`padded_len`.

All three take an optional ``counter = (counter_ref, slot)``: an SMEM int32
vector whose word ``slot`` counts the DMAs they issue (window refills,
stream windows written).  Without it they emit no counting code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: HBM tiling of 1-D int32/f32 arrays: DMA starts and sizes are multiples.
ALIGN = 1024


def padded_len(n: int, win: int) -> int:
    """Length an operand of ``n`` words is padded to: whole ``ALIGN`` tiles
    and at least one window, so every window DMA stays in bounds."""
    return max(-(-n // ALIGN) * ALIGN, win)


def _copy_all(pairs, sems, sem0):
    copies = [pltpu.make_async_copy(src, dst, sems.at[sem0 + k])
              for k, (src, dst) in enumerate(pairs)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def count(counter, n=1):
    """Add ``n`` to word ``slot`` of ``counter = (counter_ref, slot)``;
    nothing where ``counter`` is None."""
    if counter is not None:
        ref, slot = counter
        ref[slot] = ref[slot] + n


class Window:
    """A read window of one or more same-indexed HBM arrays in SMEM.

    ``base_ref[slot]`` (an SMEM scalar) holds the flat index of the
    window's first word; ``sems[sem0:sem0 + len(hbm)]`` are its DMA
    semaphores; ``win`` is a power-of-two multiple of ``ALIGN``;
    ``counter`` counts refills (module docstring).
    """

    def __init__(self, hbm, smem, base_ref, slot, sems, sem0, win,
                 counter=None):
        self.hbm, self.smem, self.win = hbm, smem, win
        self.base_ref, self.slot = base_ref, slot
        self.sems, self.sem0 = sems, sem0
        self.counter = counter

    def reset(self):
        """Mark the window empty (its next read loads)."""
        self.base_ref[self.slot] = jnp.int32(-2 * self.win)

    def read(self, g, last):
        """Values at flat index ``g``.  When ``g`` lies outside the window
        it is first re-loaded from the ``ALIGN`` tile holding ``g``,
        clamped to start at most at ``last`` (the end of the caller's
        padded slice minus ``win``)."""
        base = self.base_ref[self.slot]

        @pl.when((g < base) | (g >= base + self.win))
        def _load():
            start = pl.multiple_of(
                jnp.minimum(g & jnp.int32(-ALIGN), last), ALIGN)
            _copy_all([(h.at[pl.ds(start, self.win)], s)
                       for h, s in zip(self.hbm, self.smem)],
                      self.sems, self.sem0)
            self.base_ref[self.slot] = start
            count(self.counter)

        off = (g - self.base_ref[self.slot]) & jnp.int32(self.win - 1)
        return [s[off] for s in self.smem]


class Stream:
    """An append-only output stream to HBM through SMEM staging windows.

    ``state_ref[0]`` is the fill of the staging window and
    ``state_ref[1]`` the (``win``-aligned) HBM position of its first word.
    A full window is DMA'd out before the next push; :meth:`flush` writes
    the open window whole, so the HBM arrays must be padded to whole
    windows.  ``end`` (a multiple of ``win``) bounds the stream: windows
    that would start at or past it are never written, so a stream longer
    than its HBM slice is truncated to the slice.  ``counter`` counts the
    windows written (module docstring).
    """

    def __init__(self, hbm, stage, state_ref, sems, sem0, win, end,
                 counter=None):
        self.hbm, self.stage, self.state = hbm, stage, state_ref
        self.sems, self.sem0, self.win, self.end = sems, sem0, win, end
        self.counter = counter

    def start(self, pos):
        """Open the stream at HBM position ``pos`` (a multiple of ``win``)."""
        self.state[0] = jnp.int32(0)
        self.state[1] = pos

    def flush(self):
        """Write the open staging window to HBM, unless it lies past
        ``end``."""
        base = pl.multiple_of(self.state[1], self.win)

        @pl.when(base < self.end)
        def _write():
            _copy_all(self._window(base), self.sems, self.sem0)
            count(self.counter)

    def _window(self, base):
        """``(stage, HBM slice)`` pairs of the window at word ``base``."""
        return [(s, h.at[pl.ds(base, self.win)])
                for h, s in zip(self.hbm, self.stage)]

    def push(self, *vals):
        """Append one value to each of the stream's arrays."""
        @pl.when(self.state[0] == self.win)
        def _full():
            self.flush()
            self.state[0] = jnp.int32(0)
            self.state[1] = self.state[1] + self.win

        fill = self.state[0]
        for s, v in zip(self.stage, vals):
            s[fill & (self.win - 1)] = v
        self.state[0] = fill + 1


class RowStream(Stream):
    """An append-only output stream to HBM through a VMEM stage of
    ``LANES``-wide rows, fed a row of values at a time.

    Same contract as :class:`Stream`, but each stage is a ``(win // LANES
    + 1, LANES)`` VMEM ref and each HBM array is viewed as ``(len // LANES,
    LANES)``.  :meth:`push` appends the first ``n`` lanes of a ``(1,
    LANES)`` vector: rotated to the fill's lane, masked into the fill's row
    and stored whole into the next, so the last row is spare.  A full
    window is written out before the next push, and the spare row carried
    to row 0; :meth:`close` ends the stream.
    """

    def __init__(self, hbm, stage, *args, **kwargs):
        super().__init__(hbm, stage, *args, **kwargs)
        self.lanes = stage[0].shape[1]
        self.rows = self.win // self.lanes

    def _window(self, base):
        """The stage less its spare row, against the HBM rows at word
        ``base``."""
        row0 = pl.multiple_of(base // self.lanes, self.rows)
        return [(s.at[pl.ds(0, self.rows)], h.at[pl.ds(row0, self.rows)])
                for h, s in zip(self.hbm, self.stage)]

    def _advance(self):
        self.flush()
        for s in self.stage:
            s[pl.ds(0, 1), :] = s[pl.ds(self.rows, 1), :]
        self.state[0] = self.state[0] - self.win
        self.state[1] = self.state[1] + self.win

    def push(self, n, *vals):
        """Append lanes ``[0, n)`` of each ``(1, LANES)`` vector in
        ``vals`` to the stream's arrays (``0 < n <= LANES``)."""
        @pl.when(self.state[0] >= self.win)
        def _full():
            self._advance()

        fill = self.state[0]
        row, lane0 = fill // self.lanes, fill % self.lanes
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, self.lanes), 1)
        for s, v in zip(self.stage, vals):
            v = pltpu.roll(v, lane0, 1)
            pltpu.store(s.at[pl.ds(row, 1)], v, mask=lane >= lane0)
            s[pl.ds(row + 1, 1), :] = v
        self.state[0] = fill + n

    def close(self):
        """Write what the stream still holds: a full window, then the
        window its spare row opens."""
        @pl.when(self.state[0] > self.win)
        def _full():
            self._advance()

        self.flush()
