"""Threshold + peak-clustering state machine for the Pallas search
kernels: the harmonic-peaks kernel (harmpeaks.py) runs it once per
harmonic level on blocks it sums in VMEM.

It replaces the find_peaks_device -> cluster_peaks_device pair
(ops/peaks.py) with ONE sequential pass per spectrum row. Reference
semantics preserved exactly: Thrust copy_if thresholding
(src/kernels.cu:384-416) followed by the identify_unique_peaks walk
(include/transforms/peakfinder.hpp:27-56), including the
lastidx-advances-only-on-new-max quirk.

Why a kernel: XLA's lax.top_k — the only fast sized-compaction
primitive — lowers on TPU to a full per-lane sort whose cost is
independent of k (~400 ms per search chunk at production shapes), and
the separate cluster scan pays another pass. Crossings are sparse
(hundreds per 65k-bin spectrum at a 9-sigma threshold), so a single
streaming pass that walks blocks sequentially and handles crossings
one at a time is ~10x cheaper, AND its output is CLUSTER peaks — the
compaction size no longer needs to cover raw crossings, so the
adaptive-size escalation only ever re-dispatches for cluster-count
overflow (rare).

Design: rows are processed in stripes of ``_SUB`` rows over a grid of
(row stripes, bin blocks) in sequential order, so for each stripe the
kernel sees blocks of ``_BLOCK`` bins left to right. The machine runs
as _SUB independent rows of (cursor, raw count, open, cpeak, cpeakidx,
lastidx) vectors living in VMEM scratch across grid steps. Per block:
vector threshold mask; a stripe whose block has no crossing pays only
the mask+check. Otherwise a while loop walks crossings oldest-first in
every row at once (masked min per sublane); cluster emissions write
the (_SUB, mx) output block through a one-hot select (no dynamic-index
stores).

Outputs per row and level: cluster idxs (mx,) i32 ascending padded
with ``nbins``; cluster snrs (mx,) f32 zero-padded; counts (2,) i32 =
(raw crossings, clusters). Matches the (idxs, snrs, ccounts)
convention of cluster_peaks_device; clusters beyond ``mx`` are
dropped but still counted (callers escalate on counts[1] > mx).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PEAKS_BLOCK = 4096
# bins per grid step (128-lane multiple); 4096 measured best on v5e
# (fewer grid steps beats the larger per-step vector work; r3 scan:
# 512/1024/8704/17408 give 112/94/99/135 ms vs 87 ms for 2048-4096 at
# production shapes). The search pads its spectra to a multiple of it
# (accel_search.spectrum_pad).
_BLOCK = PEAKS_BLOCK
# rows per stripe (multiple of the f32 sublane quantum 8): taller
# stripes cut the number of grid steps — the window-merged walk (r4)
# made the per-step fixed work (per-level threshold mask + count) the
# dominant cost, and it row-vectorises for free. 24 measured best on
# v5e with the harmonic mega-kernel (dense tutorial search 140.1 ->
# 113.3 ms device; 16 gives 119.9, 8 gives 140.1); 32+ fails the Mosaic
# compile. tests/test_tpu_compile.py compiles the kernel at 24 for a
# described v5e at the production shapes.
_SUB = 24
# the crossing walk covers the whole block at once: r3 walked 512-lane
# subblocks to shrink per-TRIP vector work, but with the r4
# window-merged walk trips are few and the per-subblock guards
# dominated instead (measured 41.1 -> 35.5 ms at the dense tutorial
# grid for the full block).
# unrolled machine steps per while-loop trip (the walk is trip-latency
# bound; each step is one close/emit + one window merge)
_WSTEPS = 2
_BIG = 1 << 30  # "no crossing" sentinel for the masked min reduction


def _level_machine(
    lvl, s, *, win_ref, idx_ref, snr_ref, cnt_ref, istate, fstate, mstate,
    b, nb, gidx, slot, mx, threshold, min_gap, scale,
):
    """One harmonic level's threshold + identify_unique_peaks walk for
    the current (stripe, block) grid step. ``s`` is the level's value
    block, computed in VMEM by the harmonic mega-kernel (harmpeaks.py). State
    lives in shared scratch columns [lvl*8, lvl*8+5); outputs go to
    slices [lvl*mx, (lvl+1)*mx) of idx/snr and [2*lvl, 2*lvl+2) of
    cnt."""
    c0 = lvl * 8  # this level's state column base
    o0, o1 = lvl * mx, (lvl + 1) * mx
    lo = win_ref[lvl, 0]
    hi = win_ref[lvl, 1]
    if scale != 1.0:
        s = s * jnp.float32(scale)
    mask = (gidx >= lo) & (gidx < hi) & (s > jnp.float32(threshold))
    cnt = jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)
    istate[:, c0 + 1 : c0 + 2] = istate[:, c0 + 1 : c0 + 2] + cnt

    def emit(do, cursor, cpeakidx, cpeak):
        hot = do & (slot == cursor) & (cursor < mx)
        idx_ref[:, o0:o1] = jnp.where(hot, cpeakidx, idx_ref[:, o0:o1])
        snr_ref[:, o0:o1] = jnp.where(hot, cpeak, snr_ref[:, o0:o1])

    @pl.when(jnp.max(cnt) > 0)
    def _(mask=mask, s=s, emit=emit, c0=c0):
        mstate[:] = mask.astype(jnp.int32)

        # walk the block's crossings left to right, so the cluster
        # machine sees the same ascending crossing sequence.
        #
        # WINDOW-MERGED walk (r4): the walk is TRIP-LATENCY-bound
        # (~8.7 us/trip regardless of vector width — r3 measured
        # subblock shrinking and block-size scans flat), so the
        # lever is trip COUNT. Each trip processes the first
        # remaining crossing through the full close/emit/take
        # machine, then MERGES every further crossing j in the
        # close-free window (idx, lastidx' + min_gap) in one vector
        # step: for such j, close cannot fire (lastidx only
        # advances, so j - lastidx_at_j < min_gap), and a close-free
        # sequence of takes reduces to "final cpeak = max(cpeak,
        # window max); lastidx/cpeakidx move to the FIRST position
        # of the window max iff it strictly beats cpeak" — exactly
        # the identify_unique_peaks quirk (lastidx advances only on
        # new max, peakfinder.hpp:27-56), because intermediate
        # non-emitting takes leave no other trace. A contiguous
        # ~min_gap-wide cluster run collapses from ~30 trips to ~2.
        def body(rem):
            msk = mstate[:] > 0
            cursor = istate[:, c0 : c0 + 1]
            open_ = istate[:, c0 + 2 : c0 + 3]
            cpeakidx = istate[:, c0 + 3 : c0 + 4]
            lastidx = istate[:, c0 + 4 : c0 + 5]
            cpeak = fstate[:, c0 : c0 + 1]
            # _WSTEPS unrolled machine steps per trip: the loop is
            # trip-latency-bound, so more vector work per trip is
            # nearly free
            for _ in range(_WSTEPS):
                idx = jnp.min(
                    jnp.where(msk, gidx, jnp.int32(_BIG)),
                    axis=1, keepdims=True,
                )
                act = idx < jnp.int32(_BIG)
                snr = jnp.max(
                    jnp.where(msk & (gidx == idx), s, -jnp.inf),
                    axis=1, keepdims=True,
                )
                close = act & (open_ == 1) & (idx - lastidx >= min_gap)
                emit(close, cursor, cpeakidx, cpeak)
                cursor = jnp.where(close, cursor + 1, cursor)
                start = act & ((open_ == 0) | close)
                take = start | (act & (snr > cpeak))
                cpeakidx = jnp.where(take, idx, cpeakidx)
                lastidx = jnp.where(take, idx, lastidx)
                cpeak = jnp.where(take, snr, cpeak)
                open_ = jnp.where(act, 1, open_)
                # close-free window past the first element: one masked
                # max + first-argmax stands in for every crossing the
                # sequential machine could only take, never close on
                wmask = (
                    msk
                    & (gidx > idx)
                    & (gidx < lastidx + jnp.int32(min_gap))
                )
                wmax = jnp.max(
                    jnp.where(wmask, s, -jnp.inf), axis=1, keepdims=True,
                )
                wfirst = jnp.min(
                    jnp.where(wmask & (s == wmax), gidx, jnp.int32(_BIG)),
                    axis=1, keepdims=True,
                )
                wtake = act & (wmax > cpeak)
                cpeakidx = jnp.where(wtake, wfirst, cpeakidx)
                lastidx = jnp.where(wtake, wfirst, lastidx)
                cpeak = jnp.where(wtake, wmax, cpeak)
                msk = msk & ~((gidx == idx) | wmask)
            nst = msk.astype(jnp.int32)
            mstate[:] = nst
            istate[:, c0 : c0 + 1] = cursor
            istate[:, c0 + 2 : c0 + 3] = open_
            istate[:, c0 + 3 : c0 + 4] = cpeakidx
            istate[:, c0 + 4 : c0 + 5] = lastidx
            fstate[:, c0 : c0 + 1] = cpeak
            return jnp.sum(nst)

        # the guard above established crossings exist: its count seeds
        # the loop
        jax.lax.while_loop(lambda rem: rem > 0, body, jnp.sum(cnt))

    @pl.when(b == nb - 1)
    def _(emit=emit, c0=c0, lvl=lvl):
        open_ = istate[:, c0 + 2 : c0 + 3]
        emit(
            open_ == 1, istate[:, c0 : c0 + 1],
            istate[:, c0 + 3 : c0 + 4], fstate[:, c0 : c0 + 1],
        )
        cnt_ref[:, 2 * lvl : 2 * lvl + 1] = istate[:, c0 + 1 : c0 + 2]
        cnt_ref[:, 2 * lvl + 1 : 2 * lvl + 2] = (
            istate[:, c0 : c0 + 1] + open_
        )
