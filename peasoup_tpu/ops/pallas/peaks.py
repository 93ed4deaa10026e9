"""Pallas TPU kernel: fused threshold + compaction + peak clustering.

Replaces the find_peaks_device -> cluster_peaks_device pair
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

Design:
  rows are processed in stripes of ``_SUB`` rows (24, a multiple of
  the f32 sublane quantum 8 — see the tuning comment at the
  definition): grid = (row stripes, bin blocks), sequential
  ("arbitrary") order, so for each stripe the kernel sees blocks of
  ``_BLOCK`` bins left to right. The identify_unique_peaks state
  machine runs as _SUB independent rows of (cursor, raw count, open,
  cpeak, cpeakidx, lastidx) vectors living in VMEM scratch across
  grid steps. Per block: vector threshold mask; a stripe whose block
  has no crossing pays only the mask+check. Otherwise a fori_loop
  walks crossings oldest-first in every row at once (masked min per
  sublane); cluster emissions write the (_SUB, mx) output block
  through a one-hot select (no dynamic-index stores). Output blocks
  stay VMEM-resident for the whole stripe (their BlockSpec index
  ignores the bin axis).

Outputs per row: cluster idxs (mx,) i32 ascending padded with
``nbins``; cluster snrs (mx,) f32 zero-padded; counts (2,) i32 =
(raw crossings, clusters). Matches the (idxs, snrs, ccounts)
convention of cluster_peaks_device; clusters beyond ``mx`` are
dropped but still counted (callers escalate on counts[1] > mx).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import os as _os

PEAKS_BLOCK = int(_os.environ.get("PEASOUP_PEAKS_BLOCK", "4096"))
# bins per grid step (128-lane multiple); 4096 measured best on v5e
# (fewer grid steps beats the larger per-step vector work; r3 scan:
# 512/1024/8704/17408 give 112/94/99/135 ms vs 87 ms for 2048-4096 at
# production shapes).  Overridable for tuning via PEASOUP_PEAKS_BLOCK
# (read once at import); harmonic_sums(block_align=PEAKS_BLOCK) keeps
# its level padding in lockstep with this value.
if PEAKS_BLOCK <= 0 or PEAKS_BLOCK % 128:
    raise ValueError(
        f"PEASOUP_PEAKS_BLOCK must be a positive multiple of 128, got "
        f"{PEAKS_BLOCK}"
    )
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
# crossing-walk subblock width (lanes). r3 chose 512 to shrink
# per-TRIP vector work; with the r4 window-merged walk trips are few
# and the per-SUBBLOCK guards (a sum reduction + scalar branch each,
# x nlev per grid step) dominate instead, so the default is now the
# full block (one guard per level per step; measured 41.1 -> 35.5 ms
# at the dense tutorial grid).
_SBW = int(_os.environ.get("PEASOUP_PEAKS_SBW", "0")) or _BLOCK
if _SBW <= 0 or _SBW % 128 or _BLOCK % _SBW:
    raise ValueError(
        "PEASOUP_PEAKS_SBW must be a positive multiple of 128 dividing "
        f"PEASOUP_PEAKS_BLOCK: {_SBW}"
    )
# unrolled machine steps per while-loop trip (the walk is trip-latency
# bound; each step is one close/emit + one window merge); must be >= 1
# or the walk loop would never clear crossings (infinite device loop)
_WSTEPS = int(_os.environ.get("PEASOUP_PEAKS_WSTEPS", "2"))
if _WSTEPS < 1:
    raise ValueError(f"PEASOUP_PEAKS_WSTEPS must be >= 1, got {_WSTEPS}")
_BIG = 1 << 30  # "no crossing" sentinel for the masked min reduction


def _level_machine(
    lvl, s, *, win_ref, idx_ref, snr_ref, cnt_ref, istate, fstate, mstate,
    b, nb, gidx, slot, mx, threshold, min_gap, scale,
):
    """One harmonic level's threshold + identify_unique_peaks walk for
    the current (stripe, block) grid step. ``s`` is the level's (VMEM-
    resident) value block — loaded from an operand by the peaks kernel,
    computed in VMEM by the harmonic mega-kernel (harmpeaks.py). State
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

        # walk the block's crossings SUBBLOCK by subblock (left to
        # right, so the cluster machine sees the same ascending
        # crossing sequence). All slices are STATIC (python
        # unroll), so no dynamic lane indexing reaches Mosaic.
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
        for lo_l in range(0, _BLOCK, _SBW):
            mask_sb = mask[:, lo_l : lo_l + _SBW]
            gidx_sb = gidx[:, lo_l : lo_l + _SBW]
            s_sb = s[:, lo_l : lo_l + _SBW]
            # at full-block _SBW the enclosing cnt guard already
            # established crossings exist: reuse its (cheaper,
            # lane-reduced) sum as the loop seed and drop the
            # (always-true) inner guard entirely at trace time
            tot_sb = (
                jnp.sum(cnt)
                if _SBW == _BLOCK
                else jnp.sum(mask_sb.astype(jnp.int32))
            )
            guard = (
                (lambda f: f())
                if _SBW == _BLOCK
                else pl.when(tot_sb > 0)
            )

            @guard
            def _(mask_sb=mask_sb, gidx_sb=gidx_sb, s_sb=s_sb,
                  tot_sb=tot_sb, lo_l=lo_l, emit=emit, c0=c0):
                def body(rem):
                    msk = mstate[:, lo_l : lo_l + _SBW] > 0
                    cursor = istate[:, c0 : c0 + 1]
                    open_ = istate[:, c0 + 2 : c0 + 3]
                    cpeakidx = istate[:, c0 + 3 : c0 + 4]
                    lastidx = istate[:, c0 + 4 : c0 + 5]
                    cpeak = fstate[:, c0 : c0 + 1]
                    # _WSTEPS unrolled machine steps per trip: the
                    # loop is trip-latency-bound, so more vector
                    # work per trip is nearly free
                    for _ in range(_WSTEPS):
                        idx = jnp.min(
                            jnp.where(msk, gidx_sb, jnp.int32(_BIG)),
                            axis=1, keepdims=True,
                        )
                        act = idx < jnp.int32(_BIG)
                        snr = jnp.max(
                            jnp.where(
                                msk & (gidx_sb == idx), s_sb, -jnp.inf
                            ),
                            axis=1,
                            keepdims=True,
                        )
                        close = (
                            act
                            & (open_ == 1)
                            & (idx - lastidx >= min_gap)
                        )
                        emit(close, cursor, cpeakidx, cpeak)
                        cursor = jnp.where(close, cursor + 1, cursor)
                        start = act & ((open_ == 0) | close)
                        take = start | (act & (snr > cpeak))
                        cpeakidx = jnp.where(take, idx, cpeakidx)
                        lastidx = jnp.where(take, idx, lastidx)
                        cpeak = jnp.where(take, snr, cpeak)
                        open_ = jnp.where(act, 1, open_)
                        # close-free window past the first element:
                        # one masked max + first-argmax stands in
                        # for every crossing the sequential machine
                        # could only take, never close on
                        wmask = (
                            msk
                            & (gidx_sb > idx)
                            & (gidx_sb < lastidx + jnp.int32(min_gap))
                        )
                        wmax = jnp.max(
                            jnp.where(wmask, s_sb, -jnp.inf),
                            axis=1, keepdims=True,
                        )
                        wfirst = jnp.min(
                            jnp.where(
                                wmask & (s_sb == wmax), gidx_sb,
                                jnp.int32(_BIG),
                            ),
                            axis=1, keepdims=True,
                        )
                        wtake = act & (wmax > cpeak)
                        cpeakidx = jnp.where(wtake, wfirst, cpeakidx)
                        lastidx = jnp.where(wtake, wfirst, lastidx)
                        cpeak = jnp.where(wtake, wmax, cpeak)
                        msk = msk & ~((gidx_sb == idx) | wmask)
                    nst = msk.astype(jnp.int32)
                    mstate[:, lo_l : lo_l + _SBW] = nst
                    istate[:, c0 : c0 + 1] = cursor
                    istate[:, c0 + 2 : c0 + 3] = open_
                    istate[:, c0 + 3 : c0 + 4] = cpeakidx
                    istate[:, c0 + 4 : c0 + 5] = lastidx
                    fstate[:, c0 : c0 + 1] = cpeak
                    return jnp.sum(nst)

                jax.lax.while_loop(lambda rem: rem > 0, body, tot_sb)

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


def _kernel_multi(*refs, nlev, mx, nbins, threshold, min_gap, scales):
    """All nlev levels' threshold+cluster machines in ONE grid walk:
    each (stripe, block) step streams every level's block and runs nlev
    independent identify_unique_peaks machines via the shared
    _level_machine. One kernel dispatch and one fifth the grid steps of
    the per-level version — the per-step DMA latency was the dominant
    cost, not the bytes."""
    win_ref = refs[0]
    s_refs = refs[1 : 1 + nlev]
    idx_ref, snr_ref, cnt_ref = refs[1 + nlev : 4 + nlev]
    istate, fstate, mstate = refs[4 + nlev : 7 + nlev]
    b = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(b == 0)
    def _():
        istate[:] = jnp.zeros((_SUB, 128), jnp.int32)
        fstate[:] = jnp.zeros((_SUB, 128), jnp.float32)
        idx_ref[:] = jnp.full((_SUB, nlev * mx), nbins, jnp.int32)
        snr_ref[:] = jnp.zeros((_SUB, nlev * mx), jnp.float32)

    gidx = b * _BLOCK + jax.lax.broadcasted_iota(jnp.int32, (_SUB, _BLOCK), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (_SUB, mx), 1)

    for lvl in range(nlev):
        _level_machine(
            lvl, s_refs[lvl][:], win_ref=win_ref, idx_ref=idx_ref,
            snr_ref=snr_ref, cnt_ref=cnt_ref, istate=istate, fstate=fstate,
            mstate=mstate, b=b, nb=nb, gidx=gidx, slot=slot, mx=mx,
            threshold=threshold, min_gap=min_gap, scale=scales[lvl],
        )


@lru_cache(maxsize=None)
def _build_multi(
    rows: int, npad: int, nlev: int, mx: int, nbins: int,
    threshold: float, min_gap: int, scales: tuple, interpret: bool,
):
    kernel = partial(
        _kernel_multi, nlev=nlev, mx=mx, nbins=nbins, threshold=threshold,
        min_gap=min_gap, scales=scales,
    )
    nblk = npad // _BLOCK
    return pl.pallas_call(
        kernel,
        grid=(rows // _SUB, nblk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [
            pl.BlockSpec((_SUB, _BLOCK), lambda r, b: (r, b))
            for _ in range(nlev)
        ],
        out_specs=[
            pl.BlockSpec((_SUB, nlev * mx), lambda r, b: (r, 0)),
            pl.BlockSpec((_SUB, nlev * mx), lambda r, b: (r, 0)),
            pl.BlockSpec((_SUB, nlev * 2), lambda r, b: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, nlev * mx), jnp.int32),
            jax.ShapeDtypeStruct((rows, nlev * mx), jnp.float32),
            jax.ShapeDtypeStruct((rows, nlev * 2), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_SUB, 128), jnp.int32),
            pltpu.VMEM((_SUB, 128), jnp.float32),
            pltpu.VMEM((_SUB, _BLOCK), jnp.int32),
        ],
        interpret=interpret,
    )


def find_cluster_peaks_multi(
    levels,  # sequence of nlev (..., nbins) f32 spectra (level 0 = base)
    windows: jnp.ndarray,  # (nlev, 2) i32 [start, limit) per level
    *,
    threshold: float,
    max_peaks: int,
    scales: tuple,  # per-level in-VMEM factors (1.0 for pre-scaled)
    min_gap: int = 30,
    interpret: bool = False,
    nbins: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-dispatch equivalent of nlev find_cluster_peaks_pallas calls.
    Returns (idxs (..., nlev, max_peaks), snrs, raw counts (..., nlev),
    cluster counts (..., nlev)).  ``nbins`` is the TRUE bin count (the
    idx pad sentinel) when the level arrays arrive pre-padded past it
    (harmonic_sums block_align) — the pad region must be masked by the
    windows' hi bounds."""
    nlev = len(levels)
    nbins_in = levels[0].shape[-1]
    nbins = nbins if nbins is not None else nbins_in
    # the pad region past the true nbins is GARBAGE (harmonic sums
    # gather real low bins there), so no window may reach into it —
    # clamp rather than trust every caller's window construction
    windows = jnp.stack(
        [windows[:, 0], jnp.minimum(windows[:, 1], nbins)], axis=1
    )
    batch = levels[0].shape[:-1]
    rows = 1
    for d in batch:
        rows *= d
    npad = -(-nbins_in // _BLOCK) * _BLOCK
    rpad = -(-rows // _SUB) * _SUB
    flats = []
    for s in levels:
        flat = s.reshape(rows, nbins_in)
        if npad != nbins_in or rpad != rows:
            flat = jnp.pad(flat, ((0, rpad - rows), (0, npad - nbins_in)))
        flats.append(flat)
    fn = _build_multi(
        rpad, npad, nlev, max_peaks, nbins, float(threshold), min_gap,
        tuple(float(x) for x in scales), interpret,
    )
    cidx, csnr, counts = fn(windows.astype(jnp.int32), *flats)
    cidx = cidx[:rows].reshape(*batch, nlev, max_peaks)
    csnr = csnr[:rows].reshape(*batch, nlev, max_peaks)
    counts = counts[:rows].reshape(*batch, nlev, 2)
    return cidx, csnr, counts[..., 0], counts[..., 1]


def find_cluster_peaks_pallas(
    spec: jnp.ndarray,  # (..., nbins) f32 normalised spectrum/harmonic sum
    windows: jnp.ndarray,  # (nlev, 2) i32 [start, limit) per level
    lvl: int,
    *,
    threshold: float,
    max_peaks: int,
    min_gap: int = 30,
    interpret: bool = False,
    scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused equivalent of find_peaks_device + cluster_peaks_device for
    one harmonic level: a thin nlev=1 wrapper over the multi-level
    kernel so the cluster state machine exists in exactly one place.
    Returns (cluster idxs (..., max_peaks), cluster snrs, raw count
    (...,), cluster count (...,)). With ``scale`` != 1 the spectrum is
    multiplied by it in VMEM before thresholding (for unscaled
    cumulative harmonic sums)."""
    cidx, csnr, counts, ccounts = find_cluster_peaks_multi(
        [spec], windows[lvl : lvl + 1],
        threshold=threshold, max_peaks=max_peaks, scales=(scale,),
        min_gap=min_gap, interpret=interpret,
    )
    return (
        cidx[..., 0, :],
        csnr[..., 0, :],
        counts[..., 0],
        ccounts[..., 0],
    )
