"""Pallas TPU mega-kernel: harmonic summing fused into the peaks walk.

Replaces harmonic_sums + the per-level find_peaks_device ->
cluster_peaks_device pair in the TPU search. The conv formulation of
the harmonic sums (ops/harmonics.py) is XLA-optimal
but HBM-bound BY CONSTRUCTION: the cumulative val chain is 31 (nharms=5)
or 15 (nharms=4) separate conv+add HLOs, each of which must round-trip
the full (rows, npad) accumulator through HBM — measured 38.7 GB /
51.6 ms at the dense tutorial grid, plus ~18 ms of layout copies
between the conv outputs and the peaks custom call and a further level
write+read for the walk (NOTES.md round-4 trace). Here the whole
chain — gather, accumulate, scale, threshold, cluster-walk — runs in
VMEM; HBM traffic drops to the spectrum block reads (~sum(k/2^h)+1
passes) and the tiny peak outputs.

Harmonic gather in VMEM (reference math: harmonic_sum_kernel,
src/kernels.cu:33-208; same exact integer index map as
ops/harmonics.py): for stream (h, k odd < 2^h) the source index of
output bin i is (i*k + 2^(h-1)) >> h. Per bin block b of width B the
sources live in [b*Bq, (b+1)*Bq] with Bq = B*k >> h (exact: 2^h | B*k),
fetched as one (SUB, Bq) operand at block index b plus two (SUB, 128)
edge operands at lanes (b+1)*Bq and (b+1)*Bq + 128. Writing
i = g*128 + r the local source is g*s + c_r with s = 128*k >> h and
c_r = (r*k + 2^(h-1)) >> h <= s < 128; each 128-lane group's window is
carved from VMEM as an ALIGNED 256-wide slice (pure vreg renames) plus
one pltpu.roll by the group's phase g*s mod 128 (Mosaic CRASHES on
misaligned 128-slices — probed r4), then all G groups are gathered by
one shared constant one-hot (128, 128) MXU dot. One-hot matmul is an
exact gather (harmonics.py "conv" argument; Mosaic rejects
per-operand precision, and at plain HIGHEST the one-hot side's extra
split terms are exact zeros), so accumulated level values are BITWISE
identical to method="take" and the walk outputs (the state machine of
peaks.py) are bitwise identical to find_peaks_device +
cluster_peaks_device on those levels.

Accumulation order per element matches the reference exactly: base
spectrum, then levels h ascending, odd k ascending within each level —
one `+` at a time (harmonics.py harmonic_sums contract).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .peaks import _BIG, _BLOCK, _SUB, _level_machine  # shared walk machine


def _streams(nharms: int) -> list[tuple[int, int]]:
    """(h, k) per fresh gather, in the reference's accumulation order."""
    return [
        (h, k) for h in range(1, nharms + 1) for k in range(1, 1 << h, 2)
    ]


@lru_cache(maxsize=None)
def _gather_consts(nharms: int) -> np.ndarray:
    """(nstreams*128, 128) stacked one-hot gather matrices: block si
    holds C[c, r] = 1 iff (r*k + 2^(h-1)) >> h == c for stream si."""
    mats = []
    for h, k in _streams(nharms):
        r = np.arange(128)
        c_r = (r * k + (1 << (h - 1))) >> h
        C = np.zeros((128, 128), dtype=np.float32)
        C[c_r, r] = 1.0
        mats.append(C)
    return np.concatenate(mats, axis=0)


def _kernel_harm(*refs, nharms, mx, nbins, threshold, min_gap, scales):
    ns = len(_streams(nharms))
    nlev = nharms + 1
    win_ref, c_ref, base_ref = refs[:3]
    mains = refs[3 : 3 + ns]
    edges1 = refs[3 + ns : 3 + 2 * ns]
    edges2 = refs[3 + 2 * ns : 3 + 3 * ns]
    idx_ref, snr_ref, cnt_ref = refs[3 + 3 * ns : 6 + 3 * ns]
    istate, fstate, mstate = refs[6 + 3 * ns : 9 + 3 * ns]
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
    G = _BLOCK // 128

    def machine(lvl, val):
        _level_machine(
            lvl, val, win_ref=win_ref, idx_ref=idx_ref, snr_ref=snr_ref,
            cnt_ref=cnt_ref, istate=istate, fstate=fstate, mstate=mstate,
            b=b, nb=nb, gidx=gidx, slot=slot, mx=mx,
            threshold=threshold, min_gap=min_gap, scale=scales[lvl],
        )

    val = base_ref[:]
    machine(0, val)
    si = 0
    for h in range(1, nharms + 1):
        for k in range(1, 1 << h, 2):
            s_ = (128 * k) >> h
            inb = jnp.concatenate(
                [mains[si][:], edges1[si][:], edges2[si][:]], axis=1
            )
            # group g's window inb[g*s_ : g*s_+128] is MISALIGNED
            # (g*s_ mod 128 != 0) and Mosaic crashes lowering such
            # slices: carve an aligned 256-wide slice (vreg renames)
            # and phase-align it with one cheap lane roll instead
            wnds = []
            for g in range(G):
                a = (g * s_) // 128 * 128
                ph = g * s_ - a
                w = inb[:, a : a + 256]
                if ph:
                    w = pltpu.roll(w, 256 - ph, 1)
                wnds.append(w[:, :128])
            x = jnp.stack(wnds, axis=1)  # (SUB, G, 128), natural order
            chk = c_ref[si * 128 : (si + 1) * 128, :]
            # Mosaic rejects per-operand dot precision (the XLA conv
            # path's (HIGHEST, DEFAULT) trick) and HIGHEST-both-sides
            # pays dead extra passes against the one-hot operand, so
            # split the data side into an exact 3-term bf16 sum and run
            # three 1-pass bf16 dots. The split TRUNCATES via bit
            # masking (each term = the next 16 bits of the f32 word,
            # always exactly representable in bf16; each residual
            # subtraction is exact by cancellation) rather than
            # round-trip casts, which compilers may elide under
            # --xla_allow_excess_precision (observed: the rounding
            # split collapses to r1 == 0 in interpret mode). Each dot's
            # output is the exact gather of its term (one-hot), and
            # (hi+mid)+lo reconstructs x[src] bitwise — measured equal
            # to the HIGHEST dot on v5e and ~9% faster
            msk = jnp.uint32(0xFFFF0000)
            xi = jax.lax.bitcast_convert_type(x, jnp.uint32)
            hi_f = jax.lax.bitcast_convert_type(xi & msk, jnp.float32)
            r1 = x - hi_f
            r1i = jax.lax.bitcast_convert_type(r1, jnp.uint32)
            mid_f = jax.lax.bitcast_convert_type(r1i & msk, jnp.float32)
            lo_f = r1 - mid_f
            chkb = chk.astype(jnp.bfloat16)  # 0/1: exact in bf16

            def dd(a):
                # a is exactly bf16-representable, so the cast is
                # exact; the f32 output cast is a no-op on TPU (MXU
                # accumulates f32) and keeps interpret backends that
                # return bf16 exact (single one-hot term per output)
                return jax.lax.dot_general(
                    a.astype(jnp.bfloat16), chkb, (((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.float32)

            gat = (dd(hi_f) + dd(mid_f)) + dd(lo_f)
            val = val + gat.reshape(_SUB, _BLOCK)
            si += 1
        machine(h, val)


@lru_cache(maxsize=None)
def _build_harm(
    rows: int, npad: int, nharms: int, mx: int, nbins: int,
    threshold: float, min_gap: int, scales: tuple, interpret: bool,
):
    streams = _streams(nharms)
    nlev = nharms + 1
    kernel = partial(
        _kernel_harm, nharms=nharms, mx=mx, nbins=nbins,
        threshold=threshold, min_gap=min_gap, scales=scales,
    )
    nblk = npad // _BLOCK
    main_specs, edge1_specs, edge2_specs = [], [], []
    nmax = npad // 128 - 1
    for h, k in streams:
        bq = (_BLOCK * k) >> h  # lane width of one main block (mult of 128)
        main_specs.append(
            pl.BlockSpec((_SUB, bq), lambda r, b: (r, b))
        )
        e = bq // 128  # edge block index stride, in 128-lane units
        # two trailing 128-lane edge blocks cover the aligned 256-wide
        # window carve-out past the main block; the in-bounds clamp can
        # only bind for windows whose outputs lie in the masked pad
        # region (real-bin sources stay < nbins <= npad - npad/2^h)
        edge1_specs.append(
            pl.BlockSpec(
                (_SUB, 128),
                lambda r, b, e=e: (r, jnp.minimum((b + 1) * e, nmax)),
            )
        )
        edge2_specs.append(
            pl.BlockSpec(
                (_SUB, 128),
                lambda r, b, e=e: (r, jnp.minimum((b + 1) * e + 1, nmax)),
            )
        )
    return pl.pallas_call(
        kernel,
        grid=(rows // _SUB, nblk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # windows
            pl.BlockSpec(
                (len(streams) * 128, 128), lambda r, b: (0, 0)
            ),  # one-hot gather constants
            pl.BlockSpec((_SUB, _BLOCK), lambda r, b: (r, b)),  # base
        ]
        + main_specs
        + edge1_specs
        + edge2_specs,
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


def find_harmonic_cluster_peaks(
    spec,  # (..., npad) f32 normalised spectrum, pre-padded to _BLOCK
    windows: jnp.ndarray,  # (nharms+1, 2) i32 [start, limit) per level
    *,
    nharms: int,
    threshold: float,
    max_peaks: int,
    scales: tuple,  # per-level in-VMEM factors (level 0 first)
    min_gap: int = 30,
    interpret: bool = False,
    nbins: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-dispatch equivalent of harmonic_sums(method="take") +
    find_peaks_device + cluster_peaks_device on every level, scaled by
    ``scales`` in VMEM. Returns (idxs (..., nlev, max_peaks), snrs, raw
    counts (..., nlev), cluster counts (..., nlev)); nlev = nharms + 1,
    level 0 the base spectrum. ``nbins`` is the TRUE bin count (idx pad
    sentinel); windows' hi bounds are clamped to it, masking both the
    pad region and the pad-region harmonic values (which gather real
    low bins).
    """
    if not 0 < nharms <= 5:
        raise ValueError("nharms must be in 1..5")
    nbins_in = spec.shape[-1]
    if nbins_in % _BLOCK:
        raise ValueError(
            f"spec last axis must be a multiple of the peaks block "
            f"({_BLOCK}); got {nbins_in} — pad upstream"
        )
    nlev = nharms + 1
    if len(scales) != nlev or windows.shape[0] != nlev:
        raise ValueError("scales/windows must cover nharms+1 levels")
    nbins = nbins if nbins is not None else nbins_in
    windows = jnp.stack(
        [windows[:, 0], jnp.minimum(windows[:, 1], nbins)], axis=1
    )
    batch = spec.shape[:-1]
    rows = 1
    for d in batch:
        rows *= d
    rpad = -(-rows // _SUB) * _SUB
    flat = spec.reshape(rows, nbins_in)
    if rpad != rows:
        flat = jnp.pad(flat, ((0, rpad - rows), (0, 0)))
    fn = _build_harm(
        rpad, nbins_in, nharms, max_peaks, nbins, float(threshold),
        min_gap, tuple(float(x) for x in scales), interpret,
    )
    consts = jnp.asarray(_gather_consts(nharms))
    ns = len(_streams(nharms))
    args = [windows.astype(jnp.int32), consts, flat]
    args += [flat] * ns  # main stream views (index-mapped slices)
    args += [flat] * (2 * ns)  # two edge views per stream
    cidx, csnr, counts = fn(*args)
    cidx = cidx[:rows].reshape(*batch, nlev, max_peaks)
    csnr = csnr[:rows].reshape(*batch, nlev, max_peaks)
    counts = counts[:rows].reshape(*batch, nlev, 2)
    return cidx, csnr, counts[..., 0], counts[..., 1]
