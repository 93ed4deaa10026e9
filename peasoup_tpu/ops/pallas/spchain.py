"""Pallas TPU kernel for the fused single-pulse chain tail:
boxcar width sweep + dec-fold best-plane decimation in one VMEM pass.

The unfused pair (ops/pallas/boxcar.py, then the jnp reshape/max/argmax
decimation in ops.singlepulse.make_single_pulse_search_fn) writes the
full (D, tpad) best-S/N and best-width planes to HBM only for the very
next op to re-read and crush them ``dec``-fold. This kernel keeps the
whole tail resident: per (dm, tile) grid step one dynamic-offset DMA
brings in the prefix-sum window, the width sweep runs as lane-rolls of
that window exactly like the boxcar kernel, and the dec-fold
(block max, in-block argmax, width at the argmax) happens on the VMEM
tile before anything touches HBM — the planes that leave the chip are
``dec``x smaller.

Index math is the identical f32/i32 chain as the jnp twin
(ops.singlepulse.boxcar_dec_best_twin): subtract, scale, mask,
strict-> running max, then first-max argmax via a lane-iota min — so
outputs are BITWISE equal to it; the probe
(ops.pallas.probe_pallas_spchain) gates on exactly that. The dec-fold
needs the (1, span) sweep as (span/dec, dec) blocks, a lane retile
Mosaic refuses; the kernel gets there through a transpose instead
(see _kernel), which tests/test_tpu_compile.py compiles for v5e.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_QUANT = 1024
_LANES = 128


def fold_fits(span: int, dec: int) -> bool:
    """Whether the in-kernel dec-fold can tile ``span`` (see _kernel):
    dec-blocks must sit whole inside a 128-lane row and span whole
    sublane groups once transposed."""
    return span % _LANES == 0 and _LANES % dec == 0 and dec % 8 == 0


def _kernel(
    widths_ref,  # (W,) i32 SMEM (scalar prefetch)
    scales_ref,  # (W,) f32 SMEM (scalar prefetch)
    nvalid_ref,  # (1,) i32 SMEM (scalar prefetch)
    csum_ref,  # flat (D * row_stride,) f32 HBM
    bmax_ref,  # (128 // dec, span // 128) f32 VMEM out tile
    barg_ref,  # (128 // dec, span // 128) i32 (in-block argmax)
    bw_ref,  # (128 // dec, span // 128) i32 (width at the argmax)
    win_ref,  # (span + wext,) f32 VMEM scratch
    sem,
    *,
    span: int,
    wext: int,
    dec: int,
    row_stride: int,
    n_widths: int,
    interpret: bool,
):
    d = pl.program_id(0)
    g = pl.program_id(1)
    clen = span + wext
    u = d * row_stride + g * span  # 1024-aligned: both terms are
    copy = pltpu.make_async_copy(
        csum_ref.at[pl.ds(pl.multiple_of(u, _QUANT), clen)], win_ref, sem
    )
    copy.start()
    j = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    nvalid = nvalid_ref[0]
    neg_inf = jnp.float32(-jnp.inf)
    copy.wait()
    chunk = win_ref[...].reshape(1, clen)
    lo = chunk[:, :span]
    best = jnp.full((1, span), neg_inf, jnp.float32)
    bw = jnp.zeros((1, span), jnp.int32)
    for k in range(n_widths):
        w = widths_ref[k]
        scale = scales_ref[k]
        if interpret:
            hi = jax.lax.dynamic_slice(chunk, (0, w), (1, span))
        else:
            hi = pltpu.roll(chunk, clen - w, axis=1)[:, :span]
        snr = jnp.where(j + w <= nvalid, (hi - lo) * scale, neg_inf)
        better = snr > best
        best = jnp.where(better, snr, best)
        bw = jnp.where(better, jnp.int32(k), bw)
    # dec-fold on the resident tile: block max, FIRST-max argmax (the
    # jnp twin's jnp.argmax semantics) via a sublane-iota min, and the
    # width index at that argmax via a one-hot sum. Mosaic cannot
    # retile the (1, span) sweep into (span/dec, dec) lanes, so the tile
    # is transposed instead: (span/128, 128) -> (128, span/128) puts
    # the dec samples of each block in dec consecutive sublanes of one
    # column, and every fold is a sublane reduction. Out tile [k, r] is
    # block 128/dec * r + k; the caller restores block order.
    rows = span // _LANES
    k = _LANES // dec
    blk = best.reshape(rows, _LANES).T.reshape(k, dec, rows)
    bw_blk = bw.reshape(rows, _LANES).T.reshape(k, dec, rows)
    bmax = jnp.max(blk, axis=1, keepdims=True)  # (k, 1, rows)
    sub = jax.lax.broadcasted_iota(jnp.int32, (k, dec, rows), 1)
    barg = jnp.min(
        jnp.where(blk == bmax, sub, jnp.int32(dec)), axis=1, keepdims=True
    )
    bmax_ref[...] = bmax[:, 0, :]
    barg_ref[...] = barg[:, 0, :]
    bw_ref[...] = jnp.sum(
        jnp.where(sub == barg, bw_blk, jnp.int32(0)), axis=1
    )


@lru_cache(maxsize=None)
def _build(
    d: int, tpad: int, span: int, wext: int, dec: int, n_widths: int,
    interpret: bool,
):
    row_stride = tpad + wext  # a _QUANT multiple (plan_pad/width_extent)
    kernel = partial(
        _kernel,
        span=span,
        wext=wext,
        dec=dec,
        row_stride=row_stride,
        n_widths=n_widths,
        interpret=interpret,
    )
    tile = (_LANES // dec, span // _LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d, tpad // span),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec(
                (None, None, *tile), lambda dd, gg, *_: (dd, gg, 0, 0),
                memory_space=pltpu.VMEM,
            )
            for _ in range(3)
        ],
        scratch_shapes=[
            pltpu.VMEM((span + wext,), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((d, tpad // span, *tile), dt)
            for dt in (jnp.float32, jnp.int32, jnp.int32)
        ],
        interpret=interpret,
    )


def boxcar_dec_best_pallas(
    csum_pad: jnp.ndarray,  # (D, tpad + wext) from prefix_sum_padded
    widths: tuple[int, ...],
    scales: np.ndarray,
    nvalid: int,
    tpad: int,
    dec: int,
    *,
    span: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused width sweep + dec-fold; bitwise equal to
    ops.singlepulse.boxcar_dec_best_twin. Returns (block max S/N
    (D, tpad/dec) f32, in-block argmax (D, tpad/dec) i32, width index
    at the argmax (D, tpad/dec) i32). ``span`` must divide ``tpad``
    and ``dec`` must satisfy :func:`fold_fits`."""
    d, row = csum_pad.shape
    wext = row - tpad
    if (
        tpad % span
        or not fold_fits(span, dec)
        or row % _QUANT
        or wext <= int(max(widths))
    ):
        raise ValueError(
            f"boxcar_dec_best_pallas: incompatible geometry tpad={tpad} "
            f"span={span} dec={dec} wext={wext} widths<={max(widths)}"
        )
    fn = _build(d, tpad, span, wext, dec, len(widths), interpret)
    outs = fn(
        jnp.asarray(widths, dtype=jnp.int32),
        jnp.asarray(scales, dtype=jnp.float32),
        jnp.asarray([nvalid], dtype=jnp.int32),
        csum_pad.reshape(-1),
    )
    # out tile [k, r] holds block 128/dec * r + k of its span
    return tuple(
        o.swapaxes(-1, -2).reshape(d, tpad // dec) for o in outs
    )
