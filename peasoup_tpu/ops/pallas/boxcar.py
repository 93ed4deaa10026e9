"""Pallas TPU kernel for the single-pulse boxcar width sweep.

The jnp twin (ops/singlepulse.boxcar_best_twin) reads the padded
prefix-sum rows W + 1 times from HBM (one shifted stream per width plus
the base). This kernel streams each prefix-sum tile into VMEM ONCE and
runs the whole width sweep there: per (dm, tile) grid step, one
dynamic-offset DMA brings in ``span + wext`` contiguous samples, and
every boxcar width becomes a lane-roll of that resident window —
W shifted reads of VMEM instead of W passes over HBM.

The width list and its 1/sqrt(w) scales ride in as SCALAR-PREFETCH
operands (SMEM), so one compiled kernel serves every width
configuration of the same count: the sweep loop is unrolled statically
over the width COUNT while each width VALUE is a runtime scalar read.

Lowering constraints follow ops/pallas/resample.py: the input is a
flat 1-D array of 1024-aligned padded rows (1-D dynamic-offset DMA
slices must start/size on 1024-lane quanta — here both the row stride
and the tile span are 1024 multiples, so window starts are aligned by
construction), and the dynamic per-width shift uses pltpu.roll on the
VMEM window (dynamic_slice in interpret mode).

Index math is the identical f32 chain as the twin — subtract, scale,
mask, strict-> running max — so outputs are BITWISE equal to it; the
probe (ops.pallas.probe_pallas_boxcar) gates on exactly that.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_QUANT = 1024


def _kernel(
    widths_ref,  # (W,) i32 SMEM (scalar prefetch)
    scales_ref,  # (W,) f32 SMEM (scalar prefetch)
    nvalid_ref,  # (1,) i32 SMEM (scalar prefetch)
    csum_ref,  # flat (D * row_stride,) f32 HBM
    best_ref,  # (1, span) f32 VMEM out tile
    bw_ref,  # (1, span) i32 VMEM out tile
    win_ref,  # (span + wext,) f32 VMEM scratch
    sem,
    *,
    span: int,
    wext: int,
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
    best_ref[:] = best.reshape(-1)
    bw_ref[:] = bw.reshape(-1)


@lru_cache(maxsize=None)
def _build(
    d: int, tpad: int, span: int, wext: int, n_widths: int, interpret: bool
):
    row_stride = tpad + wext  # already a _QUANT multiple (plan_pad/width_extent)
    kernel = partial(
        _kernel,
        span=span,
        wext=wext,
        row_stride=row_stride,
        n_widths=n_widths,
        interpret=interpret,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d, tpad // span),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec(
                (None, span), lambda dd, gg, *_: (dd, gg),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (None, span), lambda dd, gg, *_: (dd, gg),
                memory_space=pltpu.VMEM,
            ),
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
            jax.ShapeDtypeStruct((d, tpad), jnp.float32),
            jax.ShapeDtypeStruct((d, tpad), jnp.int32),
        ],
        interpret=interpret,
    )


def boxcar_best_pallas(
    csum_pad: jnp.ndarray,  # (D, tpad + wext) from prefix_sum_padded
    widths: tuple[int, ...],
    scales: np.ndarray,
    nvalid: int,
    tpad: int,
    *,
    span: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """VMEM-resident width sweep; bitwise equal to boxcar_best_twin.
    ``span`` must divide ``tpad`` (both from ops.singlepulse.plan_pad);
    the row length tpad + wext doubles as the (1024-aligned) flat row
    stride."""
    d, row = csum_pad.shape
    wext = row - tpad
    if tpad % span or row % _QUANT or wext <= int(max(widths)):
        raise ValueError(
            f"boxcar_best_pallas: incompatible geometry tpad={tpad} "
            f"span={span} wext={wext} widths<={max(widths)}"
        )
    fn = _build(d, tpad, span, wext, len(widths), interpret)
    return fn(
        jnp.asarray(widths, dtype=jnp.int32),
        jnp.asarray(scales, dtype=jnp.float32),
        jnp.asarray([nvalid], dtype=jnp.int32),
        csum_pad.reshape(-1),
    )
