"""Time-series folding into (subint, phase-bin) profiles.

Reference: fold_time_series_kernel — one CUDA block per subint builds a
shared-memory phase histogram with atomicAdd, phase from
frac(jj*tsamp/period)*nbins in f64, and a count array initialised to 1
(an off-by-one bias kept for parity; src/kernels.cu:597-651).

TPU design: the phase->bin map is data-independent integer-valued
metadata; it is computed EXACTLY in host f64 (TPU f64 is emulated and
slow) and shipped as an i32 array, while the fold itself is an on-device
segment-sum — which batches naturally over many candidates (the
reference's abandoned fold_subintegration_kernel intent,
src/folding_kernels.cu).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def fold_bins_np(
    nsamps: int, tsamp: float, period: float, nbins: int, nints: int
) -> np.ndarray:
    """Exact (f64) flattened (subint*nbins + phase_bin) index per sample.

    Samples beyond nints*(nsamps//nints) are dropped, like the kernel's
    per-block ranges. Returns (nints*(nsamps//nints),) int32.
    """
    nsps = nsamps // nints
    used = nsps * nints
    jj = np.arange(used, dtype=np.float64)
    frac = np.mod(jj * (tsamp / period), 1.0)
    bins = np.floor(frac * nbins).astype(np.int32)
    subs = (np.arange(used) // nsps).astype(np.int32)
    return subs * nbins + bins


@partial(jax.jit, static_argnames=("nbins", "nints"))
@jax.named_scope("fold.profile")
def fold_time_series(
    x: jnp.ndarray,  # (..., used_nsamps) resampled time series
    flat_bins: jnp.ndarray,  # (..., used_nsamps) int32 from fold_bins_np
    *,
    nbins: int,
    nints: int,
) -> jnp.ndarray:
    """Segment-sum fold -> (..., nints, nbins), value = sum/(1+hits)."""
    nseg = nints * nbins

    def one(xi, bi):
        sums = jax.ops.segment_sum(xi, bi, num_segments=nseg)
        counts = jax.ops.segment_sum(jnp.ones_like(xi), bi, num_segments=nseg)
        return (sums / (counts + 1.0)).reshape(nints, nbins)

    batch = x.shape[:-1]
    if batch:
        flat = x.reshape(-1, x.shape[-1])
        fb = flat_bins.reshape(-1, x.shape[-1])
        out = jax.vmap(one)(flat, fb)
        return out.reshape(*batch, nints, nbins)
    return one(x, flat_bins)


def fold_time_series_np(
    x: np.ndarray, nsamps: int, tsamp: float, period: float, nbins: int, nints: int
) -> np.ndarray:
    """NumPy f64 oracle of the CUDA fold, count-bias included."""
    flat = fold_bins_np(nsamps, tsamp, period, nbins, nints)
    used = len(flat)
    sums = np.bincount(flat, weights=x[:used].astype(np.float64), minlength=nints * nbins)
    counts = np.bincount(flat, minlength=nints * nbins) + 1.0
    return (sums / counts).reshape(nints, nbins)


# --- audit registry: representative shape plus a ShapeCtx hook at a
# bucket's fold geometry (pipeline.folder.fold_geometry rides the ctx
# as fold_nsamps/fold_nbins/fold_nints) ---
from .registry import register_program, sds  # noqa: E402


def _param_fold_time_series(ctx):
    if ctx.fold_nsamps <= 0:
        return None
    used = ctx.fold_nints * (ctx.fold_nsamps // ctx.fold_nints)
    if used <= 0:
        return None
    return (
        fold_time_series,
        (sds((used,), "float32"), sds((used,), "int32")),
        {"nbins": ctx.fold_nbins, "nints": ctx.fold_nints},
    )


register_program(
    "ops.fold.fold_time_series",
    lambda: (
        fold_time_series,
        (sds((1024,), "float32"), sds((1024,), "int32")),
        {"nbins": 16, "nints": 4},
    ),
    param=_param_fold_time_series,
)
