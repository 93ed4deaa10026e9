"""Time-series folding into (subint, phase-bin) profiles.

Reference: fold_time_series_kernel — one CUDA block per subint builds a
shared-memory phase histogram with atomicAdd, phase from
frac(jj*tsamp/period)*nbins in f64, and a count array initialised to 1
(an off-by-one bias kept for parity; src/kernels.cu:597-651).

TPU design: the phase->bin map is data-independent integer-valued
metadata; it is computed EXACTLY in host f64 (TPU f64 is emulated and
slow) and shipped as one unsigned byte per sample (up to 256 bins). The
subint needs no table: subint i is the contiguous block i of
``used // nints`` samples. So the fold is dense — each (subint, bin)
cell is a compare-select-sum over its subint's block, which the TPU
compiler fuses into one pass that never writes the one-hot to memory —
with no scatter (a serial update loop on a TPU), and it batches over
many candidates (the reference's abandoned fold_subintegration_kernel
intent, src/folding_kernels.cu).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def phase_bin_dtype(nbins: int) -> np.dtype:
    """The bin table's dtype: the smallest unsigned integer that holds
    nbins - 1 (uint8 up to 256 bins)."""
    return np.min_scalar_type(nbins - 1)


def fold_bins_np(
    nsamps: int, tsamp: float, period: float, nbins: int, nints: int
) -> np.ndarray:
    """Exact (f64) phase bin of each sample.

    Samples beyond nints*(nsamps//nints) are dropped, like the kernel's
    per-block ranges. Returns (nints*(nsamps//nints),) of
    :func:`phase_bin_dtype`.
    """
    used = nints * (nsamps // nints)
    jj = np.arange(used, dtype=np.float64)
    frac = np.mod(jj * (tsamp / period), 1.0)
    return np.floor(frac * nbins).astype(phase_bin_dtype(nbins))


@partial(jax.jit, static_argnames=("nbins", "nints"))
@jax.named_scope("fold.profile")
def fold_time_series(
    x: jnp.ndarray,  # (..., used_nsamps) resampled time series
    phase_bins: jnp.ndarray,  # (..., used_nsamps) from fold_bins_np
    *,
    nbins: int,
    nints: int,
) -> jnp.ndarray:
    """Dense fold -> (..., nints, nbins), value = sum/(1+hits)."""
    blocks = (*x.shape[:-1], nints, 1, x.shape[-1] // nints)
    hit = phase_bins.reshape(blocks) == jnp.arange(
        nbins, dtype=phase_bins.dtype
    )[:, None]
    sums = jnp.where(hit, x.reshape(blocks), 0.0).sum(axis=-1)
    hits = hit.sum(axis=-1, dtype=x.dtype)
    return sums / (hits + 1.0)


def fold_time_series_np(
    x: np.ndarray, nsamps: int, tsamp: float, period: float, nbins: int, nints: int
) -> np.ndarray:
    """NumPy f64 oracle of the CUDA fold, count-bias included."""
    bins = fold_bins_np(nsamps, tsamp, period, nbins, nints)
    used = len(bins)
    flat = np.arange(used) // (used // nints) * nbins + bins
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
        (sds((used,), "float32"), sds((used,), phase_bin_dtype(ctx.fold_nbins))),
        {"nbins": ctx.fold_nbins, "nints": ctx.fold_nints},
    )


register_program(
    "ops.fold.fold_time_series",
    lambda: (
        fold_time_series,
        (sds((1024,), "float32"), sds((1024,), "uint8")),
        {"nbins": 16, "nints": 4},
    ),
    param=_param_fold_time_series,
)
