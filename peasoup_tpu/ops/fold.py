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

from .resample import resample_accel_quadratic


# the folder's candidate rows per DM group are padded to a multiple of
# this, so one compiled program serves every group
FOLD_BUCKET = 8


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


@partial(jax.jit, static_argnames=("span", "nbins", "nints"))
def fold_accel_quadratic(
    x: jnp.ndarray,  # (N,) f32 series shared by the batch (one DM trial)
    afs: jnp.ndarray,  # (K,) f32 acceleration factors a*tsamp/2c
    phase_bins: jnp.ndarray,  # (K, used) from fold_bins_np per row
    *,
    span: int | None,
    nbins: int,
    nints: int,
) -> jnp.ndarray:
    """Resample ``x`` at each row's acceleration by the folder's
    quadratic map and fold the row -> (K, nints, nbins), in one program.

    Bitwise equal to :func:`~.resample.resample_accel_quadratic` per row
    followed by :func:`fold_time_series`. ``span``
    (:func:`~.resample.quadratic_span`) says how each row's samples are
    picked: s >= 0, each sample is a select among the 2s+1 edge-padded
    shifted views of ``x`` (x[clip(i + shift, 0, N-1)], as
    :func:`~.resample.resample_select`; at 0 the one view is ``x``
    itself); None, the index-map gather. The select fuses into the
    fold's reduction, so only the gather path writes a (K, N) resampled
    copy to device memory."""
    n = x.shape[-1]
    k, used = phase_bins.shape
    if span is None:
        xr = jax.vmap(resample_accel_quadratic, in_axes=(None, 0))(x, afs)
        xr = xr[:, :used]
    else:
        # resample_accel_quadratic's f32 chain, over the folded samples
        half = jnp.float32(n) / 2.0
        idx = jnp.arange(used, dtype=jnp.float32)
        quad = (idx - half) ** 2 - half * half
        shift = jnp.rint(afs[:, None] * quad).astype(jnp.int32)
        xp = jnp.pad(x, (span, span), mode="edge")
        xr = jnp.zeros((k, used), x.dtype)
        for s in range(-span, span + 1):
            arm = xp[span + s : span + s + used]
            xr = jnp.where(shift == jnp.int32(s), arm, xr)
    return fold_time_series(xr, phase_bins, nbins=nbins, nints=nints)


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


def _param_fold_accel_quadratic(ctx):
    # one DM group of the folder at the bucket's fold geometry, at span
    # 0 (taken whenever every row's shift rounds to 0)
    if ctx.fold_nsamps <= 0:
        return None
    k = FOLD_BUCKET
    used = ctx.fold_nints * (ctx.fold_nsamps // ctx.fold_nints)
    if used <= 0:
        return None
    return (
        fold_accel_quadratic,
        (
            sds((ctx.fold_nsamps,), "float32"),
            sds((k,), "float32"),
            sds((k, used), phase_bin_dtype(ctx.fold_nbins)),
        ),
        {"span": 0, "nbins": ctx.fold_nbins, "nints": ctx.fold_nints},
    )


register_program(
    "ops.fold.fold_accel_quadratic",
    lambda: (
        fold_accel_quadratic,
        (
            sds((1024,), "float32"),
            sds((4,), "float32"),
            sds((4, 1024), "uint8"),
        ),
        {"span": 2, "nbins": 16, "nints": 4},
    ),
    param=_param_fold_accel_quadratic,
)
