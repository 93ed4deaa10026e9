"""Survey-scale batched folding: many candidates, one device program.

The per-observation folder (:mod:`peasoup_tpu.pipeline.folder`) batches
candidates *within* one DM trial: every group shares a single
dereddened series, so its resample vmaps over accelerations only. At
campaign scale the unit of work inverts — thousands of candidates from
*different* observations and DM trials fold together (PulsarX,
arXiv:2309.02544: survey throughput hinges on bulk folding) — so this
program carries one dereddened series **per row**: each row resamples
its own series at its own acceleration factor and folds through its own
phase-bin map. Row independence makes the result bitwise-identical to
the per-observation path on the same candidate (pinned by
tests/test_sift.py), while the fixed ``(batch, nsamps)`` shape lets the
sift service stream the whole campaign DB through ONE compiled program
per shape bucket with zero steady-state recompiles.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .fold import fold_time_series, phase_bin_dtype
from .resample import resample_accel_quadratic


@partial(jax.jit, static_argnames=("nbins", "nints"))
def survey_fold_batch(
    xd: jnp.ndarray,  # (B, N) f32 dereddened series, one per candidate
    afs: jnp.ndarray,  # (B,) f32 acceleration factors (a*tsamp/2c)
    phase_bins: jnp.ndarray,  # (B, used) from fold_bins_np per row
    *,
    nbins: int,
    nints: int,
) -> jnp.ndarray:
    """Resample + fold a batch of candidates -> (B, nints, nbins).

    Exactly the folder's per-candidate chain (quadratic resample then
    the dense fold with the reference's 1+hits count bias), just
    batched with per-row series instead of a shared one.
    """
    xr = jax.vmap(resample_accel_quadratic)(xd, afs)  # (B, N)
    used = phase_bins.shape[-1]
    return fold_time_series(
        xr[:, :used], phase_bins, nbins=nbins, nints=nints
    )


# --- audit registry: the representative shapes are tiny; the ShapeCtx
# hook rebuilds at the sift service's production fold bucket (batch x
# power-of-two series length) so campaign warmup covers it ---
from .registry import register_program, sds  # noqa: E402


def _param_survey_fold(ctx):
    if ctx.fold_batch <= 0 or ctx.fold_nsamps <= 0:
        return None
    used = ctx.fold_nints * (ctx.fold_nsamps // ctx.fold_nints)
    return (
        survey_fold_batch,
        (
            sds((ctx.fold_batch, ctx.fold_nsamps), "float32"),
            sds((ctx.fold_batch,), "float32"),
            sds((ctx.fold_batch, used), phase_bin_dtype(ctx.fold_nbins)),
        ),
        {"nbins": ctx.fold_nbins, "nints": ctx.fold_nints},
    )


register_program(
    "ops.survey_fold.survey_fold_batch",
    lambda: (
        survey_fold_batch,
        (
            sds((4, 1024), "float32"),
            sds((4,), "float32"),
            sds((4, 1024), "uint8"),
        ),
        {"nbins": 16, "nints": 4},
    ),
    param=_param_survey_fold,
)
