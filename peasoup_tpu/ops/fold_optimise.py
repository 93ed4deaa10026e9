"""Fold optimisation (mini-PDMP): phase-shift x boxcar-template matched
filtering of folded subintegrations.

Reference: FoldOptimiser (include/transforms/folder.hpp:65-335) and its
kernels (src/kernels.cu:653-865). Pipeline per fold:
  FFT subints along phase -> multiply by nshifts linear phase ramps
  (subint-proportional shift) -> collapse subints -> multiply by
  ntemplates FFT'd boxcars (/ sqrt(width), bin0 zeroed) -> inverse FFT
  -> |.| -> 3-D argmax (template, shift, bin) -> S/N from on/off-pulse
  statistics of the recovered profile.

TPU design: everything becomes a handful of batched einsum/FFT ops on
(K, nshifts, nints, nbins) tensors — K candidates are optimised in ONE
jitted call instead of the reference's one-candidate-at-a-time loop.
Quirks preserved for parity: the (32 - opt_shift) period-update constant
(folder.hpp:330, assumes nbins=64), calculate_sn's width coming from the
0-based template index, and S/N values > 99999 squashed to 0.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _shift_array(nbins: int, nints: int) -> np.ndarray:
    """(nshifts, nints, nbins) complex64 phase ramps (kernels.cu:665-684)."""
    nshifts = nbins
    shift_mags = np.arange(nshifts, dtype=np.float64) - nshifts // 2
    subint = np.arange(nints, dtype=np.float64)
    b = np.arange(nbins, dtype=np.float64)
    ramp = b * 2.0 * np.pi / nbins
    ramp = np.where(b > nbins / 2, ramp - 2.0 * np.pi, ramp)
    shift = (subint / nints)[None, :, None] * shift_mags[:, None, None]
    return np.exp(-1j * ramp[None, None, :] * shift).astype(np.complex64)


def _templates_fft(nbins: int) -> tuple[np.ndarray, int]:
    """FFT'd boxcar templates (ntemplates, nbins) (kernels.cu:686-696)."""
    ntemplates = nbins - 1
    w = np.arange(ntemplates)[:, None]
    b = np.arange(nbins)[None, :]
    boxcars = (b <= w).astype(np.complex64)
    return np.fft.fft(boxcars, axis=-1).astype(np.complex64), ntemplates


@partial(jax.jit, static_argnames=("nbins", "nints"))
def _optimise_device(
    folds: jnp.ndarray,  # (K, nints, nbins) float32
    shiftar_re: jnp.ndarray,  # (nshifts, nints, nbins) float32
    shiftar_im: jnp.ndarray,
    templates_re: jnp.ndarray,  # (ntemplates, nbins) float32
    templates_im: jnp.ndarray,
    *,
    nbins: int,
    nints: int,
):
    # complex tables are shipped as re/im pairs (f32 host<->device
    # transfers only)
    shiftar = jax.lax.complex(shiftar_re, shiftar_im)
    templates = jax.lax.complex(templates_re, templates_im)
    nshifts = nbins
    f = jnp.fft.fft(folds.astype(jnp.complex64), axis=-1)  # (K, I, B)
    shifted = f[:, None, :, :] * shiftar[None, :, :, :]  # (K, S, I, B)
    profiles = shifted.sum(axis=2)  # (K, S, B) collapse subints
    width = jnp.sqrt(jnp.arange(1, templates.shape[0] + 1, dtype=jnp.float32))
    final = (
        profiles[:, None, :, :]
        * templates[None, :, None, :]
        / width[None, :, None, None]
    )  # (K, W, S, B)
    final = final.at[..., 0].set(0.0)  # bin0 zeroed (kernels.cu:741-742)
    # cuFFT INVERSE is unnormalised; only |.| feeds argmax, so the
    # constant nbins factor is irrelevant here.
    tdom = jnp.abs(jnp.fft.ifft(final, axis=-1))
    flat = tdom.reshape(tdom.shape[0], -1)
    argmax = jnp.argmax(flat, axis=-1).astype(jnp.int32)
    opt_template = argmax // (nbins * nshifts)
    opt_bin = argmax % nbins - opt_template // 2
    opt_shift = (argmax // nbins) % nbins
    # Recover optimal subints and profile (unnormalised inverse -> *nbins
    # to match the reference's stored fold amplitudes).
    k = jnp.arange(folds.shape[0])
    opt_subs = (
        jnp.fft.ifft(shifted[k, opt_shift], axis=-1).real * nbins
    )  # (K, I, B)
    opt_prof = jnp.fft.ifft(profiles[k, opt_shift], axis=-1).real * nbins  # (K, B)
    return opt_template, opt_bin, opt_shift, opt_subs, opt_prof


def calculate_sn(
    prof: np.ndarray, bin: int, width: int, nbins: int
) -> tuple[float, float]:
    """On/off-pulse S/N of a profile (folder.hpp:140-183).

    ``width`` is the 0-based template index, as passed by the reference's
    optimise() (folder.hpp:311). Negative centred indices wrap positively
    here (the reference's C % would go out of bounds — UB we do not copy).
    """
    edge = int(width * 0.3 + 0.5)
    width_by_2 = int(width / 2.0 + 0.5)
    rprof = np.array(
        [prof[(bin - nbins // 2 + ii) % nbins] for ii in range(nbins)],
        dtype=prof.dtype,
    )
    centre = nbins // 2 - 1
    upper = centre + (width_by_2 + edge)
    lower = centre - (width_by_2 + edge)
    sel = (np.arange(nbins) <= upper) & (np.arange(nbins) >= lower)
    on, off = rprof[sel], rprof[~sel]
    on_mean = on.mean()
    off_mean = off.mean()
    off_std = np.sqrt(np.mean((off - off_mean) ** 2))
    with np.errstate(divide="ignore", invalid="ignore"):
        sn1 = (on_mean - off_mean) * np.sqrt(width) / off_std
        sn2 = ((rprof - off_mean) / off_std).sum() / np.sqrt(width)
    sn1 = 0.0 if not np.isfinite(sn1) or sn1 > 99999 else float(sn1)
    sn2 = 0.0 if not np.isfinite(sn2) or sn2 > 99999 else float(sn2)
    return sn1, sn2


class FoldOptimiser:
    """Batched fold optimiser; one device call for K candidates."""

    def __init__(self, nbins: int = 64, nints: int = 16):
        self.nbins = nbins
        self.nints = nints
        shiftar = _shift_array(nbins, nints)
        self.shiftar_re = jnp.asarray(np.real(shiftar).astype(np.float32))
        self.shiftar_im = jnp.asarray(np.imag(shiftar).astype(np.float32))
        templates, self.ntemplates = _templates_fft(nbins)
        self.templates_re = jnp.asarray(np.real(templates).astype(np.float32))
        self.templates_im = jnp.asarray(np.imag(templates).astype(np.float32))

    def optimise(
        self, folds: np.ndarray, periods: np.ndarray, tobs
    ) -> list[dict]:
        """Optimise K folded candidates.

        Args:
          folds: (K, nints, nbins) fold profiles.
          periods: (K,) trial periods in seconds.
          tobs: observation length (seconds) — a scalar, or a (K,)
            array when the batch mixes observations of different
            lengths (the survey folder's cross-observation batches).

        Returns one dict per candidate: opt_sn, opt_period, opt_width,
        opt_bin, opt_fold (nints, nbins), opt_prof (nbins,).
        """
        folds = jnp.asarray(np.asarray(folds, dtype=np.float32))
        opt_template, opt_bin, opt_shift, opt_subs, opt_prof = _optimise_device(
            folds,
            self.shiftar_re,
            self.shiftar_im,
            self.templates_re,
            self.templates_im,
            nbins=self.nbins,
            nints=self.nints,
        )
        opt_template = np.asarray(opt_template)
        opt_bin = np.asarray(opt_bin)
        opt_shift = np.asarray(opt_shift)
        opt_subs = np.asarray(opt_subs)
        opt_prof = np.asarray(opt_prof)
        tobs_k = np.broadcast_to(
            np.asarray(tobs, dtype=np.float64), (folds.shape[0],)
        )
        results = []
        for k in range(folds.shape[0]):
            sn1, sn2 = calculate_sn(
                opt_prof[k], int(opt_bin[k]), int(opt_template[k]), self.nbins
            )
            p = float(periods[k])
            opt_period = p * (((32.0 - float(opt_shift[k])) * p) / (self.nbins * float(tobs_k[k])) + 1.0)
            results.append(
                dict(
                    opt_sn=max(sn1, sn2),
                    opt_period=opt_period,
                    opt_width=int(opt_template[k]) + 1,
                    opt_bin=int(opt_bin[k]),
                    opt_shift=int(opt_shift[k]),
                    opt_fold=opt_subs[k],
                    opt_prof=opt_prof[k],
                )
            )
        return results


# --- audit registry: the shift/template operands come from the module's
# own host precompute (tiny at nbins=32) so the registered shapes stay
# consistent with the builders ---
from .registry import register_program  # noqa: E402


def _example_optimise(batch: int = 2, nbins: int = 32, nints: int = 8):
    import jax

    shiftar = _shift_array(nbins, nints)
    templates, _ = _templates_fft(nbins)
    return (
        _optimise_device,
        (
            jax.ShapeDtypeStruct((batch, nints, nbins), np.float32),
            shiftar.real.astype(np.float32),
            shiftar.imag.astype(np.float32),
            templates.real.astype(np.float32),
            templates.imag.astype(np.float32),
        ),
        {"nbins": nbins, "nints": nints},
    )


def _param_optimise(ctx):
    # candidate-level program: the fold bucket sets its geometry; the
    # candidate batch is rung-independent but bounded by fold_batch
    if ctx.fold_batch <= 0 or ctx.fold_nsamps <= 0:
        return None
    return _example_optimise(
        batch=max(2, min(ctx.fold_batch, 64)),
        nbins=ctx.fold_nbins,
        nints=ctx.fold_nints,
    )


register_program(
    "ops.fold_optimise.optimise_device", _example_optimise,
    param=_param_optimise,
)
