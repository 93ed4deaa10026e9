"""Incoherent harmonic summing of power spectra.

Reference: harmonic_sum_kernel (src/kernels.cu:33-208) produces, for
fold level h in 1..5, sum_{k=1..2^h} p[(int)(i*k/2^h + 0.5)] scaled by
rsqrt(2^h), accumulating across levels (level h reuses level h-1's sum
and adds only the odd-k/2^h gathers).

TPU design. The reference's float index expression (int)(i*k/2^h + 0.5)
is EXACT integer math: (i*k + 2^(h-1)) >> h (the double value is exactly
representable, truncation == floor). Two implementations:

* ``method="take"``: direct batched jnp.take gathers — the oracle of
  the tests and of the harmonic-peaks kernel's probe.
* ``method="conv"`` (default, the search's path off TPU): every (h, k)
  gather is a STRIDED 1-D CONVOLUTION. At output period P = 128 (one
  full lane vector), i = q*P + r: src(i) = q*s + c_r with
  s = P*k >> h (integral for h <= 7) and c_r = (r*k + 2^(h-1)) >> h
  <= s. So the gather is conv_general_dilated(p[None, :, None], W,
  stride=s, VALID) with the (s+1, 1, P) one-hot taps W[c_r, 0, r] = 1:
  conv windows overlap natively, the MXU contraction is the window
  (s+1 <= 121), and the (Q, P) output merges to natural bin order for
  FREE because P is exactly the lane width. Because each tap column is
  one-hot the result is the exact gather value (zeros add exactly),
  and gathers are summed one `+` at a time in reference order, so
  "conv" is bitwise-identical to "take" (tests assert it). It needs
  Precision.HIGHEST on the spectrum operand: only the 3-term bf16
  split (24 mantissa bits) keeps the gathered values exact; HIGH's
  2-term split loses the low 8 mantissa bits (measured ~5e-6 rel
  error).

On TPU the search sums harmonics inside the harmonic-peaks kernel
(ops/pallas/harmpeaks.py) instead.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np


_CONV_P = 128  # conv output period = the f32 lane width


@lru_cache(maxsize=None)
def _conv_taps(k: int, h: int) -> np.ndarray:
    """(s+1, 1, P) one-hot conv filter with W[c_r, 0, r] = 1,
    c_r = (r*k + 2^(h-1)) >> h, s = P*k >> h."""
    s = (_CONV_P * k) >> h
    r = np.arange(_CONV_P)
    c_r = (r * k + (1 << (h - 1))) >> h
    W = np.zeros((s + 1, 1, _CONV_P), dtype=np.float32)
    W[c_r, 0, r] = 1.0
    return W


def _gather_conv(x: jnp.ndarray, Q: int, k: int, h: int) -> jnp.ndarray:
    """out[..., i] = p[..., (i*k + 2^(h-1)) >> h] for i < Q*P via one
    strided conv. ``x`` is the padded spectrum as (rows, >=Q*s+1, 1)."""
    s = (_CONV_P * k) >> h
    # per-operand precision: the spectrum operand needs the full bf16x3
    # split (HIGHEST) for exactness, but the TAPS are one-hot — exactly
    # representable in ONE bf16 term — so DEFAULT on that side halves
    # the MXU pass count while staying BITWISE equal (each output is a
    # single 1.0*x product; measured equal on v5e, gated by the
    # bitwise ==take twin tests)
    g = jax.lax.conv_general_dilated(
        x, jnp.asarray(_conv_taps(k, h)),
        window_strides=(s,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        precision=(jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT),
    )
    return g[:, :Q]  # (rows, Q, P)


@partial(jax.jit, static_argnames=("nharms", "method", "scaled"))
def harmonic_sums(
    p: jnp.ndarray, *, nharms: int = 4, method: str = "conv",
    scaled: bool = True,
) -> list[jnp.ndarray]:
    """Cumulative fractional-harmonic sums of a spectrum.

    Args:
      p: (..., nbins) float32 spectrum (normalised).
      nharms: number of fold levels (<= 5, like the unrolled kernel).
      method: "conv" (one strided conv per (level, harmonic)) or
        "take" (direct gather); bitwise-identical.
      scaled: apply the reference's rsqrt(2^h) per-level factor here.
        False skips it for consumers that scale downstream.

    Returns a list of ``nharms`` arrays shaped like ``p``; entry h-1 is
    the 2^h-harmonic sum, scaled by rsqrt(2^h) unless ``scaled=False``.
    """
    if not 0 < nharms <= 5:
        raise ValueError("nharms must be in 1..5")
    nbins = p.shape[-1]

    def lvl_out(val, h):
        return val * jnp.float32(2.0 ** (-h / 2.0)) if scaled else val

    if method == "conv":
        P = _CONV_P
        npad = -(-nbins // P) * P
        Q = npad // P
        # src indices for i < nbins stay < nbins, so zero pad is inert
        # for the real bins (the pad region's outputs are sliced away)
        pp = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(0, npad + 1 - nbins)])
        x = pp.reshape(-1, pp.shape[-1], 1)
        # accumulate IN THE CONV OUTPUT BLOCK SPACE (rows, Q, P): every
        # (h, k) conv emits the same (q, lane) -> bin q*P+lane order, so
        # the val chain needs no per-gather reshape/slice — XLA fuses
        # each add into its conv — and only the nharms level outputs pay
        # a (free, contiguous) flatten.  Add ORDER per element is
        # unchanged, so results stay bitwise identical to "take".
        val = pp[..., :npad].reshape(-1, Q, P)
        out = []
        for h in range(1, nharms + 1):
            for k in range(1, 1 << h, 2):  # odd: new gathers this level
                val = val + _gather_conv(x, Q, k, h)
            flat = val.reshape(*p.shape[:-1], npad)[..., :nbins]
            out.append(lvl_out(flat, h))
        return out
    if method == "take":
        i = jnp.arange(nbins, dtype=jnp.int32)
        out = []
        val = p
        for h in range(1, nharms + 1):
            half = 1 << (h - 1)
            for k in range(1, 1 << h, 2):  # odd: new gathers this level
                src = (i * k + half) >> h
                val = val + jnp.take(p, src, axis=-1)
            out.append(lvl_out(val, h))
        return out
    raise ValueError(f"unknown method {method!r}")


# --- audit registry (the ShapeCtx hook rebuilds the conv chain at a
# periodicity bucket's production tile and fold count) ---
from .registry import register_program, sds  # noqa: E402


def _param_harmonic_sums(ctx):
    if ctx.fft_size <= 0 or ctx.accel_pad <= 0:
        return None
    return (
        harmonic_sums,
        (
            sds(
                (ctx.dm_block, ctx.accel_pad, ctx.fft_size // 2 + 1),
                "float32",
            ),
        ),
        {"nharms": min(5, max(1, ctx.nharms))},
    )


register_program(
    "ops.harmonics.harmonic_sums",
    lambda: (harmonic_sums, (sds((512,), "float32"),), {"nharms": 4}),
    param=_param_harmonic_sums,
)
