"""Real FFT for the per-accel hot path: a packed-real four-step matmul
rfft that beats XLA's TPU FFT on both axes.

XLA lowers TPU FFTs to matmul passes too, but its radix-128
decomposition for a 2^17-point real transform moves ~18.5 MB/trial in
transpose/copy passes (measured by trace `raw_bytes_accessed`,
NOTES.md) and its accuracy is the known TPU-FFT ~1e-5..1e-3 envelope.
This formulation packs the real series into a half-length complex
sequence (z[m] = x[2m] + i*x[2m+1]), runs ONE four-step complex DFT
(two dense (sqrt(M), sqrt(M)) MXU einsums at Precision.HIGHEST with a
twiddle multiply between), and untwists to the true rfft bins.
Measured on v5e at (1416, 131072): 27.8 ms device vs 48.5 ms for
jnp.fft.rfft — 1.75x — with max rel error 1.4e-6 vs the f64 oracle
(~35x tighter than stock, which also tightens candidate S/N parity).

The search runs it on TPU, feeding the interbin kernel
(ops/pallas/interbin.py), for power-of-two lengths >= _MIN_N
(pipeline/accel_search.py:search_route); elsewhere, and on CPU where
its O(N^1.5) arithmetic would bury pocketfft, it runs jnp.fft.rfft.
:func:`rfft_pow2_matmul_parts` is the jnp twin the kernels' probes
check against.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

_MIN_N = 1 << 14

# Matmul precision for the packed four-step DFT einsums. The chain is
# layout-bound, so HIGH would buy only ~3 ms while perturbing the S/N
# chain the acc-tie parity analysis is anchored to (NOTES.md round-4
# continuation).
_PRECISION = jax.lax.Precision.HIGHEST


@lru_cache(maxsize=None)
def _plan(n: int):
    """DFT/twiddle/untwist constants for the packed four-step rfft of a
    pow2 length ``n``: M = n/2 = N1*N2 with N1 = 2^floor(log2(sqrt(M)))."""
    m = n // 2
    n1 = 1 << ((m.bit_length() - 1) // 2)
    n2 = m // n1
    w1 = np.exp(-2j * np.pi * np.outer(np.arange(n1), np.arange(n1)) / n1)
    w2 = np.exp(-2j * np.pi * np.outer(np.arange(n2), np.arange(n2)) / n2)
    tw = np.exp(-2j * np.pi * np.outer(np.arange(n1), np.arange(n2)) / m)
    k = np.arange(m + 1)
    # untwist phasor e^{-i theta_k} = unc - i*uns (uns = +sin theta_k)
    un = np.exp(-2j * np.pi * k / n)
    return {
        "n1": n1,
        "n2": n2,
        "d1r": np.ascontiguousarray(w1.real, np.float32),
        "d1i": np.ascontiguousarray(w1.imag, np.float32),
        "d2r": np.ascontiguousarray(w2.real, np.float32),
        "d2i": np.ascontiguousarray(w2.imag, np.float32),
        "twr": np.ascontiguousarray(tw.real, np.float32),
        "twi": np.ascontiguousarray(tw.imag, np.float32),
        "unc": np.ascontiguousarray(un.real, np.float32),
        "uns": np.ascontiguousarray(-un.imag, np.float32),
    }


def packed_dft_z(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The matmul four-step half-length packed complex DFT: returns
    (zr, zi), each (R, n//2) f32 with the batch flattened, Z in natural
    bin order. The untwist to rfft bins is left to the caller — either
    the jnp formulas below or the fused Pallas interbin kernel
    (ops/pallas/interbin.py)."""
    m = x.shape[-1] // 2
    # materialise the input ONCE: without the barrier XLA fuses the
    # producer chain (e.g. the resample select) separately into the
    # even- and odd-sample operands, computing it twice (measured:
    # resample_select 1.9 -> 94 ms when this fed the deinterleave)
    x = jax.lax.optimization_barrier(x.astype(jnp.float32))
    z = x.reshape(-1, m, 2)
    return packed_dft_z_parts(z[..., 0], z[..., 1])


def packed_dft_z_parts(
    xe: jnp.ndarray, xo: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`packed_dft_z` on pre-deinterleaved even/odd sample planes
    (..., n//2) — producers that can emit the planes directly (e.g.
    resample_select_packed) skip the stride-2 relayout entirely."""
    # one joint barrier: each plane feeds two einsum operands, and
    # without it XLA would fuse (= recompute) the producer chain into
    # every operand (see packed_dft_z)
    xe, xo = jax.lax.optimization_barrier((xe, xo))
    m = xe.shape[-1]
    n = 2 * m
    p = _plan(n)
    n1, n2 = p["n1"], p["n2"]
    P = _PRECISION
    d1r, d1i = jnp.asarray(p["d1r"]), jnp.asarray(p["d1i"])
    d2r, d2i = jnp.asarray(p["d2r"]), jnp.asarray(p["d2i"])
    twr, twi = jnp.asarray(p["twr"]), jnp.asarray(p["twi"])

    ar = xe.reshape(-1, n1, n2)  # A[j1, j2] = z[j1*n2 + j2]
    ai = xo.reshape(-1, n1, n2)
    # step 1: DFT over j1 (columns)  C[k1, j2] = sum_j1 W1[k1,j1] A[j1,j2]
    f1 = lambda D, A: jnp.einsum("lj,rjm->rlm", D, A, precision=P)
    cr = f1(d1r, ar) - f1(d1i, ai)
    ci = f1(d1r, ai) + f1(d1i, ar)
    # step 2: twiddle W_M^{k1*j2}
    tr = cr * twr - ci * twi
    ti = cr * twi + ci * twr
    # step 3: DFT over j2, emitted K2-MAJOR so the flat k = k1 + N1*k2
    # order falls out of a plain reshape (no transpose pass)
    f2 = lambda A, D: jnp.einsum("rlj,jk->rkl", A, D, precision=P)
    er = f2(tr, d2r) - f2(ti, d2i)
    ei = f2(tr, d2i) + f2(ti, d2r)
    zr = er.reshape(-1, m)  # (r, k2, k1) -> k = k1 + N1*k2
    zi = ei.reshape(-1, m)
    return zr, zi


def rfft_pow2_matmul_parts(
    x: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """rfft via the packed four-step matmul DFT, returned as lazy
    (re, im) f32 parts so elementwise consumers (interbin) fuse with
    the untwist instead of reading a materialised complex array."""
    n = x.shape[-1]
    m = n // 2
    p = _plan(n)
    batch = x.shape[:-1]
    zr, zi = packed_dft_z(x)

    # untwist the packed transform to the real-input spectrum:
    # X[k] = (Z[k] + conj(Z[M-k]))/2 - i/2 e^{-2pi i k/n}(Z[k] - conj(Z[M-k]))
    zkr = jnp.concatenate([zr, zr[..., :1]], axis=-1)  # Z[k], k = 0..M
    zki = jnp.concatenate([zi, zi[..., :1]], axis=-1)
    zmr = jnp.concatenate([zr[..., :1], zr[..., ::-1]], axis=-1)  # Z[M-k]
    zmi = jnp.concatenate([zi[..., :1], zi[..., ::-1]], axis=-1)
    arr = 0.5 * (zkr + zmr)
    aii = 0.5 * (zki - zmi)
    br = zkr - zmr
    bi = zki + zmi
    c = jnp.asarray(p["unc"])
    s = jnp.asarray(p["uns"])
    xr = arr + 0.5 * (c * bi - s * br)
    xi = aii - 0.5 * (c * br + s * bi)
    return xr.reshape(*batch, m + 1), xi.reshape(*batch, m + 1)
