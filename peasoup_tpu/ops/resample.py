"""Time-domain acceleration resampling as an index-map gather.

Reference kernels: resample_kernelII (the search pipeline's version,
out[i] = in[rn(i + i*af*(i-N))], src/kernels.cu:314-346) and the
quadratic resample_kernel (used by the candidate folder,
out[i] = in[rn(i + af*((i-N/2)^2-(N/2)^2))], kernels.cu:308-332), with
af = a*tsamp/(2c) computed in f64 (kernels.cu:354).

TPU design: the reference does the index math per element in f64; TPU
f64 is emulated and slow, so we exploit that the output index is
integer + small shift: rn(i + s) == i + rn(s) for integer i away from
half-sample ties, and the shift s = af*i*(i-N) is computed accurately
in f32 because i and (i-N) are exactly representable (|i| < 2^24) and
af is tiny. Worst-case f32 error in s is ~1e-5 samples — tie-breaking
differences only. Batched over a leading axis of accelerations: one
gather per (accel, sample) tile, MXU-free but VPU/HBM friendly.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SPEED_OF_LIGHT = 299792458.0


def accel_factor(accs: np.ndarray, tsamp: float) -> np.ndarray:
    """af = (a*tsamp) / (2c): the a*tsamp product is an F32 multiply in
    the reference (``float a, float tsamp``, kernels.cu:348-354), the
    division by 2c is f64."""
    prod = (np.asarray(accs, dtype=np.float32) * np.float32(tsamp)).astype(
        np.float32
    )
    return prod.astype(np.float64) / (2.0 * SPEED_OF_LIGHT)


@jax.jit
def resample_accel(x: jnp.ndarray, afs: jnp.ndarray) -> jnp.ndarray:
    """Resample a time series for each acceleration factor.

    Args:
      x: (N,) float32 time series.
      afs: (A,) float32 acceleration factors (a*tsamp/2c).

    Returns (A, N): out[a, i] = x[i + rint(afs[a]*i*(i-N))].
    """
    n = x.shape[-1]
    idx = jnp.arange(n, dtype=jnp.float32)
    quad = idx * (idx - jnp.float32(n))  # exact inputs, one f32 rounding

    def one(af: jnp.ndarray) -> jnp.ndarray:
        shift = jnp.rint(af * quad).astype(jnp.int32)
        src = jnp.clip(jnp.arange(n, dtype=jnp.int32) + shift, 0, n - 1)
        return jnp.take(x, src)

    return jax.vmap(one)(afs)


@partial(jax.jit, static_argnames=("smax",))
def resample_select(
    x: jnp.ndarray,  # (D, N) f32 time series per DM trial
    afs: jnp.ndarray,  # (D, A) f32 acceleration factors a*tsamp/2c
    *,
    smax: int,
) -> jnp.ndarray:
    """Gather-free resampling for small shift spans.

    For physical accelerations the shift s(i) = rint(af*i*(i-N)) spans
    only a handful of integer values over the WHOLE series
    (|s| <= |af|*N^2/4); each output is then a SELECT among 2*smax+1
    shifted copies of x — pure elementwise VPU work at full HBM
    bandwidth instead of a gather. Edge-padding reproduces the
    reference's index clip exactly (x[clip(i+s, 0, N-1)],
    src/kernels.cu:341-345), so results are bitwise identical to
    :func:`resample_accel`. ``smax`` must be >= max|afs|*N^2/4
    (see :func:`select_span`).

    Returns (D, A, N).
    """
    n = x.shape[-1]
    idx = jnp.arange(n, dtype=jnp.float32)
    quad = idx * (idx - jnp.float32(n))  # exact inputs, one f32 rounding
    shift = jnp.rint(afs[..., None] * quad).astype(jnp.int32)  # (D, A, N)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (smax, smax)), mode="edge")
    out = jnp.zeros(shift.shape, jnp.float32)
    for s in range(-smax, smax + 1):
        arm = jax.lax.dynamic_slice_in_dim(xp, smax + s, n, axis=1)  # (D, N)
        out = jnp.where(shift == jnp.int32(s), arm[:, None, :], out)
    return out


@partial(jax.jit, static_argnames=("smax",))
def resample_select_packed(
    x: jnp.ndarray,  # (D, N) f32 time series per DM trial
    afs: jnp.ndarray,  # (D, A) f32 acceleration factors a*tsamp/2c
    *,
    smax: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`resample_select` emitted directly as (even, odd) sample
    planes — the packed matmul rfft's complex deinterleave
    (ops/fft.py:packed_dft_z) costs a stride-2 relayout of the full
    (D, A, N) resample output (~21 ms/dense search on v5e); selecting
    into the two half-length planes costs the same select work and
    makes the relayout FREE (the per-trial input is tiny, so its own
    parity split is noise). Values are BITWISE those of
    resample_select: out_even[..., j] == out[..., 2j],
    out_odd[..., j] == out[..., 2j+1].

    Returns ((D, A, N//2), (D, A, N//2)).
    """
    n = x.shape[-1]
    m = n // 2
    idx = jnp.arange(n, dtype=jnp.float32)
    quad = idx * (idx - jnp.float32(n))  # exact inputs, one f32 rounding
    she = jnp.rint(afs[..., None] * quad[0::2]).astype(jnp.int32)
    sho = jnp.rint(afs[..., None] * quad[1::2]).astype(jnp.int32)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (smax, smax)), mode="edge")
    planes = (xp[:, 0::2], xp[:, 1::2])  # xp[2t], xp[2t+1]
    oute = jnp.zeros(she.shape, jnp.float32)
    outo = jnp.zeros(sho.shape, jnp.float32)
    for s in range(-smax, smax + 1):
        # even output j reads xp[smax + s + 2j]: parity of (smax+s)
        # picks the plane, its half-index the slice offset
        p = smax + s
        arm = jax.lax.dynamic_slice_in_dim(planes[p % 2], p // 2, m, axis=1)
        oute = jnp.where(she == jnp.int32(s), arm[:, None, :], oute)
        p = smax + s + 1  # odd output j reads xp[smax + s + 2j + 1]
        arm = jax.lax.dynamic_slice_in_dim(planes[p % 2], p // 2, m, axis=1)
        outo = jnp.where(sho == jnp.int32(s), arm[:, None, :], outo)
    return oute, outo


@partial(jax.jit, static_argnames=("smax", "n1", "n2"))
def resample_select_packed_planes(
    x: jnp.ndarray,  # (D, N) f32 time series per DM trial
    afs: jnp.ndarray,  # (D, A) f32 acceleration factors a*tsamp/2c
    *,
    smax: int,
    n1: int,
    n2: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`resample_select_packed` emitted directly as the fused DFT
    kernel's (D, A, n1, n2) input planes (flat sample j = j1*n2 + j2,
    row-major — ops/pallas/dftspec.py). Computing the select IN the
    4-D shape matters: a reshape between the 3-D select output and the
    kernel operand changes the XLA tile layout, which materialises as
    two full-plane relayout copy passes (~25 ms at the dense tutorial
    grid, traced r5 — the whole einsum-chain win eaten); here the
    select's one fused loop writes the kernel's tiled layout directly.
    Every arm must stay an index-map view (not a materialised array):
    a per-arm reshape of the flat slice makes XLA materialise the arm
    AND its (D, A, n1, n2) broadcast (traced r5: 12 broadcast passes,
    +12 ms), so the arms are instead STATIC slices of one overlapped-
    window base XB[d, j1, t] = plane[d, j1*n2 + t] (t < n2 + smax;
    built once per parity, ~plane-sized) — two small fusion operands,
    nineteen offsets. Values are BITWISE those of resample_select:
    out_even[..., j1, j2] == out[..., 2*(j1*n2+j2)], odd likewise."""
    n = x.shape[-1]
    m = n // 2
    if n1 * n2 != m:
        raise ValueError(f"bad plane factorisation {n1}x{n2} != {m}")
    idx = jnp.arange(n, dtype=jnp.float32)
    quad = idx * (idx - jnp.float32(n))  # exact inputs, one f32 rounding
    q4e = quad[0::2].reshape(n1, n2)
    q4o = quad[1::2].reshape(n1, n2)
    she = jnp.rint(afs[..., None, None] * q4e).astype(jnp.int32)
    sho = jnp.rint(afs[..., None, None] * q4o).astype(jnp.int32)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (smax, smax)), mode="edge")
    # overlapped row windows: arm offsets o = (smax+s[+1])//2 <= smax,
    # so XB width n2+smax covers every arm's [o, o+n2) row slice and
    # the build's max flat index is m+smax-1 — exactly the plane length
    win = jnp.arange(n1)[:, None] * n2 + jnp.arange(n2 + smax)[None, :]
    xbs = tuple(
        jnp.take(xp[:, par::2], win, axis=1) for par in (0, 1)
    )  # (D, n1, n2+smax) each
    oute = jnp.zeros(she.shape, jnp.float32)
    outo = jnp.zeros(sho.shape, jnp.float32)
    for s in range(-smax, smax + 1):
        # even output j reads xp[smax + s + 2j]: parity of (smax+s)
        # picks the plane, its half-index the slice offset
        p = smax + s
        arm = xbs[p % 2][:, :, p // 2 : p // 2 + n2]
        oute = jnp.where(she == jnp.int32(s), arm[:, None], oute)
        p = smax + s + 1  # odd output j reads xp[smax + s + 2j + 1]
        arm = xbs[p % 2][:, :, p // 2 : p // 2 + n2]
        outo = jnp.where(sho == jnp.int32(s), arm[:, None], outo)
    # one joint barrier, like packed_dft_z_parts': without it XLA's
    # priority fusion pre-materialises several arm broadcasts as
    # full-size (D, A, n1, n2) passes instead of emitting ONE select
    # loop (traced r5: 16.8 -> ~5 ms)
    return jax.lax.optimization_barrier((oute, outo))


# largest shift span a select resamples; past it the gather is cheaper
SELECT_LIMIT = 64


def select_span(af_max: float, n: int, limit: int = SELECT_LIMIT) -> int:
    """Static shift bound for :func:`resample_select`: ceil of
    max|af|*N^2/4 plus one guard sample, or 0 when the span exceeds
    ``limit`` (caller should use the gather path instead)."""
    smax = int(np.ceil(af_max * (n / 2.0) ** 2)) + 1
    return smax if smax <= limit else 0


def quadratic_span(afs: np.ndarray, n: int) -> int | None:
    """Static shift bucket for the folder's quadratic resample of a
    batch of f32 acceleration factors over an n-sample series.

    The map's quad = f32(f32(i - N/2)^2 - f32(N/2 * N/2)) lies in
    [-f32(N/2 * N/2), 0] (rounding is monotone) and reaches the low end
    at i = N/2, so the shift rint(f32(af) * quad) is largest in size
    there: one product per row bounds it exactly. Returns 0 when every
    row's map is the identity (every shift rounds to 0, as in the
    search's identity class), else that bound plus one guard sample
    rounded up to a power of two (so a stream of batches compiles few
    programs), or None past :data:`SELECT_LIMIT` (the caller gathers)."""
    half = np.float32(n) / np.float32(2.0)
    prods = np.asarray(afs, dtype=np.float32) * (half * half)
    smax = int(np.abs(np.rint(prods)).max(initial=0))
    if smax == 0:
        return 0
    span = 1 << smax.bit_length()  # >= smax + 1
    return span if span <= SELECT_LIMIT else None


@jax.jit
def resample_accel_quadratic(x: jnp.ndarray, af: jnp.ndarray) -> jnp.ndarray:
    """The folder's variant: out[i] = x[i + rint(af*((i-N/2)^2-(N/2)^2))]
    (kernels.cu:308-332)."""
    n = x.shape[-1]
    half = jnp.float32(n) / 2.0
    idx = jnp.arange(n, dtype=jnp.float32)
    quad = (idx - half) ** 2 - half * half
    shift = jnp.rint(af * quad).astype(jnp.int32)
    src = jnp.clip(jnp.arange(n, dtype=jnp.int32) + shift, 0, n - 1)
    return jnp.take(x, src)


# --- audit registry (ShapeCtx hooks rebuild the resample programs at
# a periodicity bucket's (dm_block, accel_pad, fft_size) production
# tile, derived from the accel plan in perf.warmup.shape_ctx_for_
# bucket; non-periodicity ctxs decline) ---
from .registry import register_program, sds  # noqa: E402


def _param_resample_accel(ctx):
    if ctx.fft_size <= 0 or ctx.accel_pad <= 0:
        return None
    return (
        resample_accel,
        (sds((ctx.fft_size,), "float32"), sds((ctx.accel_pad,), "float32")),
        {},
    )


def _param_select(fn):
    def hook(ctx, fn=fn):
        # the gather-free select only dispatches when the span probe
        # admits it (pipeline/search.py); mirror that gate here
        if ctx.fft_size <= 0 or ctx.accel_pad <= 0 or ctx.select_smax <= 0:
            return None
        return (
            fn,
            (
                sds((ctx.dm_block, ctx.fft_size), "float32"),
                sds((ctx.dm_block, ctx.accel_pad), "float32"),
            ),
            {"smax": ctx.select_smax},
        )
    return hook


def _param_select_planes(ctx):
    base = _param_select(resample_select_packed_planes)(ctx)
    if base is None or ctx.fft_size & (ctx.fft_size - 1):
        return None
    from .pallas.dftspec import plane_factors

    n1, n2 = plane_factors(ctx.fft_size // 2)
    fn, args, kwargs = base
    return fn, args, {**kwargs, "n1": n1, "n2": n2}


register_program(
    "ops.resample.resample_accel",
    lambda: (resample_accel, (sds((256,), "float32"), sds((4,), "float32")), {}),
    param=_param_resample_accel,
)
def _param_resample_quadratic(ctx):
    # the jerk-trial variant resamples one series per scalar adot at
    # the same fft tile as the linear path
    if ctx.fft_size <= 0:
        return None
    return (
        resample_accel_quadratic,
        (sds((ctx.fft_size,), "float32"), sds((), "float32")),
        {},
    )


register_program(
    "ops.resample.resample_accel_quadratic",
    lambda: (
        resample_accel_quadratic,
        (sds((256,), "float32"), sds((), "float32")),
        {},
    ),
    param=_param_resample_quadratic,
)
register_program(
    "ops.resample.resample_select",
    lambda: (
        resample_select,
        (sds((4, 256), "float32"), sds((4, 3), "float32")),
        {"smax": 4},
    ),
    param=_param_select(resample_select),
)
register_program(
    "ops.resample.resample_select_packed",
    lambda: (
        resample_select_packed,
        (sds((4, 256), "float32"), sds((4, 3), "float32")),
        {"smax": 4},
    ),
    param=_param_select(resample_select_packed),
)
register_program(
    "ops.resample.resample_select_packed_planes",
    lambda: (
        resample_select_packed_planes,
        (sds((4, 256), "float32"), sds((4, 3), "float32")),
        {"smax": 4, "n1": 8, "n2": 16},
    ),
    param=_param_select_planes,
)
