"""Pallas TPU kernel: packed four-step DFT fused with untwist+interbin.

Replaces the XLA einsum chain (ops/fft.py packed_dft_z_parts) PLUS the
untwist+interbin+normalise kernel (ops/pallas/interbin.py) for the
production pow2 search sizes. The einsum chain is LAYOUT-bound, not
MXU-bound: XLA materialises both DFT stages through HBM and inserts
four full-array {3,2,1,0}<->{3,1,2,0} relayout copies around them
(compiled-HLO-verified, NOTES.md round-4 continuation) — einsums
29.2 ms + copies 9.2 ms + interbin kernel 7.6 ms at the dense tutorial
grid. Here one kernel does the whole chain per 8-row stripe in VMEM:

  planes (8, n1, n2) -> step1 DFT over j1 -> twiddle -> step2 DFT over
  j2 -> Z (k2, k1) in natural bin order -> mirror/untwist -> interbin
  -> normalise -> (8, npad) spectrum pre-padded for the harmonic
  mega-kernel.

Key structural tricks:
  * Both DFT stages contract dim 0 of both operands (the MXU's
    transposed-lhs form), so the four-step's classic middle transpose
    NEVER materialises: step1 emits Ct (j2, l) from A (j1, j2) against
    the symmetric W1 (j1, l), and step2 emits Et (k2, k1) from
    Tt (j2, k1) against W2 (j2, k2) — flat (k2, k1) IS bin order
    k = k1 + n1*k2, so the output reshape is a free bitcast.
  * f32 x f32 matmuls run as an explicit THREE-PASS bf16 term
    expansion (x = xh+xm by exact 16-bit word truncation, w likewise;
    passes xm*wh, xh*wm, xh*wh summed small-to-large) — the same
    accuracy class as XLA's Precision.HIGH (~1.5e-5 rel), which the
    golden-recall gate accepts END TO END: the einsum chain run at
    Precision.HIGH measured recall 1.0 with exact ranks and ~0 dS/N
    deltas (NOTES.md round-4 continuation). A full six-pass
    HIGHEST-class variant was built and measured — 41 ms vs the
    chain's 46, all of the win eaten by split/pass overhead — so the
    shipped kernel is the 3-pass form (21.8 ms standalone). Gating is
    TWO-LAYERED (probe_pallas_dftspec): (a) a STRUCTURAL per-bin gate
    against :func:`dft_untwist_interbin_twin` — a pure-jnp replay of
    the kernel built from the SAME helper functions with the SAME term
    grouping, so beyond Mosaic-vs-XLA accumulation-order noise
    (measured <= 8.9e-6 of the 3e-5 envelope) the two differ only if
    Mosaic mis-lowers something (roll off by a lane, bad flip, wrong
    clamp); and (b) an
    ACCURACY-CLASS gate against the exact HIGHEST einsum chain:
    per-bin |amp - amp_ref| / (|amp_ref| + rms) max <= 1e-3 and
    99.9%-quantile <= 2e-4 (measured 3.7e-4 / 5.7e-5; the golden-
    recall gate remains the end-to-end arbiter). Where the shape does
    not admit it, the search runs the einsum + interbin-kernel chain
    (exact HIGHEST; pipeline/accel_search.py:search_route).
  * The mirror term Z[M-k] is built with one-hot reversals: plane
    order by an anti-identity dot on the sublane dim, lane order by
    the aligned-slice + ANTI-128 dot (interbin.py's _rev_lanes
    argument), both at the same 2-term class as the DFT (the one-hot
    side is exact; term-separate flips skip one split); the k1=0
    column is patched from a plane-shifted column-0 extract whose
    CIRCULAR roll supplies the k=0 wrap to Z[0], and the Nyquist bin
    is a (1,1) store (Mosaic cannot broadcast (1,1) across both
    sublanes and lanes, even staged).

Reference chain: cuFFT R2C -> bin_interbin_series -> normalise
(src/kernels.cu:231-304 + 469-494); same bin conventions as
ops/pallas/interbin.py.

VMEM: ~2 MB/plane operands (x2 double-buffered), (8, n1, n2) x2 Z
scratch, (8, npad) output — gated to m <= 2^17 (the benchmark sizes);
survey-scale m falls back to the einsum + interbin-kernel path via the
shape gate in the caller.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_SUB = 8  # rows per stripe (f32 sublane quantum)
_MAX_M = 1 << 17  # VMEM gate: per-plane stripe buffer = 8*m*4 bytes

_MSK32 = np.uint32(0xFFFF0000)


def _split3_np(x: np.ndarray):
    """Exact 3-term bf16 split by 16-bit word truncation (hi+mid+lo
    == x in f32; each term exactly bf16-representable)."""
    xi = x.view(np.uint32)
    hi = (xi & _MSK32).view(np.float32)
    r1 = x - hi
    mid = (r1.view(np.uint32) & _MSK32).view(np.float32)
    lo = r1 - mid
    return hi, mid, lo


def _split3(x: jnp.ndarray):
    """The same split traced (kernel or jnp twin)."""
    m = jnp.uint32(0xFFFF0000)
    xi = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(xi & m, jnp.float32)
    r1 = x - hi
    mid = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(r1, jnp.uint32) & m, jnp.float32
    )
    lo = r1 - mid
    return hi, mid, lo


_DN0 = (((0,), (0,)), ((), ()))  # contract dim0 x dim0 (xT @ y form)


def _bd(a, b, dn=_DN0):
    return jax.lax.dot_general(
        a, b, dn, preferred_element_type=jnp.float32
    ).astype(jnp.float32)


def _dot3(xs, ws, dn=_DN0):
    """Three-pass bf16-split f32 matmul (xm*wh + xh*wm + xh*wh,
    small-to-large): Precision.HIGH-class accuracy (~1.5e-5 rel), the
    class the golden gate accepts for the FFT chain."""
    xh, xm = xs
    wh, wm = ws
    return (_bd(xm, wh, dn) + _bd(xh, wm, dn)) + _bd(xh, wh, dn)


def _b16(x):
    return x.astype(jnp.bfloat16)


def _split2_b16(x):
    h, m_, _l = _split3(x)
    return _b16(h), _b16(m_)


@lru_cache(maxsize=None)
def _consts(n: int):
    """Kernel constants for series length n (m = n//2 = n1*n2):
    pre-split bf16 DFT matrices, transposed twiddles, untwist phasor in
    (k2, k1) plane space, and the two anti-identities."""
    m = n // 2
    n1, n2 = plane_factors(m)
    j1 = np.arange(n1)
    j2 = np.arange(n2)
    w1 = np.exp(-2j * np.pi * np.outer(j1, j1) / n1)  # symmetric
    w2 = np.exp(-2j * np.pi * np.outer(j2, j2) / n2)  # symmetric
    tw = np.exp(-2j * np.pi * np.outer(j1, j2) / m)
    out = {"n1": n1, "n2": n2}
    for name, mat in (
        ("w1r", w1.real), ("w1i", w1.imag),
        ("w2r", w2.real), ("w2i", w2.imag),
    ):
        # hi+mid terms only (3-pass class); stored f32 (exactly bf16-
        # representable), cast to bf16 at trace time (exact)
        out[name] = np.stack(
            _split3_np(np.ascontiguousarray(mat, np.float32))[:2]
        )
    out["twtr"] = np.ascontiguousarray(tw.real.T, np.float32)  # (j2, l)
    out["twti"] = np.ascontiguousarray(tw.imag.T, np.float32)
    out["anti_n2"] = np.eye(n2, dtype=np.float32)[::-1].copy()
    out["anti128"] = np.eye(128, dtype=np.float32)[::-1].copy()
    return out


def _flip2(z, anti_rows, anti128, n1, n2):
    """Both-dims reversal P[k2,k1] = z[n2-1-k2, n1-1-k1] at the 2-term
    class: lane order by aligned 128-slices + one-hot ANTI-128 dots
    applied PER TERM (flipping a term is exact, so no re-split between
    the stages), then plane order by the anti-identity from the left
    on a fresh 2-term split of the lane-flipped value."""
    g = n1 // 128
    dnl = (((2,), (0,)), ((), ()))
    a128 = _b16(anti128)

    def fl(t):
        xg = jnp.concatenate(
            [t[:, i * 128 : (i + 1) * 128] for i in reversed(range(g))],
            axis=1,
        )
        return _bd(xg.reshape(n2, g, 128), a128, dnl).reshape(n2, n1)

    h, m_ = _split2_b16(z)
    lf = fl(h) + fl(m_)
    h2, m2 = _split2_b16(lf)
    dn0 = (((1,), (0,)), ((), ()))
    ab = _b16(anti_rows)
    return _bd(ab, h2, dn0) + _bd(ab, m2, dn0)


def _rev_rows2(z, anti_rows):
    """Reverse dim0 (sublane planes) of (n, w) at the 2-term class:
    one-hot anti-identity matmul from the left."""
    zs = _split2_b16(z)
    a = _b16(anti_rows)
    dn = (((1,), (0,)), ((), ()))  # ANTI (rev, j) @ z (j, w)
    return _bd(a, zs[0], dn) + _bd(a, zs[1], dn)


def _row_dft_tail(ctr, cti, w2s, w2is, twtr, twti):
    """Steps 2+3 of one plane's DFT from its step-1 result Ct (j2, l):
    twiddle, then the j2 contraction emitting Z as (k2, k1)."""
    # step 2 twiddle in transposed (j2, l) space
    ttr = ctr * twtr - cti * twti
    tti = ctr * twti + cti * twtr
    # step 3 (contract j2): Et (k2, k1) = sum_j2 W2[j2,k2] Tt[j2,k1]
    ttrs = _split2_b16(ttr)
    ttis = _split2_b16(tti)
    zr = _dot3(w2s, ttrs) - _dot3(w2is, ttis)
    zi = _dot3(w2s, ttis) + _dot3(w2is, ttrs)
    return zr, zi


_DNB = (((1,), (0,)), ((), ()))  # contract j1 of (S, n1, n2) with w dim0


def _stripe_dft_step1(xe3, xo3, w1s, w1is):
    """Step 1 for a whole (S, n1, n2) stripe, BATCHED: contracting j1
    against W1 makes each dot M = S*n2 rows instead of n2 (better MXU
    utilisation at these small tiles), with the complex
    (W1r + iW1i)(ar + i*ai) result emitted naturally (S, j2, l).
    Shared VERBATIM by the kernel and the twin (same _dot3 contract,
    just the _DNB dimension numbers) so batched-matmul accumulation
    blocking can never open a kernel/twin gap."""
    ars = _split2_b16(xe3)
    ais = _split2_b16(xo3)
    ctr = _dot3(ars, w1s, _DNB) - _dot3(ais, w1is, _DNB)
    cti = _dot3(ais, w1s, _DNB) + _dot3(ars, w1is, _DNB)
    return ctr, cti


def _row_spectrum(
    zr, zi, unc, uns, anti_n, anti128, mean, std, *, n1, n2, roll
):
    """One plane's untwist + interbin + normalise: Z (k2, k1) -> the
    (n2, n1) main spectrum block plus the (1, 1) Nyquist bin. ``roll``
    is ``pltpu.roll`` inside the kernel and ``jnp.roll`` in the twin
    (identical circular semantics); everything else is the same traced
    ops in the same order."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (n2, n1), 1)
    plane = jax.lax.broadcasted_iota(jnp.int32, (n2, n1), 0)
    first = (lane == 0) & (plane == 0)
    # mirror zm[k] = Z[M-k]: for k1 >= 1 it is P[k2, k1-1] with
    # P = flip_planes(flip_lanes(Z)); for k1 == 0 (k2 >= 1) it is
    # Z[n2-k2, 0] = plane-shifted flip of column 0; (0,0) -> Z[0]
    pr = _flip2(zr, anti_n, anti128, n1, n2)
    pi = _flip2(zi, anti_n, anti128, n1, n2)
    prr = roll(pr, 1, 1)
    pir = roll(pi, 1, 1)
    # column 0 fix: zm(k2, 0) = Z[n2-k2, 0] = roll_planes(flipped
    # col0, 1); flipped col0 [k2] = Z[n2-1-k2, 0]. The roll is
    # CIRCULAR, so (0,0) wraps to flipped[n2-1] = Z[0,0] — exactly
    # the k=0 mirror (zm[0] = Z[0]); no separate override needed
    # (and none is possible: Mosaic refuses (1,1)->both-dims
    # broadcasts, even staged — it fuses the chain back together)
    c0r = roll(_rev_rows2(zr[:, 0:1], anti_n), 1, 0)
    c0i = roll(_rev_rows2(zi[:, 0:1], anti_n), 1, 0)
    zmr = jnp.where(lane == 0, c0r, prr)
    zmi = jnp.where(lane == 0, c0i, pir)
    # untwist (ops/fft.py formulas, identical to interbin.py)
    arr_ = 0.5 * (zr + zmr)
    aii = 0.5 * (zi - zmi)
    br = zr - zmr
    bi = zi + zmi
    xr = arr_ + 0.5 * (unc * bi - uns * br)
    xi = aii - 0.5 * (unc * br + uns * bi)
    # interbin shift X[k-1]: lane roll + previous-plane column fix
    xr_l = roll(xr, 1, 1)
    xi_l = roll(xi, 1, 1)
    cl_r = roll(xr[:, n1 - 1 : n1], 1, 0)
    cl_i = roll(xi[:, n1 - 1 : n1], 1, 0)
    xr_l = jnp.where(lane == 0, cl_r, xr_l)
    xi_l = jnp.where(lane == 0, cl_i, xi_l)
    xr_l = jnp.where(first, 0.0, xr_l)
    xi_l = jnp.where(first, 0.0, xi_l)
    ampsq = xr * xr + xi * xi
    dsq = 0.5 * ((xr - xr_l) ** 2 + (xi - xi_l) ** 2)
    amp = jnp.sqrt(jnp.maximum(ampsq, dsq))
    main = (amp - mean) / std
    # Nyquist bin m: X[m] = ReZ[0] - ImZ[0] (real; the untwist
    # identities), X[m-1] = X[n2-1, n1-1]
    xnr = zr[0:1, 0:1] - zi[0:1, 0:1]
    xml_r = xr[n2 - 1 : n2, n1 - 1 : n1]
    xml_i = xi[n2 - 1 : n2, n1 - 1 : n1]
    namp = jnp.sqrt(
        jnp.maximum(
            xnr * xnr, 0.5 * ((xnr - xml_r) ** 2 + xml_i * xml_i)
        )
    )
    return main, (namp - mean) / std


def _kernel(
    w1_ref, w2_ref, twtr_ref, twti_ref, unc_ref, uns_ref, antin_ref,
    anti128_ref, mean_ref, std_ref, xe_ref, xo_ref, out_ref, zr3, zi3,
    *, n1, n2, m, kpad,
):
    w1s = tuple(_b16(w1_ref[t]) for t in range(2))
    w1is = tuple(_b16(w1_ref[t + 2]) for t in range(2))
    w2s = tuple(_b16(w2_ref[t]) for t in range(2))
    w2is = tuple(_b16(w2_ref[t + 2]) for t in range(2))
    twtr = twtr_ref[:]
    twti = twti_ref[:]

    ctr3, cti3 = _stripe_dft_step1(
        xe_ref[:], xo_ref[:], w1s, w1is
    )  # (S, n2, n1) each
    for r in range(_SUB):
        zr3[r], zi3[r] = _row_dft_tail(
            ctr3[r], cti3[r], w2s, w2is, twtr, twti
        )

    # ---- untwist + interbin + normalise over the whole stripe ----
    anti_n = antin_ref[:]
    anti128 = anti128_ref[:]
    unc = unc_ref[:]
    uns = uns_ref[:]

    for r in range(_SUB):
        # mean/std arrive as SMEM scalars: scalar SPLATS against 2-D
        # values are supported where (1,1)-array broadcasts are not
        row = pl.program_id(0) * _SUB + r
        main, nyq = _row_spectrum(
            zr3[r], zi3[r], unc, uns, anti_n, anti128,
            mean_ref[row], std_ref[row], n1=n1, n2=n2, roll=pltpu.roll,
        )
        out_ref[r, :n2, :] = main
        # the pad planes past the Nyquist stay zero and the single real
        # bin is a (1,1) store — no broadcast
        out_ref[r, n2:, :] = jnp.zeros((kpad - n2, n1), jnp.float32)
        out_ref[r, n2 : n2 + 1, 0:1] = nyq


# ---- shared two-layer oracle (single source for probe_pallas_dftspec
# AND tests/test_pallas.py, so the production gate and CI can't drift) --
STRUCT_ENV_REL = 3e-5  # per-bin envelope factor vs the twin
ACC_MAX_REL = 1e-3  # accuracy class vs the HIGHEST chain: per-bin max
ACC_Q999_REL = 2e-4  # ... and 99.9%-quantile


def twin_envelope(twin: np.ndarray) -> np.ndarray:
    """Per-bin structural tolerance |got - twin| <=
    STRUCT_ENV_REL * (|twin| + row rms): Mosaic-vs-XLA accumulation
    order (TPU probe) and cross-host FMA codegen (CI, cached
    executables) both measure well inside it, while a broken lowering
    perturbs bins by O(rms) — five orders above — and fails every bin
    it breaks. Shared by the interbin oracle (same numeric class)."""
    scale = np.sqrt((twin**2).mean(axis=-1, keepdims=True))
    return STRUCT_ENV_REL * (np.abs(twin) + scale)


def oracle_data(n: int, r: int = 9, seed: int = 0):
    """The tone+noise case both gates run on: interbin's max() takes
    both branches and the accuracy gate sees the cancellation-heavy
    bins adjacent to the tone. Returns (x, xe, xo, mean, std) as
    numpy."""
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    x = (
        rng.normal(size=(r, n)) + 3.0 * np.sin(2 * np.pi * t * 0.1317)
    ).astype(np.float32)
    xe = np.ascontiguousarray(x[:, 0::2])
    xo = np.ascontiguousarray(x[:, 1::2])
    mean = rng.normal(size=r).astype(np.float32)
    std = (0.5 + rng.random(r)).astype(np.float32)
    return x, xe, xo, mean, std


def accuracy_rel(
    got: np.ndarray, ref: np.ndarray, mean: np.ndarray, std: np.ndarray,
    m: int,
) -> np.ndarray:
    """Per-bin accuracy-class residual vs the exact chain:
    |amp - amp_ref| / (|amp_ref| + row rms) on the un-normalised
    amplitudes (gate: max <= ACC_MAX_REL, q99.9 <= ACC_Q999_REL;
    measured 3.7e-4 / 5.7e-5 — the max sits at untwist-cancellation
    bins, inherent to any HIGH-class DFT)."""
    stdn = std[:, None]
    meann = mean[:, None]
    amp_g = got[:, : m + 1] * stdn + meann
    amp_r = ref * stdn + meann
    scale = np.sqrt((amp_r**2).mean(axis=1, keepdims=True))
    return np.abs(amp_g - amp_r) / (np.abs(amp_r) + scale)


def plane_factors(m: int) -> tuple[int, int]:
    """The kernel's DFT factorisation m = n1 * n2 (n1 = the pow2 at or
    below sqrt(m)); producers that emit (.., n1, n2) planes directly
    (ops/resample.py:resample_select_packed_planes) use this so the
    select writes the kernel's tile layout with no relayout pass."""
    n1 = 1 << ((m.bit_length() - 1) // 2)
    return n1, m // n1


def _geometry(m: int, npad: int) -> tuple[int, int, int]:
    """Validate the kernel's shape preconditions for half-length ``m``
    and output pad ``npad``; returns (n1, n2, kpad) or raises."""
    if m <= 0 or m & (m - 1):
        raise ValueError(f"fused DFT kernel needs pow2 m, got {m}")
    if m > _MAX_M:
        raise ValueError(f"fused DFT kernel gated to m <= {_MAX_M}, got {m}")
    n1, n2 = plane_factors(m)
    if npad % n1 or npad <= m or n1 % 128 or n2 % 8:
        raise ValueError(f"bad dftspec geometry {m=} {npad=} {n1=} {n2=}")
    return n1, n2, npad // n1


def dftspec_supported(size: int, npad: int) -> bool:
    """Shape gate for the driver: True iff the fused kernel's geometry
    preconditions hold for series length ``size`` and output pad
    ``npad`` (survey-scale m falls back to the einsum chain here, not
    via a trace-time ValueError)."""
    if size <= 0 or size % 2:
        return False
    try:
        _geometry(size // 2, npad)
    except ValueError:
        return False
    return True


def _phasor(n: int, n1: int, n2: int):
    """Untwist phasor in (k2, k1) plane space: bin k = k1 + n1*k2 < m."""
    k = (np.arange(n2)[:, None] * n1 + np.arange(n1)[None, :]).astype(
        np.float64
    )
    un = np.exp(-2j * np.pi * k / n)
    return (
        jnp.asarray(un.real.astype(np.float32)),
        jnp.asarray((-un.imag).astype(np.float32)),
    )


@lru_cache(maxsize=None)
def _build(rpad: int, n: int, npad: int, interpret: bool):
    c = _consts(n)
    n1, n2 = c["n1"], c["n2"]
    m = n1 * n2
    kpad = npad // n1
    kernel = partial(_kernel, n1=n1, n2=n2, m=m, kpad=kpad)
    cspec = lambda shape: pl.BlockSpec(shape, lambda r: tuple(0 for _ in shape))
    return pl.pallas_call(
        kernel,
        grid=(rpad // _SUB,),
        in_specs=[
            cspec((4, n1, n1)),  # w1 parts (r/i x 2 terms)
            cspec((4, n2, n2)),  # w2 parts
            cspec((n2, n1)),  # twtr
            cspec((n2, n1)),  # twti
            cspec((n2, n1)),  # unc
            cspec((n2, n1)),  # uns
            cspec((n2, n2)),  # anti_n (plane reversal)
            cspec((128, 128)),  # anti128 (lane reversal)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # mean (rpad,)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # std (rpad,)
            pl.BlockSpec((_SUB, n1, n2), lambda r: (r, 0, 0)),  # xe
            pl.BlockSpec((_SUB, n1, n2), lambda r: (r, 0, 0)),  # xo
        ],
        out_specs=pl.BlockSpec((_SUB, kpad, n1), lambda r: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rpad, kpad, n1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((_SUB, n2, n1), jnp.float32),
            pltpu.VMEM((_SUB, n2, n1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )


def _plane_view(xe, npad):
    """Resolve the input view: (R, m) flat planes are reshaped to the
    kernel's (R, n1, n2); (R, n1, n2) pre-shaped planes (the zero-copy
    producer path) are validated and passed through."""
    if xe.ndim == 3:
        r, a1, a2 = xe.shape
        m = a1 * a2
        n1, n2, kpad = _geometry(m, npad)
        if (a1, a2) != (n1, n2):
            raise ValueError(
                f"pre-shaped planes {a1}x{a2} != kernel factorisation "
                f"{n1}x{n2}"
            )
        return xe, r, m, n1, n2, kpad
    r, m = xe.shape
    n1, n2, kpad = _geometry(m, npad)
    return xe.reshape(r, n1, n2), r, m, n1, n2, kpad


def dft_untwist_interbin(
    xe: jnp.ndarray,  # (R, m) f32 even-sample planes — or (R, n1, n2)
    xo: jnp.ndarray,  # (R, m) f32 odd-sample planes — or (R, n1, n2)
    mean: jnp.ndarray,  # (R,)
    std: jnp.ndarray,  # (R,)
    *,
    npad: int,  # output width, a multiple of n1 and > m
    interpret: bool = False,
) -> jnp.ndarray:
    """(R, npad) f32 normalised interbin spectrum of the real series
    whose even/odd sample planes are xe/xo — the fused equivalent of
    packed_dft_z_parts + untwist_interbin_normalise. bins k in [0, m]
    real, the rest zero. Producers should pass (R, n1, n2) pre-shaped
    planes (plane_factors): the flat (R, m) form costs two full-plane
    relayout copy passes at the XLA/Mosaic tile boundary."""
    xe3, r, m, n1, n2, kpad = _plane_view(xe, npad)
    xo3 = _plane_view(xo, npad)[0]
    n = 2 * m
    c = _consts(n)
    unc, uns = _phasor(n, n1, n2)
    rpad = -(-r // _SUB) * _SUB
    mean2 = mean.astype(jnp.float32)
    std2 = std.astype(jnp.float32)
    if rpad != r:
        pad3 = [(0, rpad - r), (0, 0), (0, 0)]
        xe3 = jnp.pad(xe3, pad3)
        xo3 = jnp.pad(xo3, pad3)
        mean2 = jnp.pad(mean2, (0, rpad - r))
        # std pads with ONES so pad rows never divide by zero
        std2 = jnp.pad(std2, (0, rpad - r), constant_values=1.0)
    fn = _build(rpad, n, npad, interpret)
    out = fn(
        jnp.asarray(np.concatenate([c["w1r"], c["w1i"]])),
        jnp.asarray(np.concatenate([c["w2r"], c["w2i"]])),
        jnp.asarray(c["twtr"]), jnp.asarray(c["twti"]),
        unc, uns,
        jnp.asarray(c["anti_n2"]),
        jnp.asarray(c["anti128"]),
        mean2, std2, xe3, xo3,
    )
    return out.reshape(rpad, npad)[:r]


def dft_untwist_interbin_twin(
    xe: jnp.ndarray,  # (R, m) f32 even-sample planes
    xo: jnp.ndarray,  # (R, m) f32 odd-sample planes
    mean: jnp.ndarray,  # (R,)
    std: jnp.ndarray,  # (R,)
    *,
    npad: int,
) -> jnp.ndarray:
    """Pure-jnp contraction-exact replay of :func:`dft_untwist_interbin`:
    the SAME helper functions (_stripe_dft_step1 / _row_dft_tail /
    _row_spectrum) run outside Pallas, with ``jnp.roll`` standing in
    for ``pltpu.roll`` (identical circular semantics) and the kernel's
    exact stripe batching so every dot has the kernel's operand
    shapes. On a given backend the op sequence — bf16 splits,
    three-pass dots, one-hot flips, rolls — is identical term for
    term, so beyond accumulation-order noise
    (Mosaic MXU vs XLA dots: measured <= 8.9e-6 of the 3e-5 per-bin
    envelope on v5e; bitwise 0 under fresh same-backend CPU compiles)
    any kernel/twin difference is a broken Mosaic lowering. Used by
    probe_pallas_dftspec (on TPU) and the interpret-mode tests (on
    CPU); test-only — O(rows) trace size."""
    xe3, r, m, n1, n2, kpad = _plane_view(xe, npad)
    xo3 = _plane_view(xo, npad)[0]
    n = 2 * m
    c = _consts(n)
    unc, uns = _phasor(n, n1, n2)
    w1cat = jnp.asarray(np.concatenate([c["w1r"], c["w1i"]]))
    w2cat = jnp.asarray(np.concatenate([c["w2r"], c["w2i"]]))
    w1s = tuple(_b16(w1cat[t]) for t in range(2))
    w1is = tuple(_b16(w1cat[t + 2]) for t in range(2))
    w2s = tuple(_b16(w2cat[t]) for t in range(2))
    w2is = tuple(_b16(w2cat[t + 2]) for t in range(2))
    twtr = jnp.asarray(c["twtr"])
    twti = jnp.asarray(c["twti"])
    anti_n = jnp.asarray(c["anti_n2"])
    anti128 = jnp.asarray(c["anti128"])
    xe3 = xe3.astype(jnp.float32)
    xo3 = xo3.astype(jnp.float32)
    mean2 = mean.astype(jnp.float32)
    std2 = std.astype(jnp.float32)
    # replicate the kernel's _SUB-row stripes exactly, including the
    # BATCHED step-1 dot per stripe (shared _stripe_dft_step1): the
    # batched matmul's accumulation blocking is then identical by
    # construction, not by hope
    rpad = -(-r // _SUB) * _SUB
    if rpad != r:
        pad3 = [(0, rpad - r), (0, 0), (0, 0)]
        xe3 = jnp.pad(xe3, pad3)
        xo3 = jnp.pad(xo3, pad3)
    rows = []
    for st in range(rpad // _SUB):
        sl = slice(st * _SUB, (st + 1) * _SUB)
        ctr3, cti3 = _stripe_dft_step1(xe3[sl], xo3[sl], w1s, w1is)
        for i in range(_SUB):
            gr = st * _SUB + i
            if gr >= r:
                break
            zr, zi = _row_dft_tail(
                ctr3[i], cti3[i], w2s, w2is, twtr, twti
            )
            main, nyq = _row_spectrum(
                zr, zi, unc, uns, anti_n, anti128, mean2[gr], std2[gr],
                n1=n1, n2=n2, roll=jnp.roll,
            )
            blk = jnp.zeros((kpad, n1), jnp.float32)
            blk = blk.at[:n2].set(main)
            blk = blk.at[n2, 0].set(nyq[0, 0])
            rows.append(blk.reshape(npad))
    return jnp.stack(rows)
