"""Pallas TPU kernel: fused rfft-untwist + interbin + normalise.

Completes the packed four-step matmul rfft (ops/fft.py): the two MXU
einsums emit the half-length complex DFT Z[k] in natural order, and
this kernel turns Z straight into the NORMALISED interbin spectrum the
search consumes (reference chain: cuFFT R2C -> bin_interbin_series
-> normalise, src/kernels.cu:231-304 + 469-494) in ONE streaming pass.

Why a kernel: the untwist needs conj(Z[M-k]) — in pure XLA that is a
rev + concat per (re, im) plus separate interbin-shift concats and a
normalise pass, ~6 full HBM round trips that ate the matmul FFT's
standalone 1.75x win in-pipeline (NOTES.md round 3). Here the mirror
term needs NO materialised reversal at all (r4; the XLA rev copy it
replaces ran at ~300 GB/s for 9.9 ms in-pipeline): the mirrored
operands are the FORWARD zr/zi arrays fetched at the mirrored block
index (nbz-1-b), reversed in VMEM — group order by 128-aligned lane
slices (pure vreg renames) and within-group by one anti-identity MXU
dot (one-hot, so bitwise-exact) — and the shift-by-one patterns
(mirror + interbin's X[k-1]) are carried lane boundaries in VMEM
scratch across a sequential k-block grid, so the whole chain is
einsums -> one fused pass.

Bin layout (matches the jnp path's pad convention): output (R, npad)
f32 with bins k = 0..m real, k > m zeroed (npad = the harmonic-peaks
kernel's block alignment so no separate pad pass is spent downstream).

Special bins, from the real-input untwist identities:
  X[0] = Re Z[0] + Im Z[0]   (mirror wraps to Z[0] itself)
  X[m] = Re Z[0] - Im Z[0]   (Nyquist; Z[0] carried from block 0)
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB = 8  # rows per stripe (f32 sublane quantum)


def _rev_lanes(x: jnp.ndarray, anti: jnp.ndarray, block: int) -> jnp.ndarray:
    """Reverse the lane axis of an (_SUB, block) VMEM value exactly:
    group order via 128-aligned static slices (vreg renames), then
    within-group via one anti-identity MXU dot (one-hot products are
    exact, so the result is bitwise the reversed input). Measured
    ~1 ms per 742 MB over a plain copy — vs 6.8 ms for XLA's rev."""
    g = block // 128
    xg = jnp.concatenate(
        [x[:, i * 128 : (i + 1) * 128] for i in reversed(range(g))], axis=1
    )
    z = jax.lax.dot_general(
        xg.reshape(_SUB, g, 128), anti, (((2,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return z.reshape(_SUB, block)


def _untwist_block(zr, zi, zrv, ziv, carry, c, s, mean, std, lane, gk,
                   m, roll):
    """One (stripe, k-block) step's untwist + interbin + normalise at
    FIXED term grouping — shared VERBATIM by the Pallas kernel
    (roll=pltpu.roll) and the jnp twin (roll=jnp.roll), so the twin is
    a contraction-order-exact replay (see dftspec.py's _row_spectrum
    for the same pattern). ``carry`` = (zrv_last, ziv_last, xr_last,
    xi_last, z0r, z0i) as values; returns (out, xr_last', xi_last').

    Steps: forward term Z[k] (wrapping the Nyquist k == m to the
    carried Z[0]); mirror term Z[M-k] = zrev[k-1] from the reversed
    mirrored block via in-block right-shift + carried boundary lane;
    the untwist X[k] = (Z[k]+conj(Zm))/2 - i/2 e^{-2pi i k/n}
    (Z[k]-conj(Zm)) (ops/fft.py formulas); interbin X[k-1] by the same
    shift pattern (kernels.cu:231-252); normalise (kernels.cu:469-494)
    + zero the pad past the true bins."""
    nyq = gk == m
    zr = jnp.where(nyq, carry[4], zr)
    zi = jnp.where(nyq, carry[5], zi)
    zmr = jnp.where(lane == 0, carry[0], roll(zrv, 1, 1))
    zmi = jnp.where(lane == 0, carry[1], roll(ziv, 1, 1))
    arr = 0.5 * (zr + zmr)
    aii = 0.5 * (zi - zmi)
    br = zr - zmr
    bi = zi + zmi
    xr = arr + 0.5 * (c * bi - s * br)
    xi = aii - 0.5 * (c * br + s * bi)
    xr_l = jnp.where(lane == 0, carry[2], roll(xr, 1, 1))
    xi_l = jnp.where(lane == 0, carry[3], roll(xi, 1, 1))
    ampsq = xr * xr + xi * xi
    dsq = 0.5 * ((xr - xr_l) ** 2 + (xi - xi_l) ** 2)
    amp = jnp.sqrt(jnp.maximum(ampsq, dsq))
    out = (amp - mean) / std
    out = jnp.where(gk <= m, out, 0.0)
    return out, xr[:, -1:], xi[:, -1:]


def _kernel(
    anti_ref, unc_ref, uns_ref, mean_ref, std_ref, zr_ref, zi_ref,
    zmr_ref, zmi_ref, out_ref, state, *, block, m,
):
    b = pl.program_id(1)
    zr = zr_ref[:]
    zi = zi_ref[:]

    @pl.when(b == 0)
    def _():
        # carries: [zrv_last, ziv_last, xr_last, xi_last, z0r, z0i]
        # k=0's mirror wraps to Z[0]; X[-1] = 0 (the interbin kernel's
        # idx==0 branch, kernels.cu:242)
        state[:, 0:1] = zr[:, 0:1]
        state[:, 1:2] = zi[:, 0:1]
        state[:, 2:3] = jnp.zeros((_SUB, 1), jnp.float32)
        state[:, 3:4] = jnp.zeros((_SUB, 1), jnp.float32)
        state[:, 4:5] = zr[:, 0:1]
        state[:, 5:6] = zi[:, 0:1]

    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, block), 1)
    gk = b * block + lane  # global bin index
    # mirror operands: the mirrored-index FORWARD block (zm*_ref, block
    # nbz-1-b) reversed in VMEM gives this block of zrev = flip(Z)
    zrv = _rev_lanes(zmr_ref[:], anti_ref[:], block)
    ziv = _rev_lanes(zmi_ref[:], anti_ref[:], block)
    carry = tuple(state[:, i : i + 1] for i in range(6))
    out, xr_last, xi_last = _untwist_block(
        zr, zi, zrv, ziv, carry, unc_ref[:], uns_ref[:],
        mean_ref[:, 0:1], std_ref[:, 0:1], lane, gk, m, roll=pltpu.roll,
    )
    out_ref[:] = out
    # advance carries: zrev's last lane == the mirrored forward block's
    # FIRST lane, so the carry needs no reversed value at all
    state[:, 0:1] = zmr_ref[:, 0:1]
    state[:, 1:2] = zmi_ref[:, 0:1]
    state[:, 2:3] = xr_last
    state[:, 3:4] = xi_last


@lru_cache(maxsize=None)
def _build(rpad: int, m: int, npad: int, block: int, interpret: bool):
    nbz = m // block  # z blocks (m is a multiple of block by gating)
    zspec = pl.BlockSpec(
        (_SUB, block), lambda r, b: (r, jnp.minimum(b, nbz - 1))
    )
    # mirrored fetch: block b of flip(Z) is the REVERSE of forward
    # block nbz-1-b; for b >= nbz (the pad block) clamp to block 0,
    # matching the old zrv spec's min(b, nbz-1) on the flipped array
    mspec = pl.BlockSpec(
        (_SUB, block), lambda r, b: (r, jnp.maximum(nbz - 1 - b, 0))
    )
    return pl.pallas_call(
        partial(_kernel, block=block, m=m),
        grid=(rpad // _SUB, npad // block),
        in_specs=[
            pl.BlockSpec((128, 128), lambda r, b: (0, 0)),  # anti
            pl.BlockSpec((1, block), lambda r, b: (0, b)),  # unc
            pl.BlockSpec((1, block), lambda r, b: (0, b)),  # uns
            pl.BlockSpec((_SUB, 128), lambda r, b: (r, 0)),  # mean
            pl.BlockSpec((_SUB, 128), lambda r, b: (r, 0)),  # std
            zspec, zspec, mspec, mspec,  # zr, zi, mirrored zr, zi
        ],
        out_specs=pl.BlockSpec((_SUB, block), lambda r, b: (r, b)),
        out_shape=jax.ShapeDtypeStruct((rpad, npad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_SUB, 128), jnp.float32)],
        interpret=interpret,
    )


def untwist_interbin_normalise(
    zr: jnp.ndarray,  # (R, m) f32 packed-DFT real part, natural order
    zi: jnp.ndarray,  # (R, m) f32 imaginary part
    mean: jnp.ndarray,  # (R,) f32 per-row spectrum mean
    std: jnp.ndarray,  # (R,) f32 per-row spectrum std
    *,
    npad: int,  # output width (multiple of ``block``, > m)
    block: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    """(R, npad) f32 normalised interbin spectrum of the real series
    whose packed half-length DFT is Z = zr + i*zi; bins k in [0, m]
    real, the rest zero. ``m`` must be a multiple of ``block`` and
    ``npad`` a strictly larger multiple."""
    r, m = zr.shape
    if m % block or npad % block or npad <= m:
        raise ValueError(f"bad interbin kernel geometry {m=} {npad=} {block=}")
    n = 2 * m
    k = np.arange(npad, dtype=np.float64)
    un = np.exp(-2j * np.pi * np.minimum(k, m) / n)
    unc = jnp.asarray(un.real[None, :].astype(np.float32))
    uns = jnp.asarray((-un.imag)[None, :].astype(np.float32))
    rpad = -(-r // _SUB) * _SUB
    mean2 = jnp.broadcast_to(mean[:, None], (r, 128))
    std2 = jnp.broadcast_to(std[:, None], (r, 128))
    if rpad != r:
        pad = [(0, rpad - r), (0, 0)]
        zr, zi = (jnp.pad(a, pad) for a in (zr, zi))
        # std pads with ONES so the pad rows' normalise never divides
        # by zero (their outputs are dropped)
        mean2 = jnp.pad(mean2, pad)
        std2 = jnp.pad(std2, pad, constant_values=1.0)
    anti = jnp.asarray(np.eye(128, dtype=np.float32)[::-1].copy())
    fn = _build(rpad, m, npad, block, interpret)
    out = fn(anti, unc, uns, mean2, std2, zr, zi, zr, zi)
    return out[:r]


def untwist_interbin_normalise_twin(
    zr: jnp.ndarray,
    zi: jnp.ndarray,
    mean: jnp.ndarray,
    std: jnp.ndarray,
    *,
    npad: int,
    block: int = 4096,
) -> jnp.ndarray:
    """Pure-jnp contraction-exact replay of
    :func:`untwist_interbin_normalise`: the kernel's per-(stripe, block)
    grid walk — mirrored-block fetch, _rev_lanes one-hot reversal,
    carry lanes, untwist, interbin, normalise — run outside Pallas with
    ``jnp.roll`` for ``pltpu.roll`` and Python loops for the grid, so
    every expression tree matches the kernel term for term. Kernel and
    twin agree bitwise when both compile fresh; when the persistent
    compile cache serves a cross-host executable the residual is pure
    FMA-contraction codegen (measured max 5.2e-6 rel), so the CI
    oracle asserts a per-bin 1e-5 envelope that still fails every bin
    a structural half-lane fault breaks — without TPU hardware (the
    on-TPU probe gates bitwise against the differently-grouped jnp
    chain instead). Test-only — O(grid) trace size."""
    r, m = zr.shape
    if m % block or npad % block or npad <= m:
        raise ValueError(f"bad interbin kernel geometry {m=} {npad=} {block=}")
    n = 2 * m
    k = np.arange(npad, dtype=np.float64)
    un = np.exp(-2j * np.pi * np.minimum(k, m) / n)
    unc = jnp.asarray(un.real[None, :].astype(np.float32))
    uns = jnp.asarray((-un.imag)[None, :].astype(np.float32))
    rpad = -(-r // _SUB) * _SUB
    mean2 = jnp.broadcast_to(mean.astype(jnp.float32)[:, None], (r, 1))
    std2 = jnp.broadcast_to(std.astype(jnp.float32)[:, None], (r, 1))
    if rpad != r:
        pad = [(0, rpad - r), (0, 0)]
        zr, zi = (jnp.pad(a, pad) for a in (zr, zi))
        mean2 = jnp.pad(mean2, pad)
        std2 = jnp.pad(std2, pad, constant_values=1.0)
    anti = jnp.asarray(np.eye(128, dtype=np.float32)[::-1].copy())
    nbz = m // block
    stripes = []
    for st in range(rpad // _SUB):
        sl = slice(st * _SUB, (st + 1) * _SUB)
        zrs, zis = zr[sl], zi[sl]
        mean_s, std_s = mean2[sl], std2[sl]
        # carries: [zrv_last, ziv_last, xr_last, xi_last, z0r, z0i]
        zero = jnp.zeros((_SUB, 1), jnp.float32)
        carry = [zrs[:, 0:1], zis[:, 0:1], zero, zero,
                 zrs[:, 0:1], zis[:, 0:1]]
        blocks = []
        for b in range(npad // block):
            # the kernel's BlockSpec index maps as python slices
            zb = min(b, nbz - 1) * block
            mb = max(nbz - 1 - b, 0) * block
            zmr_b = zrs[:, mb : mb + block]
            zmi_b = zis[:, mb : mb + block]
            lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, block), 1)
            zrv = _rev_lanes(zmr_b, anti, block)
            ziv = _rev_lanes(zmi_b, anti, block)
            out, xr_last, xi_last = _untwist_block(
                zrs[:, zb : zb + block], zis[:, zb : zb + block],
                zrv, ziv, tuple(carry),
                unc[:, b * block : (b + 1) * block],
                uns[:, b * block : (b + 1) * block],
                mean_s, std_s, lane, b * block + lane, m, roll=jnp.roll,
            )
            blocks.append(out)
            carry = [zmr_b[:, 0:1], zmi_b[:, 0:1], xr_last, xi_last,
                     carry[4], carry[5]]
        stripes.append(jnp.concatenate(blocks, axis=1))
    return jnp.concatenate(stripes, axis=0)[:r]
