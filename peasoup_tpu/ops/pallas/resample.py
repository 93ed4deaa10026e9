"""Pallas TPU kernel for time-domain acceleration resampling.

Reference: resample_kernelII, out[i] = in[rint(i + i*af*(i-N))]
(src/kernels.cu:314-346) — a per-element gather in CUDA. BASELINE.md
names this op as a Pallas target.

TPU design — NO gather at all. The shift s(i) = rint(af*i*(i-N)) is a
slowly varying step function: its slope |d s/d i| = |af*(2i-N)| <=
|af|*N is tiny for physical accelerations (~1e-7..1e-4 samples/sample).
Pick a block size BLK with |af|*N*BLK <= 2; then within one output
block the shift takes at most 4 distinct values, so the block is a
SELECT among 4 shifted copies of one contiguous window:

  HBM --async DMA--> VMEM window, then
  out[j] = select(s(i0+j) - s_base, window[j+v], ..., window[j+v+3])

which is pure vector ops + one dynamic-offset DMA per tile — the
gather is traded for HBM streaming at full bandwidth.

Mosaic DMA/layout constraints (discovered on real v5e lowering) shape
the implementation:
  * dynamic-offset DMA slices are only unrestricted for 1-D refs, and
    1-D refs are tiled in 1024-lane quanta: both the slice length and
    the start offset must be multiples of 1024 (asserted to the
    compiler with pl.multiple_of). The input is therefore passed as a
    FLAT 1-D array of 1024-aligned padded rows, and the window start
    is quantized down to a 1024 boundary; the remainder is absorbed by
    the in-VMEM chunk+roll below.
  * VMEM vector loads need provably-128-aligned starts, so the select
    arms load a 128-aligned chunk covering [vmin, vmin+3+BLK) and
    lane-rotate it with pltpu.roll (dynamic shift).
  * output block shapes must end in (8k, 128m), so one invocation
    computes a SUPER=8 stack of consecutive BLK-blocks as an (8, BLK)
    tile of a (D, A, N/BLK, BLK) output (reshaped to (D, A, N) by the
    caller — free, same contiguous layout). All 8 sub-blocks share ONE
    window DMA: across a super-block the shift drifts by at most
    |af|*N*8*BLK <= 16 samples.

Correctness bounds, under the choose_block precondition
|af|*N*BLK <= 2 (so |af|*N < 1):
  * p = i0 + s(i0) is in [0, N - 8*BLK + 16]: |s(i0)| <= |af|*i0*(N-i0)
    < i0, i0 + s(i0) is increasing in i0 (derivative
    1 + af*(2*i0 - N) > 0), and |s(i0)| <= |af|*N*8*BLK <= 16 at
    i0 = N - 8*BLK.
  * window coverage: reads span x positions [max(0, p-3), p + 8*BLK
    + 18]; the window [q, q + W) with q = floor((dS + p)/1024)*1024,
    W = 8*BLK + _WIN_EXTRA (= 8*BLK + 4096) covers them with >= 61
    lanes of head slack, and q + W stays inside the padded row since
    the row stride is >= n + M + _WIN_EXTRA + 2 (dS = row start,
    M = 64 apron).
  * in-window select offsets vs = rem_q + M + (src - p) - j lie in
    [0, 7*BLK + 1106], so the 1024-aligned chunk [base, base + clen)
    with base = floor(vmin/1024)*1024 and clen = roundup(BLK + 1026,
    1024) <= BLK + 2048 ends at most at 8*BLK + 3154 < W — inside
    the window.
The index arithmetic uses the same f32 ops as the jnp twin
(ops/resample.py), so results are bitwise identical to it.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_MARGIN = 64  # head apron per padded row
_SELECT_SPAN = 4  # distinct shift values handled per sub-block
_SUPER = 8  # sub-blocks per kernel invocation (TPU sublane quantum)
_QUANT = 1024  # 1-D tiling quantum (lanes): DMA and VMEM loads alike
_WIN_EXTRA = 4 * _QUANT  # window slack beyond SUPER*BLK (coverage proof above)


def _row_stride(n: int) -> int:
    # room for quantization (1024) + margin + drift, rounded to 1024
    return -(-(n + _MARGIN + _WIN_EXTRA + 2) // _QUANT) * _QUANT


def _window_len(blk: int) -> int:
    # single source of truth for the DMA length AND the scratch size
    return _SUPER * blk + _WIN_EXTRA


def choose_block(af_max: float, n: int) -> int:
    """Largest power-of-two sub-block with shift spread <= SELECT_SPAN-1,
    clamped to [128, 2048]. Returns 0 if no valid block exists (caller
    must use the jnp fallback). This is the single source of truth for
    the kernel's preconditions."""
    if af_max < 0:
        raise ValueError("af_max must be >= 0")
    limit = 2.0 / (af_max * n) if af_max > 0 else float("inf")
    blk = 128
    if blk > limit or n % (_SUPER * blk):
        return 0
    while blk * 2 <= min(limit, 2048) and n % (_SUPER * blk * 2) == 0:
        blk *= 2
    return blk


def _kernel(
    af_ref, x_ref, out_ref, win_ref, sem, *, n: int, blk: int, interpret: bool
):
    d = pl.program_id(0)
    a = pl.program_id(1)
    t = pl.program_id(2)
    sup = _SUPER * blk
    w = _window_len(blk)
    stride = _row_stride(n)
    af = af_ref[d, a]
    nf = jnp.float32(n)
    i0 = t * sup
    i0f = jnp.float32(i0)
    s0 = jnp.rint(af * (i0f * (i0f - nf))).astype(jnp.int32)
    p = i0 + s0  # window anchor in x coords; in [0, n - sup + 2]
    u = d * stride + p  # unquantized window start (flat padded coords)
    q = pl.multiple_of((u // _QUANT) * _QUANT, _QUANT)
    rem_q = u - q  # in [0, 1024)

    copy = pltpu.make_async_copy(x_ref.at[pl.ds(q, w)], win_ref, sem)
    copy.start()

    j = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
    # 1-D VMEM loads share the 1024 tiling rule: round the chunk start
    # down to 1024 and its length up; the roll absorbs the remainder
    clen = -(-(blk + _QUANT + _SELECT_SPAN - 2) // _QUANT) * _QUANT
    # all index math is independent of the window data — do it while
    # the DMA is in flight
    sel = []
    for r in range(_SUPER):
        base_i = i0 + r * blk
        ivec = (base_i + j).astype(jnp.float32)  # exact: i < 2^24
        quad = ivec * (ivec - nf)  # same single f32 rounding as jnp twin
        shift = jnp.rint(af * quad).astype(jnp.int32)
        src = jnp.clip(base_i + j + shift, 0, n - 1)  # reference's clip
        # flat offset of src in window, minus lane index
        vs = rem_q + _MARGIN + (src - p) - j
        vmin = jnp.min(vs)
        base = pl.multiple_of((vmin // _QUANT) * _QUANT, _QUANT)
        sel.append((vs, vmin, base, vmin - base))
    copy.wait()
    rows = []
    for vs, vmin, base, rem in sel:
        chunk = win_ref[pl.ds(base, clen)].reshape(1, clen)
        acc = jnp.zeros((1, blk), jnp.float32)
        for s in range(_SELECT_SPAN):
            if interpret:
                arm = jax.lax.dynamic_slice(chunk, (0, rem + s), (1, blk))
            else:
                arm = pltpu.roll(chunk, clen - (rem + s), axis=1)[:, :blk]
            acc = jnp.where(vs == vmin + s, arm, acc)
        rows.append(acc)
    out_ref[:] = jnp.concatenate(rows, axis=0)


@lru_cache(maxsize=None)
def _build(d: int, a: int, n: int, blk: int, interpret: bool):
    w = _window_len(blk)
    kernel = partial(_kernel, n=n, blk=blk, interpret=interpret)
    return pl.pallas_call(
        kernel,
        grid=(d, a, n // (_SUPER * blk)),
        in_specs=[
            # whole (D, A) table in SMEM: TPU lowering rejects (1, 1)
            # blocks; the kernel indexes it by program_id instead
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_specs=pl.BlockSpec(
            # (8, blk) tile keeps the block tail TPU-compliant; the
            # squeezed (dm, accel) dims are indexed by the grid
            (None, None, _SUPER, blk), lambda dd, aa, tt: (dd, aa, tt, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((d, a, n // blk, blk), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((w,), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )


def resample_block_pallas(
    x: jnp.ndarray,  # (D, N) f32 time series per DM trial
    afs: jnp.ndarray,  # (D, A) f32 acceleration factors a*tsamp/2c
    *,
    block: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """(D, A, N) resampled series; ``block`` must come from
    choose_block (guarantees max|afs|*N*block <= 2)."""
    d, n = x.shape
    a = afs.shape[1]
    if n % (_SUPER * block):
        raise ValueError(f"N={n} incompatible with block={block}")
    stride = _row_stride(n)
    # flat 1024-aligned padded rows: [MARGIN apron][x row][tail slack]
    xp = jnp.pad(
        x.astype(jnp.float32), ((0, 0), (_MARGIN, stride - n - _MARGIN))
    ).reshape(-1)
    fn = _build(d, a, n, block, interpret)
    return fn(afs.astype(jnp.float32), xp).reshape(d, a, n)


def resample_block(
    x: jnp.ndarray, afs: jnp.ndarray, af_max: float, *, interpret: bool = False
) -> jnp.ndarray:
    """Dispatch: Pallas kernel when choose_block accepts and the
    backend proves it can compile it (or we're interpreting); else the
    jnp gather twin."""
    from ..resample import resample_accel
    from . import probe_pallas_resample

    _, n = x.shape
    blk = choose_block(af_max, n)
    if blk and (interpret or probe_pallas_resample(n, blk)):
        return resample_block_pallas(x, afs, block=blk, interpret=interpret)
    return jax.vmap(resample_accel)(x, afs)
