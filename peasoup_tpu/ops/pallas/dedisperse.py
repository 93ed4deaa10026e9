"""Pallas TPU kernel for incoherent dedispersion.

Reference: the shift-and-sum the external ``dedisp`` CUDA library does
inside ``dedisp_execute`` (used at /root/reference/include/transforms/
dedisperser.hpp:98-113): out[d, t] = sum_c x[t + delay[d, c], c].

The jnp twin (ops/dedisperse.py:_dedisperse_core) scans channels with a
(D, T_out) HBM-resident accumulator: every channel step re-reads and
re-writes the whole accumulator, and every per-channel shift is a
dynamic slice. This kernel removes both costs:

  * the output block accumulates in VMEM scratch across the channel
    grid axis (written to HBM once, at the last channel step);
  * each channel window arrives by ONE dynamic-offset async DMA shared
    by all 8 trials of the block — adjacent DM trials' delays differ by
    at most SPREAD samples (computed from the actual delay table), so
    one window [min-delay .. min-delay + B + SPREAD) covers the whole
    trial chunk, and each trial's residual shift is one in-VMEM
    pltpu.roll (dynamic lane rotate).

Layout (round 2, blocked-roll rewrite): the filterbank is passed as a
(C, TR, 128) BLOCKED array of padded channel rows (killmask
pre-multiplied); window DMA starts are quantized down to 128-sample
row boundaries, and each trial's residual alignment decomposes into a
row offset (select among statically row-rolled window versions) plus a
lane shift (one dynamic lane roll + row-boundary select), so every
vector op runs at full (8, 128) vreg width.

Summation order is channel-ascending per output element — identical to
the jnp twin, and for <=8-bit inputs channel sums are exact integers in
f32, so results are bitwise equal either way (tests assert equality).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_DT = 8  # DM trials per output block (f32 sublane quantum)
_CC = 16  # channels per grid step (windows DMA'd per step)
_QUANT = 1024  # output block-size quantum (keeps t_out a lane multiple)


def _nbw(nb: int, k_max: int) -> int:
    # window rows: nb output rows + k_max per-trial row offset + 1 for
    # the lane-boundary next-row, rounded to the sublane quantum
    return -(-(nb + k_max + 1) // 8) * 8


def _tr_rows(t_in: int, nb: int, k_max: int) -> int:
    # blocked channel row count: data rows + window slack (zero rows)
    return -(-t_in // 128) + _nbw(nb, k_max) + 1


def _kernel(
    del_ref,  # SMEM (DT, C) i32 delays for this trial chunk (all channels)
    x_ref,  # HBM (C, TR, 128) blocked padded channel rows
    out_ref,  # VMEM (DT, nb, 128) output block (accumulated across c)
    acc_ref,  # VMEM scratch (DT, nb, 128) f32
    win_ref,  # VMEM scratch (CC, NBW, 128) f32 channel windows
    sems,  # DMA semaphores (CC,)
    *,
    nb: int,
    nbw: int,
    k_max: int,
    cc_count: int,
    interpret: bool,
):
    """Blocked shift-and-sum: one shared (NBW, 128) window per channel
    per 8-trial chunk, per-trial alignment resolved as
    (row offset k_i, lane shift s_i) with k_i handled by selecting
    among k_max+1 statically row-rolled window versions (computed once
    per channel) and s_i by one dynamic lane roll + row-boundary
    select — every vector op runs at full (8, 128) vreg width, unlike
    the round-1 kernel's (1, W) single-sublane rolls (measured ~5x).
    Channel sums accumulate ascending per trial, so results stay
    bitwise equal to the jnp twin for integer inputs."""
    t = pl.program_id(1)
    c = pl.program_id(2)
    nc = pl.num_programs(2)
    t0 = t * (nb * 128)

    @pl.when(c == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def roll(x, shift, axis):
        if interpret:
            return jnp.roll(x, shift, axis=axis)
        return pltpu.roll(x, shift, axis=axis)

    copies = []
    for cc in range(cc_count):
        chan = c * cc_count + cc
        d0 = del_ref[0, chan]  # chunk-min delay (delays ascend with trial)
        u0 = t0 + d0
        q0 = u0 // 128
        cp = pltpu.make_async_copy(
            x_ref.at[chan, pl.ds(q0, nbw)],
            win_ref.at[cc],
            sems.at[cc],
        )
        cp.start()
        copies.append((cp, u0 - q0 * 128, chan))

    lane = jax.lax.broadcasted_iota(jnp.int32, (nb, 128), 1)
    for cc, (cp, base, chan) in enumerate(copies):
        cp.wait()
        wnd = win_ref[cc]  # (NBW, 128)
        d0 = del_ref[0, chan]
        # versions[k][r] = wnd[r + k]: static sublane rolls, shared by
        # all 8 trials of the chunk
        versions = [
            wnd if k == 0 else roll(wnd, nbw - k, axis=0)
            for k in range(k_max + 1)
        ]
        for di in range(_DT):
            rel = base + (del_ref[di, chan] - d0)  # in [0, 127 + spread]
            k_i = rel // 128
            s_i = rel % 128
            sel = versions[0]
            for k in range(1, k_max + 1):
                sel = jnp.where(k_i == k, versions[k], sel)
            a = roll(sel, 128 - s_i, axis=1)  # a[r, l] = sel[r, l+s mod 128]
            nxt = roll(a, nbw - 1, axis=0)  # nxt[r] = a[r + 1]
            arm = jnp.where(lane < 128 - s_i, a[:nb], nxt[:nb])
            acc_ref[di] += arm

    @pl.when(c == nc - 1)
    def _():
        out_ref[:] = acc_ref[:]


@lru_cache(maxsize=None)
def _build(
    d: int, t_out: int, c: int, b: int, spread: int, interpret: bool,
):
    nb = b // 128
    k_max = (127 + spread) // 128
    nbw = _nbw(nb, k_max)
    kernel = partial(
        _kernel, nb=nb, nbw=nbw, k_max=k_max, cc_count=_CC,
        interpret=interpret,
    )
    tb = t_out // 128
    return pl.pallas_call(
        kernel,
        grid=(d // _DT, tb // nb, c // _CC),
        in_specs=[
            # full channel width per trial chunk (SMEM blocks must have
            # their last dim equal to the array's); 8 x C x 4 B = 32 KB
            # at 1024 channels
            pl.BlockSpec(
                (_DT, c), lambda dd, tt, cc: (dd, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_specs=pl.BlockSpec(
            (_DT, nb, 128), lambda dd, tt, cc: (dd, tt, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((d, tb, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((_DT, nb, 128), jnp.float32),
            pltpu.VMEM((_CC, nbw, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((_CC,)),
        ],
        interpret=interpret,
    )


def plan_spread(delays: np.ndarray) -> int:
    """Max in-chunk delay spread: max over channels and aligned _DT-trial
    chunks of delay[last, c] - delay[first, c] (delays ascend with DM)."""
    d = np.asarray(delays)
    spread = 0
    for lo in range(0, d.shape[0], _DT):
        blk = d[lo : lo + _DT]
        spread = max(spread, int((blk.max(axis=0) - blk.min(axis=0)).max()))
    return spread


def pallas_hbm_bytes(
    t_in: int, c: int, d: int, out_nsamps: int, spread: int | None = None
) -> int:
    """Rough peak HBM need of dedisperse_pallas: the padded f32 flat
    filterbank + the full f32 output (+ the caller-held input). Used by
    dedisperse_device to keep near-limit trial sets on the blocked jnp
    path, whose working set is one trial block. Pass the REAL delay
    ``spread`` (plan_spread(delays)) when the caller holds the table —
    the one-block fallback bound undercounts when coarse high-DM steps
    spread further than one block (ADVICE r1)."""
    b = min(16384, max(_QUANT, -(-out_nsamps // _QUANT) * _QUANT))
    t_out = -(-out_nsamps // b) * b
    cpad = -(-c // _CC) * _CC
    dpad = -(-d // _DT) * _DT
    sp = spread if spread is not None else _QUANT
    tr = _tr_rows(t_in, b // 128, (127 + sp) // 128)
    return 4 * (cpad * tr * 128 + dpad * t_out) + t_in * c


def dedisperse_pallas(
    fil_tc,  # (T, C) u8/f32 filterbank (numpy or device array)
    delays: np.ndarray,  # (D, C) int32
    killmask: np.ndarray,  # (C,)
    out_nsamps: int,
    *,
    quantize: bool = True,
    scale: float = 1.0,
    block: int = 16384,
    interpret: bool = False,
    spread: int | None = None,
) -> jax.Array:
    """All DM trials in ONE kernel dispatch, bitwise equal to the jnp
    twin. Trials/channels pad to the (8, 16) grid quanta with repeated/
    zero rows; output time pads to ``block`` lanes and is trimmed.
    Pass ``spread`` (plan_spread(delays)) when the caller already
    computed it — the O(D*C) host scan is not free at survey scale."""
    delays = np.asarray(delays, dtype=np.int32)
    d, c = delays.shape
    t_in = fil_tc.shape[0]
    # don't let a small search pay a full survey-sized block: the padded
    # tail beyond out_nsamps is computed and trimmed (window slack rows
    # keep every DMA in range regardless)
    b = min(block, max(_QUANT, -(-out_nsamps // _QUANT) * _QUANT))
    t_out = -(-out_nsamps // b) * b
    if spread is None:
        spread = plan_spread(delays)

    dpad = -(-d // _DT) * _DT
    cpad = -(-c // _CC) * _CC
    if dpad > d:
        # repeat the last trial: keeps delays ascending within chunks
        delays = np.concatenate(
            [delays, np.repeat(delays[-1:], dpad - d, axis=0)]
        )
    if cpad > c:
        # extra channels: zero data rows at the max existing delay so
        # windows stay in range and contribute exact zeros
        delays = np.concatenate(
            [delays, np.tile(delays[:, -1:], (1, cpad - c))], axis=1
        )

    run = _jit_full(
        dpad, t_out, cpad, b, spread, d, c, t_in, out_nsamps,
        quantize, float(scale), interpret,
    )
    return run(jnp.asarray(fil_tc), jnp.asarray(delays),
               jnp.asarray(np.asarray(killmask)))


@lru_cache(maxsize=None)
def _jit_full(
    dpad, t_out, cpad, b, spread, d, c, t_in, out_nsamps,
    quantize, scale, interpret,
):
    """Prep (mask, f32, pad/transpose/block), the kernel, and the
    trim/scale/quantize tail as ONE jitted program: each eager op is a
    separately dispatched executable, and on a high-latency link the
    half-dozen dispatches cost more than the kernel itself."""
    fn = _build(dpad, t_out, cpad, b, spread, interpret)
    k_max = (127 + spread) // 128
    tr = _tr_rows(t_in, b // 128, k_max)

    @jax.jit
    @jax.named_scope("dedisperse_pallas")
    def run(fil_tc, delays, killmask):
        x = fil_tc.astype(jnp.float32) * killmask.astype(jnp.float32)[None, :]
        # (C, TR, 128) blocked channel rows (tail zero rows = window
        # slack; never selected into real output samples)
        xp = jnp.pad(
            x.T, ((0, cpad - c), (0, tr * 128 - t_in))
        ).reshape(cpad, tr, 128)
        out = fn(delays, xp).reshape(dpad, t_out)[:d, :out_nsamps]
        if scale != 1.0:
            out = out * jnp.float32(scale)
        if quantize:
            out = jnp.clip(jnp.rint(out), 0, 255).astype(jnp.uint8)
        return out

    return run
