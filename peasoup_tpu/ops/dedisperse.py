"""Incoherent dedispersion as a batched XLA gather/reduce.

The reference delegates this to the external `dedisp` CUDA library
(reference: include/transforms/dedisperser.hpp:98-113). TPU-native
design: the (DM trial, channel) delay table becomes a per-channel
dynamic-slice of the (time, channel) filterbank, summed over channels —
one jitted program batched over a DM-trial block, which XLA lowers to
large fused gathers feeding the VPU. No scalar loops, static shapes.

Output matches the reference's u8 trials when ``quantize=True``
(dedisp is called with 8-bit output; for <=6-bit inputs with <=64
channels raw channel sums fit u8 exactly).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.telemetry import current as current_telemetry
from ..utils.device import device_bytes_limit


def _shift_slice(row_b: jax.Array, delay: jax.Array, nb: int) -> jax.Array:
    """row[delay : delay + nb*128] from a (T/128, 128) blocked channel
    row, decomposed as delay = 128q + s.

    An arbitrary-offset 1-D dynamic slice makes XLA rotate lanes the
    slow way (measured 10x over a static slice); slicing the BLOCKED
    row on its leading axis is pure addressing, and the s < 128
    residual becomes one whole-array lane-roll plus a row-boundary
    select — measured 2x faster end-to-end, bitwise identical.
    """
    q = delay // 128
    s = delay % 128
    # the 0 start index must carry q's dtype: a bare Python 0
    # canonicalises to i64 under enable_x64 and vmap then stacks
    # mismatched index dtypes (audit contract pass traces under x64)
    v = jax.lax.dynamic_slice(row_b, (q, jnp.int32(0)), (nb + 1, 128))
    a = jnp.roll(v, -s, axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (nb, 128), 1)
    return jnp.where(lane < 128 - s, a[:nb], a[1:]).reshape(-1)


def _pad_blocks(x_tc: jax.Array) -> jax.Array:
    """Zero-pad the time axis so every channel row reshapes to
    (T/128, 128) blocks with one spare block for _shift_slice's
    window (the pad is never read when delay + out_nsamps <= T)."""
    t = x_tc.shape[0]
    tpad = (-(-t // 128) + 2) * 128
    return jnp.pad(x_tc, ((0, tpad - t), (0, 0)))


def _dedisperse_core(
    x_cb: jax.Array,  # (C, T/128, 128) blocked, masked, f32-summable rows
    delays: jax.Array,  # (D, C) int32
    *,
    out_nsamps: int,
    quantize: bool,
    scale: float,
) -> jax.Array:
    """Channel-major shift-and-sum scan (the shared engine of the
    direct path and both subband stages; channel-major input means no
    transposes anywhere on the subband path)."""
    nb = -(-out_nsamps // 128)

    # accumulate channel by channel with a lax.scan: a (D, C, T_out)
    # shifted tensor would not fit HBM at survey scale (XLA materialises
    # vmapped dynamic slices before reducing), while the (D, T_out)
    # carry is one trial block. Channel sums of <=8-bit samples are
    # exact integers in f32, so the summation order cannot change the
    # result.
    def body(acc, cin):
        row_b, dcol = cin  # (T/128, 128) blocked samples, (D,) delays
        return (
            acc
            + jax.vmap(lambda d: _shift_slice(row_b, d, nb))(dcol)[
                :, :out_nsamps
            ],
            None,
        )

    acc0 = jnp.zeros((delays.shape[0], out_nsamps), jnp.float32)
    out, _ = jax.lax.scan(body, acc0, (x_cb, delays.T))  # (D, T_out)
    if scale != 1.0:
        out = out * jnp.float32(scale)
    if quantize:
        out = jnp.clip(jnp.rint(out), 0, 255).astype(jnp.uint8)
    return out


@partial(jax.jit, static_argnames=("out_nsamps", "quantize", "scale"))
def dedisperse_block(
    fil_tc: jax.Array,  # (T, C) uint8/float32 filterbank samples
    delays: jax.Array,  # (D, C) int32 per-trial per-channel delay in samples
    killmask: jax.Array,  # (C,) int32/float32, 1 = keep
    *,
    out_nsamps: int,
    quantize: bool = True,
    scale: float = 1.0,
) -> jax.Array:
    """Dedisperse one block of DM trials: out[d, t] = sum_c x[t + delay[d,c], c].

    ``scale`` rescales channel sums into the u8 output range like dedisp's
    8-bit output mode; use :func:`output_scale` for a data-independent
    factor (1.0 for the 2-bit golden data, keeping raw-sum parity).
    Returns (D, out_nsamps) u8 (quantize=True) or f32.
    """
    x_ct = _pad_blocks(fil_tc).astype(jnp.float32).T
    x_ct = x_ct * killmask.astype(jnp.float32)[:, None]
    x_cb = x_ct.reshape(x_ct.shape[0], -1, 128)  # (C, T/128, 128)
    return _dedisperse_core(
        x_cb, delays, out_nsamps=out_nsamps, quantize=quantize, scale=scale
    )


@partial(jax.jit, static_argnames=("nbits", "nsamps", "nchans"))
def unpack_fil_device(
    raw: jax.Array, *, nbits: int, nsamps: int, nchans: int
) -> jax.Array:
    """Unpack sub-byte filterbank samples ON DEVICE (LSB-first within
    each byte, matching io.sigproc.unpack_bits and libdedisp's sub-word
    extraction). The host uploads the PACKED bytes — 4x less
    host->device traffic for 2-bit data — exactly as the reference
    hands dedisp the packed filterbank and unpacks on the GPU."""
    per = 8 // nbits
    shifts = (jnp.arange(per, dtype=jnp.uint8) * nbits)[None, :]
    w = (raw[:, None] >> shifts) & jnp.uint8((1 << nbits) - 1)
    return w.reshape(nsamps, nchans)


def fil_to_device(fil) -> jax.Array:
    """Stage a Filterbank's samples on device, uploading packed bytes
    when the file had sub-byte samples."""
    raw = getattr(fil, "raw", None)
    if raw is not None and fil.nbits in (1, 2, 4):
        return unpack_fil_device(
            jnp.asarray(raw), nbits=fil.nbits, nsamps=fil.nsamps,
            nchans=fil.nchans,
        )
    return jnp.asarray(fil.data)


def output_scale(nbits: int, nchans_kept: int) -> float:
    """Data-independent factor keeping worst-case channel sums inside u8.

    1.0 whenever raw sums already fit (e.g. 2-bit x 64 channels = 192),
    else shrink so the maximum possible sum maps to 255.
    """
    max_sum = (2**nbits - 1) * max(1, nchans_kept)
    return 1.0 if max_sum <= 255 else 255.0 / max_sum


def dedisperse_device(
    fil_tc: np.ndarray,
    delays: np.ndarray,
    killmask: np.ndarray,
    out_nsamps: int,
    *,
    quantize: bool = True,
    scale: float = 1.0,
    block: int = 16,
    chunk_bytes: int = 3_000_000_000,
) -> jax.Array:
    """Channel-chunking front end: both engines below materialise an
    f32 copy of their input (C * T * 4 bytes), which at survey scale
    (2^21 samples x 1024+ channels ~ 8.6 GB) crowds HBM and has been
    seen to crash the XLA compile helper outright. Channels split into
    chunks whose f32 copy stays under ``chunk_bytes``; f32 partial
    sums accumulate in channel-ascending order (bitwise-identical for
    the <=8-bit integer inputs the pipeline produces — channel sums
    are exact in f32; pure-f32 filterbanks may differ by summation
    association, i.e. 1 quantized LSB), and quantize/scale apply once
    at the end. The DM axis also splits when the live f32 partials
    (acc + part) would exceed the chunk budget."""
    c = delays.shape[1]
    t_in = fil_tc.shape[0]
    cc = max(1, int(chunk_bytes // max(1, 4 * t_in)))
    if cc >= c:
        return _dedisperse_device_once(
            fil_tc, delays, killmask, out_nsamps,
            quantize=quantize, scale=scale, block=block,
        )
    delays = np.asarray(delays)
    seg = -(-max(block, chunk_bytes // (out_nsamps * 8)) // block) * block
    if seg < delays.shape[0]:
        # bound the two live (D, out) f32 partials: recurse per DM
        # segment (segments concatenate as quantized u8); when even one
        # block-sized segment overshoots the budget, proceed anyway —
        # a single block is the minimum unit of work
        parts = [
            dedisperse_device(
                fil_tc, delays[s0 : s0 + seg], killmask, out_nsamps,
                quantize=quantize, scale=scale, block=block,
                chunk_bytes=chunk_bytes,
            )
            for s0 in range(0, delays.shape[0], seg)
        ]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    killmask = np.asarray(killmask)
    # pad the tail chunk (repeated delay column, zero killmask — inert)
    # so every chunk reuses ONE compiled shape
    cpad = -(-c // cc) * cc
    if cpad > c:
        delays = np.concatenate(
            [delays, np.tile(delays[:, -1:], (1, cpad - c))], axis=1
        )
        killmask = np.concatenate(
            [killmask, np.zeros(cpad - c, killmask.dtype)]
        )
        pad_cols = np.zeros(
            (t_in, cpad - c), dtype=np.asarray(fil_tc[:1, :1]).dtype
        )
    acc = None
    for lo in range(0, cpad, cc):
        if lo + cc <= c:
            fil_chunk = fil_tc[:, lo : lo + cc]
        else:
            fil_chunk = jnp.concatenate(
                [jnp.asarray(fil_tc[:, lo:c]), jnp.asarray(pad_cols)], axis=1
            )
        part = _dedisperse_device_once(
            fil_chunk,
            delays[:, lo : lo + cc],
            killmask[lo : lo + cc],
            out_nsamps,
            quantize=False,
            scale=1.0,
            block=block,
        )
        acc = part if acc is None else acc + part
    if scale != 1.0:
        acc = acc * jnp.float32(scale)
    if quantize:
        acc = jnp.clip(jnp.rint(acc), 0, 255).astype(jnp.uint8)
    return acc


def _dedisperse_device_once(
    fil_tc: np.ndarray,
    delays: np.ndarray,
    killmask: np.ndarray,
    out_nsamps: int,
    *,
    quantize: bool = True,
    scale: float = 1.0,
    block: int = 16,
) -> jax.Array:
    """Dedisperse all DM trials in device-sized blocks, keeping the
    (ndm, out_nsamps) result RESIDENT on device.

    The filterbank is transferred once and the trials never round-trip
    through the host — the search slices trial rows on device (the
    reference instead keeps trials in host RAM and re-uploads each one,
    timeseries.hpp:335-344). Blocks bound peak HBM ((block+1) * T * 4
    bytes of working set).

    On TPU backends where the probe passes, the whole trial set runs as
    ONE Pallas dispatch (ops/pallas/dedisperse.py: VMEM-resident
    accumulators, per-channel windows DMA'd at dynamic offsets) —
    bitwise equal to the jnp scan below, ~1.5x faster at survey scale
    and free of per-block dispatch overhead.
    """
    from .pallas import probe_pallas_dedisperse

    # probe first (cached, instant False off-TPU) so non-TPU backends
    # skip the O(D*C) monotonicity scan entirely; the kernel also needs
    # its full f32 output + padded f32 filterbank copy to fit HBM —
    # bigger sets stay on the blocked scan, whose working set is one
    # trial block. The route lands in telemetry (``dedisp_engine``).
    route = dict(ndm=int(delays.shape[0]), nchans=int(delays.shape[1]))
    if probe_pallas_dedisperse() and np.all(
        np.diff(np.asarray(delays), axis=0) >= 0
    ):
        from .pallas.dedisperse import (
            dedisperse_pallas,
            pallas_hbm_bytes,
            plan_spread,
        )

        spread = plan_spread(delays)
        need = pallas_hbm_bytes(
            fil_tc.shape[0], delays.shape[1], delays.shape[0], out_nsamps,
            spread=spread,
        )
        route["fits"] = bool(need < 0.6 * device_bytes_limit())
        if route["fits"]:
            current_telemetry().event(
                "dedisp_engine", engine="pallas", **route
            )
            return dedisperse_pallas(
                fil_tc, delays, killmask, out_nsamps,
                quantize=quantize, scale=scale, spread=spread,
            )
    current_telemetry().event("dedisp_engine", engine="scan", **route)
    ndm = delays.shape[0]
    fil_dev = jnp.asarray(fil_tc)
    kill_dev = jnp.asarray(killmask)
    outs = []
    for start in range(0, ndm, block):
        d = np.asarray(delays[start : start + block], dtype=np.int32)
        pad = 0
        if len(d) < block:  # pad to a fixed block shape to avoid recompiles
            pad = block - len(d)
            d = np.pad(d, ((0, pad), (0, 0)))
        res = dedisperse_block(
            fil_dev,
            jnp.asarray(d),
            kill_dev,
            out_nsamps=out_nsamps,
            quantize=quantize,
            scale=scale,
        )
        outs.append(res[: block - pad] if pad else res)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


# ---------------------------------------------------------------------------
# MXU banded-matmul engine (ISSUE 12): the shift-and-sum recast as a
# one-hot banded contraction so the inner loop runs on the MXU.
#
# For a block of adjacent DM trials the per-channel delays decompose as
# delay[d, c] = base[c] + resid[d, c] with base[c] the block minimum and
# resid small (adjacent trials' delays differ slowly). With the one-hot
# operand W[d, c, v] = (resid[d, c] == v) the shift-and-sum becomes
#
#     out[d, t] = sum_{c, v} W[d, c, v] * x[t + base[c] + v, c]
#
# — a VALID cross-correlation of the base-aligned channel windows with a
# (D, C, band) selection kernel, i.e. exactly the (trials x band) @
# (band x samples) banded matmul of arXiv:1201.5380's factorisation
# once XLA im2col-unfolds it, which on TPU lowers to MXU convolutions.
# MACs grow from D*C*T to D*C*band*T, but each MAC runs at matrix-unit
# rather than gather/add throughput; the planner's cost model
# (plan/dedisp_plan.py) and the per-device tuner (perf/tuning.py)
# arbitrate. Products are x*1 or x*0 and channel sums of <=8-bit
# samples are exact integers in f32, so the result is BITWISE equal to
# the gather engines for integer inputs regardless of summation order;
# pure-f32 filterbanks may differ by association (pinned ULP tolerance
# in tests/test_matmul_dedisp.py).
# ---------------------------------------------------------------------------

MATMUL_BAND_QUANT = 8  # resid band rounds up to this (bounds compile count)
MATMUL_BLOCK = 64  # DM trials per banded-matmul dispatch


def matmul_band(delays_block: np.ndarray, quant: int = MATMUL_BAND_QUANT) -> int:
    """The padded one-hot band of one DM-trial block: the largest
    per-channel delay spread across the block plus one, rounded up to
    ``quant`` so nearby blocks share a compiled shape."""
    d = np.asarray(delays_block)
    spread = int((d.max(axis=0) - d.min(axis=0)).max()) + 1
    return -(-spread // quant) * quant


def banded_onehot(
    delays_block: np.ndarray, band: int
) -> tuple[np.ndarray, np.ndarray]:
    """(base (C,) i32, onehot (D, C, band) f32) for one trial block:
    the sparse shift-selection operand of the banded matmul."""
    d = np.asarray(delays_block, dtype=np.int64)
    base = d.min(axis=0)
    resid = d - base[None, :]
    onehot = (
        resid[:, :, None] == np.arange(band, dtype=np.int64)[None, None, :]
    ).astype(np.float32)
    return base.astype(np.int32), onehot


def _banded_conv(xb: jax.Array, onehot: jax.Array) -> jax.Array:
    """out[d, t] = sum_{c, v} onehot[d, c, v] * xb[c, t + v] as a VALID
    1-D correlation (XLA lowers this to the MXU on TPU backends)."""
    return jax.lax.conv_general_dilated(
        xb[None],
        onehot,
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NCH", "OIH", "NCH"),
        preferred_element_type=jnp.float32,
    )[0]


@partial(jax.jit, static_argnames=("out_nsamps", "quantize", "scale"))
def dedisperse_matmul_block(
    fil_tc: jax.Array,  # (T, C) u8/f32 filterbank (zero-padded so that
    # base[c] + out_nsamps + band - 1 <= T for every channel)
    base: jax.Array,  # (C,) i32 per-channel block-minimum delay
    onehot: jax.Array,  # (D, C, band) f32 one-hot shift selection
    killmask: jax.Array,  # (C,) 1 = keep
    *,
    out_nsamps: int,
    quantize: bool = True,
    scale: float = 1.0,
) -> jax.Array:
    """One DM-trial block on the MXU: slice each channel's base-aligned
    window, then contract against the one-hot band. Returns
    (D, out_nsamps) u8 (quantize) or f32, bitwise equal to
    :func:`dedisperse_block` for integer inputs."""
    band = onehot.shape[-1]
    win = out_nsamps + band - 1
    x_ct = fil_tc.T  # stays in the upload dtype until after the slice
    xb = jax.vmap(
        lambda row, b: jax.lax.dynamic_slice(row, (b,), (win,))
    )(x_ct, base)
    xb = xb.astype(jnp.float32) * killmask.astype(jnp.float32)[:, None]
    out = _banded_conv(xb, onehot)
    if scale != 1.0:
        out = out * jnp.float32(scale)
    if quantize:
        out = jnp.clip(jnp.rint(out), 0, 255).astype(jnp.uint8)
    return out


def dedisperse_matmul(
    fil_tc,  # (T, C) u8/f32 filterbank (numpy or device array)
    delays: np.ndarray,  # (D, C) int32
    killmask: np.ndarray,
    out_nsamps: int,
    *,
    quantize: bool = True,
    scale: float = 1.0,
    block: int = MATMUL_BLOCK,
    band_quant: int = MATMUL_BAND_QUANT,
    chunk_bytes: int = 3_000_000_000,
) -> jax.Array:
    """All DM trials through the banded-matmul engine, ``block`` trials
    per dispatch. Per block, the one-hot band adapts to the real delay
    spread (rounded to ``band_quant`` so a survey's blocks share a few
    compiled shapes). Channels chunk when a block's f32 window copy
    (C * (out + band) * 4 bytes) would exceed ``chunk_bytes``, with f32
    partials accumulated channel-ascending exactly like
    :func:`dedisperse_device` (bitwise-identical for integer inputs)."""
    delays = np.asarray(delays, dtype=np.int32)
    d, c = delays.shape
    # per-block bands first: the input pad must cover the largest window
    blocks = []
    for lo in range(0, d, block):
        blk = delays[lo : lo + block]
        blocks.append((lo, lo + len(blk), matmul_band(blk, band_quant)))
    band_max = max(b for _, _, b in blocks)
    win_max = out_nsamps + band_max - 1
    cc = max(1, int(chunk_bytes // max(1, 4 * win_max)))
    if cc < c:
        # channel-chunk recursion: unquantized partials, one final tail
        acc = None
        for c0 in range(0, c, cc):
            part = dedisperse_matmul(
                fil_tc[:, c0 : c0 + cc], delays[:, c0 : c0 + cc],
                np.asarray(killmask)[c0 : c0 + cc], out_nsamps,
                quantize=False, scale=1.0, block=block,
                band_quant=band_quant, chunk_bytes=chunk_bytes,
            )
            acc = part if acc is None else acc + part
        if scale != 1.0:
            acc = acc * jnp.float32(scale)
        if quantize:
            acc = jnp.clip(jnp.rint(acc), 0, 255).astype(jnp.uint8)
        return acc
    t_in = fil_tc.shape[0]
    t_need = int(delays.max()) + out_nsamps + band_max
    x_dev = jnp.asarray(fil_tc)
    if t_need > t_in:  # zero tail: only ever multiplied by onehot zeros
        x_dev = jnp.pad(x_dev, ((0, t_need - t_in), (0, 0)))
    kill_dev = jnp.asarray(np.asarray(killmask))
    outs = []
    for lo, hi, band in blocks:
        blk = delays[lo:hi]
        pad = 0
        if hi - lo < block:  # repeat the last trial: one shape per band
            pad = block - (hi - lo)
            blk = np.concatenate([blk, np.repeat(blk[-1:], pad, axis=0)])
        base, onehot = banded_onehot(blk, band)
        res = dedisperse_matmul_block(
            x_dev, jnp.asarray(base), jnp.asarray(onehot), kill_dev,
            out_nsamps=out_nsamps, quantize=quantize, scale=scale,
        )
        outs.append(res[: block - pad] if pad else res)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def subband_groups(
    delay_table: np.ndarray,  # (D, C) int32 per-trial per-channel delays
    nsub: int,
    max_smear: float,
    budgets: np.ndarray | None = None,
) -> list[tuple[int, int]]:
    """Greedy grouping of adjacent DM trials sharing one nominal DM for
    two-stage subband dedispersion (the scheme of the dedisp library
    the reference links, dedisperser.hpp:25-31 — there hidden inside
    `dedisp_execute`). Trials join the group opened by trial ``lo``
    while the worst-case intra-subband smear of substituting trial lo's
    channel shape stays <= the joining trial's budget — ``max_smear``
    samples for every trial, or ``budgets[hi]`` when the caller passes
    the DM-scaled per-trial budgets (plan/dedisp_plan.py:
    dm_smear_budgets, so high-DM trials whose intrinsic smearing
    already dwarfs a sample stop forcing conservative plans).
    ``max_smear=0`` gives singleton groups (exact direct equality).
    Returns [lo, hi) spans.
    """
    D, C = delay_table.shape
    w = -(-C // nsub)
    groups = []
    lo = 0
    while lo < D:
        hi = lo + 1
        while hi < D:
            cap = max_smear if budgets is None else float(budgets[hi])
            # smear of trial hi under trial lo's intra-band shape:
            # max_c |(d[hi,c]-d[hi,ref]) - (d[lo,c]-d[lo,ref])|
            err = 0
            for b in range(0, C, w):
                dl = delay_table[lo, b : b + w]
                dh = delay_table[hi, b : b + w]
                # same min-reference convention as dedisperse_subband,
                # so this bound is exactly the stage-2 index error
                err = max(
                    err, int(np.abs((dh - dh.min()) - (dl - dl.min())).max())
                )
                if err > cap:
                    break
            if err > cap:
                break
            hi += 1
        groups.append((lo, hi))
        lo = hi
    return groups


@partial(jax.jit, static_argnames=("nb1",))
def _subband_stage1(
    x_swt: jax.Array,  # (S, w, T) u8/f32 filterbank grouped into subbands
    kill_sw: jax.Array,  # (S, w) f32 killmask in the same grouping
    d1: jax.Array,  # (S, w) int32 intra-band delays at the nominal DM
    *,
    nb1: int,  # output length in 128-blocks (ceil(t1/128) + 2 spare)
) -> jax.Array:
    """Per-subband shift-and-sum at one nominal DM:
    out[b, t] = sum_c kill[b, c] * x[b, c, t + d1[b, c]] — the same
    scan-over-channels pattern as the direct core, vmapped over
    subbands. The f32 cast + killmask happen per scan step so the
    resident grouped filterbank stays u8. Output is the CHANNEL-MAJOR
    BLOCKED (S, nb1, 128) form that stage 2's core consumes directly,
    so the subband path has no transposes at all."""
    s_count, _, t_tot = x_swt.shape
    x_blk = x_swt.reshape(s_count, -1, t_tot // 128, 128)

    def body(acc, cin):
        rows, kcol, dcol = cin  # (S, T/128, 128), (S,), (S,)
        sl = jax.vmap(lambda r, d: _shift_slice(r, d, nb1))(rows, dcol)
        if sl.dtype != jnp.float32:  # spill path keeps the rows u8
            sl = sl.astype(jnp.float32)
        return acc + sl * kcol[:, None], None

    acc0 = jnp.zeros((s_count, nb1 * 128), jnp.float32)
    out, _ = jax.lax.scan(
        body, acc0, (jnp.swapaxes(x_blk, 0, 1), kill_sw.T, d1.T)
    )
    return out.reshape(s_count, nb1, 128)


@lru_cache(maxsize=None)
def _stage1_batched(nb1: int):
    """Jitted group-batched stage 1, cached so repeat calls (multi-file
    surveys, resumed runs) reuse the compiled program."""
    return jax.jit(
        jax.vmap(partial(_subband_stage1, nb1=nb1), in_axes=(None, None, 0))
    )


@lru_cache(maxsize=None)
def _stage2_batched(out_nsamps: int, quantize: bool, scale: float):
    """Jitted group-batched stage 2 (the channel-major core over
    subbands), cached like _stage1_batched."""
    return jax.jit(
        jax.vmap(
            partial(
                _dedisperse_core,
                out_nsamps=out_nsamps,
                quantize=quantize,
                scale=scale,
            ),
        )
    )


@lru_cache(maxsize=None)
def _stage1_matmul_batched(out_len: int, band: int):
    """Jitted group-batched stage 1 as a banded matmul: the grouped
    filterbank's per-band rows correlate against a per-(group, band)
    one-hot shift selection, vmapped over subbands — the stage-1 twin
    of :func:`dedisperse_matmul_block` (groups play the trial role:
    adjacent nominal DMs have slowly-varying intra-band shapes, so the
    one-hot band stays narrow). fn(x_swt (S, w, T) u8/f32,
    kill_sw (S, w), base_sw (S, w) i32, onehot (G, S, w, band)) ->
    (G, S, out_len/128, 128) f32, bitwise the scan stage's output for
    integer inputs."""

    def per_band(x_wt, kill_w, base_w, onehot_gwb):
        rows = x_wt.astype(jnp.float32) * kill_w[:, None]
        # static tail pad keeps every base-aligned window in range; the
        # pad region is only ever multiplied by one-hot zeros
        rows = jnp.pad(rows, ((0, 0), (0, band)))
        win = out_len + band - 1
        xb = jax.vmap(
            lambda r, b: jax.lax.dynamic_slice(r, (b,), (win,))
        )(rows, base_w)
        return _banded_conv(xb, onehot_gwb)  # (G, out_len)

    def run(x_swt, kill_sw, base_sw, onehot_gswb):
        out = jax.vmap(per_band, in_axes=(0, 0, 0, 1))(
            x_swt, kill_sw, base_sw, onehot_gswb
        )  # (S, G, out_len)
        g = out.shape[1]
        return jnp.swapaxes(out, 0, 1).reshape(g, out.shape[0], -1, 128)

    return jax.jit(run)


@lru_cache(maxsize=None)
def _stage2_matmul_batched(
    out_nsamps: int, quantize: bool, scale: float, band: int
):
    """Jitted group-batched stage 2 as a banded matmul over subband
    partial series (subbands play the channel role). fn(s1
    (G, S, nb1, 128) f32, base (G, S) i32, onehot (G, g_pad, S, band))
    -> (G, g_pad, out_nsamps), bitwise the scan stage's output for
    integer-valued stage-1 sums."""

    def per_group(x_blk, base_s, onehot_dsb):
        rows = x_blk.reshape(x_blk.shape[0], -1)
        rows = jnp.pad(rows, ((0, 0), (0, band)))
        win = out_nsamps + band - 1
        xb = jax.vmap(
            lambda r, b: jax.lax.dynamic_slice(r, (b,), (win,))
        )(rows, base_s)
        out = _banded_conv(xb, onehot_dsb)
        if scale != 1.0:
            out = out * jnp.float32(scale)
        if quantize:
            out = jnp.clip(jnp.rint(out), 0, 255).astype(jnp.uint8)
        return out

    return jax.jit(jax.vmap(per_group))


def dedisperse_subband(
    fil_tc,  # (T, C) u8/f32 filterbank (numpy or device)
    delay_table: np.ndarray,  # (D, C) int32 from DMPlan.delay_samples()
    killmask: np.ndarray,
    out_nsamps: int,
    *,
    nsub: int,
    max_smear: float = 1.0,
    quantize: bool = True,
    scale: float = 1.0,
    to_host: bool = False,
    use_matmul: bool = False,
    budgets: np.ndarray | None = None,
):
    """Two-stage subband dedispersion of ALL trials.

    Stage 1 (once per nominal DM, the first trial of each group):
    align channels WITHIN each of ``nsub`` subbands, giving (S, T1)
    partial time series. Stage 2 (per trial): combine the nominal's
    subbands with the trial's own reference-channel delays — which is
    exactly :func:`dedisperse_block` treating subbands as channels.
    Arithmetic per group of g trials: C*T + g*S*T instead of the direct
    g*C*T — ~sqrt(C)-fold less at survey channel counts when
    g ~ C/S ~ S. The approximation replaces each trial's intra-band
    delay shape by its nominal's; grouping bounds that error to
    ``max_smear`` samples (0 => bitwise equal to the direct path), or
    to the per-trial ``budgets`` when given (the DM-scaled smear
    budget, plan/dedisp_plan.py). With ``use_matmul`` both stages run
    as banded matmuls on the MXU (bitwise-identical for integer
    inputs; see the banded-matmul engine block above).

    Returns (D, out_nsamps), device-resident (or numpy with
    ``to_host``, for surveys whose trial block spills to host RAM).
    """
    delay_table = np.asarray(delay_table, dtype=np.int32)
    D, C = delay_table.shape
    # effective band count: ceil(C / w) bands of width w cover C for ANY
    # requested nsub (e.g. nsub=5 over 16 chans -> w=4, 4 bands)
    w = -(-C // max(1, min(nsub, C)))
    nsub = -(-C // w)
    cpad = w * nsub - C
    groups = subband_groups(delay_table, nsub, max_smear, budgets)

    # per-band reference = the band's MINIMUM delay (robust to either
    # frequency ordering and to rint non-monotonicity): d1 >= 0 always
    band_of = np.minimum(np.arange(C) // w, nsub - 1)
    refdel = np.stack(
        [delay_table[:, b : b + w].min(axis=1) for b in range(0, C, w)],
        axis=1,
    )  # (D, S)
    d1_all = delay_table - refdel[:, band_of]
    t1 = fil_tc.shape[0] - int(d1_all[[lo for lo, _ in groups]].max())
    # rint rounding can leave t1 one or two samples short of what
    # stage 2 addresses (interior-band rounded spans may exceed the
    # last band's); pad the time axis with zeros to cover the deficit.
    # For max_smear=0 the stage-2 index telescopes to t + d[d, c]
    # < fil_tc.shape[0], so the pad is NEVER read (exactness holds);
    # with smear it only touches the last <= smear samples per channel.
    deficit = max(0, int(refdel.max()) + out_nsamps - t1)
    t1 += deficit

    # the grouped filterbank stays in its upload dtype (u8 for packed
    # files), and stage 1 upcasts after slicing: HBM holds one u8 copy
    # instead of an f32 one (per-window upcasting before the roll was
    # tried and regressed — extra f32 write per slice, see NOTES.md)
    x = jnp.asarray(fil_tc)
    # pad time to whole 128-blocks (+3 spare: stage 1 windows reach
    # q1 + nb1 + 1 blocks with nb1 = ceil(t1/128) + 2) and pad channels
    # to equal-width bands; all pad zeros are inert
    nb1 = -(-t1 // 128) + 2
    t_need = fil_tc.shape[0] + deficit
    tpad = (-(-t_need // 128) + 3) * 128 - t_need
    if cpad or deficit or tpad:
        x = jnp.pad(x, ((0, deficit + tpad), (0, cpad)))
    x_swt = x.T.reshape(nsub, w, -1)  # (S, w, T)
    kill_sw = jnp.asarray(
        np.pad(np.asarray(killmask, np.float32), (0, cpad)).reshape(nsub, w)
    )

    # process groups in vmapped batches: per-group dispatches (2 per
    # group) would dominate at survey scale where groups hold only a
    # few trials each. Group heights shrink with DM, so first bucket
    # the (DM-ordered) groups into contiguous runs sharing a
    # power-of-two padded height, then size each bucket's batches from
    # ITS height so the live working set — the (gb, S, nb1*128) stage-1
    # partials PLUS the (gb, g_pad, out_nsamps) stage-2 f32 output
    # (ADVICE r1: the output term dominates for tall groups) — stays
    # ~1 GB without one tall low-DM bucket collapsing the batching of
    # the small-group tail. Compiled shapes: one per (gb, g_pad) bucket.
    stage1_b = None if use_matmul else _stage1_batched(nb1)
    stage2_b = (
        None if use_matmul else _stage2_batched(out_nsamps, quantize, scale)
    )

    def g_pad_of(lo, hi):
        return 1 << (hi - lo - 1).bit_length() if hi - lo > 1 else 1

    def band_of(resid) -> int:
        return -(
            -(int(resid.max()) + 1) // MATMUL_BAND_QUANT
        ) * MATMUL_BAND_QUANT

    def onehot_of(resid, band):
        return (
            resid[..., None] == np.arange(band, dtype=resid.dtype)
        ).astype(np.float32)

    outs = []
    i = 0
    while i < len(groups):
        g_pad = g_pad_of(*groups[i])
        j = i
        while j < len(groups) and g_pad_of(*groups[j]) == g_pad:
            j += 1
        per_group = 4 * nsub * nb1 * 128 + 4 * g_pad * out_nsamps
        gb = max(1, min(j - i, 1_000_000_000 // max(1, per_group)))
        for b0 in range(i, j, gb):
            batch = groups[b0 : min(b0 + gb, j)]
            if len(batch) < gb and b0 > i:  # pad: keep one shape per bucket
                batch = batch + [batch[-1]] * (gb - len(batch))
            d1 = np.stack(
                [
                    np.pad(d1_all[lo], (0, cpad)).reshape(nsub, w)
                    for lo, _ in batch
                ]
            )
            if use_matmul:
                # both stages as banded matmuls: groups play the trial
                # role in stage 1 (adjacent nominals' intra-band shapes
                # vary slowly), trials within a group in stage 2; pad
                # trials repeat the last row so the band stays narrow
                # (zero-delay pad rows would blow it open)
                base1 = d1.min(axis=0)
                r1 = d1 - base1[None]
                band1 = band_of(r1)
                rd = np.stack(
                    [
                        np.pad(
                            refdel[lo:hi],
                            ((0, g_pad - (hi - lo)), (0, 0)),
                            mode="edge",
                        )
                        for lo, hi in batch
                    ]
                )
                base2 = rd.min(axis=1)
                r2 = rd - base2[:, None, :]
                band2 = band_of(r2)
                s1 = _stage1_matmul_batched(nb1 * 128, band1)(
                    x_swt, kill_sw,
                    jnp.asarray(base1.astype(np.int32)),
                    jnp.asarray(onehot_of(r1, band1)),
                )
                res = _stage2_matmul_batched(
                    out_nsamps, quantize, scale, band2
                )(
                    s1,
                    jnp.asarray(base2.astype(np.int32)),
                    jnp.asarray(onehot_of(r2, band2)),
                )
            else:
                rd = np.stack(
                    [
                        np.pad(
                            refdel[lo:hi], ((0, g_pad - (hi - lo)), (0, 0))
                        )
                        for lo, hi in batch
                    ]
                )
                s1 = stage1_b(x_swt, kill_sw, jnp.asarray(d1))
                res = stage2_b(s1, jnp.asarray(rd, dtype=np.int32))
            if to_host:
                res = np.asarray(res)  # ONE transfer per batch
            for bi, (lo, hi) in enumerate(batch[: min(b0 + gb, j) - b0]):
                outs.append(res[bi, : hi - lo])
        i = j
    if to_host:
        return np.concatenate(outs, axis=0)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def dedisperse(
    fil_tc: np.ndarray,
    delays: np.ndarray,
    killmask: np.ndarray,
    out_nsamps: int,
    *,
    quantize: bool = True,
    scale: float = 1.0,
    block: int = 16,
) -> np.ndarray:
    """Host-resident variant: trials land in host RAM segment by
    segment, so HBM never holds more than one DM segment's outputs
    (for surveys whose full trial set would crowd the chip; cf.
    reference host-RAM trials, dedisperser.hpp:101-103). The u8
    filterbank stages on device ONCE and every segment routes through
    dedisperse_device, inheriting its Pallas dispatch and
    channel-chunking (the f32-input-copy bound applies here too)."""
    ndm = delays.shape[0]
    delays = np.asarray(delays)
    fil_dev = jnp.asarray(fil_tc)
    seg = -(-max(block, 1_000_000_000 // max(1, out_nsamps)) // block) * block
    outs = []
    for start in range(0, ndm, seg):
        res = dedisperse_device(
            fil_dev, delays[start : start + seg], killmask, out_nsamps,
            quantize=quantize, scale=scale, block=block,
        )
        outs.append(np.asarray(res))
    return np.concatenate(outs, axis=0)


# --- audit registry: representative shapes for the contract engine
# (peasoup_tpu/analysis/contracts.py) plus ShapeCtx hooks so the AOT
# warmup (peasoup_tpu/perf/warmup.py) can compile at a campaign
# bucket's production geometry; build thunks are lazy, nothing traces
# at import time ---
from .registry import register_program, sds  # noqa: E402


def _param_dedisperse_block(ctx):
    # the single-channel-chunk driver path: full filterbank against
    # one dedisp_block of delay rows, quantized at the bucket's
    # data-independent output scale (scale is a static argname, so it
    # is part of the compiled program's identity)
    d = max(1, min(ctx.dedisp_block, ctx.ndm))
    return (
        dedisperse_block,
        (
            sds((ctx.nsamps, ctx.nchans), "uint8"),
            sds((d, ctx.nchans), "int32"),
            sds((ctx.nchans,), "float32"),
        ),
        {
            "out_nsamps": ctx.out_nsamps,
            "scale": output_scale(ctx.nbits, ctx.nchans),
        },
    )


def _param_unpack(ctx):
    if ctx.nbits not in (1, 2, 4):  # byte data uploads unpacked
        return None
    return (
        unpack_fil_device,
        (sds((ctx.nsamps * ctx.nchans * ctx.nbits // 8,), "uint8"),),
        {"nbits": ctx.nbits, "nsamps": ctx.nsamps, "nchans": ctx.nchans},
    )


register_program(
    "ops.dedisperse.dedisperse_block",
    lambda: (
        dedisperse_block,
        (sds((256, 8), "uint8"), sds((4, 8), "int32"), sds((8,), "float32")),
        {"out_nsamps": 192},
    ),
    param=_param_dedisperse_block,
)
register_program(
    "ops.dedisperse.unpack_fil_device",
    lambda: (
        unpack_fil_device,
        (sds((128,), "uint8"),),
        {"nbits": 2, "nsamps": 64, "nchans": 8},
    ),
    param=_param_unpack,
)
def _param_subband_stage1(ctx):
    # the tuned-plan subband path (plan/dedisp_plan.py selects, the
    # tuning cache persists): compile stage 1 at the bucket's grouped
    # filterbank geometry. Declines non-subband ctxs.
    if ctx.subbands <= 0:
        return None
    c = ctx.nchans
    w = -(-c // max(1, min(ctx.subbands, c)))
    nsub = -(-c // w)
    nb1 = -(-ctx.out_nsamps // 128) + 2
    tpad = (-(-ctx.nsamps // 128) + 3) * 128
    return (
        _subband_stage1,
        (
            sds((nsub, w, tpad), "uint8"),
            sds((nsub, w), "float32"),
            sds((nsub, w), "int32"),
        ),
        {"nb1": nb1},
    )


register_program(
    "ops.dedisperse.subband_stage1",
    lambda: (
        _subband_stage1,
        (
            sds((2, 4, 512), "uint8"),
            sds((2, 4), "float32"),
            sds((2, 4), "int32"),
        ),
        {"nb1": 2},
    ),
    param=_param_subband_stage1,
)
def _param_stage1_batched(ctx):
    # the gather-staged subband engine's group-batched stage 1; the
    # matmul-staged variant has its own hooks below
    if ctx.subbands <= 0 or ctx.subband_matmul:
        return None
    c = ctx.nchans
    w = -(-c // max(1, min(ctx.subbands, c)))
    nsub = -(-c // w)
    nb1 = -(-ctx.out_nsamps // 128) + 2
    tpad = (-(-ctx.nsamps // 128) + 3) * 128
    return (
        _stage1_batched(nb1),
        (
            sds((nsub, w, tpad), "uint8"),
            sds((nsub, w), "float32"),
            sds((4, nsub, w), "int32"),  # vmapped over DM groups
        ),
        {},
    )


def _param_stage2_batched(ctx):
    if ctx.subbands <= 0 or ctx.subband_matmul:
        return None
    c = ctx.nchans
    w = -(-c // max(1, min(ctx.subbands, c)))
    nsub = -(-c // w)
    nb1 = -(-ctx.out_nsamps // 128) + 2
    d = max(1, min(ctx.dedisp_block, ctx.ndm))
    return (
        _stage2_batched(
            ctx.out_nsamps, True, output_scale(ctx.nbits, ctx.nchans)
        ),
        (
            sds((4, nsub, nb1, 128), "float32"),
            sds((4, d, nsub), "int32"),
        ),
        {},
    )


register_program(
    "ops.dedisperse.subband_stage1_batched",
    lambda: (
        _stage1_batched(2),
        (
            sds((2, 4, 512), "uint8"),
            sds((2, 4), "float32"),
            sds((3, 2, 4), "int32"),  # vmapped over DM groups
        ),
        {},
    ),
    param=_param_stage1_batched,
)
register_program(
    "ops.dedisperse.subband_stage2",
    lambda: (
        _stage2_batched(192, True, 1.0),
        (
            sds((2, 4, 4, 128), "float32"),  # (G, S, T/128, 128) blocked
            sds((2, 3, 4), "int32"),  # (G, D, S) stage-2 delays
        ),
        {},
    ),
    param=_param_stage2_batched,
)


def _param_dedisperse_matmul(ctx):
    # the banded-matmul engine's unit of work (the planner's third
    # alternative): one MATMUL_BLOCK trial chunk at the bucket's padded
    # window geometry. Declines ctxs whose resolved plan names another
    # engine — warmup compiles what the driver will dispatch.
    if ctx.dedisp_engine not in ("", "matmul"):
        return None
    d = max(1, min(MATMUL_BLOCK, ctx.ndm))
    band = MATMUL_BAND_QUANT
    return (
        dedisperse_matmul_block,
        (
            sds((ctx.nsamps + band, ctx.nchans), "uint8"),
            sds((ctx.nchans,), "int32"),
            sds((d, ctx.nchans, band), "float32"),
            sds((ctx.nchans,), "float32"),
        ),
        {
            "out_nsamps": ctx.out_nsamps,
            "scale": output_scale(ctx.nbits, ctx.nchans),
        },
    )


register_program(
    "ops.dedisperse.dedisperse_matmul_block",
    lambda: (
        dedisperse_matmul_block,
        (
            sds((256, 8), "uint8"),
            sds((8,), "int32"),
            sds((4, 8, 8), "float32"),
            sds((8,), "float32"),
        ),
        {"out_nsamps": 192},
    ),
    param=_param_dedisperse_matmul,
)


def _param_subband_matmul(ctx):
    """Shared geometry for the subband matmul-stage hooks: the tuned
    plan must have selected the matmul-staged subband engine."""
    if ctx.subbands <= 0 or not ctx.subband_matmul:
        return None
    c = ctx.nchans
    w = -(-c // max(1, min(ctx.subbands, c)))
    nsub = -(-c // w)
    nb1 = -(-ctx.out_nsamps // 128) + 2
    tpad = (-(-ctx.nsamps // 128) + 3) * 128
    return nsub, w, nb1, tpad


def _param_stage1_matmul(ctx):
    geo = _param_subband_matmul(ctx)
    if geo is None:
        return None
    nsub, w, nb1, tpad = geo
    return (
        _stage1_matmul_batched(nb1 * 128, MATMUL_BAND_QUANT),
        (
            sds((nsub, w, tpad), "uint8"),
            sds((nsub, w), "float32"),
            sds((nsub, w), "int32"),
            sds((4, nsub, w, MATMUL_BAND_QUANT), "float32"),
        ),
        {},
    )


def _param_stage2_matmul(ctx):
    geo = _param_subband_matmul(ctx)
    if geo is None:
        return None
    nsub, w, nb1, tpad = geo
    return (
        _stage2_matmul_batched(
            ctx.out_nsamps, True, output_scale(ctx.nbits, ctx.nchans),
            MATMUL_BAND_QUANT,
        ),
        (
            sds((4, nsub, nb1, 128), "float32"),
            sds((4, nsub), "int32"),
            sds((4, 8, nsub, MATMUL_BAND_QUANT), "float32"),
        ),
        {},
    )


register_program(
    "ops.dedisperse.subband_stage1_matmul",
    lambda: (
        _stage1_matmul_batched(256, 8),
        (
            sds((2, 4, 512), "uint8"),  # (S, w, T) grouped filterbank
            sds((2, 4), "float32"),  # (S, w) killmask
            sds((2, 4), "int32"),  # (S, w) batch-min intra-band delays
            sds((3, 2, 4, 8), "float32"),  # (G, S, w, band) one-hot
        ),
        {},
    ),
    param=_param_stage1_matmul,
)
register_program(
    "ops.dedisperse.subband_stage2_matmul",
    lambda: (
        _stage2_matmul_batched(192, True, 1.0, 8),
        (
            sds((2, 4, 4, 128), "float32"),  # (G, S, nb1, 128) stage-1 sums
            sds((2, 4), "int32"),  # (G, S) group-min stage-2 delays
            sds((2, 3, 4, 8), "float32"),  # (G, D, S, band) one-hot
        ),
        {},
    ),
    param=_param_stage2_matmul,
)
