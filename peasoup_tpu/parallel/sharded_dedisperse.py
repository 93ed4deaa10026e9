"""DM-trial-sharded dedispersion over a device mesh.

The reference dedisperses across ALL GPUs in one node
(`dedisp_create_plan_multi`, reference include/transforms/
dedisperser.hpp:25-31). Here the DM-trial axis is laid out on the mesh's
``dm`` axis with ``shard_map``. The host sends the input's packed bytes
once per beam (2 bits a sample for 2-bit data: a quarter of the
unpacked bytes, a sixteenth of an f32 copy), each chip a 1/N block of
consecutive time rows; an all-gather over the chips' interconnect then
assembles the whole input on every chip, since each chip's trials read
every channel at every sample. Each chip then walks the
channel-chunk x DM-segment decomposition of the one-chip
``ops.dedisperse.dedisperse_device``, sized from the same budget, over
ITS slice of the delay table: it unpacks one channel chunk at a time,
runs the Pallas blocked-roll kernel (ops/pallas/dedisperse.py; off TPU
its jnp twin, ``_dedisperse_core``), adds the f32 partials in ascending
channel order and quantises once. The (ndm, out_nsamps) trial block
materialises ALREADY SHARDED the way the search consumes it: the search
deals each chip the rows it holds, and every chip reads them from its
own shard (``make_local_gather``), never through the host or ICI.

Bitwise identical to ``dedisperse_device``: channel sums of <=8-bit
samples are exact integers in f32, so neither the chunking nor the
per-chip split can change a sum, and scale and rounding apply once.
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.telemetry import current as current_telemetry
from ..ops.dedisperse import CHUNK_BYTES, _dedisperse_core, chunk_sizes


def _shard_map_nocheck(local_fn, mesh, in_specs, out_specs):
    # check_vma off: the local bodies are collective-free, and values
    # created inside (loop carries, iotas) start unvarying while the
    # delays are device-varying — the check would demand pvary casts
    # inside shared single-device code
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def packed_input(fil) -> tuple[np.ndarray, int]:
    """(samples, nbits) to lay over the mesh for ``fil``: its packed
    bytes when whole bytes hold whole channel groups, else its (T, C)
    samples."""
    raw = getattr(fil, "raw", None)
    if (
        raw is not None and fil.nbits in (1, 2, 4)
        and fil.nchans % (8 // fil.nbits) == 0
    ):
        return raw, fil.nbits
    return fil.data, 8


def shard_plan(
    ndm: int, n_dev: int, t_in: int, nchans: int, out_nsamps: int, *,
    block: int, chunk_bytes: int, quantum: int, chan_quantum: int,
) -> tuple[int, int, int]:
    """(trials per chip, trials per DM segment, channels per chunk).

    Chunk and segment caps are ``chunk_sizes``' (the one-chip engine's
    budget). Segments split each chip's share evenly and round up to
    ``quantum`` trials; chunks round down to ``chan_quantum`` channels,
    and the last chunk overlaps its predecessor instead of padding."""
    cc_cap, seg_cap = chunk_sizes(
        t_in, out_nsamps, block=block, chunk_bytes=chunk_bytes
    )
    share = -(-ndm // n_dev)
    n_seg = -(-share // seg_cap)
    seg = -(-share // (n_seg * quantum)) * quantum
    cc = nchans
    if cc_cap < nchans:
        cc = min(nchans, max(chan_quantum, cc_cap // chan_quantum * chan_quantum))
    return n_seg * seg, seg, cc


@lru_cache(maxsize=None)
def _sharded_program(
    mesh: Mesh, axis: str, t_in: int, nchans: int, nbits: int,
    per_dev: int, seg: int, cc: int, out_nsamps: int, spread: int,
    quantize: bool, scale: float, pallas: bool, interpret: bool,
):
    """Jitted (rows, delays, killmask) -> (ndm_pad, out_nsamps) trials,
    sharded over ``axis``; ``rows`` is the replicated byte-major, blocked
    input from ``_blocked_bytes``."""
    per = 8 // nbits if nbits < 8 else 1
    n_seg = per_dev // seg
    n_ch = -(-nchans // cc)

    nrows = block_rows(pallas, t_in, out_nsamps, spread)
    if pallas:
        from ..ops.pallas.dedisperse import _CC, _QUANT, _build

        b = min(16384, max(_QUANT, -(-out_nsamps // _QUANT) * _QUANT))
        acc_shape = (seg, -(-out_nsamps // b) * b // 128, 128)
        ccp = -(-cc // _CC) * _CC
        kernel = _build(seg, acc_shape[1] * 128, ccp, b, spread, interpret)

        def partial_sum(x_cb, d):
            # zero channels to the kernel's 16-channel step, at the last
            # channel's delay
            if ccp > cc:
                x_cb = jnp.pad(x_cb, ((0, ccp - cc), (0, 0), (0, 0)))
                d = jnp.concatenate(
                    [d, jnp.broadcast_to(d[:, -1:], (seg, ccp - cc))], axis=1
                )
            return kernel(d, x_cb)
    else:
        acc_shape = (seg, out_nsamps)

        def partial_sum(x_cb, d):
            return _dedisperse_core(
                x_cb, d, out_nsamps=out_nsamps, quantize=False, scale=1.0
            )

    def unpack(rows, lo):
        """(cc, nrows, 128) blocked samples of channels lo .. lo + cc."""
        with jax.named_scope("unpack_fil"):
            x = jax.lax.dynamic_slice_in_dim(rows, lo // per, cc // per, 0)
            if per > 1:
                shifts = jnp.arange(per, dtype=jnp.uint8) * jnp.uint8(nbits)
                x = (x[:, None] >> shifts[None, :, None, None]) & jnp.uint8(
                    (1 << nbits) - 1
                )
            return x.reshape(cc, nrows, 128)

    def local(rows, delays, kill):
        def segment(s, out):
            d_seg = jax.lax.dynamic_slice_in_dim(delays, s * seg, seg, 0)

            def step(i, acc):
                # the last chunk overlaps its predecessor, whose
                # channels it weighs 0
                lo = jnp.minimum(i * cc, nchans - cc)
                chans = lo + jnp.arange(cc)
                w = jnp.where(
                    chans >= i * cc,
                    jax.lax.dynamic_slice_in_dim(kill, lo, cc), 0.0,
                )
                d = jax.lax.dynamic_slice_in_dim(d_seg, lo, cc, 1)
                x_cb = unpack(rows, lo).astype(jnp.float32) * w[:, None, None]
                return acc + partial_sum(x_cb, d)

            acc = jax.lax.fori_loop(
                0, n_ch, step, jnp.zeros(acc_shape, jnp.float32)
            ).reshape(seg, -1)[:, :out_nsamps]
            if scale != 1.0:
                acc = acc * jnp.float32(scale)
            if quantize:
                acc = jnp.clip(jnp.rint(acc), 0, 255).astype(jnp.uint8)
            return jax.lax.dynamic_update_slice_in_dim(out, acc, s * seg, 0)

        with jax.named_scope("dedisperse_sharded"):
            out = jnp.zeros(
                (per_dev, out_nsamps), jnp.uint8 if quantize else jnp.float32
            )
            return jax.lax.fori_loop(0, n_seg, segment, out)

    return jax.jit(
        _shard_map_nocheck(
            local, mesh, (P(), P(axis, None), P()), P(axis, None)
        )
    )


def block_rows(pallas: bool, t_in: int, out_nsamps: int, spread: int) -> int:
    """Rows of 128 samples each channel is blocked into: the kernel's
    (with its window slack) or ``_pad_blocks``' (the jnp twin's), in
    whole 32-row tiles. A chip lays 8-bit rows out four to a word along
    the second-minor axis; with it a tile multiple, the blocked input's
    own layout is the one the chunk loops slice, and no program copies
    it."""
    if pallas:
        from ..ops.pallas.dedisperse import _QUANT, _tr_rows

        b = min(16384, max(_QUANT, -(-out_nsamps // _QUANT) * _QUANT))
        rows = _tr_rows(t_in, b // 128, (127 + spread) // 128)
    else:
        rows = -(-t_in // 128) + 2
    return -(-rows // 32) * 32


def _put_rows(rows: np.ndarray, mesh: Mesh, axis: str):
    """(head, tail, bytes sent) for the (T, B) ``rows`` on ``mesh``: the
    leading T - T % n_dev rows sharded over ``axis`` (each chip takes
    T / n_dev consecutive rows from the host), the few rows past them
    replicated. Both are views of ``rows``: no host copy."""
    n_dev = mesh.shape[axis]
    cut = rows.shape[0] - rows.shape[0] % n_dev
    head = jax.device_put(rows[:cut], NamedSharding(mesh, P(axis, None)))
    tail = jax.device_put(rows[cut:], NamedSharding(mesh, P()))
    return head, tail, rows[:cut].nbytes + n_dev * rows[cut:].nbytes


@lru_cache(maxsize=None)
def _blocked_bytes(mesh: Mesh, nrows: int):
    """Jitted (head, tail) from ``_put_rows`` -> replicated (B, nrows,
    128): the head all-gathered over the mesh and the tail appended give
    the (T, B) rows, and each byte column (a group of channels) becomes
    blocked rows of 128 samples, zero past T, so a channel chunk is one
    contiguous slice in the kernels' layout."""
    rep = NamedSharding(mesh, P())

    @jax.named_scope("unpack_fil")
    def blocked(head, tail):
        rows = jnp.concatenate(
            [jax.lax.with_sharding_constraint(head, rep), tail]
        )
        t_in = rows.shape[0]
        x = jnp.pad(rows.T, ((0, 0), (0, nrows * 128 - t_in)))
        return x.reshape(rows.shape[1], nrows, 128)

    return jax.jit(blocked, out_shardings=rep)


class _Layout(NamedTuple):
    rows: np.ndarray  # (T, bytes a sample row) as the file packs it
    delays: np.ndarray  # padded to n_dev * per_dev trials
    use_pallas: bool
    per_dev: int
    seg: int
    cc: int
    spread: int
    nrows: int


def _layout(
    samples, delays, out_nsamps: int, n_dev: int, *, nbits: int,
    block: int, chunk_bytes: int, use_pallas: bool | None, interpret: bool,
) -> _Layout:
    """How ``dedisperse_sharded`` lays these arguments over ``n_dev``
    chips (``use_pallas`` None = auto, as there)."""
    delays = np.asarray(delays, dtype=np.int32)
    ndm, nchans = delays.shape
    per = 8 // nbits if nbits < 8 else 1
    rows = samples.reshape(-1, nchans // per)
    t_in = rows.shape[0]

    if use_pallas is None:
        from ..ops.pallas import probe_pallas_dedisperse

        use_pallas = (
            not interpret
            and probe_pallas_dedisperse()
            and bool(np.all(np.diff(delays, axis=0) >= 0))
        )
    if use_pallas:
        from ..ops.pallas.dedisperse import _CC, _DT, plan_spread

        # segments of whole 8-trial kernel chunks, chips' shares of whole
        # segments: every chip's chunks are the global table's chunks
        quantum, chan_quantum = _DT, np.lcm(per, _CC)
    else:
        quantum, chan_quantum = 1, per
    per_dev, seg, cc = shard_plan(
        ndm, n_dev, t_in, nchans, out_nsamps, block=block,
        chunk_bytes=chunk_bytes, quantum=quantum,
        chan_quantum=int(chan_quantum),
    )
    ndm_pad = per_dev * n_dev
    if ndm_pad > ndm:
        delays = np.concatenate(
            [delays, np.tile(delays[-1:], (ndm_pad - ndm, 1))], axis=0
        )
    spread = plan_spread(delays) if use_pallas else 0
    nrows = block_rows(bool(use_pallas), t_in, out_nsamps, spread)
    return _Layout(
        rows, delays, bool(use_pallas), per_dev, seg, cc, spread, nrows
    )


def chip_bytes(
    samples, delays, out_nsamps: int, n_dev: int, *, nbits: int = 8,
    block: int = 16, chunk_bytes: int = CHUNK_BYTES,
    use_pallas: bool | None = None, interpret: bool = False,
) -> int:
    """Bytes one chip holds at the peak of ``dedisperse_sharded`` with
    these arguments: while the input is laid out, the chip's block of
    rows from the host (its 1/n_dev share and the tail rows), the rows
    all-gathered from every chip, their padded transpose and its blocked
    copy (the chip's compiler keeps both); then the blocked copy, one
    channel chunk unpacked (8-bit and f32), a DM segment's f32 partials
    (the sum, the chunk's part and the scaled sum) and the chip's share
    of the 8-bit trials. Callers shard only where this fits a chip."""
    lay = _layout(
        samples, delays, out_nsamps, n_dev, nbits=nbits, block=block,
        chunk_bytes=chunk_bytes, use_pallas=use_pallas, interpret=interpret,
    )
    t_in, width = lay.rows.shape
    own = (t_in // n_dev + t_in % n_dev) * width
    blocked = width * lay.nrows * 128
    chunk = lay.cc * lay.nrows * 128 * 5
    partials = 3 * lay.seg * out_nsamps * 4
    share = lay.per_dev * out_nsamps
    return int(max(own + lay.rows.nbytes + 2 * blocked,
                   blocked + chunk + partials + share))


def shard_input(
    fil, delays, out_nsamps: int, n_dev: int, budget: int, *,
    block: int = 16,
) -> tuple[np.ndarray, int] | None:
    """(samples, nbits) to dedisperse ``fil`` sharded over ``n_dev``
    chips, or None where one chip's peak (``chip_bytes``) passes
    ``budget`` bytes and the beam stays on one chip."""
    samples, nbits = packed_input(fil)
    need = chip_bytes(
        samples, delays, out_nsamps, n_dev, nbits=nbits, block=block
    )
    return (samples, nbits) if need <= budget else None


def dedisperse_sharded(
    samples,
    delays: np.ndarray,
    killmask: np.ndarray,
    out_nsamps: int,
    mesh: Mesh,
    *,
    nbits: int = 8,
    axis: str = "dm",
    quantize: bool = True,
    scale: float = 1.0,
    block: int = 16,
    chunk_bytes: int = CHUNK_BYTES,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Dedisperse all DM trials with the trial axis sharded over ``mesh``.

    ``samples`` is the input as the file holds it: packed bytes
    (time-major, channel fastest, LSB first) for ``nbits`` 1, 2 or 4, or
    the (T, C) samples for ``nbits`` 8. Returns a GLOBAL
    (ndm_padded, out_nsamps) array laid out ``P(axis, None)``: each chip
    holds ndm_padded / n_dev consecutive trials, and ndm pads by
    repeating the last trial row; callers index rows < ndm only (the
    search's chunk dispatch does exactly that).

    ``use_pallas`` None = auto: on TPU backends that pass the kernel
    probe (and monotone delay tables), each chip runs the blocked-roll
    Pallas kernel; elsewhere the jnp channel scan. Both bitwise equal.
    """
    tel = current_telemetry()
    n_dev = mesh.shape[axis]
    nchans = np.shape(delays)[1]
    lay = _layout(
        samples, delays, out_nsamps, n_dev, nbits=nbits, block=block,
        chunk_bytes=chunk_bytes, use_pallas=use_pallas, interpret=interpret,
    )

    with tel.stage("dedispersion.replicate"):
        head, tail, h2d = _put_rows(lay.rows, mesh, axis)
        rows_dev = jax.block_until_ready(
            _blocked_bytes(mesh, lay.nrows)(head, tail)
        )
        del head, tail  # the chip's block goes before the trials come
    tel.incr("dedisp.shards", n_dev)
    tel.incr("dedisp.chunks", (lay.per_dev // lay.seg) * -(-nchans // lay.cc))
    tel.incr("dedisp.replicated_bytes", int(lay.rows.nbytes))
    tel.incr("dedisp.h2d_bytes", int(h2d))
    with tel.stage("dedispersion.shard"):
        fn = _sharded_program(
            mesh, axis, lay.rows.shape[0], nchans, nbits, lay.per_dev,
            lay.seg, lay.cc, out_nsamps, lay.spread, quantize, float(scale),
            lay.use_pallas, interpret,
        )
        return fn(
            rows_dev,
            jax.device_put(lay.delays, NamedSharding(mesh, P(axis, None))),
            jax.device_put(
                np.asarray(killmask, np.float32), NamedSharding(mesh, P())
            ),
        )


@lru_cache(maxsize=None)
def make_local_gather(mesh: Mesh, axis: str, tim_len: int):
    """Jitted (trials, local idx) -> (len(idx), tim_len) rows, where each
    chip takes its block of ``idx`` (P(axis)) from its own shard of the
    ``P(axis, None)`` trials, as indices into that shard: no row leaves
    its chip."""

    def local(trials, idx):
        return jnp.take(trials, idx, axis=0)[:, :tim_len]

    return jax.jit(
        _shard_map_nocheck(local, mesh, (P(axis, None), P(axis)), P(axis, None))
    )
