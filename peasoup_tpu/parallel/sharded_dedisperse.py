"""DM-trial-sharded dedispersion over a device mesh.

The reference dedisperses across ALL GPUs in one node
(`dedisp_create_plan_multi`, reference include/transforms/
dedisperser.hpp:25-31).  Round 1 of this framework instead dedispersed
the whole trial set on one chip while the mesh's other chips idled.
Here the DM-trial axis of the shift-and-sum engine is laid out on the
mesh's ``dm`` axis with ``shard_map``: the (channel-blocked, masked)
filterbank is replicated to every chip, each chip scans its local slice
of the delay table, and the (ndm, out_nsamps) trial block materialises
ALREADY SHARDED the way the search consumes it — trial rows then move
chip-to-chip only as u8 over ICI when a search chunk regroups them
(make_row_gather), never through the host.

Bitwise identical to ops.dedisperse.dedisperse_device's jnp scan:
channel sums of <=8-bit samples are exact in f32 so the per-chip
accumulation order cannot change the result.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.dedisperse import _dedisperse_core, _pad_blocks


def _shard_map_nocheck(local_fn, mesh, in_specs, out_specs):
    # check_vma off: the local bodies are collective-free, and values
    # created inside (scan carries, iotas) start unvarying while the
    # delays are device-varying — the check would demand pvary casts
    # inside shared single-device code
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


@lru_cache(maxsize=None)
def _make_sharded_dd(
    mesh: Mesh,
    axis: str,
    out_nsamps: int,
    quantize: bool,
    scale: float,
    block: int,
    per_dev: int,
):
    def local_fn(x_cb, delays):
        # delays: (per_dev, C) — this chip's slice of the trial table.
        # Python loop over fixed-size blocks bounds the live f32 carry
        # exactly like dedisperse_device's blocked scan.
        outs = [
            _dedisperse_core(
                x_cb, delays[s : s + block],
                out_nsamps=out_nsamps, quantize=quantize, scale=scale,
            )
            for s in range(0, per_dev, block)
        ]
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)

    return jax.jit(
        _shard_map_nocheck(
            local_fn, mesh, (P(), P(axis, None)), P(axis, None)
        )
    )


@lru_cache(maxsize=None)
def _make_sharded_dd_pallas(
    mesh: Mesh,
    axis: str,
    t_out: int,
    cpad: int,
    b: int,
    spread: int,
    quantize: bool,
    scale: float,
    per_dev: int,
    out_nsamps: int,
    interpret: bool,
):
    """Per-shard Pallas blocked-roll kernel (ops/pallas/dedisperse.py):
    each chip runs the 13x kernel on ITS slice of the delay table — the
    multi-chip analogue of dedisp_create_plan_multi with dedisp's GPU
    kernel on every device."""
    from ..ops.pallas.dedisperse import _build

    fn = _build(per_dev, t_out, cpad, b, spread, interpret)

    def local_fn(xp, delays):
        out = fn(delays, xp).reshape(per_dev, t_out)[:, :out_nsamps]
        if scale != 1.0:
            out = out * jnp.float32(scale)
        if quantize:
            out = jnp.clip(jnp.rint(out), 0, 255).astype(jnp.uint8)
        return out

    return jax.jit(
        _shard_map_nocheck(
            local_fn, mesh, (P(), P(axis, None)), P(axis, None)
        )
    )


def dedisperse_sharded(
    fil_tc,
    delays: np.ndarray,
    killmask: np.ndarray,
    out_nsamps: int,
    mesh: Mesh,
    *,
    axis: str = "dm",
    quantize: bool = True,
    scale: float = 1.0,
    block: int = 16,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Dedisperse all DM trials with the trial axis sharded over ``mesh``.

    Returns a GLOBAL (ndm_padded, out_nsamps) array laid out
    ``P(axis, None)`` — ndm is padded up to a multiple of the mesh axis
    size by repeating the last trial row; callers index rows < ndm only
    (the search's chunk dispatch does exactly that).

    ``use_pallas`` None = auto: on TPU backends that pass the kernel
    probe (and monotone delay tables), each shard runs the blocked-roll
    Pallas kernel; elsewhere the jnp channel scan. Both bitwise equal.
    """
    n_dev = mesh.shape[axis]
    delays = np.asarray(delays, dtype=np.int32)
    ndm = delays.shape[0]

    if use_pallas is None:
        from ..ops.pallas import probe_pallas_dedisperse

        use_pallas = (
            not interpret
            and probe_pallas_dedisperse()
            and bool(np.all(np.diff(delays, axis=0) >= 0))
        )

    if use_pallas:
        from ..ops.pallas.dedisperse import (
            _CC, _DT, _QUANT, _tr_rows, plan_spread,
        )

        # per-shard trial count must hit the kernel's 8-trial quantum;
        # shard boundaries at multiples of 8 keep the global 8-chunk
        # walk of plan_spread aligned with every shard's local chunks
        per_dev = -(-(-(-ndm // n_dev)) // _DT) * _DT
        ndm_pad = per_dev * n_dev
        c = delays.shape[1]
        cpad = -(-c // _CC) * _CC
        if ndm_pad > ndm:
            delays = np.concatenate(
                [delays, np.tile(delays[-1:], (ndm_pad - ndm, 1))], axis=0
            )
        if cpad > c:
            delays = np.concatenate(
                [delays, np.tile(delays[:, -1:], (1, cpad - c))], axis=1
            )
        t_in = fil_tc.shape[0]
        b = min(16384, max(_QUANT, -(-out_nsamps // _QUANT) * _QUANT))
        t_out = -(-out_nsamps // b) * b
        spread = plan_spread(delays)
        k_max = (127 + spread) // 128
        tr = _tr_rows(t_in, b // 128, k_max)
        x = jnp.asarray(fil_tc).astype(jnp.float32) * jnp.asarray(
            np.asarray(killmask), dtype=jnp.float32
        )[None, :]
        xp = jax.device_put(
            jnp.pad(x.T, ((0, cpad - c), (0, tr * 128 - t_in))).reshape(
                cpad, tr, 128
            ),
            NamedSharding(mesh, P()),
        )
        fn = _make_sharded_dd_pallas(
            mesh, axis, t_out, cpad, b, spread, quantize, float(scale),
            per_dev, out_nsamps, interpret,
        )
        delays_dev = jax.device_put(
            delays, NamedSharding(mesh, P(axis, None))
        )
        return fn(xp, delays_dev)

    per_dev = -(-ndm // n_dev)
    ndm_pad = per_dev * n_dev
    if ndm_pad > ndm:
        delays = np.concatenate(
            [delays, np.tile(delays[-1:], (ndm_pad - ndm, 1))], axis=0
        )

    # Preprocessing (identical to dedisperse_block's front half:
    # pad/block the time axis, mask channels, f32) runs ONCE on the
    # default device, then the finished blocked tensor replicates to the
    # mesh — eager ops on an already-replicated array would execute on
    # every device (8x the work), and on TPU the one broadcast rides ICI.
    x = _pad_blocks(jnp.asarray(fil_tc))
    x = x.astype(jnp.float32).T * jnp.asarray(
        np.asarray(killmask), dtype=jnp.float32
    )[:, None]
    x_cb = jax.device_put(
        x.reshape(x.shape[0], -1, 128), NamedSharding(mesh, P())
    )  # (C, T/128, 128) replicated

    fn = _make_sharded_dd(
        mesh, axis, out_nsamps, quantize, float(scale), block, per_dev
    )
    delays_dev = jax.device_put(
        delays, NamedSharding(mesh, P(axis, None))
    )
    return fn(x_cb, delays_dev)


@lru_cache(maxsize=None)
def make_row_gather(mesh: Mesh, axis: str, tim_len: int):
    """Jitted (trials, idx) -> (len(idx), tim_len) row regroup with the
    output pinned to ``P(axis, None)``: XLA moves exactly the u8 rows a
    chunk needs between chips over ICI — no host hop, no full-array
    migration (replaces the eager take + device_put in the search's
    chunk dispatch)."""
    sh = NamedSharding(mesh, P(axis, None))

    @jax.jit
    def gather(trials, idx):
        rows = jnp.take(trials, idx, axis=0)[:, :tim_len]
        return jax.lax.with_sharding_constraint(rows, sh)

    return gather
