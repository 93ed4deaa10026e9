"""DM-trial-sharded acceleration search over a device mesh.

The reference scales by running one share-nothing worker per GPU over a
dynamically-dealt DM list (src/pipeline_multi.cu:33-81,342-359). Here a
BLOCK of DM trials is laid out on the mesh's 'dm' axis with
``shard_map``: each chip runs the identical jitted per-trial program on
its local trials; there is no cross-chip communication in the search
itself (trial grid parallelism rides on data placement, not
collectives), and the fixed-size peak arrays gather back to the host
for distilling — the analogue of the reference's per-worker candidate
merge on join (pipeline_multi.cu:356-359).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import get_logger
from ..obs.telemetry import current as current_telemetry
from ..pipeline.accel_search import AccelSearchPeaks, search_block_core

log = get_logger("parallel.sharded_search")


@lru_cache(maxsize=None)
def make_sharded_search_fn(
    mesh: Mesh,
    threshold: float,
    axis: str = "dm",
    pallas_block: int = 0,
    select_smax: int = 0,
    pallas_peaks: bool = False,
    fused_interbin: bool = False,
    mega_harm: bool = False,
    fused_dft: bool = False,
    fused_spec: bool = False,
):
    """Jitted (D, ...) -> (D, ...) search with D sharded over ``axis``.

    D must be a multiple of the mesh axis size (pad the trial block and
    the afs rows; padded rows are searched but discarded by the host).
    Each chip runs the block-batched core on its local trials with the
    same kernel flags as the one-chip search, so both give bitwise
    equal peaks; with ``pallas_block`` > 0 the Pallas resample kernel
    runs per chip.
    Cached (mesh/threshold/axis/block are hashable) so repeat runs reuse
    the compiled executable like make_batched_search_fn.
    """
    log.debug(
        "building sharded search: %d-chip '%s' mesh, pallas_block=%d, "
        "pallas_peaks=%s", mesh.shape[axis], axis, pallas_block,
        pallas_peaks,
    )
    current_telemetry().event(
        "sharded_search_built", n_chips=int(mesh.shape[axis]), axis=axis,
        pallas_block=int(pallas_block), pallas_peaks=bool(pallas_peaks),
        mega_harm=bool(mega_harm), fused_dft=bool(fused_dft),
        fused_spec=bool(fused_spec), process_index=int(jax.process_index()),
    )

    @partial(
        jax.jit,
        static_argnames=("size", "nsamps_valid", "nharms", "max_peaks",
                         "pos5", "pos25"),
    )
    def sharded_search(
        tims: jax.Array,  # (D, >=size) u8 trials, sharded over axis
        afs: jax.Array,  # (D, A) f32 per-trial accel factors
        zapmask: jax.Array,  # (size//2+1,) bool, replicated
        windows: jax.Array,  # (nharms+1, 2) i32, replicated
        *,
        size: int,
        nsamps_valid: int,
        nharms: int,
        max_peaks: int,
        pos5: int,
        pos25: int,
    ) -> AccelSearchPeaks:
        def local(tims_l, afs_l, zap_l, win_l):
            return search_block_core(
                tims_l, afs_l, zap_l, win_l,
                threshold=threshold, size=size, nsamps_valid=nsamps_valid,
                nharms=nharms, max_peaks=max_peaks, pos5=pos5, pos25=pos25,
                pallas_block=pallas_block, select_smax=select_smax,
                pallas_peaks=pallas_peaks, fused_interbin=fused_interbin,
                mega_harm=mega_harm, fused_dft=fused_dft,
                fused_spec=fused_spec,
            )

        # check_vma off: the local body is collective-free, and the
        # Pallas kernels in it declare outputs without a vma
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=AccelSearchPeaks(
                idxs=P(axis), snrs=P(axis), counts=P(axis), ccounts=P(axis)
            ),
            check_vma=False,
        )(tims, afs, zapmask, windows)

    return sharded_search


def place_trials(mesh: Mesh, trials, axis: str = "dm"):
    """Device-put a (D, N) trial block sharded along the mesh axis."""
    return jax.device_put(trials, NamedSharding(mesh, P(axis)))
