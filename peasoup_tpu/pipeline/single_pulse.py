"""Host-side single-pulse search driver: the framework's new transient
workload over the dedispersed DM-time plane.

Mirrors pipeline/search.py's shape — a single host process walks the
GLOBAL DM plan in device waves, reusing the dedispersion engines
(ops/dedisperse.py), the mesh/sharding helpers (parallel/), and the
per-trial SearchCheckpoint (keyed by a single-pulse config key, so a
periodicity checkpoint can never resume a single-pulse run or vice
versa). Per-trial device work is ops/singlepulse.py's jitted
normalise -> boxcar-bank -> peak program; the host then clusters the
raw (dm, time, width) events with a friends-of-friends pass so one
broad pulse detected at many DM trials / widths / samples reports as
ONE candidate with its footprint (the clustering stage of Heimdall and
GSP, arXiv:2110.12749).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.candidates import (
    SinglePulseCandidate,
    SinglePulseCandidateCollection,
)
from ..io.masks import read_killfile
from ..io.sigproc import Filterbank
from ..obs import get_logger
from ..obs.telemetry import current as current_telemetry
from ..obs.trace import job_span
from ..ops.dedisperse import (
    dedisperse,
    dedisperse_device,
    fil_to_device,
    output_scale,
)
from ..ops.singlepulse import (
    default_widths,
    make_single_pulse_search_fn,
    plan_pad,
)
from ..plan.dm_plan import DMPlan
from ..utils import ProgressBar, trace_span
from .checkpoint import SearchCheckpoint
from .search import _is_oom

log = get_logger("pipeline.single_pulse")


@dataclass
class SinglePulseConfig:
    """Single-pulse search knobs (no reference equivalent — peasoup
    searches periodicity only; defaults follow Heimdall/GSP practice)."""

    outdir: str = "."
    killfilename: str = ""
    limit: int = 1000
    dm_start: float = 0.0
    dm_end: float = 100.0
    dm_tol: float = 1.10
    dm_pulse_width: float = 64.0
    min_snr: float = 6.0  # single-pulse searches threshold lower than
    # periodicity (each trial is one matched filter, not 2^20 bins)
    n_widths: int = 12  # octave-spaced boxcar widths 1..2^(n-1) samples
    max_width: int = 0  # optional cap on the widest boxcar (samples);
    # 0 = only the n_widths / trial-length caps apply
    max_events: int = 256  # static per-trial event-compaction size
    decimate: int = 32  # best-plane max-decimation factor before the
    # peak compaction (bounds crossings to run-length/decimate)
    time_link: float = 1.0  # friends-of-friends: events link when
    # |dt| <= time_link * max(width_i, width_j) + decimate
    dm_link: int = 2  # ... and |d dm_idx| <= dm_link
    verbose: bool = False
    progress_bar: bool = False
    max_num_threads: int = 14
    # TPU-specific knobs, mirroring SearchConfig
    dedisp_block: int = 16
    dm_block: int = 0  # DM trials per device call; 0 = auto from HBM
    hbm_bytes: int = 0
    checkpoint_file: str = ""
    use_pallas: bool = True  # Pallas boxcar kernel on TPU backends
    shard_devices: int = 0  # 0 = auto; N forces an N-chip 'dm' mesh
    tune: bool = False  # per-device tuned dedispersion shape knobs via
    # the tuning cache (perf/tuning.py; the single-pulse driver has no
    # subband path, so only the block knobs tune)
    tuning_cache: str = ""  # tuning_cache.json path ("" = default)


@dataclass
class SinglePulseResult:
    candidates: list
    dm_list: np.ndarray
    widths: tuple[int, ...]
    timers: dict
    nsamps: int
    n_events: int = 0  # raw above-threshold events before clustering
    n_overflowed: int = 0  # trials whose event count exceeded max_events


@dataclass
class PartialSinglePulseResult:
    """A single-pulse search stopped before clustering
    (``run(finalize=False)``): the raw above-threshold events of one
    process's DM slice with GLOBAL dm_idx, ready for the multi-host
    allgather (parallel/multihost.py:run_single_pulse_search). The
    merged global event set then goes through :meth:`finalize` on
    every process, so the clustered candidate list is identical (and
    deterministic) everywhere — the single-pulse analogue of the
    periodicity PartialSearchResult."""

    events: np.ndarray  # _EVENT_DTYPE records, dm_idx GLOBAL
    dm_list: np.ndarray  # the GLOBAL trial list
    widths: tuple[int, ...]
    timers: dict
    nsamps: int
    n_overflowed: int
    t_total_start: float


_EVENT_DTYPE = np.dtype(
    [
        ("dm_idx", np.int64),
        ("sample", np.int64),
        ("width_idx", np.int64),
        ("snr", np.float64),
    ]
)


def cluster_events_fof(
    events: np.ndarray,  # _EVENT_DTYPE records
    widths: tuple[int, ...],
    *,
    time_link: float = 1.0,
    dm_link: int = 2,
    dec: int = 32,
) -> list[np.ndarray]:
    """Friends-of-friends in (time, DM, width): two events are friends
    when their start samples lie within ``time_link * max(w_i, w_j) +
    dec`` AND their DM trials within ``dm_link``. Width enters through
    the time tolerance (a broad detection reaches further), which links
    the width ladder a bright pulse climbs without any explicit width
    adjacency rule. Returns index arrays, one per cluster.

    The pair scan slides over time-sorted events (the time tolerance is
    bounded by the widest filter), so cost is O(n * window) — fine for
    the tens of thousands of events a threshold sweep emits.
    """
    n = len(events)
    if n == 0:
        return []
    order = np.argsort(events["sample"], kind="stable")
    ev = events[order]
    wmax_link = time_link * float(max(widths)) + dec
    parent = np.arange(n, dtype=np.int64)

    def find(a: int) -> int:
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    w_of = np.asarray(widths, dtype=np.float64)[ev["width_idx"]]
    lo = 0
    for j in range(n):
        while ev["sample"][j] - ev["sample"][lo] > wmax_link:
            lo += 1
        for i in range(lo, j):
            dt = ev["sample"][j] - ev["sample"][i]
            if dt > time_link * max(w_of[i], w_of[j]) + dec:
                continue
            if abs(ev["dm_idx"][j] - ev["dm_idx"][i]) > dm_link:
                continue
            ra, rb = find(i), find(j)
            if ra != rb:
                parent[rb] = ra
        # liveness note: the [lo, j) window is bounded by wmax_link
    roots: dict[int, list[int]] = {}
    for i in range(n):
        roots.setdefault(find(i), []).append(i)
    return [order[np.asarray(members)] for members in roots.values()]


def candidates_from_clusters(
    events: np.ndarray,  # _EVENT_DTYPE records
    clusters: list[np.ndarray],  # index arrays from cluster_events_fof
    widths: tuple[int, ...],
    dm_list: np.ndarray,
    tsamp: float,
) -> list[SinglePulseCandidate]:
    """Package friends-of-friends clusters as SinglePulseCandidates
    (peak member + footprint extents) — shared by the batch finalize
    and the streaming driver's incremental confirmation, so a trigger
    emitted live is field-for-field the candidate a batch run of the
    same data would report."""
    w_arr = np.asarray(widths, dtype=np.int64)
    out = []
    for members in clusters:
        ev = events[members]
        peak = int(np.argmax(ev["snr"]))
        widx = int(ev["width_idx"][peak])
        out.append(
            SinglePulseCandidate(
                dm=float(dm_list[int(ev["dm_idx"][peak])]),
                dm_idx=int(ev["dm_idx"][peak]),
                snr=float(ev["snr"][peak]),
                time_s=float(ev["sample"][peak]) * tsamp,
                sample=int(ev["sample"][peak]),
                width=int(w_arr[widx]),
                width_idx=widx,
                members=len(members),
                dm_idx_lo=int(ev["dm_idx"].min()),
                dm_idx_hi=int(ev["dm_idx"].max()),
                sample_lo=int(ev["sample"].min()),
                sample_hi=int(ev["sample"].max()),
                width_lo=int(w_arr[ev["width_idx"]].min()),
                width_hi=int(w_arr[ev["width_idx"]].max()),
            )
        )
    return out


def select_sp_kernels(
    widths: tuple[int, ...],
    span: int,
    decimate: int,
    use_pallas: bool,
) -> tuple[int, int]:
    """Resolve the single-pulse device-kernel route: ``(pallas_span,
    fused_span)``. The fused sweep+dec-fold chain
    (ops/pallas/spchain.py) runs when its dec-fold can tile the span
    (``fold_fits``), the plain boxcar kernel otherwise, and ``(0, 0)``
    selects the jnp twin on backends without Pallas. On a TPU a kernel
    that fails its compile+run probe raises (ops.pallas
    KernelUnavailable) instead of dropping to a slower route."""
    if not use_pallas or span <= 0:
        return 0, 0
    from ..ops.pallas import probe_pallas_boxcar, probe_pallas_spchain
    from ..ops.pallas.spchain import fold_fits

    if fold_fits(span, decimate) and probe_pallas_spchain(
        len(widths), span, decimate
    ):
        return 0, span
    if probe_pallas_boxcar(len(widths), span):
        return span, 0
    return 0, 0


def make_checkpoint_key(
    cfg: SinglePulseConfig, fil, global_ndm: int, widths: tuple[int, ...]
) -> str:
    """Config key over everything that changes per-trial events —
    including the observation's identity and the workload TYPE prefix,
    so a periodicity checkpoint can never resume a single-pulse run."""
    h = fil.header
    fields = (
        "sp-v1",  # single-pulse per-trial payload format version
        fil.nsamps, fil.nchans, global_ndm,
        fil.tsamp, fil.fch1, fil.foff,
        getattr(h, "tstart", None), getattr(h, "source_name", None),
        getattr(h, "nbits", None),
        cfg.dm_start, cfg.dm_end, cfg.dm_tol, cfg.dm_pulse_width,
        cfg.min_snr, tuple(int(w) for w in widths), cfg.max_events,
        cfg.decimate, cfg.killfilename,
    )
    return repr(fields)


class SinglePulseSearch:
    """Walk the DM plan in device waves and cluster the events.

    HBM accounting mirrors PeasoupSearch: the per-trial working set is
    ~4 f32 planes of the padded trial length (normalised series, prefix
    sum, best-S/N, best-width), so the auto dm_block is
    budget / (16 * tpad)."""

    TOTAL_HBM = 12_000_000_000
    TRIALS_DEVICE_LIMIT = 4_000_000_000

    def __init__(self, config: SinglePulseConfig):
        self.config = config
        import os

        from ..utils.device import device_bytes_limit

        limit = config.hbm_bytes or int(
            os.environ.get("PEASOUP_HBM_BYTES", 0) or 0
        )
        if not limit:
            limit = device_bytes_limit()
        if limit:
            self.TOTAL_HBM = int(limit)
            self.TRIALS_DEVICE_LIMIT = int(limit) // 3

    def build_dm_plan(self, fil: Filterbank) -> DMPlan:
        """The GLOBAL dedispersion plan (same construction as the
        periodicity search's — the two workloads share the DM-time
        plane by design)."""
        cfg = self.config
        killmask = None
        if cfg.killfilename:
            killmask = read_killfile(cfg.killfilename, fil.nchans)
        return DMPlan.create(
            nsamps=fil.nsamps,
            nchans=fil.nchans,
            tsamp=fil.tsamp,
            fch1=fil.fch1,
            foff=fil.foff,
            dm_start=cfg.dm_start,
            dm_end=cfg.dm_end,
            pulse_width=cfg.dm_pulse_width,
            tol=cfg.dm_tol,
            killmask=killmask,
        )

    def widths_for(self, out_nsamps: int) -> tuple[int, ...]:
        """The run's boxcar bank: octave-spaced, capped so the widest
        filter is at most a quarter of the trial (beyond that the
        'pulse' is baseline, not transient) and by cfg.max_width."""
        cap = max(1, out_nsamps // 4)
        if self.config.max_width:
            cap = min(cap, self.config.max_width)
        return default_widths(self.config.n_widths, max_width=cap)

    def _pick_devices(self) -> list:
        cfg = self.config
        devs = jax.local_devices()
        if cfg.shard_devices > 0:
            return devs[: min(cfg.shard_devices, len(devs))]
        if devs and devs[0].platform == "tpu":
            return devs[: min(len(devs), cfg.max_num_threads)]
        return devs[:1]

    def run(
        self,
        fil: Filterbank,
        dm_slice: tuple[int, int] | None = None,
        finalize: bool = True,
    ) -> "SinglePulseResult | PartialSinglePulseResult":
        """Full search. With ``dm_slice=(lo, hi)`` only that contiguous
        block of the global DM-trial list is dedispersed and searched
        (events come back with GLOBAL dm_idx); with ``finalize=False``
        the run stops before clustering and returns a
        PartialSinglePulseResult for the multi-host event merge."""
        cfg = self.config
        tel = current_telemetry()
        timers: dict[str, float] = {}
        t_total = time.perf_counter()

        # --- plan ------------------------------------------------------
        t0 = time.perf_counter()
        tel.set_stage("plan")
        global_plan = self.build_dm_plan(fil)
        widths = self.widths_for(global_plan.out_nsamps)
        lo = 0
        dm_plan = global_plan
        if dm_slice is not None:
            lo, hi = dm_slice
            dm_plan = global_plan.subset(lo, hi)
        timers["plan"] = time.perf_counter() - t0
        tel.gauge("sp.n_dm_trials", int(global_plan.ndm))
        tel.gauge("sp.n_widths", len(widths))
        tel.event(
            "sp_plan", ndm=int(global_plan.ndm),
            out_nsamps=int(global_plan.out_nsamps),
            widths=[int(w) for w in widths],
            dm_slice=[int(lo), int(lo + dm_plan.ndm)],
        )

        # --- checkpoint store (load before dedispersion: a fully
        # restored run skips the expensive part, like the periodicity
        # driver's resume fast path). Keyed on the GLOBAL trial count
        # with per-slice store files, so resuming under a different
        # process count reuses every completed trial -------------------
        ckpt = None
        restored: dict[int, tuple] = {}
        if cfg.checkpoint_file:
            ckpt = SearchCheckpoint(
                cfg.checkpoint_file,
                make_checkpoint_key(cfg, fil, global_plan.ndm, widths),
                slice_bounds=dm_slice,
            )
            restored = ckpt.load()
        skip_dedisp = dm_plan.ndm > 0 and all(
            d in restored for d in range(dm_plan.ndm)
        )
        if dm_plan.ndm == 0:
            # empty multi-host slice (more processes than DM trials):
            # contribute zero events without touching the device
            part = PartialSinglePulseResult(
                events=np.zeros(0, dtype=_EVENT_DTYPE),
                dm_list=global_plan.dm_list,
                widths=widths,
                timers={
                    **timers, "dedispersion": 0.0, "searching": 0.0,
                },
                nsamps=fil.nsamps,
                n_overflowed=0,
                t_total_start=t_total,
            )
            return part if not finalize else self.finalize(fil, part)

        # --- auto-tuned dedispersion shape knobs -----------------------
        dedisp_block = cfg.dedisp_block
        if cfg.tune:
            # a planning failure stops the run (as in PeasoupSearch)
            from ..perf.tuning import resolve_plan_for_filterbank

            dplan = resolve_plan_for_filterbank(
                fil, "spsearch", cfg, cache_path=cfg.tuning_cache or None,
            )
            if dplan is not None:
                dedisp_block = dplan.dedisp_block or dedisp_block
                tel.event("dedisp_plan", **dplan.summary())
                tel.set_context(dedisp_plan=dplan.summary())

        # --- dedispersion (reusing the periodicity engines) ------------
        t0 = time.perf_counter()
        tel.set_stage("dedispersion")
        devices = self._pick_devices()
        mesh = None
        if len(devices) > 1:
            from ..parallel.mesh import make_mesh

            mesh = make_mesh({"dm": len(devices)}, devices=devices)
        trials_bytes = dm_plan.ndm * dm_plan.out_nsamps
        spill = trials_bytes > self.TRIALS_DEVICE_LIMIT * (
            len(devices) if mesh is not None else 1
        )
        tel.event(
            "sp_device_plan", n_devices=len(devices),
            sharded=mesh is not None, trials_spill=bool(spill),
            trials_bytes=int(trials_bytes),
        )
        scale = output_scale(fil.nbits, int(dm_plan.killmask.sum()))
        if skip_dedisp:
            log.info(
                "Resume fast path: all %d trials checkpointed — "
                "skipping dedispersion", dm_plan.ndm,
            )
            tel.event("sp_resume_fast_path", ndm=int(dm_plan.ndm))
            trials = np.zeros((0, dm_plan.out_nsamps), dtype=np.uint8)
            spill = True
        else:
            with trace_span("Dedisperse"):
                shard_dd = (
                    mesh is not None
                    and not spill
                    and 4 * fil.nsamps * fil.nchans < 3_000_000_000
                )
                if shard_dd:
                    from ..parallel.sharded_dedisperse import (
                        dedisperse_sharded,
                    )

                    trials = dedisperse_sharded(
                        fil_to_device(fil),
                        dm_plan.delay_samples(),
                        dm_plan.killmask,
                        dm_plan.out_nsamps,
                        mesh,
                        scale=scale,
                        block=dedisp_block,
                    )
                else:
                    dd = dedisperse if spill else dedisperse_device
                    trials = dd(
                        fil.data if spill else fil_to_device(fil),
                        dm_plan.delay_samples(),
                        dm_plan.killmask,
                        dm_plan.out_nsamps,
                        scale=scale,
                        block=dedisp_block,
                    )
                if not spill:
                    # async dispatch (mirrors pipeline/search.py): the
                    # first boxcar waves overlap the dedispersion tail;
                    # PEASOUP_SYNC_DEDISP=1 restores the barrier
                    import os as _os

                    if _os.environ.get("PEASOUP_SYNC_DEDISP"):
                        jax.block_until_ready(trials)
                    else:
                        tel.event(
                            "dedisp_async_dispatch",
                            dispatch_s=round(time.perf_counter() - t0, 4),
                        )
        timers["dedispersion"] = time.perf_counter() - t0
        tel.capture_device_memory("dedispersion")

        # --- device waves over the DM axis -----------------------------
        t0 = time.perf_counter()
        tel.set_stage("searching")
        nsamps = dm_plan.out_nsamps
        tpad, span = plan_pad(nsamps)
        pallas_span, fused_span = select_sp_kernels(
            widths, span, cfg.decimate, cfg.use_pallas
        )
        self._pallas_span = pallas_span
        self._fused_span = fused_span
        from ..ops.pallas.spchain import fold_fits

        tel.event(
            "sp_route", backend=jax.default_backend(),
            fused_span=int(fused_span), pallas_span=int(pallas_span),
            span=int(span), decimate=int(cfg.decimate),
            fold_fits=bool(fold_fits(span, cfg.decimate)),
        )
        sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            sharding = NamedSharding(mesh, PartitionSpec("dm"))

        per_dm: dict[int, tuple] = restored
        if per_dm and not skip_dedisp:
            log.info(
                "Resuming: %d/%d DM trials restored from %s",
                len(per_dm), dm_plan.ndm, cfg.checkpoint_file,
            )
            tel.event(
                "sp_checkpoint_resume", restored=len(per_dm),
                ndm=int(dm_plan.ndm),
            )

        # auto block: ~4 f32 planes of tpad per trial (norm, csum,
        # best, argw) with 4x headroom; mesh runs round up to a
        # devices multiple so every chip gets equal rows
        if cfg.dm_block > 0:
            dm_block = cfg.dm_block
        else:
            per_trial = 16 * tpad
            dm_block = int(
                max(1, min(256, (self.TOTAL_HBM // 4) // max(1, per_trial)))
            )
        n_dev = len(devices)
        if n_dev > 1:
            dm_block = max(n_dev, -(-dm_block // n_dev) * n_dev)

        from ..resilience import DegradationLadder, faults

        # the memory ladder: halve dm_block (repeatable rung), and when
        # the blocks are already at the floor fall THROUGH to the CPU
        # backend (host RAM dwarfs HBM; slow beats dead) instead of
        # raising — candidates stay bitwise-equal because the per-trial
        # program is shape-identical and the Pallas kernels are gated on
        # bitwise equality with their jnp twins
        ladder = DegradationLadder(
            "spsearch.memory", ("dm_block_shrink", "cpu_backend")
        )
        shrink = 1
        cpu_mode = False
        while True:
            blk = max(
                n_dev if n_dev > 1 else 1, dm_block // shrink
            )
            if n_dev > 1:
                blk = max(n_dev, -(-blk // n_dev) * n_dev)
            chunks = [
                list(range(s, min(s + blk, dm_plan.ndm)))
                for s in range(0, dm_plan.ndm, blk)
            ]
            tel.event(
                "sp_wave_plan", n_chunks=len(chunks), dm_block=blk,
                shrink=shrink, pallas_span=self._pallas_span,
                fused_span=self._fused_span,
                backend="cpu" if cpu_mode else "default",
            )
            try:
                faults.fire(
                    "device.oom",
                    context=(
                        "spsearch:cpu" if cpu_mode
                        else f"spsearch:shrink{shrink}"
                    ),
                )
                if cpu_mode:
                    with jax.default_device(jax.devices("cpu")[0]):
                        self._run_waves(
                            chunks, blk, trials, per_dm, ckpt, widths,
                            sharding=None, spill=True,
                        )
                else:
                    self._run_waves(
                        chunks, blk, trials, per_dm, ckpt, widths,
                        sharding=sharding, spill=spill,
                    )
                break
            except Exception as exc:
                if not _is_oom(exc):
                    raise
                if blk > max(1, n_dev):
                    shrink *= 2
                    log.warning(
                        "device OOM at dm_block=%d; retrying with "
                        "dm_block=%d: %.200s", blk,
                        max(1, dm_block // shrink), exc,
                    )
                    tel.event(
                        "sp_oom_shrink_retry", dm_block_old=blk,
                        shrink=shrink, error=f"{exc!s:.200}",
                    )
                    # once a later rung stepped, in-rung shrinks keep
                    # the event trail but not a ladder step (a ladder
                    # never climbs back up)
                    if ladder.current_rung in (None, "dm_block_shrink"):
                        ladder.step(
                            "dm_block_shrink", dm_block_old=blk,
                            dm_block_new=max(1, dm_block // shrink),
                            error=f"{exc!s:.200}",
                        )
                    continue
                if cpu_mode:
                    # nothing below the CPU rung
                    ladder.exhausted(dm_block=blk, error=f"{exc!s:.200}")
                    raise
                # shrink exhausted: fall through to the CPU backend.
                # The rung is a new memory regime (host RAM), so block
                # sizing restarts from the top — which also keeps the
                # successful attempt's per-chunk shapes identical to an
                # untroubled run's (the bitwise-equality guarantee).
                cpu_mode = True
                shrink = 1
                trials = np.asarray(trials)  # host-resident input
                n_dev = 1
                self._pallas_span = 0  # TPU kernels are moot on CPU
                self._fused_span = 0
                log.warning(
                    "device OOM with dm_block already at the floor "
                    "(%d); falling through to the CPU backend: %.200s",
                    blk, exc,
                )
                tel.event(
                    "sp_oom_cpu_fallback", dm_block=blk,
                    error=f"{exc!s:.200}",
                )
                ladder.step(
                    "cpu_backend", dm_block=blk, error=f"{exc!s:.200}"
                )
        timers["searching"] = time.perf_counter() - t0
        tel.capture_device_memory("search")

        # --- event extraction (GLOBAL dm_idx) --------------------------
        recs = []
        n_overflowed = 0
        for dm_idx in range(dm_plan.ndm):
            pos_w, snrs, count = per_dm[dm_idx]
            c = int(np.asarray(count))
            k = min(c, len(snrs))
            if c > len(snrs):
                n_overflowed += 1
            for i in range(k):
                recs.append(
                    (dm_idx + lo, int(pos_w[0, i]), int(pos_w[1, i]),
                     float(snrs[i]))
                )
        events = np.asarray(recs, dtype=_EVENT_DTYPE)
        if n_overflowed:
            log.warning(
                "%d DM trials overflowed the %d-event compaction; "
                "keeping the first %d (ascending time) per trial",
                n_overflowed, cfg.max_events, cfg.max_events,
            )
            tel.event(
                "sp_event_overflow", trials=n_overflowed,
                max_events=cfg.max_events,
            )
        part = PartialSinglePulseResult(
            events=events,
            dm_list=global_plan.dm_list,
            widths=widths,
            timers=timers,
            nsamps=fil.nsamps,
            n_overflowed=n_overflowed,
            t_total_start=t_total,
        )
        if not finalize:
            return part
        return self.finalize(fil, part)

    def finalize(
        self, fil: Filterbank, part: PartialSinglePulseResult
    ) -> SinglePulseResult:
        """Cluster a (possibly multi-host-merged) global event set and
        package candidates. Deterministic in the event set, so every
        process of a multi-host run reaches the identical result."""
        cfg = self.config
        tel = current_telemetry()
        timers = part.timers
        events, widths = part.events, part.widths

        t0 = time.perf_counter()
        tel.set_stage("clustering")
        clusters = cluster_events_fof(
            events, widths, time_link=cfg.time_link, dm_link=cfg.dm_link,
            dec=cfg.decimate,
        )
        cands = SinglePulseCandidateCollection()
        cands.append(
            candidates_from_clusters(
                events, clusters, widths, part.dm_list, fil.tsamp
            )
        )
        out = sorted(cands, key=lambda c: -c.snr)[: cfg.limit]
        timers["clustering"] = time.perf_counter() - t0
        timers["total"] = time.perf_counter() - part.t_total_start
        tel.gauge("sp.n_events", len(events))
        tel.gauge("sp.n_clusters", len(clusters))
        tel.gauge("candidates.final", len(out))
        log.info(
            "single-pulse search: %d events -> %d clusters -> %d "
            "candidates", len(events), len(clusters), len(out),
        )
        return SinglePulseResult(
            candidates=out,
            dm_list=part.dm_list,
            widths=widths,
            timers=timers,
            nsamps=part.nsamps,
            n_events=len(events),
            n_overflowed=part.n_overflowed,
        )

    def _run_waves(
        self, chunks, blk, trials, per_dm, ckpt, widths, *, sharding, spill
    ) -> None:
        cfg = self.config
        tel = current_telemetry()
        progress = ProgressBar() if cfg.progress_bar else None
        if progress:
            progress.start()
        search_fn = make_single_pulse_search_fn(
            widths, float(cfg.min_snr), cfg.max_events, cfg.decimate,
            self._pallas_span, self._fused_span,
        )
        tel.set_progress(0, len(chunks), unit="chunks")
        try:
            for ci, chunk in enumerate(chunks):
                if all(d in per_dm for d in chunk):
                    tel.set_progress(ci + 1, len(chunks), unit="chunks")
                    continue
                lo, hi = chunk[0], chunk[-1] + 1
                # fleet-trace span (obs/trace.py, no-op outside a
                # campaign job): one search wave of the job's timeline
                with job_span("wave", wave=ci), trace_span("SP-Chunk"):
                    block = trials[lo:hi]
                    if spill:
                        block = jnp.asarray(block)
                    pad = blk - (hi - lo)
                    if pad:
                        block = jnp.concatenate(
                            [block, jnp.zeros((pad, block.shape[1]),
                                              block.dtype)]
                        )
                    if sharding is not None:
                        block = jax.device_put(block, sharding)
                    samples, widx, snrs, counts = search_fn(block)
                    # one packed fetch per wave (tiny arrays)
                    samples = np.asarray(samples)
                    widx = np.asarray(widx)
                    snrs = np.asarray(snrs)
                    counts = np.asarray(counts)
                for j, dm_idx in enumerate(chunk):
                    per_dm[dm_idx] = (
                        np.stack([samples[j], widx[j]]).astype(np.int32),
                        snrs[j].astype(np.float32),
                        np.int32(counts[j]),
                    )
                if ckpt is not None:
                    with job_span("checkpoint", wave=ci):
                        ckpt.save(per_dm)
                tel.set_progress(ci + 1, len(chunks), unit="chunks")
                if progress:
                    progress.update((ci + 1) / len(chunks))
                # revoke seam: a preempt/retire observed by the lease
                # renewer stops here — the checkpoint just saved is the
                # state the resumed run restores, so candidates stay
                # bitwise-equal to an uninterrupted sweep
                from ..resilience import check_revoke

                check_revoke("spsearch.wave")
        finally:
            if progress:
                progress.stop()
