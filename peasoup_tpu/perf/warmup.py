"""AOT warmup: compile every registered program before the data needs it.

A fresh process pays first-job XLA compiles (~70 s per subband-stage
shape, ~30 s for the fold phase at 2^21 samples — NOTES.md) before
touching data. The auto-tuning literature the pipeline follows
(arXiv:1601.01165, arXiv:2309.02544) treats per-shape compile cost as
something paid once offline, never per observation. This module is
that offline pass: walk :mod:`peasoup_tpu.ops.registry` and
``jax.jit(fn).lower(*specs).compile()`` every program — nothing
executes, but every compile lands in the persistent compilation cache
(utils/cache.py), so every subsequent process (and every campaign
worker on the same filesystem) cold-starts warm.

Two parameterisations:

* **representative** (``warm_registry()``) — each program's registered
  tiny shapes. Cheap; what ``peasoup-perf warmup`` and the CI
  structural gate use (a second pass must be 100% cache hits).
* **bucket** (``warm_registry(ctx=...)`` via each entry's ShapeCtx
  hook, or ``warm_bucket``) — the production shapes a campaign bucket
  implies, derived with the drivers' own plan machinery. The campaign
  runner warms each new bucket on a background thread, overlapping the
  first observation's filterbank read. ``mode="dryrun"`` additionally
  runs the real pipeline once over a synthetic bucket-shaped
  observation, which by construction traces every driver-side shape —
  the first real job then compiles exactly zero programs.

Attribution uses thread-local jax.monitoring sinks: compiles run on
the warmup thread, so concurrent workers' events never cross-pollute.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from ..obs import get_logger

log = get_logger("perf.warmup")

_TLS = threading.local()
_listeners_installed = False


def _install_listeners() -> None:
    """One pair of process-wide jax.monitoring listeners forwarding to
    whatever sink the CURRENT THREAD has active (the registry has no
    unregister, so per-call listeners would accumulate)."""
    global _listeners_installed
    if _listeners_installed:
        return
    _listeners_installed = True
    try:
        from jax import monitoring

        def _on_duration(event: str, duration: float, **kw) -> None:
            sink = getattr(_TLS, "sink", None)
            if sink is not None and "backend_compile" in event:
                sink["backend_compile"] += 1
                sink["backend_compile_s"] += max(0.0, float(duration))

        def _on_event(event: str, **kw) -> None:
            sink = getattr(_TLS, "sink", None)
            if sink is not None:
                if event.endswith("cache_hits"):
                    sink["cache_hits"] += 1
                elif event.endswith("cache_misses"):
                    sink["cache_misses"] += 1

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:
        pass  # no monitoring API: reports lack hit/miss attribution


class _sink_scope:
    """Route this thread's compile/cache events into a fresh dict."""

    def __enter__(self) -> dict:
        _install_listeners()
        self._prev = getattr(_TLS, "sink", None)
        _TLS.sink = {
            "backend_compile": 0,
            "backend_compile_s": 0.0,
            "cache_hits": 0,
            "cache_misses": 0,
        }
        return _TLS.sink

    def __exit__(self, *exc) -> None:
        _TLS.sink = self._prev


@dataclass
class ProgramWarmup:
    """One program's warmup outcome."""

    name: str
    seconds: float  # wall time of lower + compile
    compiled: bool  # a real backend compile ran (persistent-cache miss)
    cache_hit: bool  # served from the persistent compilation cache
    error: str | None = None

    def to_doc(self) -> dict:
        return {
            "name": self.name,
            "seconds": round(self.seconds, 6),
            "compiled": self.compiled,
            "cache_hit": self.cache_hit,
            "error": self.error,
        }


@dataclass
class WarmupReport:
    """Aggregate of one warmup pass."""

    programs: list[ProgramWarmup] = field(default_factory=list)
    seconds: float = 0.0
    cache_dir: str | None = None
    parameterised: bool = False
    skipped: int = 0  # ctx mode: entries with no hook for this ctx

    @property
    def compiled(self) -> int:
        return sum(p.compiled for p in self.programs)

    @property
    def cache_hits(self) -> int:
        return sum(p.cache_hit for p in self.programs)

    @property
    def errors(self) -> list[ProgramWarmup]:
        return [p for p in self.programs if p.error]

    def to_doc(self) -> dict:
        return {
            "seconds": round(self.seconds, 3),
            "programs": len(self.programs),
            "compiled": self.compiled,
            "cache_hits": self.cache_hits,
            "skipped": self.skipped,
            "errors": [p.to_doc() for p in self.errors],
            "cache_dir": self.cache_dir,
            "parameterised": self.parameterised,
            "per_program": [p.to_doc() for p in self.programs],
        }


def warm_registry(
    specs=None,
    ctx=None,
    programs: list[str] | None = None,
) -> WarmupReport:
    """AOT-compile registered programs, populating the persistent
    compilation cache. With ``ctx`` (a ShapeCtx), entries are built
    through their shape-parameterisation hook at the ctx's production
    geometry — entries without a hook (or whose hook declines the ctx)
    are skipped and counted. Per-program failures are recorded, never
    raised: a program that stops tracing is the audit's PSC105 finding,
    not a warmup crash."""
    import jax

    from ..utils.cache import enable_compilation_cache

    if specs is None:
        from ..ops.registry import registered_programs

        specs = registered_programs()
    if programs:
        wanted = set(programs)
        specs = [s for s in specs if s.name in wanted]
    cache_dir = enable_compilation_cache()
    report = WarmupReport(
        cache_dir=cache_dir, parameterised=ctx is not None
    )
    t_all = time.perf_counter()
    for spec in specs:
        try:
            built = spec.build_for(ctx)
        except Exception as exc:
            report.programs.append(
                ProgramWarmup(
                    name=spec.name, seconds=0.0, compiled=False,
                    cache_hit=False,
                    error=f"build: {type(exc).__name__}: {exc!s:.300}",
                )
            )
            continue
        if built is None:
            report.skipped += 1
            continue
        fn, args, kwargs = built
        t0 = time.perf_counter()
        with _sink_scope() as sink:
            err = _compile_with_cache_recovery(
                jax, fn, args, kwargs, spec.name, cache_dir
            )
        report.programs.append(
            ProgramWarmup(
                name=spec.name,
                seconds=time.perf_counter() - t0,
                compiled=sink["cache_misses"] > 0
                or (sink["backend_compile"] > 0 and sink["cache_hits"] == 0),
                cache_hit=sink["cache_hits"] > 0,
                error=err,
            )
        )
    report.seconds = time.perf_counter() - t_all
    return report


def _compile_with_cache_recovery(
    jax, fn, args, kwargs, name: str, cache_dir: str | None
) -> str | None:
    """One program's lower+compile with the ``cache.corrupt`` recovery:
    a failure classified CORRUPT (an injected garbled entry, or a real
    torn cache deserialisation) quarantines the persistent cache's
    entries to ``*.corrupt`` and recompiles once from scratch — warmup
    degrades to a cold compile, never to a crash. Returns the error
    string (None on success, including success-after-recovery)."""
    from ..resilience import CORRUPT, classify, faults
    from ..utils.cache import quarantine_cache_entries

    for attempt in (1, 2):
        try:
            # the cache.corrupt seam: deterministic injection point for
            # "a garbled persistent-cache entry broke this compile"
            faults.fire("cache.corrupt", context=f"warmup:{name}")
            if not hasattr(fn, "lower"):
                fn = jax.jit(fn)
            fn.lower(*args, **kwargs).compile()
            return None
        except Exception as exc:
            suspect_cache = classify(exc) == CORRUPT or (
                "cache" in str(exc).lower() and "deserial" in str(exc).lower()
            )
            if attempt == 1 and suspect_cache:
                quarantined = quarantine_cache_entries(cache_dir)
                log.warning(
                    "warmup of %s hit a corrupt compilation-cache entry "
                    "(%.200s); quarantined %d entries to *.corrupt and "
                    "recompiling", name, exc, len(quarantined),
                )
                continue
            return f"{type(exc).__name__}: {exc!s:.300}"
    return None  # unreachable; the loop returns on both paths


# --------------------------------------------------------------------------
# campaign-bucket warmup
# --------------------------------------------------------------------------

def shape_ctx_for_bucket(bucket, pipeline: str, overrides: dict):
    """Derive the production ShapeCtx a campaign bucket implies, using
    the drivers' own plan machinery (DMPlan, the width bank, the auto
    dm_block formula — and for the periodicity pipeline the accel plan
    + fft plan, so the spectrum/resample/harmonics/peaks hooks compile
    at the wave loop's real (dm_block, accel_pad, fft_size) tile)
    so hook-built programs match what the pipeline will trace. Tuned
    dedispersion knobs (``subbands``/``subband_smear``/``dedisp_block``
    from the tuning cache, perf/tuning.py) flow in through
    ``overrides`` and land in the ctx, so warmup compiles the tuned
    shapes."""
    from ..ops.registry import ShapeCtx
    from ..ops.singlepulse import plan_pad
    from ..pipeline.single_pulse import SinglePulseConfig, SinglePulseSearch
    from ..plan.dm_plan import DMPlan

    nchans, nbits, nsamps, tsamp, fch1, foff = bucket
    base_cls = SinglePulseConfig
    if pipeline == "search":
        from ..pipeline.search import SearchConfig

        base_cls = SearchConfig
    elif pipeline == "ffa":
        # FFA shares only the dedispersion front end with the other
        # pipelines; its ctx carries the DM-plan geometry (the
        # dedisperse/unpack hooks build from it) and the staircase
        # programs trace on the dryrun
        from ..pipeline.ffa import FFAConfig

        base_cls = FFAConfig
    elif pipeline == "fdas":
        from ..pipeline.fdas import FdasConfig

        base_cls = FdasConfig
    cfg = _filtered_config(base_cls, overrides)
    plan = DMPlan.create(
        nsamps=int(nsamps), nchans=int(nchans), tsamp=float(tsamp),
        fch1=float(fch1), foff=float(foff), dm_start=cfg.dm_start,
        dm_end=cfg.dm_end, pulse_width=cfg.dm_pulse_width, tol=cfg.dm_tol,
    )
    widths: tuple[int, ...] = ()
    dm_block = 1
    pallas_span = 0
    sp_fused_span = 0
    fft_size = 0
    nharms = 4
    accel_pad = 0
    max_peaks = 128
    select_smax = 0
    pos5 = pos25 = 0
    fdas_templates = fdas_zmax = fdas_segment = 0
    if pipeline == "spsearch":
        search = SinglePulseSearch(cfg)
        widths = search.widths_for(plan.out_nsamps)
        tpad, span = plan_pad(plan.out_nsamps)
        if cfg.dm_block > 0:
            dm_block = cfg.dm_block
        else:
            per_trial = 16 * tpad
            dm_block = int(
                max(1, min(256, (search.TOTAL_HBM // 4) // max(1, per_trial)))
            )
        # the driver's kernel selection (fused chain, boxcar kernel,
        # jnp twin) so warmup compiles exactly what the job dispatches;
        # on a TPU a kernel that fails its probe raises here too
        from ..pipeline.single_pulse import select_sp_kernels

        pallas_span, sp_fused_span = select_sp_kernels(
            widths, span, cfg.decimate, cfg.use_pallas
        )
    elif pipeline == "search":
        import numpy as np

        from ..ops.resample import accel_factor, select_span
        from ..pipeline.search import PeasoupSearch, _accel_pad
        from ..plan.accel_plan import AccelerationPlan
        from ..plan.fft_plan import choose_fft_size

        fft_size = choose_fft_size(int(nsamps), cfg.size)
        nharms = int(cfg.nharmonics)
        max_peaks = int(cfg.max_peaks)
        # the driver's whitening boundaries in bins (search.py:
        # bin_width = 1/tobs) — static args of the rednoise programs
        tobs = fft_size * float(tsamp)
        pos5 = int(cfg.boundary_5_freq * tobs)
        pos25 = int(cfg.boundary_25_freq * tobs)
        acc_plan = AccelerationPlan(
            acc_lo=cfg.acc_start, acc_hi=cfg.acc_end, tol=cfg.acc_tol,
            pulse_width=cfg.acc_pulse_width, nsamps=fft_size,
            tsamp=float(tsamp),
            cfreq=float(fch1) + (int(nchans) / 2.0 - 0.5) * float(foff),
            bw=float(foff),
        )
        # the widest accel list sits at DM 0 (alt_a grows with DM);
        # its padded column count is the wave loop's tile width
        accs = acc_plan.generate_accel_list(float(cfg.dm_start))
        accel_pad = _accel_pad(len(accs), cfg.accel_bucket)
        af_max = (
            float(np.abs(accel_factor(accs, float(tsamp))).max())
            if len(accs) else 0.0
        )
        select_smax = select_span(af_max, fft_size)
        # the driver's auto per-chip block formula (pipeline/search.py
        # build_chunks) without the one-shot escalation
        searcher = PeasoupSearch(cfg)
        size_spec_b = (fft_size // 2 + 1) * 4
        if cfg.dm_block > 0:
            dm_block = cfg.dm_block
        else:
            cells = max(8, int(searcher.MEM_BUDGET / (size_spec_b * 16)))
            dm_block = max(1, min(128, cells // max(1, accel_pad)))
    elif pipeline == "fdas":
        import numpy as np

        from ..fdas.templates import bank_geometry, effective_zmax
        from ..pipeline.fdas import FdasSearch
        from ..plan.fft_plan import choose_fft_size

        fft_size = choose_fft_size(int(nsamps), cfg.size)
        nharms = int(cfg.nharmonics)
        max_peaks = int(cfg.max_peaks)
        # mirror the driver's f32 bin-width rounding exactly — pos5/
        # pos25 are STATIC args of the whitening program
        tobs = float(np.float32(fft_size) * np.float32(tsamp))
        bin_width = float(np.float32(1.0 / tobs))
        pos5 = int(cfg.boundary_5_freq / bin_width)
        pos25 = int(cfg.boundary_25_freq / bin_width)
        nt, width, seg = bank_geometry(
            cfg.zmax, cfg.wmax, cfg.zstep, cfg.wstep
        )
        fdas_segment = cfg.segment or seg
        fdas_zmax = int(effective_zmax(cfg.zmax, cfg.wmax))
        searcher = FdasSearch(cfg)
        db, tb = searcher._auto_blocks(fft_size // 2 + 1, nt)
        tb = min(tb, nt)
        dm_block = min(db, int(plan.ndm))
        # the per-dispatch template BATCH (the bank is padded to a tb
        # multiple and dispatched tb rows at a time)
        fdas_templates = tb
    # survey-fold geometry: the sift layer (peasoup_tpu/sift/fold.py)
    # later batch-folds this bucket's candidates over the SAME
    # dedispersed trial length, so the fold bucket is derivable right
    # here — warm_bucket pre-compiles the survey-fold program too and
    # the first sift pass over a warmed campaign compiles nothing
    from ..pipeline.folder import fold_geometry

    fold_nints = int(overrides.get("fold_nints", 16))
    fold_size = int(fold_geometry(plan.out_nsamps, float(tsamp))[0])
    if fold_size < fold_nints:
        fold_size = 0  # too short to fold: the hook declines
    return ShapeCtx(
        nsamps=int(nsamps),
        nchans=int(nchans),
        nbits=int(nbits),
        ndm=int(plan.ndm),
        out_nsamps=int(plan.out_nsamps),
        dm_block=int(min(dm_block, max(1, plan.ndm))),
        dedisp_block=int(getattr(cfg, "dedisp_block", 16)),
        widths=tuple(int(w) for w in widths),
        min_snr=float(cfg.min_snr),
        max_events=int(getattr(cfg, "max_events", 256)),
        decimate=int(getattr(cfg, "decimate", 32)),
        pallas_span=int(pallas_span),
        sp_fused_span=int(sp_fused_span),
        subbands=int(getattr(cfg, "subbands", 0)),
        subband_smear=float(getattr(cfg, "subband_smear", 1.0)),
        dedisp_engine=str(getattr(cfg, "dedisp_engine", "")),
        subband_matmul=bool(getattr(cfg, "subband_matmul", False)),
        fft_size=int(fft_size),
        nharms=int(nharms),
        accel_pad=int(accel_pad),
        max_peaks=int(max_peaks),
        select_smax=int(select_smax),
        pos5=int(pos5),
        pos25=int(pos25),
        fdas_templates=int(fdas_templates),
        fdas_zmax=int(fdas_zmax),
        fdas_segment=int(fdas_segment),
        fold_batch=(
            int(overrides.get("fold_batch", 64)) if fold_size else 0
        ),
        fold_nsamps=fold_size,
        fold_nbins=int(overrides.get("fold_nbins", 64)),
        fold_nints=fold_nints,
    )


def _filtered_config(cls, overrides: dict, **fixed):
    """Best-effort config for warmup: unknown keys are dropped rather
    than rejected — a typo'd knob must fail the JOB loudly (the runner
    validates), not abort the warmup thread."""
    import dataclasses

    names = {f.name for f in dataclasses.fields(cls)}
    merged = {k: v for k, v in overrides.items() if k in names}
    merged.update(fixed)
    return cls(**merged)


def synthetic_bucket_observation(bucket, path: str, seed: int = 0):
    """Write a synthetic observation filling a bucket exactly: noise at
    the bucket's shape/dtype plus a strong periodic broadband pulse
    train (so the candidate paths — peak compaction, clustering,
    folding — trace over non-empty work, not a zero-candidate
    shortcut). Returns the re-read Filterbank, so sub-byte buckets get
    the packed ``raw`` payload exactly like a real observation."""
    import numpy as np

    from ..io.sigproc import (
        Filterbank,
        SigprocHeader,
        read_filterbank,
        write_filterbank,
    )

    nchans, nbits, nsamps, tsamp, fch1, foff = bucket
    nchans, nbits, nsamps = int(nchans), int(nbits), int(nsamps)
    rng = np.random.default_rng(seed)
    hi = (1 << min(nbits, 8)) - 1
    base = max(1, hi // 4)
    data = rng.integers(
        0, base + 1, size=(nsamps, nchans), dtype=np.uint8
    )
    # dispersion-free pulse train every ~50 ms: bright single pulses
    # AND a periodicity candidate, without needing per-channel delays
    period = max(64, int(round(0.05 / float(tsamp))))
    for s in range(period // 2, nsamps, period):
        data[s : min(s + 4, nsamps), :] = hi
    hdr = SigprocHeader(
        source_name="WARMUP", data_type=1, nchans=nchans, nbits=nbits,
        nifs=1, tsamp=float(tsamp), tstart=50000.0, fch1=float(fch1),
        foff=float(foff),
    )
    write_filterbank(path, Filterbank(header=hdr, data=data))
    return read_filterbank(path)


def warm_bucket(
    bucket,
    pipeline: str,
    overrides: dict,
    scratch_dir: str,
    mode: str = "dryrun",
) -> dict:
    """Warm one campaign bucket's compiled programs. ``mode="aot"``
    walks the registry through the ShapeCtx hooks (lower+compile only —
    no data execution; covers the registered programs at production
    shapes). ``mode="dryrun"`` instead runs the configured pipeline
    once over a synthetic bucket-shaped observation — costs one
    observation's device work but traces every driver-side shape, so
    the first real job compiles exactly zero programs. Never raises:
    failures come back in the stats dict."""
    import os
    import shutil

    t0 = time.perf_counter()
    stats: dict = {
        "bucket": list(bucket),
        "mode": mode,
        "seconds": 0.0,
        "programs_compiled": 0,
        "cache_hits": 0,
        "error": None,
    }
    try:
        if mode == "aot":
            ctx = shape_ctx_for_bucket(bucket, pipeline, overrides)
            rep = warm_registry(ctx=ctx)
            stats["programs_compiled"] = rep.compiled
            stats["cache_hits"] = rep.cache_hits
            stats["aot_skipped"] = rep.skipped
            if rep.errors:
                stats["error"] = rep.errors[0].to_doc()["error"]
        else:  # dryrun
            os.makedirs(scratch_dir, exist_ok=True)
            fil = synthetic_bucket_observation(
                bucket, os.path.join(scratch_dir, "warmup.fil")
            )
            with _sink_scope() as sink:
                _dryrun_pipeline(pipeline, overrides, scratch_dir, fil)
            stats["programs_compiled"] = max(
                0, sink["backend_compile"] - sink["cache_hits"]
            )
            stats["cache_hits"] = sink["cache_hits"]
            shutil.rmtree(scratch_dir, ignore_errors=True)
    except Exception as exc:
        stats["error"] = f"{type(exc).__name__}: {exc!s:.300}"
        log.warning("bucket warmup failed for %s: %s", bucket, exc)
    stats["seconds"] = round(time.perf_counter() - t0, 3)
    return stats


def _dryrun_pipeline(pipeline: str, overrides: dict, outdir, fil) -> None:
    """One end-to-end pipeline run over the synthetic observation (no
    outputs kept, no checkpoint, telemetry ambient — which on a warmup
    thread is the no-op sink)."""
    if pipeline == "spsearch":
        from ..pipeline.single_pulse import (
            SinglePulseConfig,
            SinglePulseSearch,
        )

        cfg = _filtered_config(
            SinglePulseConfig, overrides, outdir=str(outdir),
            checkpoint_file="",
        )
        SinglePulseSearch(cfg).run(fil)
    elif pipeline == "ffa":
        from ..pipeline.ffa import FFAConfig, FFASearch

        cfg = _filtered_config(
            FFAConfig, overrides, outdir=str(outdir),
            checkpoint_file="",
        )
        FFASearch(cfg).run(fil)
    elif pipeline == "fdas":
        from ..pipeline.fdas import FdasConfig, FdasSearch

        cfg = _filtered_config(
            FdasConfig, overrides, outdir=str(outdir),
            checkpoint_file="",
        )
        FdasSearch(cfg).run(fil)
    else:  # "search"
        from ..pipeline.search import PeasoupSearch, SearchConfig

        cfg = _filtered_config(
            SearchConfig, overrides, outdir=str(outdir),
            checkpoint_file="",
        )
        PeasoupSearch(cfg).run(fil)
