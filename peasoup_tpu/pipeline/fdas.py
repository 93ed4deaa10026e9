"""Host-side FDAS driver: the Fourier-domain acceleration/jerk search
as a campaign-dispatchable pipeline.

Mirrors PeasoupSearch's shape — a config dataclass the runner's
``_build_config`` validates loudly, ``build_dm_plan`` for the warmup
ctx derivation, ``run(fil, dm_slice=..., finalize=...)`` for the
multi-host split (parallel/multihost.py:run_fdas_search), per-DM-block
checkpointing, stage/progress telemetry — but the device inner loop is
the FDAS correlation program (ops/fdas.py): ONE dereddened spectrum
per DM trial, correlated against the (f-dot, f-ddot) template bank
(fdas/templates.py) in fixed (dm_block, template_block) tiles, so one
compile covers the whole run.

OOM degradation: template rows are independent, so halving the
template batch leaves the peak sets bitwise equal — that is the FIRST
ladder rung; halving the DM block (vmap rows, equally independent) is
the second. Both shrink paths reproduce the untroubled run's candidates
exactly (tests/test_fdas.py pins the bitwise invariance).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from ..core import Candidate, CandidateCollection, FdasCandidate
from ..fdas.templates import (
    SPEED_OF_LIGHT,
    auto_segment,
    build_template_bank,
)
from ..io.masks import read_killfile, read_zapfile
from ..io.sigproc import Filterbank
from ..obs import get_logger
from ..obs.telemetry import current as current_telemetry
from ..ops.dedisperse import dedisperse, fil_to_device, output_scale
from ..ops.fdas import make_fdas_search_fn
from ..ops.zap import birdie_mask
from ..plan.dm_plan import DMPlan
from ..plan.fft_plan import choose_fft_size
from ..utils import ProgressBar
from .checkpoint import SearchCheckpoint
from .distill import AccelerationDistiller, DMDistiller, HarmonicDistiller
from .score import CandidateScorer
from .search import _freq_factor, _is_oom, _level_windows

log = get_logger("pipeline.fdas")


@dataclass
class FdasConfig:
    """FDAS search knobs. DM-plan/spectrum knobs mirror SearchConfig;
    zmax/wmax replace the time-domain acc_start/acc_end pair: they
    bound the f-dot (f-ddot) trial grid in DFT BINS over the
    observation (the PRESTO -z/-w convention), so the same knob value
    means the same physical coverage at any observation length."""

    outdir: str = "."
    killfilename: str = ""
    zapfilename: str = ""
    limit: int = 1000
    size: int = 0  # fft size; 0 = prev power of two
    dm_start: float = 0.0
    dm_end: float = 100.0
    dm_tol: float = 1.10
    dm_pulse_width: float = 64.0
    zmax: float = 64.0  # f-dot extent in bins (0 = pure periodicity)
    zstep: float = 2.0  # f-dot grid spacing in bins
    wmax: float = 0.0  # f-ddot (jerk) extent in bins; 0 = plane off
    wstep: float = 20.0  # f-ddot grid spacing in bins
    boundary_5_freq: float = 0.05
    boundary_25_freq: float = 0.5
    nharmonics: int = 4
    min_snr: float = 9.0
    min_freq: float = 0.1
    max_freq: float = 1100.0
    max_harm: int = 16
    freq_tol: float = 1e-4
    verbose: bool = False
    progress_bar: bool = False
    max_peaks: int = 128  # static peak-compaction size per spectrum
    segment: int = 0  # overlap-save FFT length; 0 = auto from width
    template_block: int = 0  # template rows per dispatch; 0 = auto
    dm_block: int = 0  # DM trials per dispatch; 0 = auto from budget
    checkpoint_file: str = ""  # resumable per-DM-trial result store


@dataclass
class FdasResult:
    candidates: list
    dm_list: np.ndarray
    zs: np.ndarray  # the f-dot trial grid (bins)
    ws: np.ndarray  # the f-ddot trial grid (bins)
    timers: dict
    nsamps: int
    size: int
    n_templates: int = 0
    n_trials: int = 0  # DM x template trials searched


@dataclass
class PartialFdasResult:
    """A run stopped after the per-DM distills (run(finalize=False)):
    everything :meth:`FdasSearch.finalize` needs, per process slice."""

    cands: list  # per-DM-trial candidates, dm_idx GLOBAL
    dm_offset: int
    dm_list: np.ndarray  # slice list per-process; GLOBAL once merged
    zs: np.ndarray
    ws: np.ndarray
    timers: dict
    nsamps: int
    size: int
    n_templates: int
    n_trials: int
    t_total_start: float


def _fdas_config_key(cfg: FdasConfig, fil, size: int, global_ndm: int) -> str:
    """Checkpoint config key over everything that changes per-trial
    FDAS results (SearchCheckpoint.make_key is SearchConfig-specific,
    so the FDAS driver supplies its own)."""
    h = fil.header
    fields = (
        "fdas-v1-global-dm",
        fil.nsamps, fil.nchans, size, global_ndm,
        fil.tsamp, fil.fch1, fil.foff,
        getattr(h, "tstart", None), getattr(h, "source_name", None),
        getattr(h, "nbits", None),
        cfg.dm_start, cfg.dm_end, cfg.dm_tol, cfg.dm_pulse_width,
        cfg.zmax, cfg.zstep, cfg.wmax, cfg.wstep,
        cfg.boundary_5_freq, cfg.boundary_25_freq, cfg.nharmonics,
        cfg.min_snr, cfg.min_freq, cfg.max_freq, cfg.max_peaks,
        cfg.killfilename, cfg.zapfilename,
    )
    return repr(fields)


class FdasSearch:
    """Dedisperse the DM plan, then correlation-search every trial."""

    # HBM accounting for auto (dm_block, template_block) sizing — the
    # same fallback budget split as PeasoupSearch
    TOTAL_HBM = 12_000_000_000
    MEM_BUDGET = 6_000_000_000

    def __init__(self, config: FdasConfig):
        self.config = config

    def build_dm_plan(self, fil: Filterbank) -> DMPlan:
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

    # --- block geometry ---------------------------------------------

    def _auto_blocks(self, nbins: int, ntemplates: int) -> tuple[int, int]:
        """(dm_block, template_block) from the working-set budget: the
        correlation intermediates cost ~nbins complex values per
        (dm, template) cell across the overlap-save stages, plus the
        f32 spectrum levels."""
        cfg = self.config
        cell_bytes = nbins * 64
        cells = max(8, self.MEM_BUDGET // cell_bytes)
        tb = cfg.template_block or min(ntemplates, 64)
        db = cfg.dm_block or max(1, min(32, cells // max(1, tb)))
        return db, tb

    # --- the search -------------------------------------------------

    def run(
        self,
        fil: Filterbank,
        dm_slice: tuple[int, int] | None = None,
        finalize: bool = True,
    ) -> "FdasResult | PartialFdasResult":
        cfg = self.config
        tel = current_telemetry()
        timers: dict[str, float] = {}
        t_total = time.perf_counter()

        t0 = time.perf_counter()
        tel.set_stage("plan")
        dm_plan = self.build_dm_plan(fil)
        global_ndm = dm_plan.ndm
        dm_lo = 0
        if dm_slice is not None:
            dm_lo, dm_hi = dm_slice
            dm_plan = dm_plan.subset(dm_lo, dm_hi)
        size = choose_fft_size(fil.nsamps, cfg.size)
        bank = build_template_bank(
            cfg.zmax, cfg.wmax, cfg.zstep, cfg.wstep
        )
        segment = cfg.segment or auto_segment(bank.width)
        timers["plan"] = time.perf_counter() - t0
        if dm_plan.ndm == 0:
            # empty multi-host slice: contribute zero candidates
            part = PartialFdasResult(
                cands=[], dm_offset=dm_lo, dm_list=dm_plan.dm_list,
                zs=bank.zs, ws=bank.ws,
                timers=dict.fromkeys(
                    ("dedispersion", "search_device", "search_host",
                     "searching"), 0.0
                ),
                nsamps=fil.nsamps, size=size,
                n_templates=bank.ntemplates, n_trials=0,
                t_total_start=t_total,
            )
            return part if not finalize else self.finalize(fil, part)
        tel.gauge("fdas.n_dm_trials", int(dm_plan.ndm))
        tel.gauge("fdas.n_templates", int(bank.ntemplates))
        tel.gauge("fdas.fft_size", int(size))
        tel.event(
            "fdas_plan", ndm=int(dm_plan.ndm),
            n_templates=int(bank.ntemplates), width=int(bank.width),
            segment=int(segment), zmax=float(cfg.zmax),
            wmax=float(cfg.wmax), fft_size=int(size),
        )

        # --- dedispersion (host-resident trials: the FDAS chain keeps
        # HBM for the correlation working set; blocks upload per wave)
        t0 = time.perf_counter()
        tel.set_stage("dedispersion")
        trials = dedisperse(
            fil_to_device(fil),
            dm_plan.delay_samples(),
            dm_plan.killmask,
            dm_plan.out_nsamps,
            scale=output_scale(fil.nbits, int(dm_plan.killmask.sum())),
        )
        trials = np.asarray(trials)
        timers["dedispersion"] = time.perf_counter() - t0
        tel.capture_device_memory("dedispersion")

        # --- search setup -------------------------------------------
        nsamps_valid = min(dm_plan.out_nsamps, size)
        tobs = float(np.float32(size) * np.float32(fil.tsamp))
        bin_width = float(np.float32(1.0 / tobs))
        size_spec = size // 2 + 1
        if cfg.zapfilename:
            bf, bw_ = read_zapfile(cfg.zapfilename)
            zapmask = birdie_mask(bf, bw_, bin_width, size_spec)
        else:
            zapmask = np.zeros(size_spec, dtype=bool)
        windows = _level_windows(
            size, cfg.nharmonics, cfg.min_freq, cfg.max_freq, fil.tsamp
        )
        factors = [
            _freq_factor(size, nh, fil.tsamp)
            for nh in range(cfg.nharmonics + 1)
        ]
        pos5 = int(cfg.boundary_5_freq / bin_width)
        pos25 = int(cfg.boundary_25_freq / bin_width)

        ckpt = SearchCheckpoint(
            cfg.checkpoint_file,
            _fdas_config_key(cfg, fil, size, global_ndm),
            slice_bounds=dm_slice,
        )
        per_dm_results: dict[int, tuple] = ckpt.load()
        if per_dm_results:
            log.info(
                "Resuming: %d/%d DM trials restored from %s",
                len(per_dm_results), dm_plan.ndm, cfg.checkpoint_file,
            )
            tel.event(
                "checkpoint_resume", restored=len(per_dm_results),
                ndm=int(dm_plan.ndm),
            )

        t0 = time.perf_counter()
        tel.set_stage("searching")
        progress = ProgressBar() if cfg.progress_bar else None
        if progress:
            progress.start()
        try:
            self._run_blocks(
                trials, bank, zapmask, windows, per_dm_results, ckpt,
                progress, size=size, nsamps_valid=nsamps_valid,
                segment=segment, pos5=pos5, pos25=pos25,
            )
        finally:
            if progress:
                progress.stop()
        timers["search_device"] = time.perf_counter() - t0
        tel.capture_device_memory("search")

        # --- host candidate bookkeeping -----------------------------
        t_host = time.perf_counter()
        tel.set_stage("search_host")
        harm_finder = HarmonicDistiller(
            cfg.freq_tol, cfg.max_harm, keep_related=False
        )
        tmpl_still = AccelerationDistiller(
            tobs, cfg.freq_tol, keep_related=True
        )
        dm_trial_cands = CandidateCollection()
        zs, ws = bank.zs, bank.ws
        for dm_idx, dm in enumerate(dm_plan.dm_list):
            idxs, snrs, ccounts = per_dm_results.pop(dm_idx)
            tmpl_trial_cands = CandidateCollection()
            for t in range(bank.ntemplates):
                z, w = float(zs[t]), float(ws[t])
                trial_cands: list[Candidate] = []
                for lvl in range(cfg.nharmonics + 1):
                    n_found = int(ccounts[lvl, t])
                    for b, s in zip(
                        idxs[lvl, t, :n_found], snrs[lvl, t, :n_found]
                    ):
                        trial_cands.append(
                            self._candidate(
                                float(dm), dm_idx + dm_lo, z, w,
                                int(lvl), float(s), int(b),
                                factors, tobs,
                            )
                        )
                tmpl_trial_cands.append(harm_finder.distill(trial_cands))
            dm_trial_cands.append(
                tmpl_still.distill(tmpl_trial_cands.cands)
            )
        timers["search_host"] = time.perf_counter() - t_host
        timers["searching"] = time.perf_counter() - t0
        tel.gauge("candidates.per_dm_distill", len(dm_trial_cands))

        part = PartialFdasResult(
            cands=dm_trial_cands.cands,
            dm_offset=dm_lo,
            dm_list=dm_plan.dm_list,
            zs=zs, ws=ws,
            timers=timers,
            nsamps=fil.nsamps,
            size=size,
            n_templates=bank.ntemplates,
            n_trials=dm_plan.ndm * bank.ntemplates,
            t_total_start=t_total,
        )
        if not finalize:
            return part
        return self.finalize(fil, part)

    def _candidate(
        self, dm, dm_idx, z, w, lvl, snr, bin_idx, factors, tobs
    ) -> FdasCandidate:
        """One detection -> candidate. The detection bin is the
        START-of-observation frequency of the matched drifting tone
        (the correlation peak sits where the template's own response
        aligns); the REPORTED frequency is the mean over the
        observation, f = (bin + z/2 + w/6) * factor — the quantity the
        time-domain resampling search recovers, since its pinned-ends
        resampling preserves total cycle count. At z = w = 0 the
        correction vanishes and the stored f32 freq is bit-identical
        to the plain search's f32(bin * factor)."""
        factor = float(factors[lvl])
        freq = float(np.float32(np.float32(bin_idx) * factors[lvl]))
        corr = (z / 2.0 + w / 6.0) * factor
        if corr:
            freq = float(np.float32(freq + corr))
        # the template grid is indexed in drift bins at the DETECTED
        # level; the fundamental's f-dot scales by the same per-level
        # factor as the frequency
        fdot = z * factor / tobs
        fddot = w * factor / (tobs * tobs)
        acc = -fdot * SPEED_OF_LIGHT / freq if freq > 0 and fdot else 0.0
        return FdasCandidate(
            dm=dm, dm_idx=dm_idx, acc=acc, nh=lvl, snr=snr, freq=freq,
            fdot=fdot, fddot=fddot, z=z, w=w,
        )

    def _run_blocks(
        self, trials, bank, zapmask, windows, per_dm_results, ckpt,
        progress, *, size, nsamps_valid, segment, pos5, pos25,
    ) -> None:
        """Fixed (dm_block, template_block) tiles with the two-rung OOM
        ladder. Every dispatch is the SAME tile shape (short blocks are
        padded by repeating rows — template rows and DM rows are both
        independent, so padding never perturbs the kept results and the
        steady state compiles exactly one program)."""
        import jax
        import jax.numpy as jnp

        from ..resilience import DegradationLadder, faults

        cfg = self.config
        tel = current_telemetry()
        ndm = trials.shape[0]
        nbins = size // 2 + 1
        ntemplates = bank.ntemplates
        db, tb = self._auto_blocks(nbins, ntemplates)
        tb = min(tb, ntemplates)
        db = min(db, ndm)
        search_fn = make_fdas_search_fn(float(cfg.min_snr))
        zap_dev = jnp.asarray(zapmask)
        win_dev = jnp.asarray(windows)
        tim_len = min(size, trials.shape[1])
        ladder = DegradationLadder(
            "fdas.memory", ("template_block_shrink", "dm_block_shrink")
        )
        while True:
            # template batches: pad the bank to a tb multiple with
            # copies of the last row; padded rows are sliced off below
            n_tb = -(-ntemplates // tb)
            tmpl_pad = np.concatenate(
                [bank.templates,
                 np.repeat(bank.templates[-1:], n_tb * tb - ntemplates, 0)]
            )
            tmpl_dev = [
                jnp.asarray(tmpl_pad[i * tb:(i + 1) * tb])
                for i in range(n_tb)
            ]
            todo = [d for d in range(ndm) if d not in per_dm_results]
            blocks = [todo[s:s + db] for s in range(0, len(todo), db)]
            tel.event(
                "fdas_wave_plan", n_blocks=len(blocks), dm_block=db,
                template_block=tb, n_template_batches=n_tb,
            )
            tel.set_progress(ndm - len(todo), ndm, unit="dm trials")
            try:
                faults.fire(
                    "device.oom", context=f"fdas:db{db}.tb{tb}"
                )
                for dm_indices in blocks:
                    # pad short DM blocks by repeating the last trial:
                    # one (db, tb) program shape for the whole run
                    rows = dm_indices + [dm_indices[-1]] * (
                        db - len(dm_indices)
                    )
                    tims = jnp.asarray(trials[rows][:, :tim_len])
                    parts = [
                        search_fn(
                            tims, t_dev, zap_dev, win_dev,
                            size=size, nsamps_valid=nsamps_valid,
                            segment=segment, nharms=cfg.nharmonics,
                            max_peaks=cfg.max_peaks, pos5=pos5,
                            pos25=pos25,
                        )
                        for t_dev in tmpl_dev
                    ]
                    # one packed D2H per block: concat along the
                    # template axis, trim bank padding
                    idxs = np.concatenate(
                        [np.asarray(p.idxs) for p in parts], axis=2
                    )[:, :, :ntemplates]
                    snrs = np.concatenate(
                        [np.asarray(p.snrs) for p in parts], axis=2
                    )[:, :, :ntemplates]
                    ccounts = np.concatenate(
                        [np.asarray(p.ccounts) for p in parts], axis=2
                    )[:, :, :ntemplates]
                    for k, d in enumerate(dm_indices):
                        per_dm_results[d] = (
                            idxs[k].astype(np.int32),
                            snrs[k].astype(np.float32),
                            ccounts[k].astype(np.int32),
                        )
                    ckpt.save(per_dm_results)
                    done = ndm - sum(
                        1 for d in range(ndm) if d not in per_dm_results
                    )
                    tel.set_progress(done, ndm, unit="dm trials")
                    if progress:
                        progress.update(done / ndm)
                return
            except Exception as exc:
                if not _is_oom(exc):
                    raise
                if tb > 1:
                    tb = max(1, tb // 2)
                    log.warning(
                        "device OOM; halving the template batch to %d "
                        "(bitwise-neutral: template rows are "
                        "independent): %.200s", tb, exc,
                    )
                    tel.event(
                        "fdas_oom_template_shrink", template_block=tb,
                        error=f"{exc!s:.200}",
                    )
                    if ladder.current_rung in (
                        None, "template_block_shrink"
                    ):
                        ladder.step(
                            "template_block_shrink", template_block=tb,
                            error=f"{exc!s:.200}",
                        )
                    continue
                if db > 1:
                    db = max(1, db // 2)
                    log.warning(
                        "device OOM at template_block=1; halving the "
                        "DM block to %d: %.200s", db, exc,
                    )
                    tel.event(
                        "fdas_oom_dm_shrink", dm_block=db,
                        error=f"{exc!s:.200}",
                    )
                    ladder.step(
                        "dm_block_shrink", dm_block=db,
                        error=f"{exc!s:.200}",
                    )
                    continue
                ladder.exhausted(
                    dm_block=db, template_block=tb, error=f"{exc!s:.200}"
                )
                raise

    def finalize(
        self, fil: Filterbank, part: "PartialFdasResult"
    ) -> FdasResult:
        """Global distilling/scoring over (possibly merged) per-DM
        candidates — identical on every multi-host process."""
        cfg = self.config
        tel = current_telemetry()
        timers = part.timers
        t0 = time.perf_counter()
        tel.set_stage("distilling")
        dm_still = DMDistiller(cfg.freq_tol, keep_related=True)
        harm_still = HarmonicDistiller(
            cfg.freq_tol, cfg.max_harm, keep_related=True,
            fractional_harms=False,
        )
        tel.gauge("candidates.per_dm_total", len(part.cands))
        cands = dm_still.distill(part.cands)
        cands = harm_still.distill(cands)
        tel.gauge("candidates.post_harmonic_distill", len(cands))
        timers["distilling"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        tel.set_stage("scoring")
        scorer = CandidateScorer(
            fil.tsamp, fil.cfreq, fil.foff, abs(fil.foff) * fil.nchans
        )
        scorer.score_all(cands)
        timers["scoring"] = time.perf_counter() - t0

        cands = cands[: cfg.limit]
        tel.gauge("candidates.final", len(cands))
        timers["total"] = time.perf_counter() - part.t_total_start
        log.info(
            "FDAS search: %d DM x %d template trials -> %d candidates",
            len(part.dm_list), part.n_templates, len(cands),
        )
        return FdasResult(
            candidates=cands,
            dm_list=part.dm_list,
            zs=part.zs, ws=part.ws,
            timers=timers,
            nsamps=part.nsamps,
            size=part.size,
            n_templates=part.n_templates,
            n_trials=part.n_trials,
        )
