"""Host-side search driver: the TPU equivalent of `peasoup`'s main +
Worker loop (reference: src/pipeline_multi.cu:262-419, 83-254).

The reference deals DM trials to one pthread per GPU; here a single
host process walks the DM list (optionally sharded across chips by
peasoup_tpu.parallel), launching ONE jitted program per DM trial that
covers the whole acceleration batch. Candidate bookkeeping (clustering,
distilling, scoring) is host work on tiny arrays, as in the reference.
"""

from __future__ import annotations

import math
import os
import time
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.candidates import Candidate, CandidateCollection
from ..io.masks import read_killfile, read_zapfile
from ..obs import get_logger
from ..obs.telemetry import current as current_telemetry
from ..obs.trace import job_span
from ..io.sigproc import Filterbank
from ..ops.dedisperse import (
    dedisperse,
    dedisperse_device,
    dedisperse_subband,
    fil_to_device,
    output_scale,
)
from ..ops.pallas import backend_supports_pallas
from ..ops.pallas.dftspec import dftspec_supported
from ..ops.pallas.peaks import PEAKS_BLOCK
from ..ops.resample import accel_factor, select_span
from ..ops.zap import birdie_mask
from ..plan.accel_plan import AccelerationPlan
from ..plan.dm_plan import DMPlan
from ..plan.fft_plan import choose_fft_size
from ..utils import ProgressBar
from ..utils.device import device_bytes_limit
from .accel_search import (
    CPU_ROUTE,
    interbin_fits,
    make_batched_search_fn,
    search_route,
    spectrum_pad,
)
from .checkpoint import SearchCheckpoint
from .distill import AccelerationDistiller, DMDistiller, HarmonicDistiller
from .folder import MultiFolder
from .score import CandidateScorer

log = get_logger("pipeline.search")


@dataclass
class SearchConfig:
    """Mirrors CmdLineOptions with the reference's defaults
    (include/utils/cmdline.hpp:69-209)."""

    outdir: str = "."
    killfilename: str = ""
    zapfilename: str = ""
    max_num_threads: int = 14
    limit: int = 1000
    size: int = 0  # fft size; 0 = prev power of two
    dm_start: float = 0.0
    dm_end: float = 100.0
    dm_tol: float = 1.10
    dm_pulse_width: float = 64.0
    acc_start: float = 0.0
    acc_end: float = 0.0
    acc_tol: float = 1.10
    acc_pulse_width: float = 64.0
    boundary_5_freq: float = 0.05
    boundary_25_freq: float = 0.5
    nharmonics: int = 4
    npdmp: int = 0
    min_snr: float = 9.0
    min_freq: float = 0.1
    max_freq: float = 1100.0
    max_harm: int = 16
    freq_tol: float = 1e-4
    verbose: bool = False
    progress_bar: bool = False
    # TPU-specific knobs (no reference equivalent)
    max_peaks: int = 128  # static peak-compaction size per spectrum
    # (small on purpose: top_k cost scales with the compaction size, and
    # chunks whose raw crossing count overflows are re-dispatched at the
    # next power of two automatically)
    dedisp_block: int = 16  # DM trials per dedispersion launch
    subbands: int = 0  # >0: two-stage subband dedispersion with this
    # many subbands (~sqrt(C)-fold less arithmetic at survey channel
    # counts; 0 = direct channel scan, the golden-exact default)
    subband_smear: float = 1.0  # max extra smear (samples) a trial may
    # suffer from sharing its group's nominal DM (0 = exact)
    subband_snr_loss: float = 0.1  # parity gate for the auto planner
    # (plan/dedisp_plan.py): max fractional matched-filter S/N loss a
    # subband plan may predict before exact is forced
    tune: bool = False  # auto-select exact-vs-subband-vs-matmul +
    # per-device tuned shape knobs via the tuning cache
    # (perf/tuning.py); an explicit --subbands overrides the planner
    dedisp_engine: str = ""  # force one dedispersion engine: "exact"
    # (gather scan) or "matmul" (MXU banded matmul) — "" lets the
    # plan/tuner decide ("subband" is forced via --subbands, whose
    # smear knob it needs). The CI three-way smoke pins candidate
    # parity across all of them
    subband_matmul: bool = False  # run the subband stages as banded
    # matmuls (bitwise-identical; normally set by the tuned plan)
    tuning_cache: str = ""  # tuning_cache.json path ("" = the
    # per-user default, PEASOUP_TUNING_CACHE overrides)
    accel_bucket: int = 16  # accel batch padded to a multiple of this
    dedupe_accel: bool = True  # collapse accel trials whose entire
    # rounded resample-shift maps provably coincide (identity or not)
    # into one dispatched representative per equivalence class
    # (bitwise-identical output, device work / class size)
    hbm_bytes: int = 0  # device memory budget override; 0 = ask the
    # device (memory_stats), falling back to the 12 GB v5e-ish default
    # — set this on chips that report no limit (or via the
    # PEASOUP_HBM_BYTES env var / --hbm_bytes CLI flag)
    dm_block: int = 0  # DM trials per device call; 0 = auto from HBM budget
    checkpoint_file: str = ""  # resumable per-DM-trial result store
    # device sharding: 0 = auto (all local TPU chips up to
    # max_num_threads, single-device elsewhere); N = force an N-chip
    # 'dm' mesh (tests use this on the virtual CPU mesh)
    shard_devices: int = 0


@dataclass
class SearchResult:
    candidates: list
    dm_list: np.ndarray
    acc_list_dm0: np.ndarray
    timers: dict
    nsamps: int
    size: int
    n_accel_trials: int = 0  # effective (brute-force-equivalent) DM x
    # accel trials: identity-deduped trials count — their results are
    # produced bitwise — but fewer resamplings may have been dispatched


@dataclass
class PartialSearchResult:
    """A search stopped after the per-DM distills (run(finalize=False)):
    everything PeasoupSearch.finalize needs, per process slice. The
    reference analogue is one Worker's dm_trial_cands before the join
    merge (pipeline_multi.cu:356-359)."""

    cands: list  # per-DM-trial candidates, dm_idx GLOBAL
    trials: object  # this slice's dedispersed trials (device or host)
    trials_nsamps: int
    dm_offset: int  # global dm_idx of trials[0]
    dm_list: np.ndarray  # slice dm values in a per-process partial;
    # the GLOBAL list in a merged part (finalize copies it into
    # SearchResult.dm_list, which rank 0 writes to overview.xml)
    acc_list_dm0: np.ndarray
    timers: dict
    nsamps: int
    size: int
    n_accel_trials: int
    t_total_start: float
    # trials of this slice in ``trials``; rows past it pad a block that
    # was dedispersed sharded (None: every row)
    trials_ndm: int | None = None


def _offset_dm_idx(cands: list, lo: int) -> None:
    """Shift local dm_idx to global, through the assoc trees."""
    seen: set[int] = set()
    stack = list(cands)
    while stack:
        c = stack.pop()
        if id(c) in seen:
            continue
        seen.add(id(c))
        c.dm_idx += lo
        stack.extend(c.assoc)


def _level_windows(
    size: int, nharms: int, min_freq: float, max_freq: float, tsamp: float
) -> np.ndarray:
    """[start_idx, limit) per harmonic level (peakfinder.hpp:78-84)."""
    size_spec = size // 2 + 1
    tobs = np.float32(size) * np.float32(tsamp)
    bin_width = 1.0 / float(tobs)
    nyquist = bin_width * size_spec
    orig_size = 2.0 * (size_spec - 1.0)
    rows = []
    for nh in range(nharms + 1):
        max_bin = int((max_freq / bin_width) * 2.0**nh)
        limit = min(size_spec, max_bin)
        start = int(orig_size * (min_freq / nyquist) * 2.0**nh)
        rows.append((start, limit))
    return np.asarray(rows, dtype=np.int32)


def _is_oom(exc: Exception) -> bool:
    """Device out-of-memory signature — now the shared classification's
    :func:`peasoup_tpu.resilience.errors.is_resource_exhausted`
    (kept as a module function: the single-pulse driver and tests
    import it from here, and its contract is pinned against the real
    JAX OOM exception in tests/test_aux.py)."""
    from ..resilience import is_resource_exhausted

    return is_resource_exhausted(exc)


def _densify_ragged(
    vi: np.ndarray, vs: np.ndarray, cc: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Expand a per-DM ragged peak stream back to dense
    (nlev, padded, mx) slot arrays (cells C-order, slots in order) for
    the object-path fallback."""
    flat_cc = cc.reshape(-1).astype(np.int64)
    mx = max(int(flat_cc.max()) if flat_cc.size else 0, 1)
    idxs = np.zeros((flat_cc.size, mx), np.int64)
    snrs = np.zeros((flat_cc.size, mx), np.float64)
    ends = np.cumsum(flat_cc)
    cell = np.repeat(np.arange(flat_cc.size), flat_cc)
    within = np.arange(int(flat_cc.sum()), dtype=np.int64) - np.repeat(
        ends - flat_cc, flat_cc
    )
    idxs[cell, within] = vi
    snrs[cell, within] = vs
    return (
        idxs.reshape(*cc.shape, mx),
        snrs.reshape(*cc.shape, mx),
        cc,
    )


def _deal_chunks(
    dm_indices: list[int], d_local: int, n_dev: int, shard_rows: int
) -> list[tuple[list[int], int]]:
    """(dm indices, dm_block) chunks of ``d_local`` trials a chip. With
    trials sharded ``shard_rows`` to a chip, each chip's part of a chunk
    is drawn from the trials it holds, so no row changes chip; otherwise
    chunks are consecutive runs of ``dm_indices``."""
    d_blk = d_local * n_dev
    if not shard_rows:
        return [
            (dm_indices[s : s + d_blk], d_blk)
            for s in range(0, len(dm_indices), d_blk)
        ]
    held = [[] for _ in range(n_dev)]
    for d in dm_indices:
        held[d // shard_rows].append(d)
    n = max(-(-len(h) // d_local) for h in held)
    return [
        ([d for h in held for d in h[j * d_local : (j + 1) * d_local]], d_blk)
        for j in range(n)
    ]


def _block_rows(
    dm_indices: list[int], d_blk: int, n_dev: int, shard_rows: int
) -> tuple[list[int], list[int]]:
    """(trial row at each of a chunk's ``d_blk`` positions, the position
    of each of ``dm_indices``). Unsharded: the trials in order, then the
    first repeated. Sharded ``shard_rows`` to a chip (a chunk of
    ``_deal_chunks``): chip k's d_blk / n_dev positions hold the chunk's
    trials that live on chip k, then repeats of a row of that chip."""
    if not shard_rows:
        pad = [dm_indices[0]] * (d_blk - len(dm_indices))
        return list(dm_indices) + pad, list(range(len(dm_indices)))
    d_local = d_blk // n_dev
    rows = [k * shard_rows for k in range(n_dev) for _ in range(d_local)]
    held = [0] * n_dev
    at = []
    for d in dm_indices:
        k = d // shard_rows
        if held[k] == d_local:
            raise ValueError(
                f"chunk holds more than {d_local} trials of chip {k}"
            )
        at.append(k * d_local + held[k])
        rows[at[-1]] = d
        held[k] += 1
    for k in range(n_dev):
        first = rows[k * d_local]
        for p in range(k * d_local + held[k], (k + 1) * d_local):
            rows[p] = first
    return rows, at


def _accel_pad(n: int, bucket: int) -> int:
    """Padded accel-column count for a dispatch list of length n: the
    usual bucket multiple, with one extra small shape (4) so searches
    whose accel lists collapse to a few distinct trials (the golden
    [0,-5,+5] list, or identity-deduped grids) don't pad 1-3 columns
    of real work to a 16-wide tile."""
    if n <= 4:
        return 4
    return int(math.ceil(n / bucket) * bucket)


def _dedupe_identity_accels(
    accel_lists, tsamp: float, size: int
) -> tuple[list, list]:
    """Collapse accel trials whose resamples are provably BITWISE
    EQUAL into one representative per equivalence class per DM.

    resample reads src = i + rn(af * quad(i)) with quad and the product
    each rounded once to f32 (ops/resample.py; shift-then-add — the
    bitwise claim depends on that formulation). Two trials whose entire
    rounded SHIFT MAPS i -> rn(f32(af)*quad[i]) coincide read identical
    sources, so their spectra, peaks, and candidates are bitwise
    identical; searching one representative and replicating its results
    host-side (_expand_accel_results) is output-identical to brute
    force. The IDENTITY class (map == 0 everywhere, exactly when
    |f32(af * max|quad|)| <= 0.5 by rn's monotonicity — rn(0.5) = 0
    under round-half-even) is the common case (the whole +-5 m/s^2
    tutorial grid at 2^17 samples), handled without building maps.

    Class detection (r4, VERDICT item 9): quad <= 0 everywhere, so
    maps are pointwise monotone in af and classes are CONTIGUOUS in
    af-sorted order — adjacent-pair comparison finds them all. Exact
    screens keep it cheap: equal f32 afs share a map trivially;
    differing rints at the max-|quad| bin mean the maps differ there
    (rint is odd, so rint(af*max|quad|) determines that bin's value);
    and a 64-point strided probe of the maps rejects most remaining
    unequal pairs before the full O(size) compare.

    Returns (dispatch_lists, expand_maps): expand_maps[dm] is None when
    nothing deduped, else an int array mapping each FULL accel index to
    its dispatch-list index.
    """
    max_abs_quad = _max_abs_quad_f32(size)
    dispatch_lists: list = []
    expand_maps: list = []
    max_ident_af = np.float32(0.0)
    for accs in accel_lists:
        n = len(accs)
        afs32 = accel_factor(np.asarray(accs), tsamp).astype(np.float32)
        if n <= 1:
            dispatch_lists.append(accs)
            expand_maps.append(None)
            continue
        prods = afs32 * max_abs_quad  # one f32 rounding each
        if (np.abs(prods) <= np.float32(0.5)).all():
            # whole list is the identity class: no maps needed
            class_of = np.zeros(n, dtype=np.int64)
            max_ident_af = max(max_ident_af, np.abs(afs32).max())
        else:
            quad = _quad_f32(size)
            probe = quad[:: max(1, size // 64)]
            rmax = np.rint(prods)  # the (negated) map value at max|quad|
            order = np.argsort(afs32, kind="stable")
            class_of = np.empty(n, dtype=np.int64)
            cid = -1
            prev_j = -1
            prev_map = None
            for j in order:
                if prev_j < 0:
                    new = True
                elif afs32[j] == afs32[prev_j]:
                    new = False
                elif rmax[j] != rmax[prev_j] or not np.array_equal(
                    np.rint(afs32[j] * probe), np.rint(afs32[prev_j] * probe)
                ):
                    new = True
                    prev_map = None
                else:
                    if prev_map is None:
                        prev_map = np.rint(afs32[prev_j] * quad)
                    cur = np.rint(afs32[j] * quad)
                    new = not np.array_equal(cur, prev_map)
                    prev_map = cur
                if new:
                    cid += 1
                class_of[j] = cid
                prev_j = j
        # representative = FIRST member (original order) of each class
        first_of: dict[int, int] = {}
        for i in range(n):
            first_of.setdefault(int(class_of[i]), i)
        if len(first_of) == n:
            dispatch_lists.append(accs)
            expand_maps.append(None)
            continue
        keep = sorted(first_of.values())
        pos = {full_i: j for j, full_i in enumerate(keep)}
        expand_maps.append(
            np.asarray(
                [pos[first_of[int(class_of[i])]] for i in range(n)],
                dtype=np.int64,
            )
        )
        dispatch_lists.append(np.asarray([accs[i] for i in keep]))
    if max_ident_af > 0:
        # belt-and-braces for the map-free identity fast path: replay
        # the device's exact shift chain for the LARGEST deduped |af|
        # (monotonicity covers the rest) and verify every shift is zero
        shifts = np.rint(max_ident_af * _quad_f32(size))
        assert not shifts.any(), (
            f"identity-dedupe invariant violated: af={max_ident_af!r} "
            f"has a nonzero resample shift (max |shift| = "
            f"{np.abs(shifts).max()})"
        )
    return dispatch_lists, expand_maps


@lru_cache(maxsize=8)
def _quad_f32(size: int) -> np.ndarray:
    """resample's f32-rounded quadratic index map: f32(i)*(f32(i)-f32(size))
    for all i (exactly the device computation, ops/resample.py)."""
    idx = np.arange(size, dtype=np.float32)
    quad = idx * (idx - np.float32(size))
    quad.setflags(write=False)  # cached: protect from caller mutation
    return quad


@lru_cache(maxsize=8)
def _max_abs_quad_f32(size: int) -> np.float32:
    return np.float32(np.abs(_quad_f32(size)).max())


def _expand_accel_results(vi, vs, cc, emap, padded_full):
    """Replicate a deduped dispatch's ragged per-(lvl, accel) results
    onto the full accel list (map-equivalent trials share their
    representative's spectrum bitwise). Stream cell order is C-order
    over (nlev, padded) — lvl-major — matching the device pack.
    Vectorised: one fancy-index gather, no per-cell Python loop."""
    nlev, nd = cc.shape
    flat = cc.astype(np.int64).reshape(-1)
    ends = np.cumsum(flat)
    starts = ends - flat
    a_count = len(emap)
    # output cells (lvl-major over the FULL accel list) -> source cells
    src_cells = (
        np.arange(nlev, dtype=np.int64)[:, None] * nd
        + np.asarray(emap, dtype=np.int64)[None, :]
    ).ravel()
    src_counts = flat[src_cells]
    cc_full = np.zeros((nlev, padded_full), dtype=cc.dtype)
    cc_full[:, :a_count] = src_counts.reshape(nlev, a_count)
    n_out = int(src_counts.sum())
    # per output entry: its source index = start of its source cell +
    # offset within the cell
    cell_of = np.repeat(np.arange(src_cells.size), src_counts)
    out_cell_start = np.concatenate(
        [[0], np.cumsum(src_counts)[:-1]]
    )
    within = np.arange(n_out, dtype=np.int64) - out_cell_start[cell_of]
    src = starts[src_cells][cell_of] + within
    return vi[src], vs[src], cc_full


def _freq_factor(size: int, nh: int, tsamp: float) -> np.float32:
    """Bin index -> frequency for level nh, replaying the reference's
    f32 rounding points exactly: ``float tobs = size*get_tsamp()`` (an
    f32 product — get_tsamp returns float, timeseries.hpp:123),
    ``float bin_width = 1.0/tobs`` (pipeline_multi.cu:118-119), then
    PeakFinder's ``float nyquist = bin_width*size`` and ``float factor``
    (peakfinder.hpp:77-89).  The candidate's stored f32 freq is
    ``f32(f32(idx) * factor)``."""
    size_spec = size // 2 + 1
    tobs = np.float32(size) * np.float32(tsamp)
    bin_width = np.float32(1.0 / np.float64(tobs))
    nyquist = np.float32(np.float64(bin_width) * np.float64(size_spec))
    return np.float32(
        1.0 / np.float64(size_spec) * np.float64(nyquist) / 2.0**nh
    )


class PeasoupSearch:
    # HBM accounting for auto dm_block sizing: total usable chip memory,
    # the spectra working-set budget carved from it (after the
    # device-resident trials), the cap on live peak-output buffers
    # queued per dispatch wave, and the trials size beyond which the
    # trial block spills to host RAM instead of living in HBM
    TOTAL_HBM = 12_000_000_000  # off-TPU default (the CPU test mesh)
    MEM_BUDGET = 6_000_000_000
    WAVE_BUDGET = 1_000_000_000
    TRIALS_DEVICE_LIMIT = 4_000_000_000

    def __init__(self, config: SearchConfig):
        self.config = config
        self._dm_sharding = None
        # adaptive compaction size: raw threshold crossings per spectrum
        # are data-dependent (a bright pulsar crosses at every DM trial,
        # e.g. tutorial.fil peaks at ~276); once a wave escalates, start
        # every later wave at the learned size so steady state
        # dispatches each chunk exactly once
        self._learned_max_peaks = 0
        # speculative ragged-fetch size: each wave's peak stream is
        # compacted at this pow2 size and shipped WITH the counts in one
        # transfer; chunks whose true total exceeds it pay a second
        # exact-size fetch and raise the speculation for later waves
        self._learned_total_pad = 4096
        self._shard_rows = 0  # trials per chip while trials are sharded
        # size budgets from the real chip (memory_stats is absent on
        # some backends, e.g. the CPU mesh in tests, which keep the
        # class defaults; a TPU must report its size or be given one)
        limit = config.hbm_bytes or int(
            os.environ.get("PEASOUP_HBM_BYTES", 0) or 0
        )
        if not limit:
            limit = device_bytes_limit()
        if limit:
            self.TOTAL_HBM = int(limit)
            self.MEM_BUDGET = int(limit) // 2
            self.WAVE_BUDGET = max(int(limit) // 12, 250_000_000)
            self.TRIALS_DEVICE_LIMIT = int(limit) // 3

    def build_dm_plan(self, fil: Filterbank) -> DMPlan:
        """The GLOBAL dedispersion plan for this config (also used by
        the multi-host driver to partition the trial list — single
        construction site keeps the partitioning and the search in
        sync)."""
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

    def _pick_devices(self) -> list:
        """Devices to shard DM trials over. Auto mode mirrors the
        reference's one-worker-per-GPU-up-to--t policy
        (pipeline_multi.cu:276-277) on TPU backends; elsewhere it stays
        single-device unless shard_devices forces a mesh (tests)."""

        devs = jax.local_devices()
        cfg = self.config
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
    ) -> "SearchResult | PartialSearchResult":
        """Full search. With ``dm_slice=(lo, hi)`` only that contiguous
        block of the global DM-trial list is dedispersed and searched
        (candidates come back with GLOBAL dm_idx); with
        ``finalize=False`` the run stops after the per-DM distills and
        returns a PartialSearchResult for the multi-host merge
        (parallel/multihost.py:run_search)."""
        cfg = self.config
        tel = current_telemetry()
        timers: dict[str, float] = {}
        t_total = time.perf_counter()

        # --- dedispersion plan + execution ---------------------------------
        sp = tel.begin("plan")
        dm_plan = self.build_dm_plan(fil)
        timers["plan"] = sp.end()
        global_ndm = dm_plan.ndm
        dm_lo = 0
        if dm_slice is not None:
            dm_lo, dm_hi = dm_slice
            dm_plan = dm_plan.subset(dm_lo, dm_hi)
        if dm_plan.ndm == 0:
            # empty multi-host slice (more processes than DM trials):
            # contribute zero candidates without touching the device
            size = choose_fft_size(fil.nsamps, cfg.size)
            acc_plan = AccelerationPlan(
                acc_lo=cfg.acc_start, acc_hi=cfg.acc_end, tol=cfg.acc_tol,
                pulse_width=cfg.acc_pulse_width, nsamps=size,
                tsamp=fil.tsamp, cfreq=fil.cfreq, bw=fil.foff,
            )
            part = PartialSearchResult(
                cands=[],
                trials=np.zeros((0, 1), dtype=np.uint8),
                trials_nsamps=dm_plan.out_nsamps,
                dm_offset=dm_lo,
                dm_list=dm_plan.dm_list,
                acc_list_dm0=acc_plan.generate_accel_list(0.0),
                timers=dict.fromkeys(
                    ("dedispersion", "search_device", "search_host",
                     "searching"), 0.0
                ),
                nsamps=fil.nsamps,
                size=size,
                n_accel_trials=0,
                t_total_start=t_total,
            )
            return part if not finalize else self.finalize(fil, part)
        # --- auto-tuned dedispersion plan ------------------------------
        # the measure -> decide -> cache -> reuse loop (ISSUE 8): an
        # explicit --subbands is an operator decision the planner
        # respects; otherwise resolve exact-vs-subband + tuned shape
        # knobs from the per-device tuning cache (warm buckets load
        # with zero measurement calls). A planning failure stops the
        # run rather than quietly searching with other knobs.
        subbands = cfg.subbands
        subband_smear = cfg.subband_smear
        dedisp_block = cfg.dedisp_block
        dedisp_engine = cfg.dedisp_engine  # "" = plan/tuner decides
        subband_matmul = cfg.subband_matmul
        smear_budgets = None
        self._tuned_dm_block = 0
        self._tuned_accel_bucket = 0
        if cfg.tune and cfg.subbands == 0 and not cfg.dedisp_engine:
            from ..perf.tuning import resolve_plan_for_filterbank

            dplan = resolve_plan_for_filterbank(
                fil, "search", cfg, cache_path=cfg.tuning_cache or None
            )
            if dplan is not None:
                if dplan.engine == "subband":
                    subbands = dplan.subbands
                    subband_smear = dplan.subband_smear
                    subband_matmul = subband_matmul or dplan.subband_matmul
                    if dplan.smear_dm_scaled and dplan.smear_loss_budget:
                        # rebuild the DM-scaled per-trial budgets the
                        # planner grouped under (deterministic in the
                        # plan geometry, so nothing big hits the cache)
                        from ..plan.dedisp_plan import dm_smear_budgets

                        smear_budgets = dm_smear_budgets(
                            dm_plan.dm_list,
                            tsamp=fil.tsamp, fch1=fil.fch1, foff=fil.foff,
                            nchans=len(dm_plan.delays),
                            pulse_width_us=cfg.dm_pulse_width,
                            max_snr_loss=dplan.smear_loss_budget,
                            floor=dplan.subband_smear,
                        )
                elif dplan.engine == "matmul":
                    dedisp_engine = "matmul"
                dedisp_block = dplan.dedisp_block or dedisp_block
                # tuned wave knobs: an explicit config value wins; the
                # dataclass default opts into the per-device winner
                if cfg.dm_block == 0 and dplan.dm_block:
                    self._tuned_dm_block = int(dplan.dm_block)
                fields = type(cfg).__dataclass_fields__
                if (
                    cfg.accel_bucket == fields["accel_bucket"].default
                    and dplan.accel_bucket
                ):
                    self._tuned_accel_bucket = int(dplan.accel_bucket)
                tel.event("dedisp_plan", **dplan.summary())
                tel.set_context(dedisp_plan=dplan.summary())
                log.info(
                    "dedispersion plan: %s (subbands=%d, dedisp_block=%d, "
                    "gain %.2fx, predicted S/N loss %.3f, %s)",
                    dplan.engine, dplan.subbands, dplan.dedisp_block,
                    dplan.gain, dplan.predicted_loss, dplan.source,
                )
        sp_dedisp = tel.begin("dedispersion")
        # --- device selection: shard DM trials over local chips --------
        # (the reference's analogue: one worker per GPU up to -t,
        # pipeline_multi.cu:276-277). Selected BEFORE dedispersion so the
        # trial set is produced already sharded over the mesh — the
        # reference likewise dedisperses across all GPUs
        # (dedisp_create_plan_multi, dedisperser.hpp:25-31)
        devices = self._pick_devices()
        mesh = None
        if len(devices) > 1:
            from ..parallel.mesh import make_mesh

            mesh = make_mesh({"dm": len(devices)}, devices=devices)
        # trials live on device (sliced there per chunk, no re-uploads)
        # unless the whole block would crowd out the search working set
        # — huge surveys spill to host RAM like the reference
        # (dedisperser.hpp:101-103) and pay a per-chunk upload instead.
        # The direct engine dedisperses SHARDED over a mesh (one 1/N
        # slice of the trials per chip) where a chip holds the input it
        # replicates beside its working set; the spill threshold then
        # scales with the chip count: each chip's share has to fit.
        trials_bytes = dm_plan.ndm * dm_plan.out_nsamps
        shard_in = None
        if mesh is not None and subbands == 0:
            from ..parallel.sharded_dedisperse import shard_input

            shard_in = shard_input(
                fil, dm_plan.delay_samples(), dm_plan.out_nsamps,
                len(devices), self.TOTAL_HBM, block=dedisp_block,
            )
        n_shard = len(devices) if shard_in else 1
        spill = trials_bytes > self.TRIALS_DEVICE_LIMIT * n_shard
        tel.event(
            "device_plan", n_devices=len(devices),
            sharded=mesh is not None, trials_spill=bool(spill),
            trials_bytes=int(trials_bytes), ndm=int(dm_plan.ndm),
        )
        tel.incr("search.spill_bytes", int(trials_bytes) if spill else 0)

        # --- checkpoint store (one construction + ONE load, shared by
        # the resume fast path below and the wave loop later) ---------
        ckpt = None
        restored: dict[int, tuple] = {}
        if cfg.checkpoint_file:
            ckpt = SearchCheckpoint(
                cfg.checkpoint_file,
                SearchCheckpoint.make_key(
                    cfg, fil, choose_fft_size(fil.nsamps, cfg.size),
                    global_ndm,
                ),
                slice_bounds=dm_slice,
            )
            restored = ckpt.load()

        # --- resume fast path: when EVERY trial of this run restores
        # from the checkpoint and nothing will be folded, the trial
        # data is never read — skip dedispersion entirely (it dominates
        # resume wall time at survey scale: tens of minutes of packed
        # upload + scan through a high-latency link for zero work)
        skip_dedisp = (
            ckpt is not None
            and cfg.npdmp == 0
            and dm_plan.ndm > 0
            and all(d in restored for d in range(dm_plan.ndm))
        )
        if skip_dedisp:
            log.info(
                "Resume fast path: all trials checkpointed and "
                "npdmp=0 — skipping dedispersion"
            )
            tel.event("resume_fast_path", ndm=int(dm_plan.ndm))
            trials = np.zeros((0, dm_plan.out_nsamps), dtype=np.uint8)
            spill = True  # host ndarray semantics; nothing device-resident
            self._trials_sharded = False

        def upload():
            with tel.stage("dedispersion.upload"):
                return fil_to_device(fil)

        scale = output_scale(fil.nbits, int(dm_plan.killmask.sum()))
        shard_dd = n_shard > 1 and not spill and not skip_dedisp
        self._trials_sharded = shard_dd
        if skip_dedisp:
            pass
        elif shard_dd:
            from ..parallel.sharded_dedisperse import dedisperse_sharded

            samples, nbits = shard_in
            trials = dedisperse_sharded(
                samples,
                dm_plan.delay_samples(),
                dm_plan.killmask,
                dm_plan.out_nsamps,
                mesh,
                nbits=nbits,
                scale=scale,
                block=dedisp_block,
            )
        elif subbands > 0:
            # the subband engine stages the filterbank on DEVICE
            # regardless of trial spill (to_host only routes the
            # OUTPUTS), so always take the packed-upload + on-device
            # unpack path: 4x less H2D for 2-bit survey data
            trials = dedisperse_subband(
                upload(),
                dm_plan.delay_samples(),
                dm_plan.killmask,
                dm_plan.out_nsamps,
                nsub=subbands,
                max_smear=subband_smear,
                scale=scale,
                to_host=spill,
                use_matmul=subband_matmul,
                budgets=smear_budgets,
            )
        elif dedisp_engine == "matmul" and not spill:
            # the MXU banded-matmul engine (tuned winner or forced
            # via --dedisp_engine): bitwise-equal to the gather
            # scan, so the spill/sharded paths degrading to gather
            # elsewhere never changes candidates
            from ..ops.dedisperse import dedisperse_matmul

            trials = dedisperse_matmul(
                upload(),
                dm_plan.delay_samples(),
                dm_plan.killmask,
                dm_plan.out_nsamps,
                scale=scale,
            )
        else:
            dd = dedisperse if spill else dedisperse_device
            trials = dd(
                fil.data if spill else upload(),
                dm_plan.delay_samples(),
                dm_plan.killmask,
                dm_plan.out_nsamps,
                scale=scale,
                block=dedisp_block,
            )
        if not spill and not skip_dedisp:
            # ASYNC dispatch: the trials stay in flight while the
            # host builds the wave plan and dispatches the first
            # search chunks, so dedispersion of the tail overlaps
            # the search of the head (XLA orders the per-trial
            # dependencies). The dedispersion timer therefore
            # records DISPATCH wall only; completion is absorbed
            # into search_device. PEASOUP_SYNC_DEDISP=1 restores
            # the old barrier (and the timer's old meaning) —
            # results are bitwise identical either way, pinned by
            # tests/test_dedisp_plan.py.
            # Sharded dedispersion always completes first: its working
            # set (the replicated input, a chunk's f32 copy, a segment's
            # partials) then leaves every chip before the search
            # programs take theirs.
            if shard_dd or os.environ.get("PEASOUP_SYNC_DEDISP"):
                jax.block_until_ready(trials)
            else:
                tel.event(
                    "dedisp_async_dispatch",
                    dispatch_s=round(sp_dedisp.seconds, 4),
                )
        timers["dedispersion"] = sp_dedisp.end()
        # trials per chip of sharded trials: search chunks take each
        # chip's rows from its own shard (_deal_chunks)
        self._shard_rows = (
            trials.shape[0] // len(devices) if self._trials_sharded else 0
        )

        # --- search setup ---------------------------------------------------
        size = choose_fft_size(fil.nsamps, cfg.size)
        trials_nsamps = dm_plan.out_nsamps
        nsamps_valid = min(trials_nsamps, size)
        tobs = float(np.float32(size) * np.float32(fil.tsamp))
        # float bin_width = 1.0/tobs (pipeline_multi.cu:119) — every
        # downstream consumer (pos5/pos25, zap masks) sees the f32 value
        bin_width = float(np.float32(1.0 / tobs))
        # NOTE: the reference passes foff as the accel plan's "bw" —
        # the width term uses the CHANNEL width (pipeline_multi.cu:335-337)
        acc_plan = AccelerationPlan(
            acc_lo=cfg.acc_start,
            acc_hi=cfg.acc_end,
            tol=cfg.acc_tol,
            pulse_width=cfg.acc_pulse_width,
            nsamps=size,
            tsamp=fil.tsamp,
            cfreq=fil.cfreq,
            bw=fil.foff,
        )
        size_spec = size // 2 + 1
        if cfg.zapfilename:
            bf, bw_ = read_zapfile(cfg.zapfilename)
            zapmask = birdie_mask(bf, bw_, bin_width, size_spec)
        else:
            zapmask = np.zeros(size_spec, dtype=bool)
        zapmask_dev = jnp.asarray(zapmask)
        windows = jnp.asarray(
            _level_windows(size, cfg.nharmonics, cfg.min_freq, cfg.max_freq, fil.tsamp)
        )
        factors = [
            _freq_factor(size, nh, fil.tsamp) for nh in range(cfg.nharmonics + 1)
        ]
        pos5 = int(cfg.boundary_5_freq / bin_width)
        pos25 = int(cfg.boundary_25_freq / bin_width)

        harm_finder = HarmonicDistiller(cfg.freq_tol, cfg.max_harm, keep_related=False)
        acc_still = AccelerationDistiller(tobs, cfg.freq_tol, keep_related=True)

        # --- batched DM-trial search ----------------------------------------
        # DM trials are grouped by padded accel-list size and processed in
        # fixed (dm_block, accel_bucket) tiles: one compile per distinct
        # tile shape, vmapped over the block (vs the reference's per-trial
        # kernel launches). The search itself is device work; candidate
        # clustering/distilling below is tiny host work per trial.
        #
        # Host<->device protocol (the chip may sit behind a high-latency
        # link, so transfers are the enemy): trials stay device-resident,
        # every chunk of a wave is DISPATCHED asynchronously, then the
        # wave's counts come back in ONE packed D2H, and the peak arrays
        # in ONE more, trimmed to the observed per-chunk maximum count.
        sp_search = tel.begin("searching")
        # accel lists, dedupe, kernel probes and routing, the search
        # program and the wave plan
        sp_setup = tel.begin("search.setup")
        accel_lists = [
            acc_plan.generate_accel_list(float(dm)) for dm in dm_plan.dm_list
        ]
        # trial totals published BEFORE the wave loop so the live
        # status.json heartbeat can report progress against them
        tel.gauge("search.n_dm_trials", int(dm_plan.ndm))
        tel.gauge("search.n_accel_trials", sum(len(a) for a in accel_lists))
        tel.gauge("search.fft_size", int(size))
        # identity-trial dedupe: device programs run only the DISTINCT
        # resamplings; results replicate host-side, bitwise-identical
        # to brute force (see _dedupe_identity_accels)
        if cfg.dedupe_accel:
            dispatch_lists, self._accel_expand = _dedupe_identity_accels(
                accel_lists, fil.tsamp, size
            )
        else:
            dispatch_lists = accel_lists
            self._accel_expand = [None] * len(accel_lists)
        # the tuned accel bucket (explicit config values win; see the
        # plan-resolution block above)
        accel_bucket = self._tuned_accel_bucket or cfg.accel_bucket
        self._accel_full_pad = [
            _accel_pad(len(a), accel_bucket) for a in accel_lists
        ]
        if any(m is not None for m in self._accel_expand):
            n_full = sum(len(a) for a in accel_lists)
            n_disp = sum(len(a) for a in dispatch_lists)
            log.info(
                "accel dedupe: %d/%d distinct resamplings dispatched "
                "(trials with coinciding rounded shift maps share their "
                "representative's spectrum bitwise)", n_disp, n_full,
            )
            tel.event("accel_dedupe", dispatched=n_disp, full=n_full)
        bucket = accel_bucket
        by_bucket: dict[int, list[int]] = {}
        for dm_idx, accs in enumerate(dispatch_lists):
            padded = _accel_pad(len(accs), bucket)
            by_bucket.setdefault(padded, []).append(dm_idx)

        af_max = max(
            (float(np.abs(accel_factor(a, fil.tsamp)).max())
             for a in dispatch_lists if len(a)),
            default=0.0,
        )
        # gather-free select resample whenever the shift span is small:
        # at small spans the few-way select fuses into the surrounding
        # program and beats even the Pallas kernel (which still streams
        # a separate pass over HBM)
        select_smax = select_span(af_max, size)
        from ..ops.pallas.resample import choose_block

        resample_fits = not 0 < select_smax <= 8 and choose_block(
            af_max, size
        ) > 0
        pallas_block = 0
        if resample_fits:
            from ..ops.pallas import probe_pallas_resample

            # real compile+run probe, oracle-checked (off TPU: the twin)
            pallas_block = choose_block(af_max, size)
            if not probe_pallas_resample(size, pallas_block):
                pallas_block = 0
        # the select runs where the Pallas resample does not
        block_smax = 0 if pallas_block else select_smax
        # the search program follows the platform and the series length
        # (accel_search.search_route); on TPU every kernel it names is
        # compiled, run and checked against its twin first (a failure
        # raises KernelUnavailable)
        route = search_route(
            jax.default_backend(), size, packed=block_smax > 0
        )
        if route.kernels:
            from ..ops.pallas import (
                probe_pallas_dftspec,
                probe_pallas_harmpeaks,
                probe_pallas_interbin,
                probe_pallas_specchain,
            )

            probe_pallas_specchain()
            probe_pallas_harmpeaks(
                size_spec, cfg.nharmonics,
                max(cfg.max_peaks, self._learned_max_peaks),
            )
            if route.spectrum == "interbin":
                probe_pallas_interbin(size, PEAKS_BLOCK)
            elif route.spectrum == "dftspec":
                probe_pallas_dftspec(size, spectrum_pad(size_spec))
        self._route = route
        # which kernels the shapes admit, beside the ones the search
        # programs were built with (the route event after the waves)
        self._route_fits = {
            "interbin_fits": interbin_fits(size),
            "dftspec_fits": bool(
                dftspec_supported(size, spectrum_pad(size_spec))
            ),
            "select_smax": int(select_smax),
            "resample_fits": bool(resample_fits),
        }

        # --- search-side mesh wiring (mesh chosen before dedispersion) --
        if mesh is not None:
            from ..parallel.sharded_search import make_sharded_search_fn

            from jax.sharding import NamedSharding, PartitionSpec

            search_block = make_sharded_search_fn(
                mesh, cfg.min_snr, axis="dm", pallas_block=pallas_block,
                select_smax=block_smax, route=route,
            )
            # stage blocks directly onto the mesh (no hop through chip 0)
            self._dm_sharding = NamedSharding(mesh, PartitionSpec("dm"))
            self._mesh = mesh
        else:
            search_block = make_batched_search_fn(
                cfg.min_snr, pallas_block, block_smax, route
            )
            self._dm_sharding = None
            self._mesh = None
        self._cur_pallas_block = pallas_block
        self._active_search_block = search_block
        tim_len = min(size, trials.shape[1])

        # the GLOBAL-dm_idx-keyed store was built (and loaded ONCE)
        # before dedispersion; multi-host slices write per-slice sibling
        # files (no write contention) and load() unions every sibling,
        # so a checkpoint written under one process count resumes under
        # ANY other with zero re-searched trials
        # (tests/test_pipeline.py::test_checkpoint_process_count_independent)
        per_dm_results: dict[int, tuple] = restored
        if per_dm_results:
            log.info(
                "Resuming: %d/%d DM trials restored from %s",
                len(per_dm_results), dm_plan.ndm, cfg.checkpoint_file,
            )
            tel.event(
                "checkpoint_resume", restored=len(per_dm_results),
                ndm=int(dm_plan.ndm),
            )

        # chunk sizing: a PER-CHIP block of d_local trials, auto-sized
        # from a working-set budget of ~16 spectrum-sized f32 arrays per
        # (dm, accel) cell. The device call covers d_local * n_dev
        # trials; keeping the per-chip shape independent of the device
        # count makes sharded and single-device results bitwise
        # identical (same XLA program per chip), mirroring the
        # reference's share-nothing per-GPU workers.
        size_spec_b = (size // 2 + 1) * 4
        # spectra budget: what's left of PER-CHIP HBM after that chip's
        # share of the device-resident trials (1/N when sharded) and the
        # queued wave outputs
        trials_res = 0 if spill else trials_bytes // (
            len(devices) if self._trials_sharded else 1
        )
        mem_budget = min(
            self.MEM_BUDGET,
            self.TOTAL_HBM - trials_res - self.WAVE_BUDGET,
        )
        mem_budget = max(mem_budget, 500_000_000)

        def build_chunks(shrink: int) -> list[tuple[list[int], int]]:
            """(dm indices, dm_block) chunks; ``shrink`` halves the
            auto block size on device-OOM retries."""
            out: list[tuple[list[int], int]] = []
            for padded, dm_indices in sorted(by_bucket.items()):
                if cfg.dm_block > 0:
                    d_local = max(1, cfg.dm_block // shrink)
                elif self._tuned_dm_block:
                    # per-device tuned wave height, still capped by the
                    # memory-budget formula (tuning ranks throughput;
                    # the budget owns safety — OOM shrink still applies)
                    cells = max(8, int(mem_budget / (size_spec_b * 16)))
                    cap = max(1, min(128, cells // max(1, padded)))
                    d_local = max(
                        1, min(self._tuned_dm_block, cap) // shrink
                    )
                else:
                    cells = max(8, int(mem_budget / (size_spec_b * 16)))
                    d_local = max(
                        1, min(128, cells // max(1, padded)) // shrink
                    )
                    # fewer, fuller dispatches beat conservative ones
                    # (each wave pays fixed transfer round trips), so
                    # on the first attempt try the whole bucket as ONE
                    # chunk whenever an optimistic estimate fits — the
                    # OOM shrink-retry is the safety net for the
                    # workloads where the estimate is wrong. The
                    # per-chip shape is the GLOBAL bucket size (not
                    # divided by device count), preserving the bitwise
                    # sharded == single-device invariant above
                    one_shot = len(dm_indices)
                    est = one_shot * padded * size_spec_b * 12
                    if (
                        shrink == 1
                        and one_shot <= 128
                        and est < 0.9 * self.TOTAL_HBM - trials_res
                    ):
                        d_local = max(d_local, one_shot)
                    # equalise: 59 trials at d_local=56 would pad a
                    # 3-trial tail chunk to 56 rows of device work;
                    # split evenly instead (30+29 -> 30+30). Derived
                    # from the GLOBAL trial count only, so the per-chip
                    # block shape — and therefore the XLA program and
                    # its bitwise results — stays independent of the
                    # device count
                    n_parts = -(-len(dm_indices) // d_local)
                    d_local = -(-len(dm_indices) // n_parts)
                out.extend(_deal_chunks(
                    dm_indices, d_local, len(devices), self._shard_rows
                ))
            return out

        # wave sizing: bound the live device output buffers (and give the
        # checkpoint a save point per wave)
        def chunk_out_bytes(chunk):
            dm_indices, d_blk = chunk
            padded = _accel_pad(len(dispatch_lists[dm_indices[0]]), bucket)
            # budget with the learned compaction size: later waves (and
            # repeat runs) dispatch at mp0, not cfg.max_peaks
            mp = max(cfg.max_peaks, self._learned_max_peaks)
            return d_blk * (cfg.nharmonics + 1) * padded * mp * 8

        def build_waves(chunks):
            waves: list[list[tuple[list[int], int]]] = []
            wave: list[tuple[list[int], int]] = []
            wave_bytes = 0
            for chunk in chunks:
                if wave and (
                    wave_bytes + chunk_out_bytes(chunk) > self.WAVE_BUDGET
                ):
                    waves.append(wave)
                    wave, wave_bytes = [], 0
                wave.append(chunk)
                wave_bytes += chunk_out_bytes(chunk)
            if wave:
                waves.append(wave)
            return waves

        sp_setup.end()
        progress = ProgressBar() if cfg.progress_bar else None
        if progress:
            progress.start()
        from ..resilience import DegradationLadder, faults

        # the memory degradation ladder: halving dm_block is one rung,
        # stepped repeatedly; at the floor the run falls THROUGH —
        # first to an exact (max_smear=0, bitwise-equal) subband
        # dedispersion with host-spilled trials, freeing the
        # device-resident trial block, then (off TPU only: a TPU run
        # never leaves its chip) to the CPU backend. Exhaustion
        # propagates to the campaign attempt budget.
        ladder = DegradationLadder(
            "search.memory", ("dm_block_shrink", "subband", "cpu_backend")
        )
        shrink = 1
        cpu_mode = False
        fell_subband = False
        while True:
            with tel.stage("search.setup"):
                chunks = build_chunks(shrink)
                waves = build_waves(chunks)
                tel.event(
                    "wave_plan", n_waves=len(waves), n_chunks=len(chunks),
                    shrink=shrink,
                    max_dm_block=max((d for _, d in chunks), default=0),
                    backend="cpu" if cpu_mode else "default",
                )
            try:
                faults.fire(
                    "device.oom",
                    context=(
                        "search:cpu" if cpu_mode
                        else f"search:shrink{shrink}"
                    ),
                )
                if cpu_mode:
                    with jax.default_device(jax.devices("cpu")[0]):
                        self._run_waves(
                            waves, len(chunks), per_dm_results, ckpt,
                            progress, dispatch_lists,
                            trials, tim_len, zapmask_dev, windows,
                            size=size, nsamps_valid=nsamps_valid,
                            pos5=pos5, pos25=pos25, tsamp=fil.tsamp,
                        )
                else:
                    self._run_waves(
                        waves, len(chunks), per_dm_results, ckpt,
                        progress, dispatch_lists,
                        trials, tim_len, zapmask_dev, windows,
                        size=size, nsamps_valid=nsamps_valid, pos5=pos5,
                        pos25=pos25, tsamp=fil.tsamp,
                    )
                break
            except Exception as exc:
                # device OOM: the per-cell working-set heuristic is an
                # estimate; halve the block and retry (finished trials
                # are in per_dm_results and are not re-searched)
                max_blk = max(d for _, d in chunks)
                if not _is_oom(exc):
                    raise
                if max_blk > (1 if cpu_mode else len(devices)):
                    shrink *= 2
                    new_blk = max(d for _, d in build_chunks(shrink))
                    log.warning(
                        "device OOM at dm_block=%d; retrying with "
                        "half-size blocks (dm_block=%d): %.200s",
                        max_blk, new_blk, exc,
                    )
                    tel.event(
                        "oom_shrink_retry", dm_block_old=max_blk,
                        dm_block_new=new_blk, shrink=shrink,
                        error=f"{exc!s:.200}",
                    )
                    # in-rung shrinks after a fall-through rung keep
                    # the event trail but not a ladder step (a ladder
                    # never climbs back up)
                    if ladder.current_rung in (None, "dm_block_shrink"):
                        ladder.step(
                            "dm_block_shrink", dm_block_old=max_blk,
                            dm_block_new=new_blk, error=f"{exc!s:.200}",
                        )
                    continue
                if (
                    not cpu_mode
                    and not fell_subband
                    and subbands == 0
                    and not skip_dedisp
                    and fil.nchans > 1
                ):
                    # subband rung: re-dedisperse two-stage at
                    # max_smear=0 (BITWISE the direct sum — every group
                    # shares identical delays) with the trial block
                    # spilled to host RAM, so HBM holds one chunk at a
                    # time instead of the whole (ndm, out_nsamps) block.
                    # Block sizing restarts: the rung changed the
                    # memory regime, and re-running at the original
                    # dm_block keeps the successful attempt's chunk
                    # shapes — and therefore its bits — identical to an
                    # untroubled run's.
                    fell_subband = True
                    shrink = 1
                    nsub = max(2, int(round(math.sqrt(fil.nchans))))
                    log.warning(
                        "device OOM with dm_block at the floor (%d); "
                        "falling through to exact subband dedispersion "
                        "(nsub=%d, host-spilled trials): %.200s",
                        max_blk, nsub, exc,
                    )
                    trials = dedisperse_subband(
                        fil_to_device(fil),
                        dm_plan.delay_samples(),
                        dm_plan.killmask,
                        dm_plan.out_nsamps,
                        nsub=nsub,
                        max_smear=0.0,
                        scale=scale,
                        to_host=True,
                    )
                    spill = True
                    self._trials_sharded = False
                    self._shard_rows = 0
                    tel.incr("search.spill_bytes", int(trials.nbytes))
                    tel.event(
                        "oom_subband_fallback", nsub=nsub,
                        dm_block=max_blk, error=f"{exc!s:.200}",
                    )
                    ladder.step(
                        "subband", nsub=nsub, error=f"{exc!s:.200}"
                    )
                    continue
                if not cpu_mode and not backend_supports_pallas():
                    # CPU rung: host-resident trials, single-device jnp
                    # programs (the Pallas kernels and the mesh are
                    # device-side optimisations, both bitwise-gated);
                    # block sizing restarts like the subband rung's
                    cpu_mode = True
                    shrink = 1
                    trials = np.asarray(trials)
                    spill = True
                    self._trials_sharded = False
                    self._shard_rows = 0
                    self._dm_sharding = None
                    self._mesh = None
                    self._cur_pallas_block = 0
                    self._route = CPU_ROUTE
                    zapmask_dev = np.asarray(zapmask_dev)
                    windows = np.asarray(windows)
                    self._active_search_block = make_batched_search_fn(
                        cfg.min_snr, 0, select_smax, CPU_ROUTE
                    )
                    log.warning(
                        "device OOM after the subband fall-through; "
                        "retrying the search on the CPU backend: %.200s",
                        exc,
                    )
                    tel.event(
                        "oom_cpu_fallback", dm_block=max_blk,
                        error=f"{exc!s:.200}",
                    )
                    ladder.step(
                        "cpu_backend", dm_block=max_blk,
                        error=f"{exc!s:.200}",
                    )
                    continue
                ladder.exhausted(dm_block=max_blk, error=f"{exc!s:.200}")
                raise
        if progress:
            progress.stop()
        timers["search_device"] = sp_search.seconds
        tel.event(
            "search_route", backend=jax.default_backend(),
            pallas_peaks=self._route.kernels,
            mega_harm=self._route.kernels,
            fused_interbin=self._route.spectrum in ("interbin", "dftspec"),
            fused_dft=self._route.spectrum == "dftspec",
            fused_spec=self._route.kernels,
            resample_block=int(self._cur_pallas_block),
            **self._route_fits,
        )

        # --- host candidate bookkeeping (ascending DM order) ----------------
        # idxs/snrs arrive ALREADY clustered (identify_unique_peaks ran
        # on device); the host only builds candidates and distils. The
        # per-accel-trial harmonic distill runs as ONE segmented native
        # call over every (dm, accel) trial of the run — Candidate
        # objects exist only for its survivors (the reference builds one
        # struct per raw detection, pipeline_multi.cu:233-238).
        sp_host = tel.begin("search_host")
        from .. import native

        dm_trial_cands = CandidateCollection()
        if native.available():
            self._distill_trials_segmented(
                dm_plan, accel_lists, per_dm_results, factors, harm_finder,
                acc_still, dm_trial_cands,
            )
        else:
            for dm_idx, dm in enumerate(dm_plan.dm_list):
                idxs, snrs, ccounts = _densify_ragged(
                    *per_dm_results.pop(dm_idx)
                )
                accs = accel_lists[dm_idx]
                accel_trial_cands = CandidateCollection()
                for a_idx in range(len(accs)):
                    acc = float(accs[a_idx])
                    trial_cands: list[Candidate] = []
                    for lvl in range(cfg.nharmonics + 1):
                        n_found = int(ccounts[lvl, a_idx])
                        for b, s in zip(
                            idxs[lvl, a_idx, :n_found],
                            snrs[lvl, a_idx, :n_found],
                        ):
                            trial_cands.append(
                                Candidate(
                                    dm=float(dm),
                                    dm_idx=dm_idx,
                                    acc=acc,
                                    nh=lvl,
                                    snr=float(s),
                                    freq=float(
                                        np.float32(np.float32(b) * factors[lvl])
                                    ),
                                )
                            )
                    accel_trial_cands.append(harm_finder.distill(trial_cands))
                dm_trial_cands.append(acc_still.distill(accel_trial_cands.cands))
                log.debug(
                    "DM %.3f (%d/%d): %d accel trials, %d cands so far",
                    dm, dm_idx + 1, dm_plan.ndm, len(accs),
                    len(dm_trial_cands),
                )
        timers["search_host"] = sp_host.end()
        timers["searching"] = sp_search.end()
        tel.gauge("candidates.per_dm_distill", len(dm_trial_cands))

        if dm_lo:
            _offset_dm_idx(dm_trial_cands.cands, dm_lo)
        part = PartialSearchResult(
            cands=dm_trial_cands.cands,
            # the folder owns trials_ndm rows; sharded trials keep
            # dedisperse_sharded's row padding, where a slice would
            # gather every row onto every chip
            trials=trials if self._shard_rows else trials[: dm_plan.ndm],
            trials_ndm=dm_plan.ndm,
            trials_nsamps=trials_nsamps,
            dm_offset=dm_lo,
            dm_list=dm_plan.dm_list,
            acc_list_dm0=acc_plan.generate_accel_list(0.0),
            timers=timers,
            nsamps=fil.nsamps,
            size=size,
            n_accel_trials=sum(len(a) for a in accel_lists),
            t_total_start=t_total,
        )
        if not finalize:
            return part
        return self.finalize(fil, part)

    def finalize(
        self,
        fil: Filterbank,
        part: "PartialSearchResult",
        fold_exchange=None,
    ) -> SearchResult:
        """Global distilling / scoring / folding over (possibly merged)
        per-DM-trial candidates. ``fold_exchange`` is the multi-host
        hook: callable(local fold outcomes) -> all processes' outcomes
        (parallel/multihost.py wires an allgather; None = single
        process)."""
        cfg = self.config
        tel = current_telemetry()
        timers = part.timers
        sp = tel.begin("distilling")
        dm_still = DMDistiller(cfg.freq_tol, keep_related=True)
        harm_still = HarmonicDistiller(
            cfg.freq_tol, cfg.max_harm, keep_related=True, fractional_harms=False
        )
        tel.gauge("candidates.per_dm_total", len(part.cands))
        cands = dm_still.distill(part.cands)
        tel.gauge("candidates.post_dm_distill", len(cands))
        cands = harm_still.distill(cands)
        tel.gauge("candidates.post_harmonic_distill", len(cands))
        timers["distilling"] = sp.end()

        sp = tel.begin("scoring")
        scorer = CandidateScorer(
            fil.tsamp, fil.cfreq, fil.foff, abs(fil.foff) * fil.nchans
        )
        scorer.score_all(cands)
        timers["scoring"] = sp.end()

        timers["folding"] = 0.0
        if cfg.npdmp > 0:
            sp = tel.begin("folding")
            folder = MultiFolder(
                part.trials, part.trials_nsamps, fil.tsamp,
                pos5_freq=cfg.boundary_5_freq, pos25_freq=cfg.boundary_25_freq,
                dm_offset=part.dm_offset, ndm=part.trials_ndm,
            )
            outcomes = folder.fold_outcomes(cands, cfg.npdmp)
            if fold_exchange is not None:
                outcomes = fold_exchange(outcomes)
            cands = folder.apply_outcomes(cands, outcomes)
            tel.gauge("candidates.folded", min(cfg.npdmp, len(cands)))
            timers["folding"] = sp.end()

        cands = cands[: cfg.limit]
        tel.gauge("candidates.final", len(cands))
        timers["total"] = time.perf_counter() - part.t_total_start
        return SearchResult(
            candidates=cands,
            dm_list=part.dm_list,
            acc_list_dm0=part.acc_list_dm0,
            timers=timers,
            nsamps=part.nsamps,
            size=part.size,
            n_accel_trials=part.n_accel_trials,
        )

    def _run_waves(
        self, waves, n_chunks, per_dm_results, ckpt, progress,
        dispatch_lists, trials, tim_len, zapmask_dev, windows,
        *, size, nsamps_valid, pos5, pos25, tsamp,
    ) -> None:
        disp = dict(
            size=size, nsamps_valid=nsamps_valid, pos5=pos5, pos25=pos25,
            tsamp=tsamp,
        )
        tel = current_telemetry()
        tel.set_progress(0, n_chunks, unit="chunks")
        n_done = 0
        for wi, wave in enumerate(waves):
            todo = [
                c for c in wave
                if not all(d in per_dm_results for d in c[0])
            ]
            if todo:
                tel.incr("search.waves")
                with tel.stage("search.wave"):
                    # fleet-trace span (obs/trace.py, no-op outside a
                    # campaign job): each search wave is one unit of
                    # the job's connected timeline
                    with job_span("wave", wave=wi, chunks=len(todo)):
                        self._search_wave(
                            todo, dispatch_lists, trials, tim_len,
                            zapmask_dev, windows,
                            self._active_search_block, per_dm_results,
                            **disp,
                        )
                    if ckpt is not None:
                        with job_span("checkpoint", wave=wi), tel.stage(
                            "checkpoint"
                        ):
                            ckpt.save(per_dm_results)
                # revoke seam: a preempt/retire observed by the lease
                # renewer stops here, right after the checkpoint save,
                # so the resumed run restores exactly this state and
                # the final candidates stay bitwise-equal to an
                # uninterrupted sweep
                from ..resilience import check_revoke

                check_revoke("search.wave")
            n_done += len(wave)
            # live progress: the heartbeat derives rate/ETA from this
            # counter, and the stall watchdog treats its advance (or a
            # new event) as liveness
            tel.set_progress(n_done, n_chunks, unit="chunks")
            tel.incr(
                "search.dm_trials_done",
                sum(len(c[0]) for c in wave),
            )
            if progress:
                progress.update(n_done / n_chunks)

    def _distill_trials_segmented(
        self, dm_plan, accel_lists, per_dm_results, factors, harm_finder,
        acc_still, dm_trial_cands,
    ) -> None:
        """Vectorised candidate bookkeeping: build (freq, snr, nh) row
        arrays for every detection with numpy, harmonic-distill every
        accel trial in one segmented native call, then materialise
        Candidate objects for the survivors only. Ordering matches the
        object path exactly: rows are stably sorted S/N-descending
        within each (dm, accel) segment (the !IMPORTANT sort,
        distiller.hpp:31), so downstream stable sorts see the same tie
        order."""
        cfg = self.config
        from .. import native

        nlev = cfg.nharmonics + 1
        factors_arr = np.asarray(factors, dtype=np.float32)  # (nlev,)

        # Vectorised across DMs: per-DM numpy loops cost ~1 ms x ndm of
        # pure call overhead at survey scale. DMs are grouped by their
        # chunk's (nlev, padded) count shape (uniform stacks), each
        # group's rows built with one ragged-index pass, and the groups
        # reassembled into global dm-ascending order by a stable sort —
        # row order (dm asc, a asc, lvl asc, stream order) is IDENTICAL
        # to the per-DM loop this replaces.
        from collections import defaultdict

        by_shape: dict = defaultdict(list)
        for dm_idx in range(dm_plan.ndm):
            vi, vs, cc = per_dm_results.pop(dm_idx)
            by_shape[cc.shape].append(
                (dm_idx, vi, vs, cc, len(accel_lists[dm_idx]))
            )

        g_freq, g_snr, g_lvl, g_a, g_dmrow = [], [], [], [], []
        g_segc, g_dmseg = [], []
        for (nlev_, padded), entries in by_shape.items():
            g = len(entries)
            dm_ids = np.asarray([e[0] for e in entries])
            A_arr = np.asarray([e[4] for e in entries], dtype=np.int64)
            cc3 = np.stack([e[3] for e in entries]).reshape(g, -1)
            flat_cc = cc3.astype(np.int64)
            ends = np.cumsum(flat_cc, axis=1)
            starts = ends - flat_cc
            lens = np.asarray([len(e[1]) for e in entries], dtype=np.int64)
            base = np.concatenate([[0], np.cumsum(lens)[:-1]])
            viG = np.concatenate([e[1] for e in entries])
            vsG = np.concatenate([e[2] for e in entries])

            total_A = int(A_arr.sum())
            # ragged 0..A_d-1 per dm, then cell = (dm, a, lvl) C-order
            acat = np.arange(total_A, dtype=np.int64) - np.repeat(
                np.cumsum(A_arr) - A_arr, A_arr
            )
            a_cell = np.repeat(acat, nlev_)
            lvl_cell = np.tile(np.arange(nlev_, dtype=np.int64), total_A)
            dml_cell = np.repeat(np.repeat(np.arange(g), A_arr), nlev_)
            cellidx = lvl_cell * padded + a_cell
            csel = flat_cc[dml_cell, cellidx]
            n = int(csel.sum())
            seg_e = np.cumsum(csel)
            src = np.repeat(
                starts[dml_cell, cellidx] + base[dml_cell], csel
            ) + (np.arange(n, dtype=np.int64) - np.repeat(seg_e - csel, csel))
            lvl_rows = np.repeat(lvl_cell, csel)
            # f32(f32(idx) * f32 factor): the reference's int*float
            # multiply (peakfinder.hpp:90), widened to f64 only after
            g_freq.append(
                (viG[src].astype(np.float32) * factors_arr[lvl_rows])
                .astype(np.float32)
                .astype(np.float64)
            )
            g_snr.append(vsG[src].astype(np.float64))
            g_lvl.append(lvl_rows.astype(np.int32))
            g_a.append(np.repeat(a_cell, csel).astype(np.int32))
            g_dmrow.append(np.repeat(dm_ids[dml_cell], csel))
            g_segc.append(csel.reshape(total_A, nlev_).sum(axis=1))
            g_dmseg.append(np.repeat(dm_ids, A_arr))

        dm_of_row = np.concatenate(g_dmrow) if g_dmrow else np.zeros(0, int)
        perm = np.argsort(dm_of_row, kind="stable")
        freqs_all = np.concatenate(g_freq)[perm]
        snr_all = np.concatenate(g_snr)[perm]
        lvl_all = np.concatenate(g_lvl)[perm]
        a_all = np.concatenate(g_a)[perm]
        dm_of_seg_cat = np.concatenate(g_dmseg) if g_dmseg else np.zeros(0, int)
        segperm = np.argsort(dm_of_seg_cat, kind="stable")
        seg_counts = np.concatenate(g_segc)[segperm].astype(np.int64)
        dm_of_seg = dm_of_seg_cat[segperm]
        seg_id = np.repeat(np.arange(seg_counts.size), seg_counts)

        # within-segment S/N-descending order.  The reference's sort is
        # std::sort (UNSTABLE introsort, distiller.hpp:31) whose
        # arrangement of exact S/N ties decides distill winners — replay
        # it via the native runtime; stable lexsort is the fallback.
        seg_off0 = np.concatenate(
            [np.zeros(1, np.int64), np.cumsum(seg_counts)]
        )
        # per-row acceleration lookup, built ONCE here and reused by
        # both the tie capture below and the post-distill s_acc lookup
        max_a = max((len(a) for a in accel_lists[: dm_plan.ndm]), default=1)
        acc_tab = np.zeros((dm_plan.ndm, max(max_a, 1)))
        for di, accs in enumerate(accel_lists[: dm_plan.ndm]):
            acc_tab[di, : len(accs)] = accs
        if os.environ.get("PEASOUP_TIE_CAPTURE"):
            # tie-stability capture (tools/tie_mc.py): the raw pre-sort
            # rows + segment structure — everything needed to replay
            # the full distill chain offline under S/N perturbations
            # (PARITY.md acc-tie analysis). Written, not kept: the
            # analysis runs in its own process.
            np.savez(
                os.environ["PEASOUP_TIE_CAPTURE"],
                freqs=freqs_all, snr=snr_all, lvl=lvl_all, a=a_all,
                seg_counts=seg_counts, dm_of_seg=dm_of_seg,
                acc_tab=acc_tab, dm_list=dm_plan.dm_list,
                harm_tol=harm_finder.tolerance,
                harm_max=harm_finder.max_harm,
                harm_frac=harm_finder.fractional_harms,
                acc_tobs_over_c=acc_still.tobs_over_c,
                acc_tol=acc_still.tolerance,
                freq_tol=cfg.freq_tol, max_harm=cfg.max_harm,
            )
        order = native.snr_sort_perm_seg(
            snr_all.astype(np.float32), seg_off0
        )
        if order is None:
            order = np.lexsort((-snr_all, seg_id))
        seg_off = seg_off0
        unique = native.harmonic_distill_seg(
            freqs_all[order], lvl_all[order], seg_off,
            harm_finder.tolerance, harm_finder.max_harm,
            harm_finder.fractional_harms,
        )

        surv = order[unique]  # original-row ids, in (segment, snr desc) order
        s_dm = dm_of_seg[seg_id[surv]]
        s_a = a_all[surv]
        s_lvl = lvl_all[surv]
        s_snr = snr_all[surv]
        s_freq = freqs_all[surv]

        # per-row acceleration values via the padded (ndm, maxA) lookup
        # built above (shared with the tie capture)
        s_acc = acc_tab[s_dm, s_a]

        # the acceleration distill runs as ONE segmented native call
        # over every DM trial (segment = DM, rows in the reference's
        # std::sort S/N-descending arrangement — the !IMPORTANT sort
        # applied to the per-DM concatenation of per-accel survivors),
        # with winner->loser edges building the assoc tree the scorer
        # reads.  s_dm is non-decreasing (segments were built dm-asc,
        # a-asc), so the per-DM slices of surv are exactly the
        # reference's accel_trial_cands input order.
        seg_bounds = np.searchsorted(s_dm, np.arange(dm_plan.ndm + 1))
        order2 = native.snr_sort_perm_seg(
            s_snr.astype(np.float32), seg_bounds.astype(np.int64)
        )
        if order2 is None:
            order2 = np.lexsort((-s_snr, s_dm))
        d_dm, d_a, d_lvl = s_dm[order2], s_a[order2], s_lvl[order2]
        d_snr, d_freq, d_acc = s_snr[order2], s_freq[order2], s_acc[order2]
        seg_off2 = np.searchsorted(d_dm, np.arange(dm_plan.ndm + 1))
        seg_res = native.accel_distill_seg(
            d_freq, d_acc, seg_off2, acc_still.tobs_over_c,
            acc_still.tolerance,
        )
        if seg_res is not None:
            unique2, esrc, edst = seg_res
            dm_vals = dm_plan.dm_list
            row_cands = [
                Candidate(
                    dm=float(dm_vals[d_dm[r]]),
                    dm_idx=int(d_dm[r]),
                    acc=float(d_acc[r]),
                    nh=int(d_lvl[r]),
                    snr=float(d_snr[r]),
                    freq=float(d_freq[r]),
                )
                for r in range(len(order2))
            ]
            for s_, t_ in zip(esrc, edst):
                row_cands[s_].append(row_cands[t_])
            for dm_idx in range(dm_plan.ndm):
                lo, hi = seg_off2[dm_idx], seg_off2[dm_idx + 1]
                dm_trial_cands.append(
                    [row_cands[r] for r in range(lo, hi) if unique2[r]]
                )
                log.debug(
                    "DM %.3f (%d/%d): %d accel trials, %d cands so far",
                    float(dm_vals[dm_idx]), dm_idx + 1, dm_plan.ndm,
                    len(accel_lists[dm_idx]), len(dm_trial_cands),
                )
            return

        bounds = np.searchsorted(s_dm, np.arange(dm_plan.ndm + 1))
        for dm_idx in range(dm_plan.ndm):
            dm = float(dm_plan.dm_list[dm_idx])
            accs = accel_lists[dm_idx]
            lo, hi = bounds[dm_idx], bounds[dm_idx + 1]
            accel_trial_cands = [
                Candidate(
                    dm=dm,
                    dm_idx=dm_idx,
                    acc=float(accs[s_a[r]]),
                    nh=int(s_lvl[r]),
                    snr=float(s_snr[r]),
                    freq=float(s_freq[r]),
                )
                for r in range(lo, hi)
            ]
            dm_trial_cands.append(acc_still.distill(accel_trial_cands))
            log.debug(
                "DM %.3f (%d/%d): %d accel trials, %d cands so far",
                dm, dm_idx + 1, dm_plan.ndm, len(accs),
                len(dm_trial_cands),
            )

    def _mesh_size(self) -> int:
        mesh = getattr(self, "_mesh", None)
        return mesh.shape["dm"] if mesh is not None else 1

    def _dispatch_chunk(
        self, chunk, dispatch_lists, trials, tim_len, zapmask_dev, windows,
        search_block, max_peaks, *, size, nsamps_valid, pos5, pos25, tsamp,
    ):
        """Asynchronously launch one (dm_block, accel_bucket) device
        tile; returns (device peaks, padded accel count)."""
        cfg = self.config
        bucket = cfg.accel_bucket
        dm_indices, dm_block = chunk
        real = len(dm_indices)
        padded = max(
            _accel_pad(len(dispatch_lists[d]), bucket) for d in dm_indices
        )
        # the block's fixed shape: one compile per (dm_block, padded)
        # tile shape; padding rows repeat a trial and are discarded
        block_idx, at = _block_rows(
            dm_indices, dm_block, self._mesh_size(), self._shard_rows
        )
        afs = np.zeros((dm_block, padded), dtype=np.float32)
        accs_of = {p: dispatch_lists[d] for p, d in zip(at, dm_indices)}
        for row in range(dm_block):
            accs = accs_of.get(row, dispatch_lists[dm_indices[0]])
            afs[row, : len(accs)] = accel_factor(accs, tsamp).astype(
                np.float32
            )

        idx = np.asarray(block_idx, dtype=np.int32)
        if isinstance(trials, np.ndarray):
            # spilled trials: slice on host, upload the chunk (sharded
            # straight onto the mesh when one is active)
            rows = trials[idx, :tim_len]
            tims_dev = (
                jax.device_put(rows, self._dm_sharding)
                if self._dm_sharding is not None
                else jnp.asarray(rows)
            )
        elif self._shard_rows:
            # trials live SHARDED on the mesh (dedisperse_sharded), and
            # each chip's part of the block holds only rows of its own
            # shard (_block_rows): every chip reads them in place
            from ..parallel.sharded_dedisperse import make_local_gather

            gather = make_local_gather(self._mesh, "dm", tim_len)
            tims_dev = gather(
                trials,
                jax.device_put(idx % self._shard_rows, self._dm_sharding),
            )
        else:
            # single-device trials: trial rows are sliced ON DEVICE,
            # then (with a mesh active but unsharded trials, e.g. the
            # subband path) staged onto the mesh. Chunks are almost
            # always CONSECUTIVE dm rows (build_chunks deals contiguous
            # ranges; only the block-padding tail repeats row 0), so a
            # plain slice+broadcast replaces the row gather
            lo, hi = int(idx[0]), int(idx[real - 1]) + 1
            if np.array_equal(idx[:real], np.arange(lo, hi)):
                body = jax.lax.slice(trials, (lo, 0), (hi, tim_len))
                if real < len(idx):
                    pad = jnp.broadcast_to(
                        body[:1], (len(idx) - real, tim_len)
                    )
                    rows = jnp.concatenate([body, pad], axis=0)
                else:
                    rows = body
            else:
                rows = jnp.take(trials, jnp.asarray(idx), axis=0)[
                    :, :tim_len
                ]
            tims_dev = (
                jax.device_put(rows, self._dm_sharding)
                if self._dm_sharding is not None
                else rows
            )
        afs_dev = (
            jax.device_put(afs, self._dm_sharding)
            if self._dm_sharding is not None
            else jnp.asarray(afs)
        )
        current_telemetry().incr("search.chunks")
        peaks = search_block(
            tims_dev,
            afs_dev,
            zapmask_dev,
            windows,
            size=size,
            nsamps_valid=nsamps_valid,
            nharms=cfg.nharmonics,
            max_peaks=max_peaks,
            pos5=pos5,
            pos25=pos25,
        )
        return peaks, padded

    def _search_wave(
        self, wave, dispatch_lists, trials, tim_len, zapmask_dev, windows,
        search_block, per_dm_results, *, size, nsamps_valid, pos5, pos25,
        tsamp,
    ) -> None:
        """Dispatch every chunk of the wave, then fetch results with ONE
        packed D2H transfer: counts, cluster counts, AND the ragged peak
        stream compacted at a learned speculative size ride together.
        The link's per-transfer latency dwarfs the payload, so a second
        round trip only happens when the speculation was too small (the
        first-ever wave) or a chunk's compaction overflowed."""
        from ..ops.peaks import compact_peaks_device, pack_chunk_results

        cfg = self.config
        nlev = cfg.nharmonics + 1
        disp = dict(
            size=size, nsamps_valid=nsamps_valid, pos5=pos5, pos25=pos25,
            tsamp=tsamp,
        )
        args = (dispatch_lists, trials, tim_len, zapmask_dev, windows,
                search_block)
        tel = current_telemetry()

        mp0 = max(cfg.max_peaks, self._learned_max_peaks)
        spec_pad = self._learned_total_pad
        # overflow: raw crossings outgrew the compaction (jnp path) or
        # clusters outgrew it (harmonic-peaks kernel)
        kernels = self._route.kernels
        pend = []
        packs = []
        with tel.stage("search.dispatch"):
            for chunk in wave:
                peaks, padded = self._dispatch_chunk(
                    chunk, *args, mp0, **disp
                )
                pend.append([chunk, mp0, peaks, padded])
                packs.append(
                    pack_chunk_results(
                        peaks.idxs, peaks.snrs, peaks.counts,
                        peaks.ccounts, total_pad=spec_pad,
                    )
                )
            packed = packs[0] if len(packs) == 1 else jnp.concatenate(packs)

        # ONE packed transfer for the whole wave: each chunk contributes
        # [raw counts | cluster counts | speculatively compacted peak
        # stream] from a single jitted pack. Chunks whose static
        # compaction overflowed are re-dispatched with the next
        # power-of-two size (the reference sizes for 100000 up front,
        # peakfinder.hpp:61) -- rare, and only they pay extra round trips
        with tel.stage("search.fetch"):
            packed_all = tel.fetched(np.asarray(packed))
        counts_list = []
        ccounts_list = []
        spec_pieces = []
        redispatched = []
        off = 0
        for entry in pend:
            chunk, max_peaks, peaks, padded = entry
            n = peaks.counts.shape[0] * nlev * padded
            counts = packed_all[off : off + n].reshape(-1, nlev, padded)
            ccounts = packed_all[off + n : off + 2 * n].reshape(
                -1, nlev, padded
            )
            spec_pieces.append(
                packed_all[off + 2 * n : off + 2 * n + 2 * spec_pad]
            )
            off += 2 * n + 2 * spec_pad
            redisp = False
            ov = ccounts if kernels else counts
            # escalations, their probes and fetches (the enclosing
            # search.wave span closes this one if an escalation raises)
            sp = None
            if ov.max() > max_peaks:
                sp = tel.begin("search.redispatch")
            while ov.max() > max_peaks:
                tel.incr("search.redispatches")
                old_mp = max_peaks
                max_peaks = 1 << int(np.ceil(np.log2(ov.max())))
                self._learned_max_peaks = max(
                    self._learned_max_peaks, max_peaks
                )
                log.debug(
                    "peak compaction overflow: escalating max_peaks "
                    "%d -> %d (observed %d)", old_mp, max_peaks,
                    int(ov.max()),
                )
                tel.event(
                    "max_peaks_escalated", old=int(old_mp),
                    new=int(max_peaks), observed=int(ov.max()),
                )
                if kernels:
                    # the kernel was probed at the startup compaction
                    # size: probe the escalated shape too (on a TPU a
                    # failure raises, naming the kernel)
                    from ..ops.pallas import probe_pallas_harmpeaks

                    probe_pallas_harmpeaks(
                        size // 2 + 1, cfg.nharmonics, max_peaks
                    )
                peaks, padded = self._dispatch_chunk(
                    chunk, *args, max_peaks, **disp
                )
                counts = tel.fetched(np.asarray(peaks.counts))
                ccounts = tel.fetched(np.asarray(peaks.ccounts))
                ov = ccounts if kernels else counts
                entry[1:] = [max_peaks, peaks, padded]
                redisp = True
            if sp is not None:
                sp.end()
            counts_list.append(counts)
            ccounts_list.append(ccounts)
            redispatched.append(redisp)

        # Unpack each chunk's ragged peak stream. The speculative piece
        # that rode the counts transfer serves whenever the chunk was
        # not re-dispatched and its true total fits spec_pad; otherwise
        # (first-ever wave, busier data, or escalation) compact at the
        # exact pow2-padded size and pay one extra transfer — and learn
        # the size so the next wave's speculation covers it.
        with tel.stage("search.unpack"):
            for i, (entry, ccounts) in enumerate(zip(pend, ccounts_list)):
                chunk, max_peaks, peaks, padded = entry
                cc0 = np.minimum(ccounts, max_peaks)
                total = int(cc0.sum())
                total_pad = 1 << max(6, int(np.ceil(np.log2(max(1, total)))))
                # learn upward, but cap the speculation: one RFI-storm chunk
                # must not permanently inflate every later chunk's payload
                # beyond what the saved round trip is worth (~512 KiB)
                self._learned_total_pad = min(
                    max(self._learned_total_pad, total_pad), 1 << 16
                )
                if not redispatched[i] and total <= spec_pad:
                    piece = spec_pieces[i]
                    total_pad = spec_pad
                else:
                    tel.incr("search.spec_misses")
                    piece = tel.fetched(np.asarray(
                        compact_peaks_device(
                            peaks.idxs, peaks.snrs, peaks.ccounts,
                            total_pad=total_pad,
                        )
                    ))
                vi = piece[:total_pad]
                vs = piece[total_pad : 2 * total_pad].view(np.float32)
                cc = cc0  # (d, nlev, padded)
                # per-row entry ranges within the chunk's ragged stream
                row_ends = np.cumsum(cc.reshape(cc.shape[0], -1).sum(axis=1))
                dm_indices = chunk[0]
                _, at = _block_rows(
                    dm_indices, chunk[1], self._mesh_size(), self._shard_rows
                )
                for row, dm_idx in zip(at, dm_indices):
                    lo = int(row_ends[row - 1]) if row else 0
                    hi = int(row_ends[row])
                    emap = self._accel_expand[dm_idx]
                    if emap is None:
                        per_dm_results[dm_idx] = (
                            vi[lo:hi], vs[lo:hi], cc[row]
                        )
                    else:
                        # deduped dispatch: replicate the representative's
                        # results onto every identity accel column (bitwise
                        # what brute force would have produced)
                        per_dm_results[dm_idx] = _expand_accel_results(
                            vi[lo:hi], vs[lo:hi], cc[row], emap,
                            self._accel_full_pad[dm_idx],
                        )
