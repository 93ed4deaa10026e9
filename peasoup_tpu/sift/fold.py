"""Survey folding: every campaign candidate through fixed-size batches.

The per-observation :class:`~peasoup_tpu.pipeline.folder.MultiFolder`
folds the top handful of one observation's candidates. At campaign
scale the folding workload is the union over the whole database —
thousands of candidates spread over observations of several lengths —
and survey throughput hinges on folding them in bulk (PulsarX,
arXiv:2309.02544). This driver:

- derives each observation's fold geometry with the folder's own
  :func:`~peasoup_tpu.pipeline.folder.fold_geometry` (power-of-two
  truncation, f32 tsamp/tobs, whitening band edges), so every
  per-candidate result is **bitwise-equal** to the per-observation
  path (pinned in tests/test_sift.py);
- dereddens each needed (observation, DM trial) series exactly once;
- packs candidates into **fixed-size shape-bucketed batches** — bucket
  = the power-of-two series length — and streams them through the one
  jitted :func:`~peasoup_tpu.ops.survey_fold.survey_fold_batch`
  program per bucket, then optimises all folds in fixed-size
  :class:`~peasoup_tpu.ops.fold_optimise.FoldOptimiser` batches: zero
  steady-state recompiles across same-bucket batches;
- degrades under device OOM by halving the batch size (a
  :class:`~peasoup_tpu.resilience.DegradationLadder` rung, with the
  ``device.oom`` fault seam) — row independence keeps the shrunken
  batches bitwise-equal to the full-size ones.

Multi-host campaigns dispatch through
:func:`peasoup_tpu.parallel.multihost.run_survey_fold`, which deals
observations round-robin to processes and allgathers the outcomes.
"""

from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import get_logger
from ..obs.telemetry import current as current_telemetry
from ..ops.fold import fold_bins_np
from ..ops.fold_optimise import FoldOptimiser
from ..ops.survey_fold import survey_fold_batch
from ..pipeline.folder import _deredden_tim, fold_geometry
from ..resilience import DegradationLadder, faults, is_resource_exhausted

log = get_logger("sift.fold")


@dataclasses.dataclass
class FoldCandidate:
    """One candidate to fold: ``dm_row`` indexes the observation's
    ``trials`` array; ``key`` is the caller's opaque identity (the DB
    candidate id) carried through to the outcome."""

    key: object
    period: float
    acc: float
    dm_row: int


@dataclasses.dataclass
class FoldObservation:
    """One observation's fold input: dedispersed trials (u8, one row
    per needed DM) plus the candidates referencing them."""

    job_id: str
    trials: np.ndarray  # (nrows, >=trials_nsamps) u8 dedispersed series
    trials_nsamps: int
    tsamp: float
    cands: List[FoldCandidate] = dataclasses.field(default_factory=list)


class SurveyFolder:
    """Batched cross-observation folding with fixed-shape programs."""

    # same physicality gates as MultiFolder
    min_period = 1e-3
    max_period = 10.0

    def __init__(
        self, nbins: int = 64, nints: int = 16, batch: int = 64
    ) -> None:
        self.nbins = int(nbins)
        self.nints = int(nints)
        self.batch = int(batch)
        self.optimiser = FoldOptimiser(self.nbins, self.nints)

    # --- planning -----------------------------------------------------
    def _plan(self, observations: List[FoldObservation]):
        """Group foldable candidates by shape bucket (power-of-two
        series length). Returns {size: [(obs_idx, cand), ...]} plus the
        per-observation geometry list."""
        geoms = []
        buckets: dict[int, list] = {}
        for oi, obs in enumerate(observations):
            geom = fold_geometry(obs.trials_nsamps, obs.tsamp)
            geoms.append(geom)
            size = geom[0]
            for cand in obs.cands:
                if not self.min_period < cand.period < self.max_period:
                    continue
                if not 0 <= cand.dm_row < len(obs.trials):
                    continue
                buckets.setdefault(size, []).append((oi, cand))
        return buckets, geoms

    # --- the fold pass ------------------------------------------------
    def fold_outcomes(
        self, observations: List[FoldObservation]
    ) -> list[dict]:
        """Fold + optimise every foldable candidate. Returns one
        outcome dict per candidate: ``key``, ``job_id``, ``opt_sn``,
        ``opt_period``, ``opt_fold`` (nints, nbins), ``opt_prof``."""
        from ..ops.resample import accel_factor

        tel = current_telemetry()
        buckets, geoms = self._plan(observations)
        ladder = DegradationLadder("sift.fold", ("batch_shrink",))
        batch = self.batch

        all_folds: list[np.ndarray] = []
        all_meta: list[tuple] = []  # (obs_idx, cand, tobs)
        for size in sorted(buckets):
            entries = buckets[size]
            # deredden each needed (obs, dm_row) once per bucket; the
            # cache lives only for the bucket so peak host memory stays
            # one bucket's worth of f32 series
            xd_cache: dict[tuple[int, int], np.ndarray] = {}
            rows_xd = np.empty((len(entries), size), dtype=np.float32)
            afs = np.empty(len(entries), dtype=np.float32)
            bin_rows = []
            for i, (oi, cand) in enumerate(entries):
                obs = observations[oi]
                _, tsamp32, _, pos5, pos25 = geoms[oi]
                ck = (oi, cand.dm_row)
                if ck not in xd_cache:
                    xd_cache[ck] = np.asarray(
                        _deredden_tim(
                            jnp.asarray(obs.trials[cand.dm_row]),
                            size=size, pos5=pos5, pos25=pos25,
                        )
                    )
                rows_xd[i] = xd_cache[ck]
                # (a*tsamp) is an f32 product in the reference's
                # launcher; accel_factor replays it (folder.py idiom)
                afs[i] = accel_factor(
                    np.asarray([cand.acc]), tsamp32
                ).astype(np.float32)[0]
                bin_rows.append(fold_bins_np(
                    size, tsamp32, cand.period, self.nbins, self.nints
                ))
            del xd_cache
            bins = np.stack(bin_rows)

            lo = 0
            while lo < len(entries):
                hi = min(lo + batch, len(entries))
                n = hi - lo
                # fixed batch width: pad by repeating the first row so
                # every dispatch of this bucket reuses ONE compiled
                # program (padding rows are dropped below)
                pad_idx = np.arange(batch) % n + lo
                try:
                    faults.fire(
                        "device.oom",
                        context=f"sift.fold:{size}:{lo}",
                    )
                    folds = np.asarray(
                        survey_fold_batch(
                            jnp.asarray(rows_xd[pad_idx]),
                            jnp.asarray(afs[pad_idx]),
                            jnp.asarray(bins[pad_idx]),
                            nbins=self.nbins,
                            nints=self.nints,
                        )
                    )[:n]
                except Exception as exc:
                    if not is_resource_exhausted(exc):
                        raise
                    if batch <= 1:
                        ladder.exhausted(
                            batch=batch, error=f"{exc!s:.200}"
                        )
                        raise
                    ladder.step(
                        "batch_shrink", batch_old=batch,
                        batch_new=batch // 2, error=f"{exc!s:.200}",
                    )
                    batch //= 2
                    continue  # retry the same rows at the smaller batch
                all_folds.append(folds)
                for oi, cand in entries[lo:hi]:
                    all_meta.append((oi, cand, geoms[oi][2]))
                lo = hi
            tel.event(
                "sift_fold_bucket", size=int(size),
                candidates=len(entries), batch=int(batch),
            )

        if not all_meta:
            return []
        folds = np.concatenate(all_folds, axis=0)
        periods = np.asarray(
            [c.period for _, c, _ in all_meta], dtype=np.float64
        )
        tobs = np.asarray([t for _, _, t in all_meta], dtype=np.float64)

        # optimise in the same fixed batch width (recycled-row padding,
        # the folder.py idiom) so the optimiser compiles once too
        outcomes: list[dict] = []
        lo = 0
        while lo < len(all_meta):
            hi = min(lo + batch, len(all_meta))
            n = hi - lo
            pad_idx = np.arange(batch) % n + lo
            results = self.optimiser.optimise(
                folds[pad_idx], periods[pad_idx], tobs[pad_idx]
            )[:n]
            for (oi, cand, t), res in zip(all_meta[lo:hi], results):
                outcomes.append(
                    {
                        "key": cand.key,
                        "job_id": observations[oi].job_id,
                        "opt_sn": res["opt_sn"],
                        "opt_period": res["opt_period"],
                        "opt_fold": res["opt_fold"],
                        "opt_prof": res["opt_prof"],
                        # fold context: consumers gate how much to
                        # trust the period refinement on how many
                        # pulses the observation actually spans
                        "period": float(cand.period),
                        "tobs": float(t),
                    }
                )
            lo = hi
        return outcomes
