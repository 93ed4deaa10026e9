"""Fold + optimise the top candidates (the reference's MultiFolder,
include/transforms/folder.hpp:337-442).

Candidates are grouped by DM trial; each needed trial is dereddened
once, then ALL of that trial's candidates are resampled and folded in
one batched device call, and every fold across all groups is optimised
in a single batched FoldOptimiser pass — versus the reference's strictly
sequential per-candidate fold+optimise loop.

A group's batch is padded to a multiple of ``fold_bucket`` rows so
groups share compiled programs. Padding costs the device a dense fold
per row (cheap: no scatter), and costs the host only a row copy: the
exact f64 phase-bin table is built once per real candidate and the
padding rows copy row 0's. The telemetry counters ``fold.rows`` and
``fold.rows_padded`` count the two kinds.

Resample and fold run as one program whose path follows the group's
accelerations against the series length (ops/fold.py:
fold_accel_quadratic): a shift span up to the select's limit selects
among shifted views (at span 0, where every shift rounds to 0, the one
view is the series as it is), and only a span past the limit gathers.
Each (K_pad, N) geometry compiles one program per span bucket it meets
(0, the powers of two 2..64, and the gather): up to eight.
``fold.resample_identity``, ``fold.resample_select`` and
``fold.resample_gather`` count the real rows of span 0, of the larger
spans and of the gather.
"""

from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.candidates import Candidate
from ..obs.telemetry import current as current_telemetry
from ..ops.fold import FOLD_BUCKET, fold_accel_quadratic, fold_bins_np
from ..ops.fold_optimise import FoldOptimiser
from ..ops.rednoise import whiten_fseries
from ..ops.resample import accel_factor, quadratic_span
from ..plan.fft_plan import prev_power_of_two


@partial(jax.jit, static_argnames=("size", "pos5", "pos25"))
@jax.named_scope("fold.deredden")
def _deredden_tim(tim: jax.Array, *, size: int, pos5: int, pos25: int) -> jax.Array:
    """u8 trial -> dereddened f32 time series, scaled like the
    reference's unnormalised inverse FFT (x size) so fold amplitudes
    match the CUDA output files (folder.hpp:382-389)."""
    fser = whiten_fseries(tim[:size], pos5=pos5, pos25=pos25)
    return jnp.fft.irfft(fser, n=size) * size


def fold_geometry(
    trials_nsamps: int,
    tsamp: float,
    pos5_freq: float = 0.05,
    pos25_freq: float = 0.5,
) -> tuple[int, float, float, int, int]:
    """(size, tsamp_f32, tobs, pos5, pos25) for one observation's fold.

    The reference's quirky constants in one place — the power-of-two
    truncation, the f32 tsamp/tobs roundings, the whitening band edges
    — shared by :class:`MultiFolder` and the survey folder
    (peasoup_tpu/sift/fold.py) so the two paths provably derive the
    same per-candidate geometry (their outputs are pinned bitwise-equal
    in tests/test_sift.py)."""
    size = prev_power_of_two(trials_nsamps)
    tsamp32 = float(np.float32(tsamp))
    tobs = float(np.float32(size) * np.float32(tsamp))
    bin_width = 1.0 / (size * tsamp32)
    return (
        size, tsamp32, tobs,
        int(pos5_freq / bin_width), int(pos25_freq / bin_width),
    )


class MultiFolder:
    min_period = 1e-3
    max_period = 10.0
    fold_bucket = FOLD_BUCKET  # candidate batches padded to a multiple of this

    def __init__(
        self,
        trials: np.ndarray,  # (ndm, nsamps) u8 dedispersed trials
        trials_nsamps: int,
        tsamp: float,
        nbins: int = 64,
        nints: int = 16,
        pos5_freq: float = 0.05,
        pos25_freq: float = 0.5,
        dm_offset: int = 0,  # global dm_idx of trials[0] (multi-host
        # slices hold only their own trial block; candidates outside
        # [dm_offset, dm_offset + ndm) are folded by the owner
        # process and merged via fold outcomes)
        ndm: int | None = None,  # trials owned (None: len(trials));
        # rows past it pad a block dedispersed sharded
    ):
        self.trials = trials
        self.dm_offset = dm_offset
        self.ndm = len(trials) if ndm is None else ndm
        # the reference folds with the f32 tsamp member
        # (timeseries.hpp:54; double tsamp_by_period = tsamp/period in
        # kernels.cu:641 sees the f32-rounded value) — the fold's
        # phase-bin assignment is sensitive to this at the 1e-8 level,
        # which flips ~0.06% of samples into adjacent bins over a 2^17
        # series; tobs = nsamps*tsamp is a uint*float f32 product
        # (folder.hpp:358). All derived in fold_geometry, shared with
        # the survey folder.
        (
            self.nsamps, self.tsamp, self.tobs, self.pos5, self.pos25
        ) = fold_geometry(trials_nsamps, tsamp, pos5_freq, pos25_freq)
        self.nbins = nbins
        self.nints = nints
        self.optimiser = FoldOptimiser(nbins, nints)

    def fold_n(self, cands: List[Candidate], n: int) -> List[Candidate]:
        outcomes = self.fold_outcomes(cands, n)
        return self.apply_outcomes(cands, outcomes)

    def apply_outcomes(
        self, cands: List[Candidate], outcomes: list[dict]
    ) -> List[Candidate]:
        """Write fold outcomes (possibly gathered from several
        processes) back onto the candidate list and re-sort by
        max(snr, folded_snr) (folder.hpp:25-31,433)."""
        for res in outcomes:
            ci = res["cand_idx"]
            cands[ci].folded_snr = res["opt_sn"]
            cands[ci].opt_period = res["opt_period"]
            cands[ci].fold = res["opt_fold"]
        return sorted(cands, key=lambda c: -max(c.snr, c.folded_snr))

    def fold_outcomes(self, cands: List[Candidate], n: int) -> list[dict]:
        """Fold + optimise the foldable top-``n`` candidates whose DM
        trial lives in this folder's trial block, returning one outcome
        dict per candidate (keyed back by ``cand_idx``) instead of
        mutating the list — the multi-host merge exchanges these."""
        count = min(n, len(cands))
        ndm_local = self.ndm
        dm_map: dict[int, list[int]] = {}
        for ii in range(count):
            p = 1.0 / cands[ii].freq
            if not self.min_period < p < self.max_period:
                continue
            local_dm = cands[ii].dm_idx - self.dm_offset
            if 0 <= local_dm < ndm_local:
                dm_map.setdefault(local_dm, []).append(ii)

        # pipelined dispatch: enqueue DM groups' deredden+resample+fold
        # chains ahead of their fetches — on a high-latency link the
        # first D2H absorbs the whole in-flight pipeline and the rest
        # are nearly free, instead of one full round trip per DM group.
        # The window is BOUNDED so peak HBM stays a few groups' worth
        # of intermediates (each a dereddened series and K_pad x nsamps
        # bin bytes, and a K_pad x nsamps f32 resample on the gather
        # path); an unbounded queue could exhaust device memory at
        # survey scale, and the search driver's OOM shrink-retry does
        # not cover the folder.
        max_inflight = 4
        pending = []
        all_folds, all_periods, all_cand_idx = [], [], []
        tel = current_telemetry()

        def drain_one():
            folds, k, periods, cand_ids = pending.pop(0)
            with tel.stage("fold.fetch"):
                all_folds.append(tel.fetched(np.asarray(folds))[:k])
            all_periods.extend(periods[:k])
            all_cand_idx.extend(cand_ids)

        for dm_idx, cand_ids in dm_map.items():
            tel.incr("fold.groups")
            with tel.stage("fold.dispatch"):
                pending.append(self._dispatch_group(dm_idx, cand_ids, cands))
            if len(pending) >= max_inflight:
                drain_one()
        while pending:
            drain_one()

        if not all_cand_idx:
            return []
        with tel.stage("fold.optimise"):
            results = self._optimise(all_folds, all_periods)
        return [
            {
                "cand_idx": ci,
                "opt_sn": res["opt_sn"],
                "opt_period": res["opt_period"],
                "opt_fold": res["opt_fold"],
            }
            for ci, res in zip(all_cand_idx, results)
        ]

    def _dispatch_group(self, dm_idx: int, cand_ids: list[int], cands):
        """Enqueue one DM group's deredden, resample and fold; returns
        (device folds, real count, periods, candidate indices)."""
        xd = _deredden_tim(
            jnp.asarray(self.trials[dm_idx]),
            size=self.nsamps,
            pos5=self.pos5,
            pos25=self.pos25,
        )
        # pad the candidate batch to a fixed width so DM groups of one
        # span bucket reuse one compiled (K_pad, N) resample+fold
        # program; padding rows repeat row 0, and only the real rows
        # are kept on fetch
        k = len(cand_ids)
        k_pad = int(np.ceil(k / self.fold_bucket) * self.fold_bucket)
        rows = np.r_[np.arange(k), np.zeros(k_pad - k, dtype=int)]
        ids_pad = [cand_ids[r] for r in rows]
        tel = current_telemetry()
        tel.incr("fold.rows", k)
        tel.incr("fold.rows_padded", k_pad - k)
        # batched resample (the folder uses the quadratic v1 kernel,
        # folder.hpp:396 -> kernels.cu:308-332)
        # (a*tsamp) is an f32 product in the reference's launcher
        # (float a, float tsamp, kernels.cu:367) — accel_factor
        # replays it
        afs = accel_factor(
            np.asarray([cands[ci].acc for ci in ids_pad]), self.tsamp
        ).astype(np.float32)
        # the group's shift span picks the program: a select over the
        # span (0: every row is the identity), or the gather past its
        # limit
        span = quadratic_span(afs, self.nsamps)
        path = "gather" if span is None else (
            "select" if span else "identity"
        )
        tel.incr(f"fold.resample_{path}", k)
        periods = np.array(
            [1.0 / cands[ci].freq for ci in ids_pad], dtype=np.float64
        )
        # the exact f64 bin table once per real candidate; the padding
        # rows copy row 0's
        phase_bins = np.stack(
            [
                fold_bins_np(self.nsamps, self.tsamp, p, self.nbins, self.nints)
                for p in periods[:k]
            ]
        )[rows]
        folds = fold_accel_quadratic(
            xd,
            jnp.asarray(afs),
            jnp.asarray(phase_bins),
            span=span,
            nbins=self.nbins,
            nints=self.nints,
        )
        return folds, k, periods, cand_ids

    def _optimise(self, all_folds: list, all_periods: list) -> list[dict]:
        """One batched optimiser pass over every fold of the call."""
        folds = np.concatenate(all_folds, axis=0)
        k = folds.shape[0]
        k_pad = int(np.ceil(k / self.fold_bucket) * self.fold_bucket)
        if k_pad > k:  # fixed batch width -> one compiled optimiser
            reps = int(np.ceil(k_pad / k))
            folds = np.concatenate([folds] * reps, axis=0)[:k_pad]
            all_periods = (list(all_periods) * reps)[:k_pad]
        return self.optimiser.optimise(
            folds, np.asarray(all_periods), self.tobs
        )[:k]
