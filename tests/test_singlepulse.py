"""Single-pulse search subsystem tests (ops -> pipeline -> CLI -> IO).

Acceptance gates (ISSUE 3): injection recovery with analytic
matched-filter S/N, one-cluster clustering of a broad pulse, and
``.singlepulse`` + overview.xml round-trips through the parsers.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from peasoup_tpu.io.sigproc import (
    Filterbank,
    SigprocHeader,
    read_filterbank,
    write_filterbank,
)
from peasoup_tpu.ops.singlepulse import (
    boxcar_best,
    boxcar_best_twin,
    default_widths,
    make_single_pulse_search_fn,
    matched_filter_snr,
    normalise_trials,
    plan_pad,
    prefix_sum_padded,
    width_extent,
    width_scales,
)
from peasoup_tpu.pipeline.single_pulse import (
    SinglePulseConfig,
    SinglePulseSearch,
    cluster_events_fof,
    _EVENT_DTYPE,
)
from peasoup_tpu.plan.dm_plan import DMPlan


# --------------------------------------------------------------------------
# device ops
# --------------------------------------------------------------------------

class TestBoxcarOps:
    def test_best_plane_matches_bruteforce(self, rng):
        x = rng.normal(size=(3, 3000)).astype(np.float32)
        x[1, 700:716] += 6.0
        widths = default_widths(6)
        norm = np.asarray(normalise_trials(jnp.asarray(x)))
        best, bw = boxcar_best(jnp.asarray(norm), widths)
        best, bw = np.asarray(best), np.asarray(bw)
        t = x.shape[1]
        for d in range(x.shape[0]):
            planes = np.full((len(widths), t), -np.inf)
            for k, w in enumerate(widths):
                conv = np.convolve(norm[d], np.ones(w), "valid")
                planes[k, : t - w + 1] = conv * (1.0 / np.sqrt(w)).astype(
                    np.float32
                )
            ref_best = planes.max(axis=0)
            ref_w = planes.argmax(axis=0)
            assert np.allclose(best[d, :t], ref_best, rtol=2e-5, atol=2e-5)
            # argmax ties broken identically off-noise is not guaranteed
            # by float assoc; check where the margin is clear
            margin = np.partition(planes, -2, axis=0)
            clear = ref_best - margin[-2] > 1e-3
            assert np.array_equal(bw[d, :t][clear], ref_w[clear])

    def test_validity_tail_is_masked(self, rng):
        x = rng.normal(size=(1, 1500)).astype(np.float32)
        widths = (1, 4, 16)
        best, bw = map(np.asarray, boxcar_best(jnp.asarray(x), widths))
        t = 1500
        # a boxcar starting past t - w must never win: the last 15
        # samples can only carry widths whose window still fits
        for j in range(t - 16, t):
            wsel = widths[bw[0, j]]
            assert j + wsel <= t
        # padded region (t..tpad) is all -inf
        assert np.all(np.isneginf(best[0, t:]))

    def test_normalise_is_zero_mean_unit_std(self, rng):
        x = (rng.normal(40.0, 5.0, size=(4, 8192))).astype(np.float32)
        n = np.asarray(normalise_trials(jnp.asarray(x)))
        assert np.abs(n.mean(axis=1)).max() < 0.05
        assert np.abs(n.std(axis=1) - 1.0).max() < 0.05

    def test_normalise_resists_bright_pulse(self, rng):
        x = rng.normal(0.0, 1.0, size=(1, 8192)).astype(np.float32)
        y = x.copy()
        y[0, 100:160] += 50.0  # would inflate a naive std by ~4x
        nx = np.asarray(normalise_trials(jnp.asarray(x)))
        ny = np.asarray(normalise_trials(jnp.asarray(y)))
        # the clipped re-estimate must keep the noise scale unchanged
        assert np.allclose(nx[0, 200:], ny[0, 200:], atol=0.05)

    def test_search_fn_finds_pulse_at_exact_sample(self, rng):
        x = rng.normal(size=(2, 6000)).astype(np.float32)
        t0, w, amp = 2500, 8, 6.0
        x[1, t0 : t0 + w] += amp
        widths = default_widths(6)
        fn = make_single_pulse_search_fn(widths, 6.0, 64, 32, 0)
        samples, widx, snrs, counts = map(np.asarray, fn(jnp.asarray(x)))
        assert counts[0] == 0
        assert counts[1] >= 1
        k = np.argmax(snrs[1])
        assert abs(int(samples[1, k]) - t0) <= 1
        assert widths[int(widx[1, k])] == w
        # the matched filter integrates the window's noise too: one
        # realization scatters by ~N(0, 1) around the expectation
        exp = matched_filter_snr(amp, w, 1.0)
        assert abs(float(snrs[1, k]) - exp) < 3.5


class TestPallasBoxcar:
    """Interpret-mode kernel vs the jnp twin: BITWISE (the same gate
    probe_pallas_boxcar applies on real TPU toolchains)."""

    @pytest.mark.parametrize("t,nw", [(5000, 8), (20000, 11)])
    def test_bitwise_vs_twin(self, rng, t, nw):
        from peasoup_tpu.ops.pallas.boxcar import boxcar_best_pallas

        x = rng.normal(size=(3, t)).astype(np.float32)
        x[0, t // 2 : t // 2 + 12] += 20.0
        widths = default_widths(nw)
        tpad, span = plan_pad(t)
        wext = width_extent(widths)
        norm = normalise_trials(jnp.asarray(x))
        csum = prefix_sum_padded(norm, tpad, wext)
        scales = width_scales(widths)
        gb, gw = boxcar_best_pallas(
            csum, widths, scales, t, tpad, span=span, interpret=True
        )
        rb, rw = boxcar_best_twin(csum, widths, scales, t, tpad)
        assert np.array_equal(np.asarray(gb), np.asarray(rb))
        assert np.array_equal(np.asarray(gw), np.asarray(rw))

    def test_geometry_guard(self, rng):
        from peasoup_tpu.ops.pallas.boxcar import boxcar_best_pallas

        widths = default_widths(4)
        csum = jnp.zeros((1, 2048 + 1024), jnp.float32)
        with pytest.raises(ValueError):
            boxcar_best_pallas(
                csum, widths, width_scales(widths), 2000, 2048, span=999,
                interpret=True,
            )


class TestSpKernelSelection:
    """The single-pulse kernel route: the fused spchain kernel when the
    tile span is a multiple of the decimation, else the boxcar kernel,
    the jnp twin only where the backend has no Pallas — and on a TPU a
    kernel whose probe fails raises instead of dropping a rung."""

    def _patch(self, monkeypatch, supports, spchain_ok, boxcar_ok):
        import peasoup_tpu.ops.pallas as pallas_mod

        def probe(ok):
            def run(*args):
                if not supports:
                    return False
                if not ok:
                    raise pallas_mod.KernelUnavailable("forced")
                return True

            return run

        monkeypatch.setattr(
            pallas_mod, "backend_supports_pallas", lambda: supports
        )
        monkeypatch.setattr(
            pallas_mod, "probe_pallas_spchain", probe(spchain_ok)
        )
        monkeypatch.setattr(
            pallas_mod, "probe_pallas_boxcar", probe(boxcar_ok)
        )

    def test_full_span_takes_the_fused_kernel(self, monkeypatch):
        from peasoup_tpu.pipeline.single_pulse import select_sp_kernels

        self._patch(monkeypatch, True, True, True)
        widths = default_widths(6)
        assert select_sp_kernels(widths, 8192, 32, True) == (0, 8192)

    def test_span_off_the_decimation_takes_the_boxcar_kernel(
        self, monkeypatch
    ):
        from peasoup_tpu.pipeline.single_pulse import select_sp_kernels

        self._patch(monkeypatch, True, True, True)
        widths = default_widths(6)
        assert select_sp_kernels(widths, 8192, 24, True) == (8192, 0)

    def test_failed_probe_raises_on_tpu(self, monkeypatch):
        from peasoup_tpu.ops.pallas import KernelUnavailable
        from peasoup_tpu.pipeline.single_pulse import select_sp_kernels

        self._patch(monkeypatch, True, False, True)
        with pytest.raises(KernelUnavailable):
            select_sp_kernels(default_widths(6), 8192, 32, True)

    def test_twin_on_backends_without_pallas(self, monkeypatch):
        from peasoup_tpu.pipeline.single_pulse import select_sp_kernels

        self._patch(monkeypatch, False, False, False)
        widths = default_widths(6)
        assert select_sp_kernels(widths, 8192, 32, True) == (0, 0)

    def test_use_pallas_off_probes_nothing(self, monkeypatch):
        from peasoup_tpu.pipeline.single_pulse import select_sp_kernels

        self._patch(monkeypatch, True, False, False)
        widths = default_widths(6)
        assert select_sp_kernels(widths, 8192, 32, False) == (0, 0)

    # a campaign bucket small enough to plan on the CPU
    WARM_BUCKET = (8, 8, 4096, 0.000256, 1400.0, -16.0)

    def test_warmup_compiles_the_route_the_job_takes(self, monkeypatch):
        """Campaign warmup resolves the same route as the driver: with
        the spchain probe passing, the spsearch ctx carries the fused
        kernel at the full span, not the twin."""
        from peasoup_tpu.ops.singlepulse import plan_pad
        from peasoup_tpu.perf.warmup import shape_ctx_for_bucket

        self._patch(monkeypatch, True, True, True)
        ctx = shape_ctx_for_bucket(
            self.WARM_BUCKET, "spsearch", {"dm_end": 20.0, "n_widths": 6}
        )
        _, span = plan_pad(ctx.out_nsamps)
        assert (ctx.pallas_span, ctx.sp_fused_span) == (0, span)

    def test_warmup_raises_when_the_kernel_fails(self, monkeypatch):
        from peasoup_tpu.ops.pallas import KernelUnavailable
        from peasoup_tpu.perf.warmup import shape_ctx_for_bucket

        self._patch(monkeypatch, True, False, True)
        with pytest.raises(KernelUnavailable):
            shape_ctx_for_bucket(
                self.WARM_BUCKET, "spsearch",
                {"dm_end": 20.0, "n_widths": 6},
            )

    def test_driver_raises_when_the_kernel_fails(self, monkeypatch, tmp_path):
        """End-to-end: a TPU backend whose spchain probe fails stops the
        search with the kernel's error — no silent twin run."""
        from peasoup_tpu.io.sigproc import read_filterbank
        from peasoup_tpu.ops.pallas import KernelUnavailable

        path, _, _ = make_sp_fil(
            tmp_path, nsamps=1 << 12, dm_end=20.0, t0=1500
        )
        fil = read_filterbank(path)
        cfg = SinglePulseConfig(dm_end=20.0, min_snr=7.0, n_widths=6)
        self._patch(monkeypatch, True, False, False)
        with pytest.raises(KernelUnavailable):
            SinglePulseSearch(cfg).run(fil)

    def test_driver_reports_its_route(self, tmp_path):
        """The driver emits one sp_route event naming the kernel it ran
        (on the CPU: the jnp twin, fused_span = pallas_span = 0)."""
        from peasoup_tpu.io.sigproc import read_filterbank
        from peasoup_tpu.obs.telemetry import RunTelemetry

        path, _, _ = make_sp_fil(
            tmp_path, nsamps=1 << 12, dm_end=20.0, t0=1500
        )
        cfg = SinglePulseConfig(dm_end=20.0, min_snr=7.0, n_widths=6)
        tel = RunTelemetry()
        with tel.activate():
            SinglePulseSearch(cfg).run(read_filterbank(path))
        [route] = [e for e in tel.events if e["kind"] == "sp_route"]
        assert route["backend"] == "cpu"
        assert (route["fused_span"], route["pallas_span"]) == (0, 0)
        assert route["decimate"] == cfg.decimate and route["span"] > 0

    def test_retiled_kernel_bitwise_vs_twin(self, rng):
        """A span smaller than the plan's is still bitwise the twin:
        dec-fold semantics do not depend on the tile span."""
        from peasoup_tpu.ops.pallas.spchain import boxcar_dec_best_pallas
        from peasoup_tpu.ops.singlepulse import boxcar_dec_best_twin

        t, dec = 4096, 32
        x = rng.normal(size=(2, t)).astype(np.float32)
        x[1, 700:712] += 20.0
        widths = default_widths(6)
        tpad, span = plan_pad(t)  # span == tpad == 4096 here
        retiled = span // 2  # 2048: divides tpad, multiple of dec
        wext = width_extent(widths)
        norm = normalise_trials(jnp.asarray(x))
        csum = prefix_sum_padded(norm, tpad, wext)
        scales = width_scales(widths)
        got = boxcar_dec_best_pallas(
            csum, widths, scales, t, tpad, dec, span=retiled,
            interpret=True,
        )
        ref = boxcar_dec_best_twin(csum, widths, scales, t, tpad, dec)
        for g, r in zip(got, ref):
            assert np.array_equal(np.asarray(g), np.asarray(r))


# --------------------------------------------------------------------------
# friends-of-friends clustering
# --------------------------------------------------------------------------

class TestClustering:
    def test_links_width_ladder_and_dm_chain(self):
        widths = (1, 2, 4, 8, 16, 32, 64)
        # one pulse seen at 3 DM trials, several widths, nearby samples
        rows = [
            (10, 5000, 3, 12.0), (10, 4996, 4, 10.0), (11, 5001, 3, 11.0),
            (12, 5002, 3, 9.0), (11, 4970, 6, 8.0),
            # and a second, unrelated pulse far away in time
            (10, 9000, 0, 7.5),
        ]
        ev = np.asarray(rows, dtype=_EVENT_DTYPE)
        clusters = cluster_events_fof(ev, widths, dm_link=2, dec=32)
        sizes = sorted(len(c) for c in clusters)
        assert sizes == [1, 5]

    def test_dm_gap_splits(self):
        widths = (1, 2, 4)
        rows = [(0, 100, 0, 8.0), (10, 100, 0, 8.0)]
        ev = np.asarray(rows, dtype=_EVENT_DTYPE)
        clusters = cluster_events_fof(ev, widths, dm_link=2, dec=0)
        assert len(clusters) == 2

    def test_empty(self):
        ev = np.asarray([], dtype=_EVENT_DTYPE)
        assert cluster_events_fof(ev, (1, 2)) == []


# --------------------------------------------------------------------------
# pipeline-level: synthetic injections
# --------------------------------------------------------------------------

def make_sp_fil(
    tmp_path,
    nsamps=1 << 15,
    nchans=16,
    tsamp=0.000256,
    fch1=1400.0,
    foff=-8.0,
    dm_end=60.0,
    t0=9000,
    width=8,
    amp=9.0,
    seed=3,
    name="sp.fil",
):
    """8-bit filterbank with one dispersed top-hat pulse injected with
    the search's OWN delay table at the middle DM trial, so the
    analytic matched-filter S/N applies exactly at that trial."""
    plan = DMPlan.create(
        nsamps=nsamps, nchans=nchans, tsamp=tsamp, fch1=fch1, foff=foff,
        dm_start=0.0, dm_end=dm_end, pulse_width=64.0, tol=1.10,
    )
    idx = plan.ndm // 2
    delays = plan.delay_samples()[idx]
    rng = np.random.default_rng(seed)
    data = rng.normal(32.0, 4.0, size=(nsamps, nchans))
    for c in range(nchans):
        lo = t0 + delays[c]
        data[lo : lo + width, c] += amp
    data = np.clip(np.rint(data), 0, 255).astype(np.uint8)
    hdr = SigprocHeader(
        source_name="SPFAKE", tsamp=tsamp, tstart=55000.0, fch1=fch1,
        foff=foff, nchans=nchans, nbits=8, nifs=1, data_type=1,
    )
    path = tmp_path / name
    write_filterbank(path, Filterbank(header=hdr, data=data))
    return path, plan, idx


class TestInjectionRecovery:
    def test_recovers_injected_pulse(self, tmp_path):
        """ISSUE acceptance: right DM trial, right time sample, width
        within one log-spaced step, S/N within 10% of the analytic
        matched-filter expectation."""
        nchans, width, amp = 16, 8, 9.0
        t0 = 9000
        path, plan, idx = make_sp_fil(
            tmp_path, nchans=nchans, width=width, amp=amp, t0=t0
        )
        fil = read_filterbank(path)
        cfg = SinglePulseConfig(dm_end=60.0, min_snr=7.0, n_widths=8)
        res = SinglePulseSearch(cfg).run(fil)
        assert len(res.candidates) >= 1
        top = res.candidates[0]
        assert top.dm_idx == idx
        assert abs(top.sample - t0) <= 2
        # detected width within one octave step of the injected width
        k_true = int(np.log2(width))
        assert abs(top.width_idx - k_true) <= 1
        # analytic matched filter: the dedispersed trial sums nchans
        # channels (noise std 4 each -> 16) and scales by
        # output_scale(8, 16) = 1/16, so sigma = 1.0 and the summed
        # pulse amplitude is nchans * amp / 16
        exp = matched_filter_snr(nchans * amp * (1.0 / 16.0), width, 1.0)
        assert abs(top.snr / exp - 1.0) < 0.10

    def test_broad_pulse_is_one_cluster_and_roundtrips(self, tmp_path):
        """ISSUE acceptance: ONE candidate cluster for a broad pulse
        (not one per width/DM trial), and the .singlepulse table + XML
        section round-trip through the parsers."""
        from peasoup_tpu.io.output import (
            OutputFileWriter,
            write_singlepulse,
        )
        from peasoup_tpu.tools.parsers import OverviewFile, read_singlepulse

        width = 64
        path, plan, idx = make_sp_fil(
            tmp_path, width=width, amp=4.0, t0=8000, name="broad.fil"
        )
        fil = read_filterbank(path)
        cfg = SinglePulseConfig(dm_end=60.0, min_snr=7.0, n_widths=10)
        res = SinglePulseSearch(cfg).run(fil)
        assert res.n_events > 1  # the pulse fired many (trial, width) cells
        assert len(res.candidates) == 1
        top = res.candidates[0]
        assert abs(top.width_idx - int(np.log2(width))) <= 1
        assert top.members > 1
        assert top.sample_lo <= top.sample <= top.sample_hi
        assert top.dm_idx_lo <= top.dm_idx <= top.dm_idx_hi

        # round-trip: text table
        table_path = str(tmp_path / "cands.singlepulse")
        write_singlepulse(table_path, res.candidates)
        tab = read_singlepulse(table_path)
        assert len(tab) == 1
        assert int(tab["sample"][0]) == top.sample
        assert int(tab["width"][0]) == top.width
        assert tab["snr"][0] == pytest.approx(top.snr, rel=1e-4)
        assert tab["dm"][0] == pytest.approx(top.dm, rel=1e-5)
        assert int(tab["members"][0]) == top.members

        # round-trip: overview.xml single-pulse section
        w = OutputFileWriter()
        w.add_misc_info()
        w.add_header(fil.header)
        w.add_dm_list(res.dm_list)
        w.add_single_pulse_section(cfg, str(path), res.widths, res.candidates)
        w.add_timing_info(res.timers)
        xml_path = str(tmp_path / "overview.xml")
        w.to_file(xml_path)
        ov = OverviewFile(xml_path)
        assert list(ov.sp_widths) == [int(x) for x in res.widths]
        assert len(ov.sp_candidates) == 1
        row = ov.sp_candidates[0]
        assert int(row["sample"]) == top.sample
        assert int(row["width"]) == top.width
        assert row["snr"] == pytest.approx(top.snr, rel=1e-4)
        assert float(ov.sp_parameters["min_snr"]) == cfg.min_snr
        # the periodicity candidate table stays empty/absent — the two
        # sections are disjoint
        assert len(ov.candidates) == 0

    def test_checkpoint_resume_reuses_trials(self, tmp_path):
        path, plan, idx = make_sp_fil(tmp_path, name="ck.fil")
        fil = read_filterbank(path)
        ck = str(tmp_path / "sp.ckpt")
        cfg = SinglePulseConfig(
            dm_end=60.0, min_snr=7.0, n_widths=8, checkpoint_file=ck
        )
        res1 = SinglePulseSearch(cfg).run(fil)
        assert os.path.exists(ck)

        # resume: every trial restores; the dedispersion stage is
        # skipped entirely (the resume fast path) and the candidate
        # list is identical
        res2 = SinglePulseSearch(cfg).run(fil)
        assert res2.timers["dedispersion"] < res1.timers["dedispersion"]
        assert len(res2.candidates) == len(res1.candidates)
        for a, b in zip(res1.candidates, res2.candidates):
            assert (a.dm_idx, a.sample, a.width, a.members) == (
                b.dm_idx, b.sample, b.width, b.members
            )
            assert a.snr == pytest.approx(b.snr)

        # a config that changes per-trial results invalidates the key
        cfg3 = SinglePulseConfig(
            dm_end=60.0, min_snr=8.5, n_widths=8, checkpoint_file=ck
        )
        from peasoup_tpu.pipeline.single_pulse import make_checkpoint_key

        k1 = make_checkpoint_key(
            cfg, fil, plan.ndm, SinglePulseSearch(cfg).widths_for(1024)
        )
        k3 = make_checkpoint_key(
            cfg3, fil, plan.ndm, SinglePulseSearch(cfg3).widths_for(1024)
        )
        assert k1 != k3

    def test_sharded_matches_single_device(self, tmp_path):
        """The 'dm' mesh path (virtual CPU devices) must reproduce the
        single-device candidate list."""
        path, plan, idx = make_sp_fil(tmp_path, name="mesh.fil")
        fil = read_filterbank(path)
        base = dict(dm_end=60.0, min_snr=7.0, n_widths=8)
        r1 = SinglePulseSearch(SinglePulseConfig(**base)).run(fil)
        r2 = SinglePulseSearch(
            SinglePulseConfig(**base, shard_devices=2)
        ).run(fil)
        key = lambda r: [
            (c.dm_idx, c.sample, c.width, round(c.snr, 4))
            for c in r.candidates
        ]
        assert key(r1) == key(r2)

    def test_sliced_event_merge_matches_full_run(self, tmp_path):
        """Satellite (multi-host spsearch): per-slice partial runs
        allgather-merged and finalized must reproduce the full run's
        clustered candidate list — the single-process twin of
        parallel/multihost.py:run_single_pulse_search (slice, merge
        events with GLOBAL dm_idx, cluster globally)."""
        from peasoup_tpu.parallel.multihost import dm_slice_for_process
        from peasoup_tpu.pipeline.single_pulse import (
            PartialSinglePulseResult,
        )

        path, plan, idx = make_sp_fil(tmp_path, name="slices.fil")
        fil = read_filterbank(path)
        cfg = SinglePulseConfig(dm_end=60.0, min_snr=7.0, n_widths=8)
        search = SinglePulseSearch(cfg)
        full = search.run(fil)

        parts = []
        for pid in range(3):
            lo, hi = dm_slice_for_process(plan.ndm, 3, pid)
            part = search.run(fil, dm_slice=(lo, hi), finalize=False)
            # events come back with GLOBAL dm_idx, inside the slice
            if len(part.events):
                assert part.events["dm_idx"].min() >= lo
                assert part.events["dm_idx"].max() < hi
            parts.append(part)
        merged = PartialSinglePulseResult(
            events=np.concatenate([p.events for p in parts]),
            dm_list=plan.dm_list,
            widths=parts[0].widths,
            timers=parts[0].timers,
            nsamps=parts[0].nsamps,
            n_overflowed=sum(p.n_overflowed for p in parts),
            t_total_start=parts[0].t_total_start,
        )
        got = search.finalize(fil, merged)
        key = lambda r: sorted(
            (c.dm_idx, c.sample, c.width, round(c.snr, 4))
            for c in r.candidates
        )
        assert key(got) == key(full)
        assert got.candidates[0].dm_idx == idx

    def test_run_single_pulse_search_single_process(self, tmp_path):
        """The multihost driver degrades to the plain search when
        process_count == 1 (every CI/CPU invocation)."""
        from peasoup_tpu.parallel.multihost import run_single_pulse_search

        path, plan, idx = make_sp_fil(tmp_path, name="mh1.fil")
        fil = read_filterbank(path)
        cfg = SinglePulseConfig(dm_end=60.0, min_snr=7.0, n_widths=8)
        res = run_single_pulse_search(fil, cfg)
        assert len(res.candidates) >= 1
        assert res.candidates[0].dm_idx == idx


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

class TestSpsearchCLI:
    def test_end_to_end(self, tmp_path):
        from peasoup_tpu.cli.spsearch import main as sp_main
        from peasoup_tpu.obs.schema import validate_manifest
        from peasoup_tpu.obs.telemetry import load_manifest
        from peasoup_tpu.tools.parsers import OverviewFile, read_singlepulse

        path, plan, idx = make_sp_fil(tmp_path, name="cli.fil")
        outdir = tmp_path / "out"
        rc = sp_main(
            [
                "-i", str(path), "-o", str(outdir), "--dm_end", "60",
                "-m", "7", "--n_widths", "8",
                "--status-json", str(outdir / "status.json"),
            ]
        )
        assert rc == 0
        tab = read_singlepulse(str(outdir / "candidates.singlepulse"))
        assert len(tab) >= 1
        assert int(tab["dm_idx"][0]) == idx
        ov = OverviewFile(str(outdir / "overview.xml"))
        assert len(ov.sp_candidates) == len(tab)
        assert "searching" in ov.execution_times
        assert "clustering" in ov.execution_times
        man = load_manifest(str(outdir / "telemetry.json"))
        validate_manifest(man)
        assert man["context"]["command"] == "spsearch"
        assert man["gauges"]["sp.n_dm_trials"] == plan.ndm
        assert man["gauges"]["candidates.written"] == len(tab)

    def test_version_flag(self, capsys):
        """Satellite: every CLI prints package + JAX version and the
        active backend."""
        import peasoup_tpu
        from peasoup_tpu.cli.coincidencer import build_parser as coin_bp
        from peasoup_tpu.cli.ffa import build_parser as ffa_bp
        from peasoup_tpu.cli.peasoup import build_parser as peasoup_bp
        from peasoup_tpu.cli.spsearch import build_parser as sp_bp

        for bp in (peasoup_bp, ffa_bp, coin_bp, sp_bp):
            with pytest.raises(SystemExit) as exc:
                bp().parse_args(["--version"])
            assert exc.value.code == 0
            out = capsys.readouterr().out
            assert peasoup_tpu.__version__ in out
            assert jax.__version__ in out
            assert "backend" in out
