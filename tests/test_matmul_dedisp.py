"""MXU banded-matmul dedispersion engine + fused-chain tests (ISSUE
12): matmul-vs-gather parity as a property across nbits / odd shapes /
zero-DM / the max-DM bucket edge, the matmul-staged subband engine,
the ULP contract for float inputs, the planner's third alternative
(cost profile recorded, never selected analytically), the tuner's
measured engine race (winner only when faster), the DM-scaled smear
budgets, the search-side knob grid's warm-bucket zero-measurement
contract, fused-kernel bitwise gates in interpret mode, and the
roofline stage classification."""

import numpy as np
import pytest

from peasoup_tpu.ops.dedisperse import (
    dedisperse_block,
    dedisperse_matmul,
    dedisperse_subband,
    matmul_band,
    output_scale,
    subband_groups,
)
from peasoup_tpu.perf import tuning
from peasoup_tpu.plan.dedisp_plan import (
    DedispPlan,
    dm_smear_budgets,
    effective_delay_table,
    matmul_cost_profile,
    subband_group_spans,
)
from peasoup_tpu.plan.dm_plan import DMPlan

GEO = dict(
    nsamps=4096, nchans=16, tsamp=0.000256, fch1=1400.0, foff=-16.0,
    dm_start=0.0, dm_end=30.0,
)
SURVEY = dict(
    nsamps=1 << 18, nchans=1024, tsamp=1e-5, fch1=1500.0, foff=-0.29,
    dm_start=0.0, dm_end=300.0,
)


def _data(nbits, nsamps, nchans, seed=0):
    rng = np.random.default_rng(seed)
    hi = (1 << nbits) - 1
    return rng.integers(0, hi + 1, size=(nsamps, nchans), dtype=np.uint8)


# --------------------------------------------------------------------------
# matmul-vs-gather parity as a property
# --------------------------------------------------------------------------

class TestMatmulParity:
    @pytest.mark.parametrize("nbits", [1, 2, 4, 8])
    def test_bitwise_across_nbits(self, nbits):
        plan = DMPlan.create(**GEO)
        delays = plan.delay_samples()
        data = _data(nbits, GEO["nsamps"], GEO["nchans"], seed=nbits)
        kill = np.ones(GEO["nchans"], dtype=np.float32)
        kill[5] = 0.0
        scale = output_scale(nbits, GEO["nchans"] - 1)
        ref = np.asarray(
            dedisperse_block(
                data, delays, kill, out_nsamps=plan.out_nsamps,
                scale=scale,
            )
        )
        got = np.asarray(
            dedisperse_matmul(
                data, delays, kill, plan.out_nsamps, scale=scale, block=8
            )
        )
        assert np.array_equal(got, ref)

    @pytest.mark.parametrize(
        "nsamps,nchans", [(3001, 13), (4097, 7), (2050, 17)]
    )
    def test_odd_shapes(self, nsamps, nchans):
        """Non-multiple-of-tile geometries: odd sample counts, prime
        channel counts — the block/band padding must stay inert."""
        geo = dict(GEO, nsamps=nsamps, nchans=nchans)
        plan = DMPlan.create(**geo)
        delays = plan.delay_samples()
        data = _data(2, nsamps, nchans, seed=1)
        kill = np.ones(nchans, dtype=np.float32)
        ref = np.asarray(
            dedisperse_block(
                data, delays, kill, out_nsamps=plan.out_nsamps
            )
        )
        got = np.asarray(
            dedisperse_matmul(
                data, delays, kill, plan.out_nsamps, block=8
            )
        )
        assert np.array_equal(got, ref)

    def test_zero_dm_and_max_dm_edge(self):
        """Zero-DM trials (all-zero delays: band collapses to the
        quantum) and the max-DM bucket edge (out_nsamps pinned to the
        last valid sample window)."""
        plan = DMPlan.create(**GEO)
        delays = plan.delay_samples()
        data = _data(4, GEO["nsamps"], GEO["nchans"], seed=2)
        kill = np.ones(GEO["nchans"], dtype=np.float32)
        zero = np.zeros_like(delays[:4])
        ref = np.asarray(
            dedisperse_block(data, zero, kill, out_nsamps=plan.out_nsamps)
        )
        got = np.asarray(
            dedisperse_matmul(data, zero, kill, plan.out_nsamps)
        )
        assert np.array_equal(got, ref)
        # max-DM edge: the LAST trials only, with the tightest valid
        # output length (t_in - max delay)
        tail = delays[-4:]
        out = GEO["nsamps"] - int(tail.max())
        ref = np.asarray(
            dedisperse_block(data, tail, kill, out_nsamps=out)
        )
        got = np.asarray(dedisperse_matmul(data, tail, kill, out))
        assert np.array_equal(got, ref)

    def test_channel_chunking_matches(self):
        """A tiny chunk_bytes forces the channel-chunk recursion; f32
        partial accumulation stays bitwise for integer inputs."""
        plan = DMPlan.create(**GEO)
        delays = plan.delay_samples()
        data = _data(2, GEO["nsamps"], GEO["nchans"], seed=3)
        kill = np.ones(GEO["nchans"], dtype=np.float32)
        whole = np.asarray(
            dedisperse_matmul(data, delays, kill, plan.out_nsamps)
        )
        chunked = np.asarray(
            dedisperse_matmul(
                data, delays, kill, plan.out_nsamps,
                chunk_bytes=4 * (plan.out_nsamps + 64) * 3,
            )
        )
        assert np.array_equal(whole, chunked)

    def test_float_inputs_within_ulp_tolerance(self):
        """Pure-f32 filterbanks: the conv may re-associate the channel
        sum, so the contract is a pinned ULP tolerance (documented in
        ops/dedisperse.py), not bitwise equality."""
        plan = DMPlan.create(**GEO)
        delays = plan.delay_samples()
        rng = np.random.default_rng(4)
        data = rng.normal(10.0, 2.0, size=(GEO["nsamps"], GEO["nchans"]))
        data = data.astype(np.float32)
        kill = np.ones(GEO["nchans"], dtype=np.float32)
        ref = np.asarray(
            dedisperse_block(
                data, delays, kill, out_nsamps=plan.out_nsamps,
                quantize=False,
            )
        )
        got = np.asarray(
            dedisperse_matmul(
                data, delays, kill, plan.out_nsamps, quantize=False
            )
        )
        # <= 4 ULP of the accumulated magnitude (C=16 f32 adds)
        tol = 4 * np.spacing(np.maximum(np.abs(ref), 1.0))
        assert (np.abs(got - ref) <= tol).all()

    @pytest.mark.parametrize("nbits", [1, 8])
    @pytest.mark.parametrize("max_smear", [0.0, 1.0])
    def test_subband_matmul_stages_bitwise(self, nbits, max_smear):
        """The matmul-staged subband engine is bitwise the scan-staged
        one — and therefore inherits its effective-delay-table parity
        contract."""
        plan = DMPlan.create(**GEO)
        delays = plan.delay_samples()
        data = _data(nbits, GEO["nsamps"], GEO["nchans"], seed=nbits)
        kill = np.ones(GEO["nchans"], dtype=np.float32)
        scale = output_scale(nbits, GEO["nchans"])
        scan = np.asarray(
            dedisperse_subband(
                data, delays, kill, plan.out_nsamps, nsub=4,
                max_smear=max_smear, scale=scale,
            )
        )
        mm = np.asarray(
            dedisperse_subband(
                data, delays, kill, plan.out_nsamps, nsub=4,
                max_smear=max_smear, scale=scale, use_matmul=True,
            )
        )
        assert np.array_equal(mm, scan)


# --------------------------------------------------------------------------
# DM-scaled smear budgets
# --------------------------------------------------------------------------

class TestDmScaledSmear:
    def _budgets(self, plan, geo, loss=0.1, floor=1.0):
        return dm_smear_budgets(
            plan.dm_list, tsamp=geo["tsamp"], fch1=geo["fch1"],
            foff=geo["foff"], nchans=geo["nchans"],
            pulse_width_us=64.0, max_snr_loss=loss, floor=floor,
        )

    def test_budgets_grow_with_dm_and_respect_floor(self):
        plan = DMPlan.create(**SURVEY)
        b = self._budgets(plan, SURVEY)
        assert b.shape == (plan.ndm,)
        assert (b >= 1.0).all()
        assert b[-1] > b[0]  # high-DM trials absorb more smear

    def test_budgeted_grouping_coarser_and_engine_twin(self):
        """Per-trial budgets admit more trials per group at high DM;
        the planner's vectorised grouping stays span-for-span the
        engine's, and the effective table honours each trial's own
        budget."""
        plan = DMPlan.create(**SURVEY)
        dt = plan.delay_samples()[:400]
        b = self._budgets(plan, SURVEY)[:400]
        flat = subband_group_spans(dt, 32, 1.0)
        scaled = subband_group_spans(dt, 32, 1.0, b)
        assert len(scaled) <= len(flat)
        assert [
            (lo, hi) for lo, hi, _ in scaled
        ] == subband_groups(dt, 32, 1.0, b)
        eff = effective_delay_table(dt, 32, 1.0, b)
        per_trial = np.abs(eff - dt).max(axis=1)
        assert (per_trial <= np.ceil(b)).all()

    def test_select_records_scaled_smear_provenance(self):
        plan = DMPlan.create(**SURVEY)
        p = DedispPlan.select(
            plan, nbits=2, tsamp=SURVEY["tsamp"], fch1=SURVEY["fch1"],
            foff=SURVEY["foff"],
        )
        assert p.engine == "subband"
        assert p.smear_dm_scaled and p.smear_loss_budget == 0.1
        assert p.predicted_loss <= 0.1
        flat = DedispPlan.select(
            plan, nbits=2, tsamp=SURVEY["tsamp"], fch1=SURVEY["fch1"],
            foff=SURVEY["foff"], dm_scale_smear=False,
        )
        assert not flat.smear_dm_scaled
        # scaled budgets can only merge more trials per group
        assert p.n_groups <= flat.n_groups


# --------------------------------------------------------------------------
# planner third alternative
# --------------------------------------------------------------------------

class TestMatmulPlanning:
    def test_select_profiles_matmul_but_never_picks_it(self):
        plan = DMPlan.create(**GEO)
        p = DedispPlan.select(
            plan, nbits=8, tsamp=GEO["tsamp"], fch1=GEO["fch1"],
            foff=GEO["foff"],
        )
        assert p.engine in ("exact", "subband")  # never "matmul"
        assert p.cost_matmul > 0
        assert p.matmul_band >= matmul_band(plan.delay_samples()[:1])
        prof = matmul_cost_profile(plan.delay_samples(), plan.out_nsamps)
        assert prof["effective"] == pytest.approx(p.cost_matmul)
        assert prof["macs"] > 0 and prof["bytes"] > 0

    def test_plan_doc_round_trips_new_fields(self):
        p = DedispPlan(
            engine="matmul", cost_matmul=10.0, matmul_candidate=True,
            accel_bucket=16, pallas_block=256, subband_matmul=True,
            smear_dm_scaled=True, smear_loss_budget=0.1,
        )
        doc = p.to_doc()
        assert DedispPlan.from_doc(doc) == p
        s = p.summary()
        assert s["engine"] == "matmul" and s["matmul_candidate"]


# --------------------------------------------------------------------------
# tuner: measured engine race + knob grid + warm zero-measurement
# --------------------------------------------------------------------------

BUCKET = (16, 8, 4096, 0.000256, 1400.0, -16.0)
OVR = {"dm_end": 30.0}


class TestEngineRace:
    def _race(self, monkeypatch, timings):
        """Run resolve with deterministic fake measurements: engine
        race entries read from ``timings``, everything else a constant
        (ranking within knob grids is irrelevant here)."""
        import peasoup_tpu.perf.tuning as tun

        def fake_measure(call, reps):
            tun._TUNER_INVOCATIONS += 1
            return timings.pop(0) if timings else 1e-3

        monkeypatch.setattr(tun, "_measure", fake_measure)
        return tun

    def test_matmul_wins_only_when_measured_faster(self, tmp_path):
        """The real race on THIS backend: whatever engine the tuner
        records as winner must hold the minimum measured median among
        the raced engines — the acceptance contract."""
        path = str(tmp_path / "tc.json")
        p = tuning.resolve_plan_for_bucket(BUCKET, "search", OVR, path)
        raced = {
            t["params"]["engine"]: t["median_s"]
            for t in p.trials
            if "engine" in t["params"]
        }
        assert "exact" in raced  # exact always races
        winner_name = (
            "subband_matmul"
            if p.engine == "subband" and p.subband_matmul
            else p.engine
        )
        if winner_name in raced:
            assert raced[winner_name] == min(raced.values())
        # provenance: the race landed in the persisted plan
        doc = tuning.load_cache(path)
        tuning.validate_cache(doc)

    def test_warm_bucket_zero_measurements_with_new_knobs(self, tmp_path):
        """The satellite contract: the extended knob grid (dm_block,
        accel_bucket, pallas block, engine race) still resolves warm
        buckets with ZERO measurement calls, and the knobs persist."""
        path = str(tmp_path / "tc.json")
        p1 = tuning.resolve_plan_for_bucket(BUCKET, "search", OVR, path)
        assert p1.dm_block in tuning.DM_BLOCK_CANDIDATES
        assert p1.accel_bucket in tuning.ACCEL_BUCKET_CANDIDATES
        n = tuning.measurement_count()
        p2 = tuning.resolve_plan_for_bucket(BUCKET, "search", OVR, path)
        assert tuning.measurement_count() == n
        assert p2.source == "cache"
        assert p2.dm_block == p1.dm_block
        assert p2.accel_bucket == p1.accel_bucket
        assert p2.engine == p1.engine

    def test_forced_outcomes_with_fake_timings(self, tmp_path, monkeypatch):
        """Deterministic winner selection: when the fake clock makes
        matmul faster, the tuner promotes it; when slower, the current
        engine stays — provenance lands in plan.trials either way."""
        from peasoup_tpu.plan.dedisp_plan import DedispPlan as DP

        tun = self._race(monkeypatch, [])

        def run_race(exact_s, matmul_s):
            plan = DP(engine="exact", matmul_candidate=True)
            trials, meds = [], {}
            tun._race_engines(
                plan, trials, meds,
                None, None, None, 128, 1.0, 1,
                lambda *a, **k: None,  # dedisperse_device
                lambda *a, **k: None,  # dedisperse_matmul
                lambda *a, **k: None,  # dedisperse_subband
            )
            return plan, meds

        self._race(monkeypatch, [exact := 0.002, 0.001])
        plan, meds = run_race(exact, 0.001)
        assert meds == {"exact": 0.002, "matmul": 0.001}
        assert plan.engine == "matmul" and plan.source == "tuned"
        self._race(monkeypatch, [0.001, 0.002])
        plan, meds = run_race(0.001, 0.002)
        assert plan.engine == "exact"


# --------------------------------------------------------------------------
# fused chains: bitwise twins in interpret mode
# --------------------------------------------------------------------------

class TestFusedChains:
    def test_spchain_kernel_bitwise_vs_twin(self):
        import jax.numpy as jnp

        from peasoup_tpu.ops.pallas.spchain import boxcar_dec_best_pallas
        from peasoup_tpu.ops.singlepulse import (
            boxcar_dec_best_twin,
            default_widths,
            prefix_sum_padded,
            width_extent,
            width_scales,
        )

        widths = default_widths(8)
        scales = width_scales(widths)
        span, dec = 1024, 32
        tpad = 3 * span
        wext = width_extent(widths)
        rng = np.random.default_rng(0)
        nvalid = tpad - span // 3
        norm = rng.normal(size=(4, nvalid)).astype(np.float32)
        norm[1, 500:516] += 25.0
        norm[2, 64] = norm[2, 64 + dec - 1] = 30.0  # in-block tie edges
        csum = prefix_sum_padded(jnp.asarray(norm), tpad, wext)
        got = boxcar_dec_best_pallas(
            csum, widths, scales, nvalid, tpad, dec, span=span,
            interpret=True,
        )
        ref = boxcar_dec_best_twin(csum, widths, scales, nvalid, tpad, dec)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))

    def test_spchain_routing_in_search_fn_bitwise(self):
        """The whole fused single-pulse program (normalise -> fused
        sweep+dec-fold -> compact) emits bitwise the unfused program's
        events. Interpret mode exercises the kernel route on CPU."""
        import peasoup_tpu.ops.singlepulse as sp

        rng = np.random.default_rng(1)
        trials = rng.normal(30.0, 4.0, size=(3, 4096)).astype(np.float32)
        trials[1, 1000:1008] += 40.0
        widths = sp.default_widths(6)

        def run(fused):
            # bypass the lru_cache'd builder so interpret-mode kernels
            # can ride the fused route on CPU
            norm = sp.normalise_trials(trials)
            bmax, barg, bwidx = sp.boxcar_dec_best(
                norm, widths, 32,
                fused_span=1024 if fused else 0, interpret=fused,
            )
            return map(np.asarray, (bmax, barg, bwidx))

        for g, r in zip(run(True), run(False)):
            np.testing.assert_array_equal(g, r)

    def test_specchain_kernel_vs_twin_interpret(self):
        import jax.numpy as jnp

        from peasoup_tpu.ops.pallas.specchain import (
            SPEC_BLOCK,
            interp_deredden_zap_pallas,
            s0_envelope,
        )
        from peasoup_tpu.ops.spectrum import interp_deredden_zap

        rng = np.random.default_rng(2)
        nbins = SPEC_BLOCK + 257  # odd, straddles two tiles
        d = 10  # forces the row pad
        re = jnp.asarray(rng.normal(size=(d, nbins)).astype(np.float32))
        im = jnp.asarray(rng.normal(size=(d, nbins)).astype(np.float32))
        med = jnp.asarray((0.5 + rng.random((d, nbins))).astype(np.float32))
        zap = np.zeros(nbins, dtype=bool)
        zap[3] = True  # birdie inside the zeroed low bins
        zap[100:104] = True
        zap[SPEC_BLOCK - 1 : SPEC_BLOCK + 1] = True  # tile boundary
        got = interp_deredden_zap_pallas(
            re, im, med, jnp.asarray(zap), interpret=True
        )
        ref = interp_deredden_zap(re, im, med, jnp.asarray(zap))
        # parts: pure select/divide — BITWISE. amplitude: FMA-class
        # envelope (the dftspec/interbin discipline; see s0_envelope)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
        s_g, s_r = np.asarray(got[2]), np.asarray(ref[2])
        assert (np.abs(s_g - s_r) <= s0_envelope(s_r)).all()

    def test_specchain_twin_matches_unfused_stanza(self):
        """The fused twin replays the historical complex chain
        (deredden -> zap_birdies -> form_interpolated) to numerical
        identity on the values the pipeline consumes."""
        import jax.numpy as jnp

        from peasoup_tpu.ops.rednoise import deredden
        from peasoup_tpu.ops.spectrum import (
            form_interpolated,
            interp_deredden_zap,
        )
        from peasoup_tpu.ops.zap import zap_birdies

        rng = np.random.default_rng(3)
        nbins = 513
        fser = (
            rng.normal(size=(4, nbins)) + 1j * rng.normal(size=(4, nbins))
        ).astype(np.complex64)
        med = (0.5 + rng.random((4, nbins))).astype(np.float32)
        zap = np.zeros(nbins, dtype=bool)
        zap[50:60] = True
        old = zap_birdies(deredden(jnp.asarray(fser), jnp.asarray(med)),
                          jnp.asarray(zap))
        s0_old = form_interpolated(old)
        re_d, im_d, s0 = interp_deredden_zap(
            jnp.asarray(np.real(fser)), jnp.asarray(np.imag(fser)),
            jnp.asarray(med), jnp.asarray(zap),
        )
        np.testing.assert_allclose(
            np.asarray(re_d), np.real(np.asarray(old)), rtol=1e-6,
            atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(s0), np.asarray(s0_old), rtol=1e-6, atol=1e-6
        )


# --------------------------------------------------------------------------
# roofline stage classification
# --------------------------------------------------------------------------

class TestRoofline:
    def test_every_program_maps_to_a_stage(self):
        from peasoup_tpu.ops.registry import registered_programs
        from peasoup_tpu.perf.roofline import STAGES, stage_for_program

        for spec in registered_programs():
            assert stage_for_program(spec.name) in STAGES

    def test_dedisp_programs_share_the_dedisperse_stage(self):
        from peasoup_tpu.perf.roofline import stage_for_program

        for name in (
            "ops.dedisperse.dedisperse_block",
            "ops.dedisperse.dedisperse_matmul_block",
            "ops.dedisperse.subband_stage1_matmul",
        ):
            assert stage_for_program(name) == "dedisperse"

    def test_roofline_fields_math(self):
        from peasoup_tpu.perf.roofline import (
            device_peaks,
            roofline_fields,
            stage_roofline,
        )

        assert device_peaks("TPU v5 lite") == (49e12, 819e9)
        assert device_peaks("cpu") is None
        # memory-bound: low intensity
        f = roofline_fields(1.0, 1e9, 1e9, "TPU v5 lite")
        assert f["bound"] == "memory"
        assert f["intensity_flops_per_byte"] == 1.0
        assert f["peak_fraction"] == pytest.approx(
            1e9 / 819e9, abs=1e-4  # the record rounds to 4 decimals
        )
        # compute-bound: huge intensity
        f = roofline_fields(1.0, 1e15, 1e9, "TPU v5 lite")
        assert f["bound"] == "compute"
        # unknown device: ratios stay null, measured fields survive
        f = roofline_fields(2.0, 1e9, 4e9, "cpu")
        assert f["peak_fraction"] is None
        assert f["achieved_bytes_per_s"] == pytest.approx(2e9)
        tbl = stage_roofline(
            {"dedisperse": (1.0, 1e9), "other": (0.0, 0)},
            {"dedisperse": 1e9}, "TPU v5 lite",
        )
        assert tbl["dedisperse"]["bound"] == "memory"
        assert tbl["other"]["achieved_flops_per_s"] is None

    def test_microbench_doc_carries_stages_and_dedisp(self, tmp_path):
        from peasoup_tpu.perf.microbench import (
            run_microbench,
            validate_perf,
        )

        doc = run_microbench(
            reps=1,
            programs=[
                "ops.dedisperse.dedisperse_matmul_block",
                "ops.spectrum.interp_deredden_zap",
            ],
        )
        validate_perf(doc)
        assert doc["version"] == 2
        progs = doc["programs"]
        assert progs["ops.dedisperse.dedisperse_matmul_block"]["stage"] == (
            "dedisperse"
        )
        assert progs["ops.spectrum.interp_deredden_zap"]["stage"] == (
            "spectrum_chain"
        )
        assert doc["stages"]["dedisperse"]["programs"] == 1
        assert doc["dedisp"]["engine"] == "exact"


# --------------------------------------------------------------------------
# driver: forced engines produce identical candidates (the CI smoke's
# in-process twin)
# --------------------------------------------------------------------------

def test_forced_engine_three_way_candidates(tmp_path):
    from peasoup_tpu.io.sigproc import (
        Filterbank,
        SigprocHeader,
        read_filterbank,
        write_filterbank,
    )
    from peasoup_tpu.pipeline.search import PeasoupSearch, SearchConfig

    nsamps, nchans, tsamp, fch1, foff = 1 << 12, 8, 0.000256, 1400.0, -16.0
    plan = DMPlan.create(
        nsamps=nsamps, nchans=nchans, tsamp=tsamp, fch1=fch1, foff=foff,
        dm_start=0.0, dm_end=20.0,
    )
    delays = plan.delay_samples()[plan.ndm // 2]
    rng = np.random.default_rng(5)
    data = rng.normal(32.0, 4.0, size=(nsamps, nchans))
    for s0 in range(100, nsamps - 200, 128):
        for c in range(nchans):
            data[s0 + delays[c] : s0 + 4 + delays[c], c] += 14.0
    hdr = SigprocHeader(
        source_name="3WAY", tsamp=tsamp, tstart=55000.0, fch1=fch1,
        foff=foff, nchans=nchans, nbits=8, nifs=1, data_type=1,
    )
    path = str(tmp_path / "smoke.fil")
    write_filterbank(
        path,
        Filterbank(
            header=hdr,
            data=np.clip(np.rint(data), 0, 255).astype(np.uint8),
        ),
    )
    fil = read_filterbank(path)

    def cands(**kw):
        res = PeasoupSearch(
            SearchConfig(dm_end=20.0, min_snr=6.0, **kw)
        ).run(fil)
        return [(c.dm, c.acc, c.freq, c.snr, c.nh) for c in res.candidates]

    exact = cands()
    assert exact  # the injected pulsar was found
    assert cands(dedisp_engine="matmul") == exact
    # exact-subband (max_smear=0) completes the three-way
    assert cands(subbands=4, subband_smear=0.0) == exact
    assert cands(subbands=4, subband_smear=0.0, subband_matmul=True) == exact
