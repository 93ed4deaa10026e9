"""The Fourier-domain acceleration search (ISSUE 19): template-bank
math, the batched correlation program, and end-to-end recovery of
injected accelerated/jerked pulsars through the FDAS driver.

The injection recipes are the SAME conventions the device code claims:

* constant acceleration uses the time-domain resampler's inverse map
  (tests/test_accel_recovery.py) so the identical filterbank feeds
  both search paths — the cross-validation gate asserts FDAS and the
  resampling search agree on (P, acc, DM);
* jerk uses the template's own phase model
  ``phi(u) = b0*u + z*u^2/2 + w*u^3/6`` (u = t/T), so a detection at
  trial (z, w) proves the bank's sign/centre conventions end to end.

The halving tests pin the OOM ladder's contract: the search program's
peak sets are BITWISE-identical under any template-batch split, while
correlate_bank's raw output across batch shapes agrees to f32 rounding
(XLA's CPU FFT rounds per batch shape).
"""

import os

import numpy as np
import pytest

from peasoup_tpu.fdas.templates import (
    auto_segment,
    bank_geometry,
    build_template_bank,
    effective_zmax,
    template_half_width,
    w_trials,
    z_trials,
)
from peasoup_tpu.io.sigproc import (
    Filterbank,
    SigprocHeader,
    read_filterbank,
    write_filterbank,
)
from peasoup_tpu.ops.registry import ShapeCtx, registered_programs
from peasoup_tpu.ops.resample import accel_factor
from peasoup_tpu.pipeline.fdas import SPEED_OF_LIGHT, FdasConfig, FdasSearch
from peasoup_tpu.plan.dm_plan import DMPlan

NCHANS, TSAMP = 8, 0.004
FCH1, FOFF = 1500.0, -20.0
FFTN = 1 << 15  # choose_fft_size lands here after the dedisp trim
SIZE = FFTN + 64
P_INJ, DM_INJ = 0.02, 60.0
TOBS = FFTN * TSAMP  # 131.072 s
F0 = 1.0 / P_INJ


def _a_for_z(z: float) -> float:
    """Line-of-sight acceleration whose Fourier drift is z bins:
    z = -a*f*T^2/c."""
    return -z * SPEED_OF_LIGHT / (F0 * TOBS * TOBS)


def _make_fil(path, accel=0.0, z=None, w=0.0, seed=7):
    """Synthetic filterbank with one injected pulsar at DM_INJ.

    ``accel`` injects via the resampler's inverse map (exactly
    periodic after time-domain resampling at that acceleration);
    ``z``/``w`` inject via the FDAS template phase model directly.
    """
    rng = np.random.default_rng(seed)
    plan = DMPlan.create(SIZE + 64, NCHANS, TSAMP, FCH1, FOFF, 0.0, 100.0)
    nsamps = SIZE + plan.max_delay
    j = np.arange(nsamps, dtype=np.float64)
    if z is None:
        af = float(accel_factor(np.array([accel]), TSAMP)[0])
        ginv = j - af * j * (j - FFTN)
        phase = ginv * TSAMP / P_INJ
    else:
        u = j / FFTN
        b0 = F0 * TOBS - (z / 2.0 + w / 6.0)  # mean frequency == F0
        phase = b0 * u + z * u * u / 2.0 + w * u ** 3 / 6.0
    pulse = ((phase % 1.0) < 0.08) * 20.0
    delays = np.rint(
        (np.float32(DM_INJ) * np.abs(plan.delays)).astype(np.float32)
    ).astype(int)
    data = rng.normal(100, 8, size=(nsamps, NCHANS))
    for c in range(NCHANS):
        src = np.clip(j - delays[c], 0, nsamps - 1).astype(int)
        data[:, c] += pulse[src]
    hdr = SigprocHeader(
        source_name="fdas_inj", data_type=1, nchans=NCHANS, nbits=8,
        nifs=1, tsamp=TSAMP, tstart=50000.0, fch1=FCH1, foff=FOFF,
    )
    write_filterbank(
        path,
        Filterbank(header=hdr, data=np.clip(data, 0, 255).astype(np.uint8)),
    )
    return path


def _fdas_config(**kw):
    base = dict(
        dm_start=50.0, dm_end=70.0, zmax=32.0, zstep=2.0,
        nharmonics=2, limit=20,
    )
    base.update(kw)
    return FdasConfig(**base)


# --------------------------------------------------------------- bank


class TestTemplates:
    def test_zero_drift_template_is_exact_delta(self):
        """Row 0 (z=w=0) must be a unit impulse so the z=0 trial
        reproduces the plain periodicity spectrum bit for bit."""
        bank = build_template_bank(16.0)
        row0 = np.asarray(bank.templates[0])
        assert bank.zs[0] == 0.0 and bank.ws[0] == 0.0
        assert row0[bank.half] == 1.0 + 0.0j
        assert np.all(np.delete(row0, bank.half) == 0.0)

    def test_rows_unit_energy(self):
        bank = build_template_bank(32.0, 20.0)
        energy = np.sum(np.abs(np.asarray(bank.templates)) ** 2, axis=1)
        np.testing.assert_allclose(energy, 1.0, rtol=1e-3)

    def test_trial_grids(self):
        zs = z_trials(16.0, 2.0)
        assert zs[0] == 0.0 and len(zs) == 17
        assert set(zs) == {float(z) for z in range(-16, 18, 2)}
        assert np.abs(zs).max() == 16.0
        assert list(w_trials(0.0)) == [0.0]
        ws = w_trials(20.0, 20.0)
        assert set(ws) == {0.0, 20.0, -20.0}

    def test_bank_geometry_matches_built_bank(self):
        for zmax, wmax in ((16.0, 0.0), (32.0, 20.0)):
            bank = build_template_bank(zmax, wmax)
            nt, width, seg = bank_geometry(zmax, wmax)
            assert bank.ntemplates == nt
            assert bank.templates.shape == (nt, width)
            assert seg == auto_segment(width)

    def test_effective_zmax_roundtrip(self):
        """effective_zmax folds the jerk widening into one int the
        ShapeCtx can carry: the recovered width is exact."""
        for zmax, wmax in ((16.0, 0.0), (32.0, 20.0), (64.0, 40.0)):
            ez = effective_zmax(zmax, wmax)
            assert template_half_width(ez) == template_half_width(zmax, wmax)

    def test_auto_segment_power_of_two(self):
        for width in (33, 65, 129, 513):
            seg = auto_segment(width)
            assert seg & (seg - 1) == 0
            assert seg - (width - 1) > 0  # valid overlap-save step


# -------------------------------------------------------- correlation


class TestCorrelateBank:
    def test_matches_direct_evaluation(self):
        """Overlap-save output == the direct matched-filter sum
        out[t, r] = sum_j fser[r-half+j] * conj(tmpl[t, j])."""
        import jax.numpy as jnp

        from peasoup_tpu.ops.fdas import correlate_bank

        rng = np.random.default_rng(3)
        nbins, width = 700, 33
        half = (width - 1) // 2
        fser = (
            rng.standard_normal(nbins) + 1j * rng.standard_normal(nbins)
        ).astype(np.complex64)
        tmpl = (
            rng.standard_normal((4, width))
            + 1j * rng.standard_normal((4, width))
        ).astype(np.complex64)
        out = np.asarray(
            correlate_bank(jnp.asarray(fser), jnp.asarray(tmpl), segment=1024)
        )
        fpad = np.pad(fser, (half, half))
        direct = np.stack([
            np.array([
                np.sum(fpad[r:r + width] * np.conj(tmpl[t]))
                for r in range(nbins)
            ])
            for t in range(4)
        ])
        np.testing.assert_allclose(out, direct, rtol=2e-4, atol=2e-4)

    def test_row_split_bitwise(self):
        """Each template row's output depends on that row alone: at one
        batch shape, changing every other row leaves it bitwise equal.
        A split of the bank changes the batch shape, and XLA's CPU FFT
        (JAX 0.9) rounds per batch shape, so a split agrees with the
        unsplit call to f32 rounding (measured 1.8e-7 of the peak), not
        bitwise. The search program's peak sets stay bitwise under the
        OOM ladder's template-batch halving (next test)."""
        import jax.numpy as jnp

        from peasoup_tpu.ops.fdas import correlate_bank

        rng = np.random.default_rng(0)
        nbins = 2049
        fser = jnp.asarray((
            rng.standard_normal(nbins) + 1j * rng.standard_normal(nbins)
        ).astype(np.complex64))
        bank = build_template_bank(16.0)
        tmpl = np.asarray(bank.templates)
        seg = auto_segment(bank.templates.shape[1])
        full = np.asarray(correlate_bank(fser, jnp.asarray(tmpl), segment=seg))
        others = tmpl[::-1].copy()
        others[3] = tmpl[3]
        row3 = np.asarray(
            correlate_bank(fser, jnp.asarray(others), segment=seg)
        )[3]
        assert np.array_equal(full[3].view(np.float32), row3.view(np.float32))
        peak = np.abs(full).max()
        for at in (1, 5, 9):
            split = np.concatenate([
                np.asarray(correlate_bank(fser, jnp.asarray(t), segment=seg))
                for t in (tmpl[:at], tmpl[at:])
            ])
            assert np.abs(split - full).max() <= 1e-6 * peak, (
                f"split at {at} beyond f32 rounding"
            )

    def test_program_bitwise_under_template_batch_halving(self):
        """The FULL jitted program, dispatched driver-style (batches
        padded by repeating the last row), produces bitwise-identical
        peak sets for any template-batch size."""
        import jax.numpy as jnp

        from peasoup_tpu.ops.fdas import make_fdas_search_fn

        rng = np.random.default_rng(1)
        size = 4096
        tims = rng.integers(0, 40, size=(3, size), dtype=np.uint8)
        bank = build_template_bank(16.0)
        tmpl = np.asarray(bank.templates)
        ntmpl = tmpl.shape[0]
        seg = auto_segment(tmpl.shape[1])
        nbins = size // 2 + 1
        zap = np.zeros(nbins, bool)
        wins = np.array([[2, nbins]] * 3, np.int32)
        fn = make_fdas_search_fn(6.0)
        kw = dict(size=size, nsamps_valid=size, segment=seg, nharms=2,
                  max_peaks=32, pos5=2, pos25=10)

        def run(tm):
            r = fn(jnp.asarray(tims), jnp.asarray(tm), jnp.asarray(zap),
                   jnp.asarray(wins), **kw)
            return [np.asarray(a) for a in r]

        full = run(tmpl)
        for tb in (9, 4):
            parts = []
            for s in range(0, ntmpl, tb):
                b = tmpl[s:s + tb]
                if b.shape[0] < tb:
                    b = np.concatenate(
                        [b, np.repeat(b[-1:], tb - b.shape[0], axis=0)]
                    )
                parts.append((min(s + tb, ntmpl) - s, run(b)))
            for k in range(4):
                split = np.concatenate(
                    [r[k][:, :, :n] for n, r in parts], axis=2
                )
                assert np.array_equal(
                    np.ascontiguousarray(full[k]).view(np.uint8),
                    np.ascontiguousarray(split).view(np.uint8),
                ), f"output {k} not bitwise at tb={tb}"

    def test_segment_too_short_raises(self):
        import jax.numpy as jnp

        from peasoup_tpu.ops.fdas import correlate_bank

        fser = jnp.zeros(100, jnp.complex64)
        tmpl = jnp.zeros((2, 65), jnp.complex64)
        with pytest.raises(ValueError, match="too short"):
            correlate_bank(fser, tmpl, segment=64)


# ----------------------------------------------------------- registry


class TestRegistry:
    def test_param_hook_builds_driver_shapes(self):
        """The ShapeCtx hook maps an fdas ctx to the exact
        (dm_block, template_batch) tile the driver dispatches —
        uint8 trials trimmed to the valid length, complex64 templates
        at the geometry-formula width."""
        by_name = {s.name: s for s in registered_programs()}
        ctx = ShapeCtx(
            nsamps=4096, nchans=8, nbits=8, ndm=16, out_nsamps=4000,
            dm_block=4, dedisp_block=16, fft_size=4096, nharms=2,
            max_peaks=32, pos5=2, pos25=10, min_snr=9.0,
            fdas_templates=8, fdas_zmax=32, fdas_segment=1024,
        )
        width = 2 * template_half_width(32) + 1
        fn, args, kwargs = by_name[
            "ops.fdas.fdas_correlate_search"
        ].build_for(ctx)
        assert args[0].shape == (4, 4000) and args[0].dtype == "uint8"
        assert args[1].shape == (8, width)
        assert args[1].dtype == "complex64"
        assert kwargs["size"] == 4096 and kwargs["nsamps_valid"] == 4000
        assert kwargs["segment"] == 1024 and kwargs["nharms"] == 2

        fn, args, kwargs = by_name["ops.fdas.correlate_bank"].build_for(ctx)
        assert args[0].shape == (4096 // 2 + 1,)
        assert args[1].shape == (8, width)
        assert kwargs == {"segment": 1024}

    def test_param_hook_declines_non_fdas_ctx(self):
        by_name = {s.name: s for s in registered_programs()}
        ctx = ShapeCtx(
            nsamps=4096, nchans=8, nbits=8, ndm=16, out_nsamps=4000,
            dm_block=4, dedisp_block=16, fft_size=4096,
        )
        assert by_name["ops.fdas.fdas_correlate_search"].build_for(ctx) is None
        assert by_name["ops.fdas.correlate_bank"].build_for(ctx) is None

    def test_shape_ctx_for_fdas_bucket(self):
        """perf.warmup derives the fdas ctx with the driver's own
        geometry formulas, so hook-compiled shapes match dispatch."""
        from peasoup_tpu.perf.warmup import shape_ctx_for_bucket

        bucket = (8, 8, 4096, 0.000256, 1400.0, -16.0)
        ctx = shape_ctx_for_bucket(
            bucket, "fdas", {"dm_end": 20.0, "zmax": 16.0}
        )
        nt, width, seg = bank_geometry(16.0)
        assert ctx.fdas_templates == min(nt, 64)
        assert ctx.fdas_segment == seg
        assert ctx.fdas_zmax == effective_zmax(16.0, 0.0)
        assert 2 * template_half_width(ctx.fdas_zmax) + 1 == width
        assert 1 <= ctx.dm_block <= max(1, ctx.ndm)
        assert ctx.fft_size > 0


# ----------------------------------------------------------- recovery


@pytest.fixture(scope="module")
def fdas_fils(tmp_path_factory):
    """One filterbank per injection scenario, shared by the module."""
    d = tmp_path_factory.mktemp("fdasfil")
    return {
        "z0": _make_fil(str(d / "z0.fil"), accel=0.0),
        "midz": _make_fil(str(d / "midz.fil"), accel=_a_for_z(-24.0)),
        "edge": _make_fil(str(d / "edge.fil"), accel=_a_for_z(-32.0)),
        "jerk": _make_fil(str(d / "jerk.fil"), z=-12.0, w=-20.0),
    }


def _assert_period(top):
    assert abs(1.0 / top.freq - P_INJ) / P_INJ < 1e-4, 1.0 / top.freq


class TestRecovery:
    def test_z0_parity_with_time_domain_search(self, fdas_fils):
        """Unaccelerated pulsar: the z=0 template row reproduces the
        plain periodicity search EXACTLY (same top frequency and S/N),
        and the candidate's acceleration fields are exactly zero."""
        from peasoup_tpu.pipeline.search import PeasoupSearch, SearchConfig

        fil = read_filterbank(fdas_fils["z0"])
        fres = FdasSearch(_fdas_config()).run(fil)
        assert fres.candidates
        ftop = fres.candidates[0]
        _assert_period(ftop)
        assert ftop.z == 0.0 and ftop.w == 0.0
        assert ftop.fdot == 0.0 and ftop.fddot == 0.0
        assert ftop.acc == 0.0
        assert ftop.snr > 50.0

        tres = PeasoupSearch(SearchConfig(
            dm_start=50.0, dm_end=70.0, acc_start=-30.0, acc_end=30.0,
            acc_pulse_width=834.0, nharmonics=2, npdmp=1, limit=20,
        )).run(fil)
        ttop = tres.candidates[0]
        assert ttop.acc == 0.0
        assert ftop.freq == ttop.freq  # exact: the z=0 row is a delta
        assert ftop.snr == ttop.snr

    @pytest.mark.parametrize("key,z_inj", [("midz", -24.0), ("edge", -32.0)])
    def test_recovers_injected_acceleration(self, fdas_fils, key, z_inj):
        """Mid-grid and zmax-edge drifts: the matching template wins
        and the reported f-dot is within 5% of the injected value
        (ISSUE 19 satellite gate)."""
        res = FdasSearch(_fdas_config()).run(read_filterbank(fdas_fils[key]))
        assert res.candidates
        top = res.candidates[0]
        _assert_period(top)
        assert top.z == z_inj, (top.z, top.snr)
        acc_inj = _a_for_z(z_inj)
        fdot_inj = -acc_inj * F0 / SPEED_OF_LIGHT
        assert abs(top.fdot - fdot_inj) / abs(fdot_inj) < 0.05
        assert abs(top.acc - acc_inj) / acc_inj < 0.05
        assert top.snr > 9.5
        # the DM grid is coarse at this narrow fractional bandwidth:
        # within one trial spacing of the injected DM
        assert abs(top.dm - DM_INJ) < 11.0

    def test_recovers_injected_jerk(self, fdas_fils):
        """With the f-ddot plane on, the (z, w) trial matching the
        injected phase model wins both axes."""
        cfg = _fdas_config(zmax=16.0, wmax=20.0, wstep=20.0)
        res = FdasSearch(cfg).run(read_filterbank(fdas_fils["jerk"]))
        assert res.candidates
        assert res.n_templates == 17 * 3  # z grid x w in {0, +20, -20}
        top = res.candidates[0]
        _assert_period(top)
        assert top.z == -12.0 and top.w == -20.0
        fddot_inj = -20.0 / TOBS ** 3
        assert abs(top.fddot - fddot_inj) / abs(fddot_inj) < 0.05
        assert top.snr > 10.0

    def test_cross_validation_with_time_domain_search(self, fdas_fils):
        """The tentpole gate: FDAS and the time-domain resampling
        search recover the SAME injected constant-acceleration pulsar
        from the SAME filterbank — matching period, acceleration
        (within both grids' quanta) and DM trial."""
        from peasoup_tpu.pipeline.search import PeasoupSearch, SearchConfig
        from peasoup_tpu.plan.accel_plan import AccelerationPlan

        fil = read_filterbank(fdas_fils["midz"])
        ftop = FdasSearch(_fdas_config()).run(fil).candidates[0]
        ttop = PeasoupSearch(SearchConfig(
            dm_start=50.0, dm_end=70.0, acc_start=7000.0, acc_end=10000.0,
            acc_pulse_width=1000.0, nharmonics=2, npdmp=1, limit=20,
        )).run(fil).candidates[0]
        assert abs(1.0 / ftop.freq - 1.0 / ttop.freq) / P_INJ < 1e-4
        assert abs(ftop.dm - ttop.dm) < 11.0
        # acceleration agreement bounded by the two grid quanta: the
        # time-domain trial step plus FDAS's zstep in acceleration
        plan = AccelerationPlan(
            acc_lo=7000.0, acc_hi=10000.0, tol=1.10, pulse_width=1000.0,
            nsamps=FFTN, tsamp=TSAMP,
            cfreq=FCH1 + (NCHANS / 2) * FOFF, bw=FOFF,
        )
        quantum = plan.step(ttop.dm) + abs(_a_for_z(2.0))
        assert abs(ftop.acc - ttop.acc) <= quantum, (ftop.acc, ttop.acc)
        assert ftop.acc > 0 and ttop.acc > 0

    def test_template_block_invariant_results(self, fdas_fils):
        """Driver-level halving: shrinking template_block (what the
        OOM ladder does under device pressure) leaves the full
        candidate list identical."""
        fil = read_filterbank(fdas_fils["edge"])

        def cands(tb):
            res = FdasSearch(_fdas_config(template_block=tb)).run(fil)
            return [
                (c.freq, c.snr, c.dm, c.z, c.w, c.nh, c.acc, c.fdot)
                for c in res.candidates
            ]

        full = cands(0)  # auto: the whole bank in one dispatch
        assert full
        assert cands(8) == full
        assert cands(5) == full

    def test_writes_fdas_outputs(self, fdas_fils, tmp_path):
        """overview.xml carries the <fdas_search> section and the
        (f, f-dot) candidate fields, and the text table round-trips."""
        import xml.etree.ElementTree as ET

        from peasoup_tpu.io.output import (
            OutputFileWriter,
            write_fdas_candidates,
        )

        fil = read_filterbank(fdas_fils["midz"])
        cfg = _fdas_config(outdir=str(tmp_path))
        res = FdasSearch(cfg).run(fil)
        writer = OutputFileWriter()
        writer.add_fdas_section(cfg, res.zs, res.ws)
        writer.add_candidates_fdas(res.candidates, {})
        xml_path = os.path.join(str(tmp_path), "overview.xml")
        writer.to_file(xml_path)
        root = ET.parse(xml_path).getroot()
        sec = root.find("fdas_search")
        assert sec is not None
        assert sec.find("search_parameters/zmax") is not None
        trials = sec.find("fdot_trials")
        assert trials is not None
        assert int(trials.get("count")) == len(res.zs)
        cand = root.find("candidates/candidate")
        assert cand is not None
        assert float(cand.find("fdot").text) != 0.0
        assert cand.find("z") is not None

        txt = os.path.join(str(tmp_path), "candidates.fdas")
        write_fdas_candidates(txt, res.candidates)
        lines = open(txt).read().strip().splitlines()
        assert "fdot" in lines[0]
        assert len(lines) == len(res.candidates) + 1
