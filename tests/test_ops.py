"""Per-op numeric tests against tiny NumPy oracles (SURVEY.md §4)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from peasoup_tpu.ops import (
    dedisperse,
    dedisperse_block,
    form_power,
    form_interpolated,
    spectrum_stats,
    normalise,
    median_scrunch5,
    linear_stretch,
    running_median,
    deredden,
    birdie_mask,
    zap_birdies,
    resample_accel,
    resample_accel_quadratic,
    accel_factor,
    harmonic_sums,
    find_peaks_device,
    cluster_peaks,
    fold_time_series,
    fold_time_series_np,
    coincidence_mask,
)
from peasoup_tpu.ops.fold import fold_bins_np
from peasoup_tpu.ops.fold_optimise import FoldOptimiser, calculate_sn


class TestSpectrum:
    def test_form_power(self, rng):
        z = (rng.normal(size=64) + 1j * rng.normal(size=64)).astype(np.complex64)
        out = np.asarray(form_power(jnp.asarray(z)))
        np.testing.assert_allclose(out, np.abs(z), rtol=1e-6)

    def test_form_interpolated_oracle(self, rng):
        z = (rng.normal(size=64) + 1j * rng.normal(size=64)).astype(np.complex64)
        out = np.asarray(form_interpolated(jnp.asarray(z)))
        zl = np.concatenate([[0.0 + 0j], z[:-1]])
        oracle = np.sqrt(np.maximum(np.abs(z) ** 2, 0.5 * np.abs(z - zl) ** 2))
        np.testing.assert_allclose(out, oracle, rtol=1e-5)

    def test_form_interpolated_batched(self, rng):
        z = (rng.normal(size=(3, 32)) + 1j * rng.normal(size=(3, 32))).astype(
            np.complex64
        )
        out = np.asarray(form_interpolated(jnp.asarray(z)))
        assert out.shape == (3, 32)

    def test_stats_and_normalise(self, rng):
        x = rng.normal(loc=5.0, scale=2.0, size=4096).astype(np.float32)
        mean, rms, std = spectrum_stats(jnp.asarray(x))
        assert float(mean) == pytest.approx(x.mean(), rel=1e-5)
        assert float(rms) == pytest.approx(np.sqrt((x.astype(np.float64)**2).mean()), rel=1e-5)
        assert float(std) == pytest.approx(x.std(), rel=1e-3)
        out = np.asarray(normalise(jnp.asarray(x), mean, std))
        assert abs(out.mean()) < 1e-3
        assert out.std() == pytest.approx(1.0, rel=1e-3)


class TestRednoise:
    def test_median_scrunch5_oracle(self, rng):
        x = rng.normal(size=100).astype(np.float32)
        out = np.asarray(median_scrunch5(jnp.asarray(x)))
        oracle = np.median(x.reshape(20, 5), axis=-1)
        np.testing.assert_allclose(out, oracle, rtol=1e-6)

    def test_median_scrunch5_truncates(self, rng):
        x = rng.normal(size=103).astype(np.float32)
        out = np.asarray(median_scrunch5(jnp.asarray(x)))
        assert out.shape == (20,)  # tail of 3 ignored (kernels.cu:972-973)

    def test_linear_stretch_oracle(self):
        x = np.array([0.0, 1.0, 4.0, 9.0], dtype=np.float32)
        out = np.asarray(linear_stretch(jnp.asarray(x), 7))
        step = 3.0 / 6.0
        oracle = []
        for i in range(7):
            pos = i * step
            j = int(pos)
            frac = pos - j
            if frac > 1e-5:
                oracle.append(x[j] + frac * (x[j + 1] - x[j]))
            else:
                oracle.append(x[j])
        np.testing.assert_allclose(out, oracle, rtol=1e-6)

    def test_running_median_flat_spectrum(self, rng):
        # a flat(ish) spectrum should produce a median near its level
        x = rng.normal(loc=10.0, scale=0.1, size=5**4).astype(np.float32)
        med = np.asarray(running_median(jnp.asarray(x), pos5=20, pos25=100))
        assert med.shape == x.shape
        np.testing.assert_allclose(med, 10.0, atol=0.5)

    def test_deredden_zeroes_first_bins(self, rng):
        z = (rng.normal(size=32) + 1j * rng.normal(size=32)).astype(np.complex64)
        med = np.full(32, 2.0, dtype=np.float32)
        out = np.asarray(deredden(jnp.asarray(z), jnp.asarray(med)))
        np.testing.assert_array_equal(out[:5], 0.0)
        np.testing.assert_allclose(out[5:], z[5:] / 2.0, rtol=1e-6)

    def test_running_median_tracks_red_noise(self, rng):
        # red-noise-like 1/f ramp: median should follow the ramp closely
        n = 5**5
        ramp = (1.0 + 100.0 / (np.arange(n) + 10)).astype(np.float32)
        noise = rng.normal(loc=1.0, scale=0.02, size=n).astype(np.float32)
        x = ramp * noise
        med = np.asarray(running_median(jnp.asarray(x), pos5=50, pos25=500))
        sel = slice(10, n - 200)  # away from edges
        np.testing.assert_allclose(med[sel] / ramp[sel], 1.0, atol=0.15)


class TestZap:
    def test_birdie_mask_ranges(self):
        mask = birdie_mask(np.array([10.0]), np.array([1.0]), 1.0, 64)
        # bins [floor(9), ceil(11)) = [9, 11)
        assert mask[9] and mask[10] and not mask[11] and not mask[8]

    def test_birdie_mask_clip_top_quirk(self):
        # clipped at the top: high becomes nbins-1, half-open range stops
        # at nbins-2 (kernels.cu:1054-1056)
        mask = birdie_mask(np.array([63.5]), np.array([5.0]), 1.0, 64)
        assert mask[62] and not mask[63]

    def test_zap_birdies(self, rng):
        z = (rng.normal(size=16) + 1j * rng.normal(size=16)).astype(np.complex64)
        mask = np.zeros(16, dtype=bool)
        mask[3:6] = True
        out = np.asarray(zap_birdies(jnp.asarray(z), jnp.asarray(mask)))
        np.testing.assert_array_equal(out[3:6], 1.0 + 0.0j)
        np.testing.assert_array_equal(out[~mask], z[~mask])


class TestResample:
    def test_zero_accel_identity(self, rng):
        x = rng.normal(size=1024).astype(np.float32)
        out = np.asarray(resample_accel(jnp.asarray(x), jnp.zeros(1, np.float32)))
        np.testing.assert_array_equal(out[0], x)

    def test_matches_f64_oracle(self, rng):
        n = 4096
        x = (np.arange(n) % 451).astype(np.float32)  # reference test pattern
        for a in (125.5, -125.5, 10.0):
            af = accel_factor(np.array([a]), tsamp=0.000064)
            out = np.asarray(
                resample_accel(jnp.asarray(x), jnp.asarray(af, dtype=jnp.float32))
            )[0]
            idx = np.arange(n, dtype=np.float64)
            src = np.rint(idx + af[0] * idx * (idx - n)).astype(np.int64)
            src = np.clip(src, 0, n - 1)
            oracle = x[src]
            # f32 index math may differ from f64 at round-to-half ties only
            mismatches = np.mean(out != oracle)
            assert mismatches < 1e-3

    def test_large_accel_visible_shift(self):
        n = 1 << 16
        x = np.zeros(n, dtype=np.float32)
        x[n // 2] = 1.0
        af = np.array([2e-9])  # shift at midpoint = af*n^2/4 ~ 2.1 samples
        out = np.asarray(
            resample_accel(jnp.asarray(x), jnp.asarray(af, dtype=jnp.float32))
        )[0]
        idx = np.arange(n, dtype=np.float64)
        src = np.clip(np.rint(idx + af[0] * idx * (idx - n)), 0, n - 1).astype(int)
        oracle = x[src]
        np.testing.assert_array_equal(out, oracle)
        assert out[n // 2] == 0.0  # midpoint now reads ~2 samples ahead
        assert out.sum() >= 1.0

    def test_quadratic_variant_zero_at_midpoint_shift(self, rng):
        n = 1024
        x = rng.normal(size=n).astype(np.float32)
        out = np.asarray(
            resample_accel_quadratic(jnp.asarray(x), jnp.float32(0.0))
        )
        np.testing.assert_array_equal(out, x)


class TestHarmonics:
    @staticmethod
    def oracle(p, nharms):
        n = len(p)
        outs = []
        val = p.astype(np.float64).copy()
        for h in range(1, nharms + 1):
            for k in range(1, 2 ** h, 2):
                idx = (np.arange(n) * k + 2 ** (h - 1)) >> h
                val = val + p[idx]
            outs.append(val * 2.0 ** (-h / 2.0))
        return outs

    def test_matches_float_index_oracle(self, rng):
        p = rng.normal(size=1000).astype(np.float32)
        outs = harmonic_sums(jnp.asarray(p), nharms=5)
        # cross-check integer index map == float index map of the kernel
        n = len(p)
        for h in range(1, 6):
            for k in range(1, 2 ** h, 2):
                int_idx = (np.arange(n) * k + 2 ** (h - 1)) >> h
                float_idx = (np.arange(n) * (k / 2 ** h) + 0.5).astype(np.int64)
                np.testing.assert_array_equal(int_idx, float_idx)
        oracles = self.oracle(p, 5)
        for out, oracle in zip(outs, oracles):
            # f32 accumulation vs f64 oracle
            np.testing.assert_allclose(np.asarray(out), oracle, rtol=1e-4, atol=1e-5)

    def test_impulse_train_gains(self):
        # fundamental at bin 512 with harmonics at 256, 128, ...: the
        # harmonic sum at the fundamental grows as expected
        p = np.zeros(1024, dtype=np.float32)
        for b in (512, 256, 128, 64, 32):
            p[b] = 1.0
        outs = harmonic_sums(jnp.asarray(p), nharms=4)
        assert float(outs[0][512]) == pytest.approx(2 / np.sqrt(2))
        assert float(outs[3][512]) == pytest.approx(5 / 4.0)

    def test_batched(self, rng):
        p = rng.normal(size=(3, 256)).astype(np.float32)
        outs = harmonic_sums(jnp.asarray(p), nharms=2)
        assert outs[0].shape == (3, 256)
        single = harmonic_sums(jnp.asarray(p[1]), nharms=2)
        np.testing.assert_allclose(np.asarray(outs[0][1]), np.asarray(single[0]))

    @pytest.mark.parametrize("nbins", [96, 256, 1000, 4097])
    @pytest.mark.parametrize("method", ["conv"])
    def test_matmul_methods_match_take_bitwise(self, rng, nbins, method):
        """The one-hot conv formulation must reproduce the direct
        gather EXACTLY (one-hot taps -> exact values; zero adds are
        exact; reference summation order preserved), on awkward
        non-multiple-of-32 sizes too."""
        p = rng.normal(size=(2, nbins)).astype(np.float32)
        got = harmonic_sums(jnp.asarray(p), nharms=5, method=method)
        take = harmonic_sums(jnp.asarray(p), nharms=5, method="take")
        for a, b in zip(got, take):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestPeaks:
    def test_device_compaction(self):
        spec = np.zeros(256, dtype=np.float32)
        spec[[10, 50, 51, 200]] = [5.0, 7.0, 6.0, 9.0]
        idxs, snrs, count = find_peaks_device(
            jnp.asarray(spec), 4.0, 0, 256, max_peaks=16
        )
        idxs, snrs = np.asarray(idxs), np.asarray(snrs)
        assert int(count) == 4
        np.testing.assert_array_equal(idxs[:4], [10, 50, 51, 200])
        np.testing.assert_allclose(snrs[:4], [5.0, 7.0, 6.0, 9.0])
        assert np.all(idxs[4:] == 256)

    def test_window_applied(self):
        spec = np.full(128, 10.0, dtype=np.float32)
        idxs, snrs, count = find_peaks_device(
            jnp.asarray(spec), 4.0, 30, 40, max_peaks=32
        )
        assert int(count) == 10
        np.testing.assert_array_equal(np.asarray(idxs)[:10], np.arange(30, 40))

    def test_cluster_semantics(self):
        # two clusters: [100 (snr 5), 110 (snr 8), 120 (snr 6)], [200]
        idxs = np.array([100, 110, 120, 200])
        snrs = np.array([5.0, 8.0, 6.0, 7.0])
        pi, ps = cluster_peaks(idxs, snrs, 4, min_gap=30)
        np.testing.assert_array_equal(pi, [110, 200])
        np.testing.assert_allclose(ps, [8.0, 7.0])

    def test_cluster_lastidx_quirk(self):
        # lastidx only advances on a new max: 0(5), 20(4), 40(3) ->
        # 40-0 >= 30 breaks the cluster even though 40-20 < 30
        idxs = np.array([0, 20, 40])
        snrs = np.array([5.0, 4.0, 3.0])
        pi, ps = cluster_peaks(idxs, snrs, 3, min_gap=30)
        np.testing.assert_array_equal(pi, [0, 40])

    def test_batched_shapes(self, rng):
        spec = rng.normal(size=(4, 5, 128)).astype(np.float32)
        idxs, snrs, count = find_peaks_device(
            jnp.asarray(spec), 2.0, 0, 128, max_peaks=64
        )
        assert idxs.shape == (4, 5, 64)
        assert count.shape == (4, 5)

    def test_device_cluster_matches_host_fuzz(self, rng):
        """cluster_peaks_device is an exact on-device port of the host
        identify_unique_peaks walk (quirk included)."""
        from peasoup_tpu.ops.peaks import cluster_peaks_device

        nbins = 1500
        for _ in range(60):
            spec = np.abs(rng.normal(1, 1.0, size=nbins)).astype(np.float32)
            for _ in range(rng.integers(0, 6)):
                c = int(rng.integers(0, nbins))
                w = int(rng.integers(1, 70))
                spec[max(0, c - w // 2): c + w // 2] += rng.uniform(3, 9)
            idxs, snrs, count = find_peaks_device(
                jnp.asarray(spec), 4.0, 0, nbins, max_peaks=256
            )
            idxs, snrs, count = np.asarray(idxs), np.asarray(snrs), int(count)
            if count > 256:
                continue
            hi, hs = cluster_peaks(idxs, snrs, count)
            ci, cs, cc = cluster_peaks_device(
                jnp.asarray(idxs), jnp.asarray(snrs), jnp.int32(nbins)
            )
            cc = int(cc)
            assert cc == len(hi)
            np.testing.assert_array_equal(np.asarray(ci)[:cc], hi)
            np.testing.assert_allclose(np.asarray(cs)[:cc], hs)
            assert np.all(np.asarray(ci)[cc:] == nbins)

    def test_device_cluster_full_slots(self):
        """A completely full slot axis (no padding) still flushes the
        final cluster via the appended sentinel step."""
        from peasoup_tpu.ops.peaks import cluster_peaks_device

        idxs = np.arange(0, 400, 100, dtype=np.int32)  # 4 slots, all real
        snrs = np.array([5.0, 6.0, 7.0, 8.0], dtype=np.float32)
        ci, cs, cc = cluster_peaks_device(
            jnp.asarray(idxs), jnp.asarray(snrs), jnp.int32(1000)
        )
        assert int(cc) == 4
        np.testing.assert_array_equal(np.asarray(ci), idxs)


class TestDedisperse:
    def test_realigns_dispersed_impulse(self):
        t, c, true_delay = 256, 8, 4
        fil = np.zeros((t, c), dtype=np.uint8)
        t0 = 100
        for ch in range(c):
            fil[t0 + ch * true_delay // 2, ch] = 3  # linear-ish sweep
        delays = np.array(
            [[ch * true_delay // 2 for ch in range(c)]], dtype=np.int32
        )
        out = np.asarray(
            dedisperse_block(
                jnp.asarray(fil),
                jnp.asarray(delays),
                jnp.ones(c, jnp.int32),
                out_nsamps=t - int(delays.max()),
            )
        )
        assert out.shape == (1, t - delays.max())
        assert out[0, t0] == 3 * c  # all channels realigned
        assert (out[0] > 0).sum() <= c  # everything else near-empty

    def test_killmask(self):
        t, c = 64, 4
        fil = np.ones((t, c), dtype=np.uint8)
        kill = np.array([1, 0, 1, 0], dtype=np.int32)
        out = np.asarray(
            dedisperse_block(
                jnp.asarray(fil),
                jnp.zeros((1, c), jnp.int32),
                jnp.asarray(kill),
                out_nsamps=t,
            )
        )
        np.testing.assert_array_equal(out[0], 2)

    def test_blocked_host_wrapper_matches(self, rng):
        t, c, d = 128, 8, 7
        fil = rng.integers(0, 4, size=(t, c)).astype(np.uint8)
        delays = rng.integers(0, 16, size=(d, c)).astype(np.int32)
        out_nsamps = t - int(delays.max())
        got = dedisperse(fil, delays, np.ones(c, np.int32), out_nsamps, block=3)
        oracle = np.zeros((d, out_nsamps))
        for di in range(d):
            for ch in range(c):
                oracle[di] += fil[delays[di, ch] : delays[di, ch] + out_nsamps, ch]
        np.testing.assert_array_equal(got, np.clip(np.rint(oracle), 0, 255))

    def _plan_delays(self, d=24, c=32, dm_max=80.0):
        """A realistic monotone (D, C) delay table (cold-plasma law)."""
        from peasoup_tpu.plan.dm_plan import delay_table

        dms = np.linspace(0.0, dm_max, d).astype(np.float32)
        k = np.abs(delay_table(1400.0, -8.0, c, 0.000256))
        return np.rint(dms[:, None].astype(np.float64) * k[None, :]).astype(
            np.int32
        ), dms

    def test_subband_exact_at_zero_smear(self, rng):
        """max_smear=0 forces singleton groups, where the two-stage
        decomposition telescopes: t + d[ref] + (d[c] - d[ref]) = t + d[c]
        — bitwise equal to the direct path."""
        from peasoup_tpu.ops.dedisperse import dedisperse_subband

        delays, _ = self._plan_delays()
        t = 2048 + int(delays.max())
        c = delays.shape[1]
        fil = rng.integers(0, 4, size=(t, c)).astype(np.uint8)
        out_nsamps = t - int(delays.max())
        direct = dedisperse(fil, delays, np.ones(c, np.int32), out_nsamps)
        sub = dedisperse_subband(
            fil, delays, np.ones(c, np.int32), out_nsamps,
            nsub=8, max_smear=0.0, to_host=True,
        )
        np.testing.assert_array_equal(direct, sub)

    def test_subband_grouping_bounds_smear(self, rng):
        """Grouped trials may differ from direct, but only by shifts
        bounded by max_smear: the dispersed impulse must still realign
        to (near) full amplitude at every trial."""
        from peasoup_tpu.ops.dedisperse import (
            dedisperse_subband,
            subband_groups,
        )

        delays, _ = self._plan_delays()
        d, c = delays.shape
        groups = subband_groups(delays, nsub=8, max_smear=2.0)
        assert sum(hi - lo for lo, hi in groups) == d
        assert len(groups) < d  # actually grouped something

        # impulse dispersed at trial 13's exact delays
        t = 2048 + int(delays.max())
        fil = np.zeros((t, c), dtype=np.uint8)
        t0, di = 700, 13
        for ch in range(c):
            fil[t0 + delays[di, ch], ch] = 3
        out_nsamps = t - int(delays.max())
        sub = np.asarray(
            dedisperse_subband(
                fil, delays, np.ones(c, np.int32), out_nsamps,
                nsub=8, max_smear=2.0,
            )
        )
        # energy conserved and concentrated within the smear window
        window = sub[di, t0 - 3 : t0 + 4].astype(int)
        assert window.sum() == 3 * c
        assert sub[di].astype(int).sum() == 3 * c

    def test_subband_awkward_nsub(self, rng):
        """nsub values where ceil(C/ceil(C/nsub)) != nsub (e.g. 5 bands
        over 16 channels -> width 4 -> only 4 bands) must reduce to the
        effective band count, not crash."""
        from peasoup_tpu.ops.dedisperse import dedisperse_subband

        delays, _ = self._plan_delays(d=6, c=16)
        t = 512 + int(delays.max())
        fil = rng.integers(0, 4, size=(t, 16)).astype(np.uint8)
        out_nsamps = t - int(delays.max())
        direct = dedisperse(fil, delays, np.ones(16, np.int32), out_nsamps)
        for nsub in (5, 7, 16, 40):
            sub = dedisperse_subband(
                fil, delays, np.ones(16, np.int32), out_nsamps,
                nsub=nsub, max_smear=0.0, to_host=True,
            )
            np.testing.assert_array_equal(direct, sub)

    def test_channel_chunked_device_wrapper(self, rng):
        """Tiny chunk_bytes forces channel chunking (with a padded tail
        chunk) AND the DM-segment recursion; results must equal the
        unchunked path exactly for integer inputs."""
        from peasoup_tpu.ops.dedisperse import dedisperse_device

        t, c, d = 2048, 23, 21  # awkward: c % cc != 0, d % seg != 0
        fil = rng.integers(0, 4, size=(t, c)).astype(np.uint8)
        delays = np.sort(
            rng.integers(0, 99, size=(d, c)).astype(np.int32), axis=0
        )
        kill = (rng.random(c) > 0.2).astype(np.int32)
        out_nsamps = t - int(delays.max())
        ref = np.asarray(
            dedisperse_device(fil, delays, kill, out_nsamps, scale=0.5)
        )
        got = np.asarray(
            dedisperse_device(
                fil, delays, kill, out_nsamps, scale=0.5,
                chunk_bytes=t * 4 * 5,  # 5 channels per chunk
                block=4,
            )
        )
        np.testing.assert_array_equal(ref, got)

    def test_spill_segments_match_device(self, rng):
        from peasoup_tpu.ops.dedisperse import dedisperse, dedisperse_device

        t, c, d = 1024, 8, 11
        fil = rng.integers(0, 4, size=(t, c)).astype(np.uint8)
        delays = np.sort(
            rng.integers(0, 64, size=(d, c)).astype(np.int32), axis=0
        )
        out_nsamps = t - int(delays.max())
        ref = np.asarray(
            dedisperse_device(fil, delays, np.ones(c, np.int32), out_nsamps)
        )
        got = dedisperse(fil, delays, np.ones(c, np.int32), out_nsamps,
                         block=4)
        np.testing.assert_array_equal(ref, got)

    def test_subband_killmask_and_scale(self, rng):
        from peasoup_tpu.ops.dedisperse import dedisperse_subband

        delays, _ = self._plan_delays(d=6, c=16)
        t = 1024 + int(delays.max())
        fil = rng.integers(0, 255, size=(t, 16)).astype(np.uint8)
        kill = (rng.random(16) > 0.3).astype(np.int32)
        out_nsamps = t - int(delays.max())
        a = dedisperse(fil, delays, kill, out_nsamps, scale=0.1)
        b = dedisperse_subband(
            fil, delays, kill, out_nsamps, nsub=4, max_smear=0.0,
            scale=0.1, to_host=True,
        )
        np.testing.assert_array_equal(a, b)


class TestFold:
    def test_matches_np_oracle(self, rng):
        n, nbins, nints = 4096, 32, 8
        x = rng.normal(size=n).astype(np.float32)
        period, tsamp = 0.025, 0.000064
        oracle = fold_time_series_np(x, n, tsamp, period, nbins, nints)
        flat = fold_bins_np(n, tsamp, period, nbins, nints)
        out = np.asarray(
            fold_time_series(
                jnp.asarray(x[: len(flat)]), jnp.asarray(flat), nbins=nbins, nints=nints
            )
        )
        np.testing.assert_allclose(out, oracle, rtol=1e-4)

    def test_count_bias(self):
        # constant input: output = sum/(hits+1) = hits/(hits+1) != 1
        n, nbins, nints = 1024, 16, 4
        x = np.ones(n, dtype=np.float32)
        out = fold_time_series_np(x, n, 0.000064, 0.001024, nbins, nints)
        assert np.all(out < 1.0)
        assert np.all(out > 0.5)

    def test_recovers_pulse_phase(self):
        n, nbins, nints = 1 << 15, 64, 8
        tsamp, period = 0.000064, 0.004096
        t = np.arange(n) * tsamp
        phase = (t / period) % 1.0
        x = (np.abs(phase - 0.25) < 0.02).astype(np.float32) * 10.0
        prof = fold_time_series_np(x, n, tsamp, period, nbins, nints).mean(axis=0)
        assert abs(int(np.argmax(prof)) - 16) <= 1  # 0.25 phase -> bin 16

    # tsamp = 2**-14 s is exact in binary, so a period of 16 samples
    # puts every sample on one of 16 exact phases: most bins stay empty
    @pytest.mark.parametrize(
        "nbins, nints, periods, tsamp, n_pad",
        [
            (16, 4, (0.025, 0.0371), 0.000064, 0),
            (16, 16, (0.025, 0.0371), 0.000064, 0),
            (64, 4, (0.025, 0.0371), 0.000064, 0),
            (64, 16, (0.025, 0.0371), 0.000064, 0),
            (64, 16, (16 * 2.0**-14,), 2.0**-14, 0),
            (64, 16, (0.025, 0.0371, 0.0062), 0.000064, 5),
        ],
        ids=["b16-i4", "b16-i16", "b64-i4", "b64-i16", "empty-bins",
             "padding-rows"],
    )
    def test_dense_fold_matches_oracle(self, rng, nbins, nints, periods,
                                       tsamp, n_pad):
        """Batched dense fold against the f64 oracle, row by row;
        padding rows that repeat row 0 fold to row 0's output exactly."""
        n = 1 << 14
        x = rng.normal(loc=3.0, size=(len(periods), n)).astype(np.float32)
        bins = np.stack(
            [fold_bins_np(n, tsamp, p, nbins, nints) for p in periods]
        )
        rows = np.r_[np.arange(len(periods)), np.zeros(n_pad, dtype=int)]
        out = np.asarray(
            fold_time_series(
                jnp.asarray(x[rows][:, : bins.shape[1]]),
                jnp.asarray(bins[rows]),
                nbins=nbins,
                nints=nints,
            )
        )
        assert out.shape == (len(rows), nints, nbins)
        for r, p in enumerate(periods):
            oracle = fold_time_series_np(x[r], n, tsamp, p, nbins, nints)
            np.testing.assert_allclose(out[r], oracle, rtol=1e-4)
        if tsamp == 2.0**-14:
            assert (out == 0).sum() == nints * (nbins - 16)
        for r in range(len(periods), len(rows)):
            np.testing.assert_array_equal(out[r], out[0])

    @staticmethod
    def _accel_for_shift(shift, n, tsamp):
        """An acceleration whose quadratic map's largest shift at n
        samples rounds to ``shift``."""
        from peasoup_tpu.ops.resample import SPEED_OF_LIGHT

        return (shift + 0.25) / (n / 2) ** 2 * 2 * SPEED_OF_LIGHT / tsamp

    # the tutorial geometry (2^17 samples at 320 us) folds its whole
    # +-5 m/s^2 grid on the identity path; the shorter series take
    # larger accelerations to reach each span
    @pytest.mark.parametrize(
        "n, tsamp, shifts, accs, n_real, span",
        [
            (1 << 17, 3.2e-4, (), (0.0, -5.0, 5.0), 3, 0),
            (1 << 14, 6.4e-5, (1, -1), (), 2, 2),
            (1 << 14, 6.4e-5, (3, -2, 0), (), 3, 4),
            (1 << 15, 6.4e-5, (8, -8, 5, 1), (), 4, 16),
            (1 << 14, 6.4e-5, (0, 5, 0, -3, 0), (0.0,), 6, 8),
            (1 << 14, 6.4e-5, (70, -90, 3), (), 3, None),
            (1 << 14, 6.4e-5, (2, -1), (0.0, 2.0), 4, 4),
        ],
        ids=["tutorial-identity", "select-span1", "select-span3",
             "select-span8", "mixed-identity-rows", "gather-past-limit",
             "padding-rows"],
    )
    def test_fold_accel_quadratic_matches_gather(self, rng, n, tsamp,
                                                 shifts, accs, n_real,
                                                 span):
        """The folder's one-program resample + fold is bitwise equal to
        the quadratic gather followed by the dense fold, on each path;
        rows padded to the batch of eight (repeating row 0) fold to row
        0's output."""
        from peasoup_tpu.ops.fold import fold_accel_quadratic
        from peasoup_tpu.ops.resample import (
            accel_factor, quadratic_span, resample_accel_quadratic,
        )

        nbins, nints = 64, 16
        acc = [self._accel_for_shift(s, n, tsamp) for s in shifts]
        acc += list(accs)
        assert len(acc) == n_real
        rows = np.r_[np.arange(n_real), np.zeros(8 - n_real, dtype=int)]
        afs = accel_factor(np.asarray(acc)[rows], tsamp).astype(np.float32)
        assert quadratic_span(afs, n) == span
        periods = rng.uniform(0.005, 0.5, size=n_real)
        bins = np.stack(
            [fold_bins_np(n, tsamp, p, nbins, nints) for p in periods]
        )[rows]
        x = jnp.asarray(rng.normal(loc=2.0, size=n).astype(np.float32))
        xr = jax.vmap(resample_accel_quadratic, in_axes=(None, 0))(
            x, jnp.asarray(afs)
        )
        want = np.asarray(
            fold_time_series(
                xr[:, : bins.shape[1]], jnp.asarray(bins),
                nbins=nbins, nints=nints,
            )
        )
        got = np.asarray(
            fold_accel_quadratic(
                x, jnp.asarray(afs), jnp.asarray(bins),
                span=span, nbins=nbins, nints=nints,
            )
        )
        np.testing.assert_array_equal(got, want)
        moved = [not np.array_equal(row, np.asarray(x)) for row in xr]
        assert any(moved) == (span != 0)  # the rows really were resampled
        for r in range(n_real, 8):
            np.testing.assert_array_equal(got[r], got[0])

    @pytest.mark.parametrize("program", ["fold_time_series",
                                         "survey_fold_batch"])
    def test_fold_programs_hold_no_scatter(self, program):
        from peasoup_tpu.ops.survey_fold import survey_fold_batch

        if program == "fold_time_series":
            lowered = fold_time_series.lower(
                jnp.zeros((8, 1024), jnp.float32),
                jnp.zeros((8, 1024), jnp.uint8), nbins=64, nints=16,
            )
        else:
            lowered = survey_fold_batch.lower(
                jnp.zeros((4, 1024), jnp.float32),
                jnp.zeros(4, jnp.float32),
                jnp.zeros((4, 1024), jnp.uint8), nbins=64, nints=16,
            )
        text = lowered.as_text()
        assert "fold" in text and "scatter" not in text

    def test_bin_tables_only_for_real_candidates(self, monkeypatch):
        """Three candidates on one DM trial pad to a batch of eight:
        three exact bin tables, and the counters say 3 real + 5."""
        from peasoup_tpu import obs
        from peasoup_tpu.core.candidates import Candidate
        from peasoup_tpu.pipeline import folder as folder_mod

        calls = []

        def counting(*args):
            calls.append(args)
            return fold_bins_np(*args)

        monkeypatch.setattr(folder_mod, "fold_bins_np", counting)
        rng = np.random.default_rng(5)
        trials = rng.integers(20, 45, size=(2, 4096), dtype=np.uint8)
        cands = [
            Candidate(dm=1.0, dm_idx=1, acc=a, snr=9.0, freq=1.0 / p)
            for a, p in ((0.0, 0.011), (3.0, 0.017), (-4.0, 0.023))
        ]
        mf = folder_mod.MultiFolder(trials, 4096, 0.000256)
        tel = obs.RunTelemetry()
        with tel.activate():
            out = mf.fold_outcomes(cands, len(cands))
        assert len(out) == 3
        assert mf.fold_bucket == 8
        assert sorted(c[2] for c in calls) == sorted(
            1.0 / c.freq for c in cands
        )
        assert tel.counters["fold.groups"] == 1
        assert tel.counters["fold.rows"] == 3
        assert tel.counters["fold.rows_padded"] == 5


class TestFoldOptimise:
    def make_fold(self, nbins=64, nints=16, drift_bins=6.0, width=4):
        """Pulse at drifting phase across subints (a slightly-wrong period)."""
        rng = np.random.default_rng(0)
        folds = rng.normal(0.0, 0.1, size=(nints, nbins)).astype(np.float32)
        for i in range(nints):
            centre = int(20 + drift_bins * i / nints) % nbins
            for b in range(centre - width // 2, centre + width // 2 + 1):
                folds[i, b % nbins] += 5.0
        return folds

    def test_recovers_drift(self):
        opt = FoldOptimiser(64, 16)
        folds = self.make_fold(drift_bins=6.0)
        res = opt.optimise(folds[None], np.array([0.25]), tobs=41.94)[0]
        # drift of +6 bins over the fold -> optimal shift magnitude ~6 from
        # centre (32); period correction must move away from p
        assert res["opt_sn"] > 10
        assert abs((32 - res["opt_shift"])) in range(4, 9)
        assert res["opt_period"] != pytest.approx(0.25, abs=1e-9)

    def test_zero_drift_keeps_period(self):
        opt = FoldOptimiser(64, 16)
        folds = self.make_fold(drift_bins=0.0)
        res = opt.optimise(folds[None], np.array([0.25]), tobs=41.94)[0]
        assert res["opt_shift"] == 32  # no shift -> (32-32)=0 correction
        assert res["opt_period"] == pytest.approx(0.25, rel=1e-12)
        assert res["opt_sn"] > 10

    def test_batched_equals_single(self):
        opt = FoldOptimiser(64, 16)
        f1 = self.make_fold(drift_bins=3.0)
        f2 = self.make_fold(drift_bins=-5.0)
        batch = opt.optimise(
            np.stack([f1, f2]), np.array([0.25, 0.1]), tobs=41.94
        )
        single = opt.optimise(f2[None], np.array([0.1]), tobs=41.94)[0]
        assert batch[1]["opt_shift"] == single["opt_shift"]
        assert batch[1]["opt_sn"] == pytest.approx(single["opt_sn"], rel=1e-5)

    def test_calculate_sn_width_zero(self):
        prof = np.random.default_rng(1).normal(size=64)
        sn1, sn2 = calculate_sn(prof, 10, 0, 64)
        assert sn1 == 0.0  # sqrt(0) kills sn1; sn2 -> inf -> squashed


class TestCoincidence:
    def test_mask(self):
        beams = np.zeros((4, 8), dtype=np.float32)
        beams[:, 3] = 10.0  # all beams fire at sample 3
        beams[0, 5] = 10.0  # one beam fires at sample 5
        out = np.asarray(coincidence_mask(jnp.asarray(beams), 4.0, 3))
        assert out[3] == 0.0  # multibeam -> masked
        assert out[5] == 1.0  # single beam -> kept


class TestCompactPeaks:
    """Ragged device-side peak compaction (ops/peaks.py:
    compact_peaks_device) and its host-side inverses
    (pipeline/search.py: _densify_ragged, segmented-distill reindex)."""

    def test_fuzz_against_dense(self):
        import jax.numpy as jnp

        from peasoup_tpu.ops.peaks import compact_peaks_device
        from peasoup_tpu.pipeline.search import _densify_ragged

        rng = np.random.default_rng(5)
        for trial in range(20):
            shape = tuple(
                int(rng.integers(1, 5)) for _ in range(int(rng.integers(1, 4)))
            )
            mp = int(rng.integers(1, 9))
            idxs = rng.integers(0, 1000, size=(*shape, mp)).astype(np.int32)
            snrs = rng.normal(size=(*shape, mp)).astype(np.float32)
            # counts may exceed slot capacity (fused-kernel overflow)
            cc = rng.integers(0, mp + 3, size=shape).astype(np.int32)
            total = int(np.minimum(cc, mp).sum())
            total_pad = 1 << max(3, int(np.ceil(np.log2(max(1, total)))))
            packed = np.asarray(
                compact_peaks_device(
                    jnp.asarray(idxs), jnp.asarray(snrs), jnp.asarray(cc),
                    total_pad=total_pad,
                )
            )
            vi = packed[:total_pad]
            vs = packed[total_pad:].view(np.float32)
            # oracle: concatenate each cell's first min(cc, mp) slots
            ccl = np.minimum(cc, mp).reshape(-1)
            exp_i = np.concatenate(
                [idxs.reshape(-1, mp)[k, : ccl[k]] for k in range(ccl.size)]
                or [np.zeros(0, np.int32)]
            )
            exp_s = np.concatenate(
                [snrs.reshape(-1, mp)[k, : ccl[k]] for k in range(ccl.size)]
                or [np.zeros(0, np.float32)]
            )
            np.testing.assert_array_equal(vi[:total], exp_i)
            np.testing.assert_array_equal(vs[:total], exp_s)
            assert (vi[total:] == 0).all()
            # round-trip through the fallback densifier
            di, ds, dcc = _densify_ragged(
                vi[:total], vs[:total].astype(np.float64),
                np.minimum(cc, mp),
            )
            for k in range(ccl.size):
                np.testing.assert_array_equal(
                    di.reshape(-1, di.shape[-1])[k, : ccl[k]],
                    idxs.reshape(-1, mp)[k, : ccl[k]],
                )


class TestMatmulRFFT:
    """The packed-real four-step matmul rfft (ops/fft.py) — the TPU
    hot-path FFT — against numpy's f64 rfft."""

    @pytest.mark.parametrize("n", [1 << 14, 1 << 15, 1 << 17])
    def test_matches_numpy(self, rng, n):
        from peasoup_tpu.ops.fft import rfft_pow2_matmul_parts

        # zero-mean like the whitened series the pipeline transforms (a
        # large DC term would dominate the error scale: absolute DFT
        # error grows with ||x||, and the CPU backend's einsum runs
        # plain f32 regardless of the precision request)
        x = rng.normal(0.0, 10.0, size=(3, n)).astype(np.float32)
        re, im = jax.jit(rfft_pow2_matmul_parts)(jnp.asarray(x))
        out = np.asarray(re) + 1j * np.asarray(im)
        ref = np.fft.rfft(x.astype(np.float64), axis=-1)
        scale = np.sqrt(np.mean(np.abs(ref) ** 2))
        assert np.max(np.abs(out - ref)) / scale < 1e-5
        assert out.shape == (3, n // 2 + 1)


def test_resample_select_packed_bitwise():
    """resample_select_packed's planes are BITWISE the even/odd lanes
    of resample_select (same clip-to-edge gather semantics)."""
    import jax.numpy as jnp

    from peasoup_tpu.ops.resample import (
        resample_select, resample_select_packed,
    )

    rng = np.random.default_rng(7)
    n, smax = 4096, 5
    x = jnp.asarray(rng.normal(size=(3, n)).astype(np.float32))
    afs = jnp.asarray(
        np.asarray(
            [[0.0, 2.3e-7, -2.3e-7, 1.1e-7]] * 3, dtype=np.float32
        )
    )
    full = np.asarray(resample_select(x, afs, smax=smax))
    ev, od = resample_select_packed(x, afs, smax=smax)
    np.testing.assert_array_equal(np.asarray(ev), full[..., 0::2])
    np.testing.assert_array_equal(np.asarray(od), full[..., 1::2])


def test_resample_select_packed_planes_bitwise():
    """resample_select_packed_planes' (.., n1, n2) planes are BITWISE
    the row-major reshape of resample_select's even/odd lanes — the
    zero-relayout producer for the fused DFT kernel
    (ops/pallas/dftspec.py plane_factors order j = j1*n2 + j2)."""
    import jax.numpy as jnp

    from peasoup_tpu.ops.pallas.dftspec import plane_factors
    from peasoup_tpu.ops.resample import (
        resample_select, resample_select_packed_planes,
    )

    rng = np.random.default_rng(8)
    n, smax = 1 << 13, 5
    n1, n2 = plane_factors(n // 2)
    x = jnp.asarray(rng.normal(size=(3, n)).astype(np.float32))
    afs = jnp.asarray(
        np.asarray(
            [[0.0, 2.9e-8, -2.9e-8, 1.3e-8]] * 3, dtype=np.float32
        )
    )
    full = np.asarray(resample_select(x, afs, smax=smax))
    ev, od = resample_select_packed_planes(x, afs, smax=smax, n1=n1, n2=n2)
    assert ev.shape == (3, 4, n1, n2)
    np.testing.assert_array_equal(
        np.asarray(ev).reshape(3, 4, -1), full[..., 0::2]
    )
    np.testing.assert_array_equal(
        np.asarray(od).reshape(3, 4, -1), full[..., 1::2]
    )
    import pytest

    with pytest.raises(ValueError):
        resample_select_packed_planes(x, afs, smax=smax, n1=n1, n2=n2 * 2)
