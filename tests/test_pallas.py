"""Interpret-mode parity tests for the Pallas TPU kernels vs their
pure-jnp twins (which are themselves oracle-tested in test_ops.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from peasoup_tpu.ops.pallas.resample import (
    choose_block,
    resample_block,
    resample_block_pallas,
)
from peasoup_tpu.ops.resample import accel_factor, resample_accel


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


class TestChooseBlock:
    def test_zero_accel_gives_max(self):
        assert choose_block(0.0, 1 << 20) == 2048

    def test_scales_down_with_slope(self):
        # af*N*blk <= 2 must hold for the returned block
        n = 1 << 20
        af = 1e-9
        blk = choose_block(af, n)
        assert blk >= 128 and af * n * blk <= 2.0

    def test_extreme_slope_rejects(self):
        assert choose_block(1e-3, 1 << 23) == 0

    def test_tiny_n_rejects(self):
        assert choose_block(0.0, 128) == 0


class TestResamplePallas:
    @pytest.mark.parametrize("n,accs", [
        (4096, [0.0, 50.0, -50.0]),
        (16384, [5.0, -5.0, 125.5, -125.5]),
    ])
    def test_matches_jnp_twin_bitwise(self, rng, n, accs):
        tsamp = 256e-6
        x = rng.normal(size=(2, n)).astype(np.float32)
        afs = np.stack([
            accel_factor(np.asarray(accs), tsamp).astype(np.float32)
        ] * 2)
        af_max = float(np.abs(afs).max())
        blk = choose_block(af_max, n)
        assert blk > 0
        got = resample_block_pallas(
            jnp.asarray(x), jnp.asarray(afs), block=blk, interpret=True
        )
        want = jax.vmap(resample_accel)(jnp.asarray(x), jnp.asarray(afs))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("sign", [1.0, -1.0])
    @pytest.mark.parametrize("af_n_blk", [0.5, 1.0, 1.5, 2.0])
    def test_boundary_blocks_at_high_slope(self, rng, sign, af_n_blk):
        """Regression: with af*N*blk near the precondition limit, the
        shift varies across the window margin in the FIRST block (af>0)
        and LAST block (af<0); a clamped-window design silently
        corrupted those blocks. Must stay bitwise equal to the twin."""
        n, blk = 4096, 512
        af = np.float32(sign * af_n_blk / (n * blk))
        x = rng.normal(size=(1, n)).astype(np.float32)
        afs = np.full((1, 1), af, dtype=np.float32)
        got = resample_block_pallas(
            jnp.asarray(x), jnp.asarray(afs), block=blk, interpret=True
        )
        want = jax.vmap(resample_accel)(jnp.asarray(x), jnp.asarray(afs))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_differing_afs_per_dm_row(self, rng):
        n = 4096
        x = rng.normal(size=(3, n)).astype(np.float32)
        afs = rng.uniform(-1e-7, 1e-7, size=(3, 4)).astype(np.float32)
        got = resample_block_pallas(
            jnp.asarray(x), jnp.asarray(afs), block=512, interpret=True
        )
        want = jax.vmap(resample_accel)(jnp.asarray(x), jnp.asarray(afs))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_dispatch_fallback_on_bad_shapes(self, rng):
        # N too small for any valid block: dispatcher must fall back to
        # the jnp twin, not raise
        n = 128
        x = rng.normal(size=(1, n)).astype(np.float32)
        afs = np.zeros((1, 2), dtype=np.float32)
        out = resample_block(
            jnp.asarray(x), jnp.asarray(afs), 0.0, interpret=True
        )
        want = jax.vmap(resample_accel)(jnp.asarray(x), jnp.asarray(afs))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_dispatch_uses_pallas_when_valid(self, rng, monkeypatch):
        # outputs are bitwise identical either way, so assert the Pallas
        # kernel actually ran (a dispatch regression would otherwise be
        # invisible)
        import peasoup_tpu.ops.pallas.resample as mod

        calls = []
        real = mod.resample_block_pallas

        def spy(*args, **kw):
            calls.append(kw.get("block"))
            return real(*args, **kw)

        monkeypatch.setattr(mod, "resample_block_pallas", spy)
        n = 2048
        x = rng.normal(size=(1, n)).astype(np.float32)
        afs = np.full((1, 2), 1e-8, dtype=np.float32)
        out = resample_block(
            jnp.asarray(x), jnp.asarray(afs), 1e-8, interpret=True
        )
        assert calls, "dispatch did not take the Pallas path"
        want = jax.vmap(resample_accel)(jnp.asarray(x), jnp.asarray(afs))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


class TestBlockCoreParity:
    """search_block_core must equal vmap(search_trial_core), with and
    without the Pallas resample path."""

    def _inputs(self, rng, size=4096, d=3, a=4, nharms=2):
        from peasoup_tpu.pipeline.search import _level_windows

        t = np.arange(size)
        tims = np.stack([
            np.clip(
                rng.normal(30, 3, size=size)
                + 12.0 * (((t * 0.000256) / 0.032) % 1.0 < 0.1),
                0, 255,
            ) for _ in range(d)
        ]).astype(np.uint8)
        accs = np.linspace(-20.0, 20.0, a)
        afs = np.stack([
            accel_factor(accs, 0.000256).astype(np.float32)
        ] * d)
        zap = jnp.zeros(size // 2 + 1, dtype=bool)
        windows = jnp.asarray(_level_windows(size, nharms, 0.1, 1100.0, 0.000256))
        return jnp.asarray(tims), jnp.asarray(afs), zap, windows, nharms

    def test_block_core_matches_vmapped_trial_core(self, rng):
        from peasoup_tpu.pipeline.accel_search import (
            search_block_core,
            search_trial_core,
        )

        tims, afs, zap, windows, nharms = self._inputs(rng)
        kw = dict(
            threshold=6.0, size=tims.shape[1], nsamps_valid=tims.shape[1],
            nharms=nharms, max_peaks=64, pos5=8, pos25=80,
        )
        blocked = search_block_core(tims, afs, zap, windows, **kw)
        trial = jax.vmap(
            lambda t_, a_: search_trial_core(t_, a_, zap, windows, **kw)
        )(tims, afs)
        np.testing.assert_array_equal(np.asarray(blocked.idxs), np.asarray(trial.idxs))
        np.testing.assert_array_equal(np.asarray(blocked.snrs), np.asarray(trial.snrs))
        np.testing.assert_array_equal(np.asarray(blocked.counts), np.asarray(trial.counts))

    def test_block_core_pallas_matches_jnp(self, rng):
        from peasoup_tpu.pipeline.accel_search import search_block_core
        from peasoup_tpu.ops.pallas.resample import choose_block

        tims, afs, zap, windows, nharms = self._inputs(rng)
        af_max = float(np.abs(np.asarray(afs)).max())
        blk = choose_block(af_max, tims.shape[1])
        assert blk > 0
        kw = dict(
            threshold=6.0, size=tims.shape[1], nsamps_valid=tims.shape[1],
            nharms=nharms, max_peaks=64, pos5=8, pos25=80,
        )
        plain = search_block_core(tims, afs, zap, windows, **kw)
        pallas = search_block_core(
            tims, afs, zap, windows, **kw,
            pallas_block=blk, pallas_interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(plain.idxs), np.asarray(pallas.idxs))
        np.testing.assert_array_equal(np.asarray(plain.snrs), np.asarray(pallas.snrs))
        np.testing.assert_array_equal(np.asarray(plain.counts), np.asarray(pallas.counts))


@pytest.mark.parametrize("backend,size,packed,want", [
    ("tpu", 1 << 13, True, (True, "fft")),  # below the interbin kernel
    ("tpu", 1 << 17, True, (True, "dftspec")),  # the tutorial cell
    ("tpu", 1 << 21, True, (True, "interbin")),  # the survey cell
    ("tpu", 1 << 23, True, (True, "interbin")),  # the four-chip cell
    ("cpu", 1 << 17, True, (False, "fft")),
    ("cpu", 1 << 21, True, (False, "fft")),
    # the Pallas resample emits whole series, not planes for the DFT
    ("tpu", 1 << 17, False, (True, "interbin")),
])
def test_search_route(backend, size, packed, want):
    """One search program per platform and series length."""
    from peasoup_tpu.pipeline.accel_search import SearchRoute, search_route

    assert search_route(backend, size, packed) == SearchRoute(*want)


def test_block_core_kernel_route_matches_twin():
    """The TPU route below 2^14 points (the specchain kernel, the jnp
    spectrum, the harmonic-peaks kernel), in interpret mode, against
    the CPU route's jnp twins: the same peaks, S/N inside the
    specchain kernel's amplitude envelope."""
    from peasoup_tpu.ops.pallas.specchain import s0_envelope
    from peasoup_tpu.pipeline.accel_search import (
        SearchRoute,
        search_block_core,
        search_route,
    )
    from peasoup_tpu.pipeline.search import _level_windows

    rng = np.random.default_rng(3)
    size, nharms = 2048, 2
    d, a = 2, 3
    t = np.arange(size)
    tims = jnp.asarray(
        np.clip(
            rng.normal(30, 3, size=(d, size))
            + 12.0 * (((t * 0.000256) / 0.016) % 1.0 < 0.08),
            0, 255,
        ).astype(np.uint8)
    )
    afs = jnp.asarray(np.zeros((d, a), np.float32))
    zap = jnp.zeros(size // 2 + 1, bool)
    windows = jnp.asarray(_level_windows(size, nharms, 0.1, 1100.0, 0.000256))
    kw = dict(
        threshold=6.0, size=size, nsamps_valid=size, nharms=nharms,
        max_peaks=64, pos5=8, pos25=80,
    )
    route = search_route("tpu", size, packed=True)
    assert route == SearchRoute(kernels=True, spectrum="fft")
    twin = search_block_core(tims, afs, zap, windows, **kw)
    kern = search_block_core(
        tims, afs, zap, windows, **kw, route=route, pallas_interpret=True
    )
    for field in ("idxs", "counts", "ccounts"):
        np.testing.assert_array_equal(
            np.asarray(getattr(twin, field)), np.asarray(getattr(kern, field))
        )
    assert int(np.asarray(twin.ccounts).sum()) > 0
    snr_twin, snr_kern = np.asarray(twin.snrs), np.asarray(kern.snrs)
    assert (np.abs(snr_kern - snr_twin) <= s0_envelope(snr_twin)).all()


class TestHarmPeaks:
    """Interpret-mode parity of the harmonic+peaks mega-kernel
    (ops/pallas/harmpeaks.py) against harmonic_sums(method="take") +
    the jnp find_peaks_device/cluster_peaks_device pair — BITWISE,
    including the in-VMEM one-hot gather accumulation, per-level
    scaling, garbage pad-tail masking, and row padding."""

    def _oracle_levels(self, s, nharms):
        import jax.numpy as jnp

        from peasoup_tpu.ops.harmonics import harmonic_sums

        return [jnp.asarray(s)] + harmonic_sums(
            jnp.asarray(s), nharms=nharms, method="take", scaled=True
        )

    @pytest.mark.parametrize("nharms,nbins,rows", [(4, 6000, 9), (2, 4500, 3)])
    def test_bitwise_vs_take_oracle(self, nharms, nbins, rows):
        import jax.numpy as jnp

        from peasoup_tpu.ops.pallas.harmpeaks import (
            find_harmonic_cluster_peaks,
        )
        from peasoup_tpu.ops.pallas.peaks import PEAKS_BLOCK
        from peasoup_tpu.ops.peaks import (
            cluster_peaks_device,
            find_peaks_device,
        )

        nlev = nharms + 1
        mx = 64
        rng = np.random.default_rng(0)
        s = np.abs(rng.normal(size=(rows, nbins))).astype(np.float32)
        s[::3, ::61] += 30.0
        s[min(1, rows - 1), nbins // 2 : nbins // 2 + 400 : 4] += 20.0
        lo, hi = nbins // 10, nbins - nbins // 16
        windows = np.tile(np.asarray([[lo, hi]], np.int32), (nlev, 1))
        npad = -(-nbins // PEAKS_BLOCK) * PEAKS_BLOCK
        # garbage past the true bins, like the fused-interbin pad region
        sp = jnp.asarray(
            np.pad(s, ((0, 0), (0, npad - nbins)), constant_values=1e9)
        )
        scales = tuple(
            1.0 if lv == 0 else 2.0 ** (-lv / 2.0) for lv in range(nlev)
        )
        ci, cs, rc, cc = find_harmonic_cluster_peaks(
            sp, jnp.asarray(windows), nharms=nharms, threshold=9.0,
            max_peaks=mx, scales=scales, nbins=nbins, interpret=True,
        )
        ci, cs, rc, cc = map(np.asarray, (ci, cs, rc, cc))
        levels = self._oracle_levels(s, nharms)
        for lv in range(nlev):
            i_, s_, c_ = find_peaks_device(
                levels[lv], jnp.float32(9.0), jnp.int32(lo), jnp.int32(hi),
                max_peaks=1 << 14,
            )
            ji, js, jc = cluster_peaks_device(i_, s_, jnp.int32(nbins))
            ji, js, jc, c_ = map(np.asarray, (ji, js, jc, c_))
            np.testing.assert_array_equal(rc[:, lv], c_)
            np.testing.assert_array_equal(cc[:, lv], jc)
            for r in range(rows):
                k = min(int(jc[r]), mx)
                np.testing.assert_array_equal(ci[r, lv, :k], ji[r, :k])
                np.testing.assert_array_equal(cs[r, lv, :k], js[r, :k])
                if int(jc[r]) <= mx:
                    assert (ci[r, lv, k:] == nbins).all()
                    assert (cs[r, lv, k:] == 0).all()

    def test_batched_shape_and_validation(self):
        import jax.numpy as jnp

        from peasoup_tpu.ops.pallas.harmpeaks import (
            find_harmonic_cluster_peaks,
        )
        from peasoup_tpu.ops.pallas.peaks import PEAKS_BLOCK

        rng = np.random.default_rng(3)
        nbins = PEAKS_BLOCK  # exactly one block, no separate pad
        s = np.abs(rng.normal(size=(2, 3, nbins))).astype(np.float32)
        s[..., 500] = 40.0
        windows = jnp.asarray(
            np.tile(np.asarray([[10, nbins]], np.int32), (3, 1))
        )
        ci, cs, rc, cc = find_harmonic_cluster_peaks(
            jnp.asarray(s), windows, nharms=2, threshold=9.0,
            max_peaks=8, scales=(1.0, 0.5, 0.25), interpret=True,
        )
        assert ci.shape == (2, 3, 3, 8) and rc.shape == (2, 3, 3)
        # the planted tone must be the top cluster everywhere on level 0
        assert (np.asarray(ci)[..., 0, 0] == 500).all()
        with pytest.raises(ValueError, match="multiple"):
            find_harmonic_cluster_peaks(
                jnp.asarray(s[..., : nbins - 4]), windows, nharms=2,
                threshold=9.0, max_peaks=8, scales=(1.0, 0.5, 0.25),
                interpret=True,
            )
        with pytest.raises(ValueError, match="levels"):
            find_harmonic_cluster_peaks(
                jnp.asarray(s), windows, nharms=3, threshold=9.0,
                max_peaks=8, scales=(1.0, 0.5, 0.25, 0.1), interpret=True,
            )


class TestPallasDedisperse:
    """Interpret-mode parity of the Pallas dedispersion kernel
    (ops/pallas/dedisperse.py) against the jnp scan."""

    def _delays(self, d, c, dm_max=60.0):
        from peasoup_tpu.plan.dm_plan import delay_table

        k = np.abs(delay_table(1400.0, -8.0, c, 0.000256))
        dms = np.linspace(0.0, dm_max, d)
        return np.rint(dms[:, None] * k[None, :]).astype(np.int32)

    # the large/odd-row cases cost ~15 s each in the interpreter; one
    # even and one odd geometry stay in the fast run, the rest ride
    # the slow marker (the kernel itself is identical across them)
    @pytest.mark.parametrize(
        "d,c,t",
        [
            (6, 16, 4096),
            pytest.param(24, 32, 8192, marks=pytest.mark.slow),
            (8, 16, 1500),
            pytest.param(9, 17, 3000, marks=pytest.mark.slow),
        ],
    )
    def test_matches_jnp_bitwise(self, rng, d, c, t):
        from peasoup_tpu.ops.dedisperse import dedisperse
        from peasoup_tpu.ops.pallas.dedisperse import dedisperse_pallas

        delays = self._delays(d, c)
        out_nsamps = t - int(delays.max())
        fil = rng.integers(0, 4, size=(t, c)).astype(np.uint8)
        kill = (rng.random(c) > 0.2).astype(np.int32)
        ref = dedisperse(fil, delays, kill, out_nsamps, scale=0.7)
        got = np.asarray(
            dedisperse_pallas(
                fil, delays, kill, out_nsamps, scale=0.7, interpret=True
            )
        )
        np.testing.assert_array_equal(ref, got)

    def test_unquantized_f32(self, rng):
        from peasoup_tpu.ops.dedisperse import dedisperse_block
        from peasoup_tpu.ops.pallas.dedisperse import dedisperse_pallas

        delays = self._delays(8, 16)
        t = 4096
        out_nsamps = t - int(delays.max())
        fil = rng.normal(10.0, 2.0, size=(t, 16)).astype(np.float32)
        ref = np.asarray(
            dedisperse_block(
                jnp.asarray(fil), jnp.asarray(delays),
                jnp.ones(16, jnp.float32), out_nsamps=out_nsamps,
                quantize=False,
            )
        )
        got = np.asarray(
            dedisperse_pallas(
                fil, delays, np.ones(16, np.int32), out_nsamps,
                quantize=False, interpret=True,
            )
        )
        np.testing.assert_array_equal(ref, got)

    def test_plan_spread(self):
        from peasoup_tpu.ops.pallas.dedisperse import _DT, plan_spread

        delays = self._delays(3 * _DT + 2, 16)
        s = plan_spread(delays)
        assert s >= 0
        # spread of any aligned chunk never exceeds the reported max
        for lo in range(0, delays.shape[0], _DT):
            blk = delays[lo : lo + _DT]
            assert int((blk.max(0) - blk.min(0)).max()) <= s


def _twin_tol(twin):
    # the PRODUCTION envelope (single source: ops/pallas/dftspec.py
    # twin_envelope, also used by probe_pallas_dftspec) so CI and the
    # on-TPU gate can't drift apart
    from peasoup_tpu.ops.pallas.dftspec import twin_envelope

    return twin_envelope(twin)


def _assert_per_bin_twin(got, twin):
    """Per-bin structural oracle (see dftspec.twin_envelope): the twin
    replays the kernel with the same term grouping, so the only
    legitimate deviation is FMA-contraction codegen (bitwise 0 when
    both compile fresh; measured max ~1.4e-5 of the envelope
    denominator when the persistent compile cache serves an executable
    built on a different host). The bound is per bin — a structural
    fault (shifted lanes, wrong carry, bad clamp) perturbs bins by
    O(rms), five orders above it, and fails every bin it breaks (see
    the negative tests)."""
    bad = np.abs(got - twin) > _twin_tol(twin)
    assert not bad.any(), (
        f"{bad.sum()} bins beyond the FMA-class envelope; "
        f"max dev {np.abs(got - twin).max()}"
    )


class TestPallasInterbin:
    """Fused untwist+interbin+normalise kernel (ops/pallas/interbin.py),
    interpret mode, against TWO oracles:

    1. per-bin vs untwist_interbin_normalise_twin — the kernel's grid
       walk replayed in pure jnp with the same term grouping, asserted
       at the FMA-codegen envelope (see _assert_per_bin_twin): every
       bin is checked tightly, so a structural fault that keeps some
       bins correct still fails all the bins it breaks.
    2. allclose vs the differently-grouped jnp chain (packed matmul
       rfft parts -> interbin -> normalise) — guards the twin+kernel
       pair against a shared formula bug.

    On-TPU the kernel is additionally gated BITWISE against the jnp
    chain itself (probe_pallas_interbin: 0 differing bins on v5e)."""

    def _case(self, r, n, block, seed=0):
        import jax.numpy as jnp

        from peasoup_tpu.ops.fft import (
            packed_dft_z, rfft_pow2_matmul_parts,
        )
        from peasoup_tpu.ops.pallas.interbin import (
            untwist_interbin_normalise, untwist_interbin_normalise_twin,
        )
        from peasoup_tpu.ops.spectrum import (
            form_interpolated_parts, normalise,
        )

        rng = np.random.default_rng(seed)
        m = n // 2
        npad = (m // block + 1) * block
        # a tone + noise so interbin's max() takes both branches
        t = np.arange(n)
        x = rng.normal(size=(r, n)) + 3.0 * np.sin(2 * np.pi * t * 0.1317)
        x = jnp.asarray(x.astype(np.float32))
        mean = jnp.asarray(rng.normal(size=r).astype(np.float32))
        std = jnp.asarray((0.5 + rng.random(r)).astype(np.float32))
        zr, zi = packed_dft_z(x)
        got = np.asarray(
            untwist_interbin_normalise(
                zr, zi, mean, std, npad=npad, block=block, interpret=True
            )
        )
        twin = np.asarray(
            untwist_interbin_normalise_twin(
                zr, zi, mean, std, npad=npad, block=block
            )
        )
        ref = np.asarray(
            normalise(
                form_interpolated_parts(*rfft_pow2_matmul_parts(x)),
                mean, std,
            )
        )
        assert got.shape == (r, npad)
        _assert_per_bin_twin(got, twin)
        np.testing.assert_allclose(
            got[:, : m + 1], ref, rtol=1e-5, atol=1e-5
        )
        assert not got[:, m + 1 :].any()

    def test_per_bin_vs_twin_and_close_to_chain(self):
        self._case(r=9, n=1 << 14, block=1024)

    def test_negative_lane_shift_fails_oracle(self):
        # the oracle must CATCH a structural fault: a kernel that came
        # back with every lane shifted by one (classic roll-lowering
        # bug) must not pass the bitwise-vs-twin assertion
        import jax.numpy as jnp

        from peasoup_tpu.ops.fft import packed_dft_z
        from peasoup_tpu.ops.pallas.interbin import (
            untwist_interbin_normalise, untwist_interbin_normalise_twin,
        )

        rng = np.random.default_rng(7)
        r, n, block = 8, 1 << 13, 1024
        m = n // 2
        npad = (m // block + 1) * block
        x = jnp.asarray(rng.normal(size=(r, n)).astype(np.float32))
        mean = jnp.asarray(rng.normal(size=r).astype(np.float32))
        std = jnp.asarray((0.5 + rng.random(r)).astype(np.float32))
        zr, zi = packed_dft_z(x)
        good = np.asarray(
            untwist_interbin_normalise(
                zr, zi, mean, std, npad=npad, block=block, interpret=True
            )
        )
        twin = np.asarray(
            untwist_interbin_normalise_twin(
                zr, zi, mean, std, npad=npad, block=block
            )
        )
        _assert_per_bin_twin(good, twin)
        bad = np.roll(good, 1, axis=1)
        # ... and the shift breaks MOST bins by far more than the
        # envelope, not a stray ULP
        assert (np.abs(bad - twin) > _twin_tol(twin)).mean() > 0.5

    def test_row_padding_and_multi_stripe(self):
        # r not a multiple of 8 exercises the row-pad path (std pads
        # with ones so no 0/0 NaNs leak); 17 rows = 3 stripes
        self._case(r=17, n=1 << 14, block=2048, seed=3)

    def test_block_equals_m_over_two(self):
        # two z blocks + one pure-pad block past the Nyquist
        self._case(r=8, n=1 << 13, block=2048, seed=5)

    def test_geometry_validation(self):
        import jax.numpy as jnp
        import pytest

        from peasoup_tpu.ops.pallas.interbin import (
            untwist_interbin_normalise,
        )

        z = jnp.zeros((8, 4096), jnp.float32)
        v = jnp.ones((8,), jnp.float32)
        with pytest.raises(ValueError):
            untwist_interbin_normalise(z, z, v, v, npad=4096, block=4096)
        with pytest.raises(ValueError):
            untwist_interbin_normalise(z, z, v, v, npad=8192, block=2560)


class TestPallasDftspec:
    """Fused four-step DFT + untwist + interbin + normalise kernel
    (ops/pallas/dftspec.py), interpret mode, against the same two-layer
    oracle design as probe_pallas_dftspec:

    1. per-bin vs dft_untwist_interbin_twin — the kernel's helpers
       (_stripe_dft_step1/_row_dft_tail/_row_spectrum) run outside
       Pallas with identical term
       grouping, asserted at the FMA-codegen envelope
       (_assert_per_bin_twin; bitwise when both compile fresh).
    2. accuracy class vs the exact Precision.HIGHEST einsum chain:
       per-bin |amp - amp_ref| / (|amp_ref| + rms) <= 1e-3 (the 3-pass
       bf16 split class; the max sits at untwist-cancellation bins).

    Geometry floor: n1 must be a multiple of 128, so the smallest legal
    series is n = 2^15 (m = 16384 = 128 x 128)."""

    def _data(self, r, n, seed=0):
        import jax.numpy as jnp

        from peasoup_tpu.ops.pallas.dftspec import oracle_data

        x, xe, xo, mean, std = oracle_data(n, r=r, seed=seed)
        return (
            x, jnp.asarray(xe), jnp.asarray(xo),
            jnp.asarray(mean), jnp.asarray(std),
        )

    def _case(self, r, n, npad, seed=0):
        import jax.numpy as jnp

        from peasoup_tpu.ops.fft import rfft_pow2_matmul_parts
        from peasoup_tpu.ops.pallas.dftspec import (
            dft_untwist_interbin, dft_untwist_interbin_twin,
        )
        from peasoup_tpu.ops.spectrum import (
            form_interpolated_parts, normalise,
        )

        from peasoup_tpu.ops.pallas.dftspec import (
            ACC_MAX_REL, ACC_Q999_REL, accuracy_rel,
        )

        m = n // 2
        x, xe, xo, mean, std = self._data(r, n, seed)
        got = np.asarray(
            dft_untwist_interbin(xe, xo, mean, std, npad=npad, interpret=True)
        )
        twin = np.asarray(
            dft_untwist_interbin_twin(xe, xo, mean, std, npad=npad)
        )
        assert got.shape == (r, npad)
        _assert_per_bin_twin(got, twin)
        ref = np.asarray(
            normalise(
                form_interpolated_parts(
                    *rfft_pow2_matmul_parts(jnp.asarray(x))
                ),
                mean, std,
            )
        )
        rel = accuracy_rel(got, ref, np.asarray(mean), np.asarray(std), m)
        assert float(rel.max()) <= ACC_MAX_REL
        assert float(np.quantile(rel, 0.999)) <= ACC_Q999_REL
        assert not got[:, m + 1 :].any()
        stdn = np.asarray(std)[:, None]
        meann = np.asarray(mean)[:, None]
        amp_r = ref * stdn + meann
        scale = np.sqrt((amp_r**2).mean(axis=1, keepdims=True))
        return got, amp_r, scale, stdn, meann

    def test_per_bin_vs_twin_and_accuracy_class(self):
        # n2 = n1 case (one stripe + row padding: r=9 -> rpad=16)
        self._case(r=9, n=1 << 15, npad=(1 << 14) + 128)

    def test_rectangular_n2_and_wide_pad(self):
        # n1=128, n2=256 and a pad several planes past the Nyquist
        self._case(r=4, n=1 << 16, npad=(1 << 15) + 1024, seed=3)

    def test_mirror_and_nyquist_edges(self):
        # bins 0, 1, m-1, m against an f64 rfft oracle: the k=0 wrap,
        # the carried column fixes, and the Nyquist (1,1) store are the
        # structurally distinct paths in the kernel
        got, _, scale, stdn, meann = self._case(
            r=8, n=1 << 15, npad=(1 << 14) + 128, seed=5
        )
        n = 1 << 15
        m = n // 2
        x, _, _, mean, std = self._data(8, n, seed=5)
        X = np.fft.rfft(x.astype(np.float64), axis=1)
        Xl = np.concatenate([np.zeros((8, 1)), X[:, :-1]], axis=1)
        amp64 = np.maximum(np.abs(X), np.sqrt(0.5) * np.abs(X - Xl))
        amp_g = got[:, : m + 1] * stdn + meann
        for k in (0, 1, m - 1, m):
            err = np.abs(amp_g[:, k] - amp64[:, k])
            assert (err <= 1e-3 * (np.abs(amp64[:, k]) + scale[:, 0])).all()

    def test_pre_shaped_planes_match_flat(self):
        # the zero-relayout producer path: (R, n1, n2) planes give
        # bitwise the same kernel output as the flat (R, m) form (the
        # reshape happens outside the pallas program either way)
        from peasoup_tpu.ops.pallas.dftspec import (
            dft_untwist_interbin, plane_factors,
        )

        n = 1 << 15
        m = n // 2
        npad = m + 128
        _, xe, xo, mean, std = self._data(8, n, seed=11)
        n1, n2 = plane_factors(m)
        flat = np.asarray(
            dft_untwist_interbin(xe, xo, mean, std, npad=npad, interpret=True)
        )
        shaped = np.asarray(
            dft_untwist_interbin(
                xe.reshape(8, n1, n2), xo.reshape(8, n1, n2),
                mean, std, npad=npad, interpret=True,
            )
        )
        np.testing.assert_array_equal(flat, shaped)
        import pytest

        with pytest.raises(ValueError):
            dft_untwist_interbin(
                xe.reshape(8, n2 // 2, n1 * 2), xo.reshape(8, n2 // 2, n1 * 2),
                mean, std, npad=npad, interpret=True,
            )

    def test_negative_lane_shift_fails_oracle(self):
        import jax.numpy as jnp

        from peasoup_tpu.ops.pallas.dftspec import (
            dft_untwist_interbin, dft_untwist_interbin_twin,
        )

        n = 1 << 15
        _, xe, xo, mean, std = self._data(8, n, seed=7)
        npad = (n // 2) + 128
        good = np.asarray(
            dft_untwist_interbin(xe, xo, mean, std, npad=npad, interpret=True)
        )
        twin = np.asarray(
            dft_untwist_interbin_twin(xe, xo, mean, std, npad=npad)
        )
        _assert_per_bin_twin(good, twin)
        bad = np.roll(good, 1, axis=1)
        assert (np.abs(bad - twin) > _twin_tol(twin)).mean() > 0.5

    def test_geometry_validation(self):
        import jax.numpy as jnp
        import pytest

        from peasoup_tpu.ops.pallas.dftspec import (
            dft_untwist_interbin, dftspec_supported,
        )

        v = jnp.ones((8,), jnp.float32)
        # n1 = 64 < 128 for n = 2^14: below the geometry floor
        z = jnp.zeros((8, 1 << 13), jnp.float32)
        with pytest.raises(ValueError):
            dft_untwist_interbin(z, z, v, v, npad=(1 << 13) + 128)
        # npad not a multiple of n1
        z = jnp.zeros((8, 1 << 14), jnp.float32)
        with pytest.raises(ValueError):
            dft_untwist_interbin(z, z, v, v, npad=(1 << 14) + 100)
        # npad <= m
        with pytest.raises(ValueError):
            dft_untwist_interbin(z, z, v, v, npad=1 << 14)
        assert dftspec_supported(1 << 15, (1 << 14) + 128)
        assert not dftspec_supported(1 << 14, (1 << 13) + 128)
        # survey-scale m above _MAX_M must be REJECTED by the shape
        # gate (the driver falls back instead of raising at trace time)
        assert not dftspec_supported(1 << 21, (1 << 20) + 1024)
        assert not dftspec_supported((1 << 15) + 2, (1 << 14) + 128)
