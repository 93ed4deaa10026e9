"""Multi-chip sharding tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from peasoup_tpu.parallel import (
    make_mesh,
    device_count,
    make_sharded_search_fn,
    baseline_beam,
    sharded_coincidence,
)
from peasoup_tpu.parallel.sharded_search import place_trials
from peasoup_tpu.pipeline.accel_search import make_search_fn
from peasoup_tpu.pipeline.search import _level_windows


def test_virtual_mesh_has_8_devices():
    assert device_count() == 8


def test_make_mesh_shapes():
    mesh = make_mesh()
    assert mesh.shape == {"dm": 8}
    mesh2 = make_mesh({"beam": 2, "dm": -1})
    assert mesh2.shape == {"beam": 2, "dm": 4}
    with pytest.raises(ValueError):
        make_mesh({"dm": 3})


class TestShardedSearch:
    def make_inputs(self, ndm=8, size=4096, n_accs=4):
        rng = np.random.default_rng(3)
        t = np.arange(size)
        tims = []
        for d in range(ndm):
            x = rng.normal(30, 3, size=size)
            x += 10.0 * (((t * 0.000256) / 0.016) % 1.0 < 0.1)  # P=16ms pulsar
            tims.append(np.clip(np.rint(x), 0, 255))
        tims = np.asarray(tims, dtype=np.uint8)
        afs = np.zeros((ndm, n_accs), dtype=np.float32)
        windows = _level_windows(size, 2, 0.1, 1100.0, 0.000256)
        zap = np.zeros(size // 2 + 1, dtype=bool)
        return tims, afs, zap, windows

    def test_matches_single_device(self):
        tims, afs, zap, windows = self.make_inputs()
        size = tims.shape[1]
        kw = dict(size=size, nsamps_valid=size, nharms=2, max_peaks=64,
                  pos5=10, pos25=100)
        mesh = make_mesh()
        sharded = make_sharded_search_fn(mesh, threshold=6.0)
        peaks = sharded(
            place_trials(mesh, tims), jnp.asarray(afs), jnp.asarray(zap),
            jnp.asarray(windows), **kw,
        )
        single = make_search_fn(6.0)
        for d in range(tims.shape[0]):
            ref = single(jnp.asarray(tims[d]), jnp.asarray(afs[d]),
                         jnp.asarray(zap), jnp.asarray(windows), **kw)
            np.testing.assert_array_equal(np.asarray(peaks.idxs)[d],
                                          np.asarray(ref.idxs))
            np.testing.assert_allclose(np.asarray(peaks.snrs)[d],
                                       np.asarray(ref.snrs), rtol=2e-5, atol=1e-4)
            np.testing.assert_array_equal(np.asarray(peaks.counts)[d],
                                          np.asarray(ref.counts))

    def test_finds_the_pulsar_on_every_shard(self):
        tims, afs, zap, windows = self.make_inputs()
        mesh = make_mesh()
        sharded = make_sharded_search_fn(mesh, threshold=6.0)
        peaks = sharded(
            place_trials(mesh, tims), jnp.asarray(afs), jnp.asarray(zap),
            jnp.asarray(windows), size=tims.shape[1],
            nsamps_valid=tims.shape[1], nharms=2, max_peaks=64, pos5=10,
            pos25=100,
        )
        counts = np.asarray(peaks.counts)
        assert (counts.sum(axis=(1, 2)) > 0).all()  # every DM shard fired


class TestShardedCoincidence:
    def test_matches_unsharded(self):
        rng = np.random.default_rng(0)
        beams = rng.normal(size=(8, 512)).astype(np.float32)
        beams[:, 100] = 10.0  # all beams -> RFI
        beams[0, 200] = 10.0  # one beam -> keep
        mesh = make_mesh({"beam": 8})
        out = np.asarray(
            sharded_coincidence(mesh, jnp.asarray(beams), 4.0, 4)
        )
        from peasoup_tpu.ops import coincidence_mask

        ref = np.asarray(coincidence_mask(jnp.asarray(beams), 4.0, 4))
        np.testing.assert_array_equal(out, ref)
        assert out[100] == 0.0 and out[200] == 1.0

    def test_beam_axis_smaller_than_mesh_padding(self):
        # 6 real beams padded to 8 with -inf so they never fire
        rng = np.random.default_rng(1)
        beams = rng.normal(size=(6, 256)).astype(np.float32)
        beams[:, 50] = 99.0
        pad = np.full((2, 256), -np.inf, dtype=np.float32)
        stacked = np.concatenate([beams, pad])
        mesh = make_mesh({"beam": 8})
        out = np.asarray(sharded_coincidence(mesh, jnp.asarray(stacked), 4.0, 4))
        assert out[50] == 0.0


class TestBaselineBeam:
    def test_outputs_normalised(self):
        rng = np.random.default_rng(2)
        x = np.clip(rng.normal(50, 5, size=4096), 0, 255).astype(np.uint8)
        spec, tim = baseline_beam(jnp.asarray(x), size=4096, pos5=10, pos25=100)
        spec, tim = np.asarray(spec), np.asarray(tim)
        assert spec.shape == (2049,)
        assert tim.shape == (4096,)
        assert abs(np.mean(tim)) < 0.1  # normalised
        assert np.std(tim) == pytest.approx(1.0, rel=0.1)


class TestMultihost:
    """Multi-host helpers (parallel/multihost.py). Single-process here:
    initialize() must no-op, global_mesh must build over all (virtual)
    devices with the DCN axis leading, and the per-process slice must
    cover the axis exactly."""

    def test_initialize_noop_single_process(self):
        from peasoup_tpu.parallel import multihost

        multihost.initialize()  # no coordinator -> no-op

    def test_global_mesh_dcn_axis_leading(self):
        from peasoup_tpu.parallel import multihost

        mesh = multihost.global_mesh({"dm": -1, "beam": 2}, dcn_axis="beam")
        assert mesh.axis_names[0] == "beam"
        assert mesh.shape["beam"] == 2
        assert mesh.shape["beam"] * mesh.shape["dm"] == len(
            mesh.devices.reshape(-1)
        )

    def test_process_local_slice_covers_axis(self):
        from peasoup_tpu.parallel import multihost

        mesh = multihost.global_mesh({"dm": -1})
        lo, hi = multihost.process_local_slice(mesh, "dm")
        assert (lo, hi) == (0, mesh.shape["dm"])  # single process

    def test_dm_slice_for_process_partitions(self):
        from peasoup_tpu.parallel.multihost import dm_slice_for_process

        for ndm, nproc in [(59, 4), (8, 8), (7, 3), (100, 1), (3, 5)]:
            slices = [dm_slice_for_process(ndm, nproc, p) for p in range(nproc)]
            # contiguous, ordered, exactly covering [0, ndm)
            assert slices[0][0] == 0 and slices[-1][1] == ndm
            for (a, b), (c, d) in zip(slices, slices[1:]):
                assert b == c and b - a >= d - c  # balanced, larger first
            sizes = [b - a for a, b in slices]
            assert max(sizes) - min(sizes) <= 1

    def test_allgather_pickled_single_process(self):
        from peasoup_tpu.parallel.multihost import _allgather_pickled

        assert _allgather_pickled(b"payload") == [b"payload"]

    def test_run_search_single_process_degrades(self, tmp_path):
        """run_search with one process must be exactly the local
        driver path."""
        from peasoup_tpu.parallel.multihost import run_search
        from peasoup_tpu.pipeline.search import SearchConfig
        from tests.test_pipeline import make_synthetic_fil
        from peasoup_tpu.io.sigproc import read_filterbank

        path, _, _ = make_synthetic_fil(tmp_path, nsamps=1 << 13)
        fil = read_filterbank(path)
        res = run_search(fil, SearchConfig(dm_end=10.0, nharmonics=1, limit=5))
        assert len(res.candidates) <= 5


class TestShardedDedispersion:
    """dedisperse_sharded: the DM-trial axis of the shift-and-sum engine
    sharded over the mesh (reference analogue: dedisp_create_plan_multi,
    dedisperser.hpp:25-31)."""

    def make_fil(self, nsamps=4096, nchans=32, seed=7):
        rng = np.random.default_rng(seed)
        return rng.integers(0, 4, size=(nsamps, nchans)).astype(np.uint8)

    def make_delays(self, ndm, nchans, max_delay=200, seed=8):
        rng = np.random.default_rng(seed)
        # monotone-in-channel delay curves like a real DM table
        base = np.sort(rng.integers(0, max_delay, size=(ndm, nchans)), axis=1)
        return np.asarray(base[:, ::-1], dtype=np.int32)  # high freq first

    @pytest.mark.parametrize("ndm", [16, 59])  # 59: pad (not /8)
    def test_bitwise_matches_single_device(self, ndm):
        from peasoup_tpu.ops.dedisperse import dedisperse_device
        from peasoup_tpu.parallel.sharded_dedisperse import dedisperse_sharded

        fil = self.make_fil()
        delays = self.make_delays(ndm, fil.shape[1])
        kill = np.ones(fil.shape[1], dtype=np.int32)
        kill[3] = 0
        out_nsamps = fil.shape[0] - int(delays.max())
        single = np.asarray(
            dedisperse_device(fil, delays, kill, out_nsamps, block=16)
        )
        mesh = make_mesh({"dm": 8})
        sharded = np.asarray(
            dedisperse_sharded(fil, delays, kill, out_nsamps, mesh, block=4)
        )
        assert sharded.shape[0] >= ndm  # padded to a mesh-axis multiple
        np.testing.assert_array_equal(sharded[:ndm], single)

    def test_output_is_sharded_on_mesh(self):
        from peasoup_tpu.parallel.sharded_dedisperse import dedisperse_sharded

        fil = self.make_fil()
        delays = self.make_delays(16, fil.shape[1])
        kill = np.ones(fil.shape[1], dtype=np.int32)
        mesh = make_mesh({"dm": 8})
        out = dedisperse_sharded(
            fil, delays, kill, fil.shape[0] - int(delays.max()), mesh
        )
        # trials must materialise distributed over the 'dm' axis: one
        # shard of 2 rows per device, no full-array replica anywhere
        assert len(out.sharding.device_set) == 8
        shard_rows = {s.data.shape[0] for s in out.addressable_shards}
        assert shard_rows == {2}

    def test_pallas_path_bitwise_on_mesh(self):
        """Per-shard Pallas blocked-roll kernel (interpret mode on the
        CPU mesh) matches the jnp sharded path and the single-device
        engine bitwise — the multi-chip analogue of dedisp's per-GPU
        kernels."""
        from peasoup_tpu.ops.dedisperse import dedisperse_device
        from peasoup_tpu.parallel.sharded_dedisperse import dedisperse_sharded

        fil = self.make_fil(nsamps=2048, nchans=32)
        delays = np.sort(self.make_delays(24, 32, max_delay=150), axis=0)
        kill = np.ones(32, dtype=np.int32)
        out_nsamps = fil.shape[0] - int(delays.max())
        mesh = make_mesh({"dm": 8})
        single = np.asarray(
            dedisperse_device(fil, delays, kill, out_nsamps, block=16)
        )
        pallas = np.asarray(
            dedisperse_sharded(
                fil, delays, kill, out_nsamps, mesh,
                use_pallas=True, interpret=True,
            )
        )
        np.testing.assert_array_equal(pallas[:24], single)


class TestPackedShardedDedispersion:
    """dedisperse_sharded from the input as the file packs it: every chip
    walks the one-chip engine's channel-chunk x DM-segment decomposition
    over its own DM slice, bitwise equal to dedisperse_device."""

    NCHANS = 72  # 2-bit rows of 18 bytes; not a multiple of 16 channels

    def inputs(self, ndm, nsamps=2048, seed=11):
        rng = np.random.default_rng(seed)
        fil = rng.integers(0, 4, size=(nsamps, self.NCHANS)).astype(np.uint8)
        delays = np.sort(
            np.sort(rng.integers(0, 150, size=(ndm, self.NCHANS)), axis=1)[
                :, ::-1
            ],
            axis=0,
        ).astype(np.int32)
        kill = np.ones(self.NCHANS, dtype=np.int32)
        kill[[3, 40, 71]] = 0
        return fil, delays, kill, nsamps - int(delays.max())

    # ndm 77 over 4 chips: not a multiple of 4 x 8, and 20 trials a chip
    # make two DM segments under the small budget below; 2050 samples
    # leave two time rows past the chips' equal blocks
    @pytest.mark.parametrize("nbits,use_pallas,nsamps", [
        pytest.param(2, False, 2048, id="2-False"),
        pytest.param(8, False, 2048, id="8-False"),
        pytest.param(2, True, 2048, id="2-True"),
        pytest.param(2, False, 2050, id="2-False-2050"),
        pytest.param(8, False, 2050, id="8-False-2050"),
    ])
    def test_bitwise_equals_one_chip_engine(self, nbits, use_pallas, nsamps,
                                            monkeypatch):
        from peasoup_tpu import obs
        from peasoup_tpu.io.sigproc import pack_bits
        from peasoup_tpu.ops.dedisperse import dedisperse_device, output_scale
        from peasoup_tpu.parallel import sharded_dedisperse as sd
        from peasoup_tpu.parallel.sharded_dedisperse import (
            dedisperse_sharded, shard_plan,
        )

        put_rows, puts = sd._put_rows, []

        def spy(*args):
            puts.append(put_rows(*args))
            return puts[-1]

        monkeypatch.setattr(sd, "_put_rows", spy)
        ndm = 77
        fil, delays, kill, out_nsamps = self.inputs(ndm, nsamps)
        scale = output_scale(2, int(kill.sum()))
        single = np.asarray(dedisperse_device(
            fil, delays, kill, out_nsamps, scale=scale,
        ))
        # a budget of 20 f32 channel rows: several channel chunks (the
        # last overlapping its predecessor) and two DM segments a chip
        budget = 4 * fil.shape[0] * 20
        samples = pack_bits(fil.reshape(-1), 2) if nbits == 2 else fil
        mesh = make_mesh({"dm": 4}, devices=jax.devices()[:4])
        tel = obs.RunTelemetry()
        with tel.activate():
            out = dedisperse_sharded(
                samples, delays, kill, out_nsamps, mesh, nbits=nbits,
                scale=scale, block=2, chunk_bytes=budget,
                use_pallas=use_pallas, interpret=use_pallas,
            )
        per_dev, seg, cc = shard_plan(
            ndm, 4, fil.shape[0], self.NCHANS, out_nsamps, block=2,
            chunk_bytes=budget, quantum=8 if use_pallas else 1,
            chan_quantum=16 if use_pallas else (4 if nbits == 2 else 1),
        )
        n_ch = -(-self.NCHANS // cc)
        assert per_dev // seg >= 2 and n_ch >= 4 and self.NCHANS % cc
        assert out.shape == (4 * per_dev, out_nsamps)
        assert {s.data.shape[0] for s in out.addressable_shards} == {per_dev}
        np.testing.assert_array_equal(np.asarray(out)[:ndm], single)
        assert tel.counters["dedisp.shards"] == 4
        assert tel.counters["dedisp.chunks"] == (per_dev // seg) * n_ch
        assert tel.counters["dedisp.replicated_bytes"] == samples.nbytes
        assert {"dedispersion.replicate", "dedispersion.shard"} <= set(
            tel.timers
        )
        # the host sent each chip a quarter of the time rows, and the few
        # rows past them to every chip
        (head, tail, _), = puts
        rows = samples.reshape(nsamps, -1)
        cut = nsamps - nsamps % 4
        assert {s.data.shape for s in head.addressable_shards} == {
            (cut // 4, rows.shape[1])}
        assert tail.shape == (nsamps % 4, rows.shape[1])
        assert tel.counters["dedisp.h2d_bytes"] == (
            samples.nbytes + 3 * rows[cut:].nbytes)
        # every chip gathers the whole blocked input, byte for byte
        lay = sd._layout(
            samples, delays, out_nsamps, 4, nbits=nbits, block=2,
            chunk_bytes=budget, use_pallas=use_pallas, interpret=use_pallas,
        )
        blocked = sd._blocked_bytes(mesh, lay.nrows)(head, tail)
        want = np.pad(rows.T, ((0, 0), (0, lay.nrows * 128 - nsamps)))
        for s in blocked.addressable_shards:
            np.testing.assert_array_equal(
                np.asarray(s.data), want.reshape(-1, lay.nrows, 128))


class TestShardedSearchDriver:
    """The whole search with the DM axis over a 4-chip mesh."""

    # dm_block 2: several chunks a chip; npdmp 3: folds read rows of the
    # padded sharded block
    @pytest.mark.parametrize("dm_block,npdmp", [(0, 0), (2, 3)])
    def test_candidates_bitwise_equal_one_chip(self, tmp_path, dm_block,
                                               npdmp):
        from peasoup_tpu import obs
        from peasoup_tpu.io.sigproc import read_filterbank
        from peasoup_tpu.pipeline.search import PeasoupSearch, SearchConfig
        from tests.test_pipeline import make_synthetic_fil

        path, _, _ = make_synthetic_fil(tmp_path)
        fil = read_filterbank(path)
        common = dict(dm_end=40.0, nharmonics=2, npdmp=npdmp, limit=100,
                      dm_block=dm_block)
        single = PeasoupSearch(SearchConfig(**common)).run(fil)
        tel = obs.RunTelemetry()
        with tel.activate():
            sharded = PeasoupSearch(
                SearchConfig(shard_devices=4, **common)
            ).run(fil)
        assert len(single.candidates) == len(sharded.candidates) > 0
        for a, b in zip(single.candidates, sharded.candidates):
            assert (a.freq, a.snr, a.dm, a.acc, a.nh, a.folded_snr) == (
                b.freq, b.snr, b.dm, b.acc, b.nh, b.folded_snr)
        assert sum(c.folded_snr > 0 for c in sharded.candidates) == npdmp
        # trials dedispersed on the mesh, none spilled
        assert tel.counters["dedisp.shards"] == 4
        assert tel.counters["search.spill_bytes"] == 0
        assert tel.counters["search.chunks"] >= (2 if dm_block else 1)

    def test_deal_chunks_keeps_rows_on_their_chip(self):
        from peasoup_tpu.pipeline.search import _block_rows, _deal_chunks

        # 23 trials, 8 held a chip (the last chip holds 7), 3 a chunk
        chunks = _deal_chunks(list(range(23)), 3, 3, 8)
        assert sorted(d for c, _ in chunks for d in c) == list(range(23))
        for dm_indices, d_blk in chunks:
            rows, at = _block_rows(dm_indices, d_blk, 3, 8)
            assert len(rows) == d_blk == 9
            assert [rows[p] for p in at] == dm_indices
            assert all(r // 8 == p // 3 for p, r in enumerate(rows))
        # unsharded: consecutive runs, the first trial repeated as padding
        assert _deal_chunks(list(range(5)), 2, 1, 0) == [
            ([0, 1], 2), ([2, 3], 2), ([4], 2)]
        assert _block_rows([4], 2, 1, 0) == ([4, 4], [0])

    def test_block_rows_refuses_a_chunk_that_moves_rows(self):
        from peasoup_tpu.pipeline.search import _block_rows

        # rows 0-5 all live on chip 0 of 3: a 3-row part cannot hold them
        with pytest.raises(ValueError, match="chip 0"):
            _block_rows(list(range(6)), 9, 3, 8)
        # a chip with none of the chunk's trials repeats its own first row
        assert _block_rows([9], 4, 2, 8) == ([0, 0, 9, 9], [2])

    # the cell htru_medlat.one_beam.t4 per chip: 1024 channels x
    # (2^23 + 4096) samples, 743 DM trials over 4 chips, against a v5e's
    # 16909336064 B; packed 1, 2 or 4 bits a sample it fits, stored
    # 8-bit every chip gathers four times the bytes and it has to stay
    # on one
    @pytest.mark.parametrize("nbits,fits", [
        (1, True), (2, True), (4, True), (8, False),
    ])
    def test_chip_bytes_at_the_survey_node(self, nbits, fits):
        from peasoup_tpu.parallel.sharded_dedisperse import chip_bytes

        nchans, nsamps, ndm = 1024, 8392704, 743
        per = 8 // nbits
        samples = np.broadcast_to(
            np.zeros((), np.uint8), (nsamps, nchans // per)
        )
        delays = np.broadcast_to(
            np.linspace(0, 2630, ndm).astype(np.int32)[:, None], (ndm, nchans)
        )
        need = chip_bytes(samples, delays, nsamps - 2630, 4, nbits=nbits,
                          use_pallas=False)
        assert need >= samples.nbytes + ndm * (nsamps - 2630) // 4
        # laying the input out: a chip's quarter, the gathered rows, their
        # padded transpose and its blocked copy
        assert need >= samples.nbytes // 4 + 3 * samples.nbytes
        assert (need <= 16909336064) is fits

    def test_replicated_input_past_a_chip_stays_on_one(self, tmp_path,
                                                       monkeypatch):
        """An 8-bit beam whose replicated input would not fit each chip
        dedisperses on one chip, as before sharding, and finds the same
        candidates as one chip."""
        from peasoup_tpu import obs
        from peasoup_tpu.io.sigproc import read_filterbank
        from peasoup_tpu.parallel.sharded_dedisperse import chip_bytes
        from peasoup_tpu.pipeline.search import PeasoupSearch, SearchConfig
        from tests.test_pipeline import make_synthetic_fil

        path, _, _ = make_synthetic_fil(tmp_path)
        fil = read_filterbank(path)
        common = dict(dm_end=40.0, nharmonics=2, npdmp=2, limit=100,
                      dm_block=2)
        single = PeasoupSearch(SearchConfig(**common)).run(fil)
        plan = PeasoupSearch(SearchConfig(**common)).build_dm_plan(fil)
        need = chip_bytes(fil.data, plan.delay_samples(), plan.out_nsamps, 4)
        monkeypatch.setattr(PeasoupSearch, "TOTAL_HBM", need - 1)
        tel = obs.RunTelemetry()
        with tel.activate():
            kept = PeasoupSearch(SearchConfig(shard_devices=4, **common)).run(
                fil
            )
        assert "dedisp.shards" not in tel.counters
        assert tel.counters["search.spill_bytes"] == 0
        assert len(single.candidates) == len(kept.candidates) > 0
        for a, b in zip(single.candidates, kept.candidates):
            assert (a.freq, a.snr, a.dm, a.acc, a.nh, a.folded_snr) == (
                b.freq, b.snr, b.dm, b.acc, b.nh, b.folded_snr)


def test_sharded_beam_matches_the_plain_reference(tmp_path, monkeypatch):
    """A 2-bit beam from the benchmark's generator, dedispersed and
    searched over 4 chips: each trial equals the plain reference's
    dedispersed series, and the written detections hold to its spectra
    under the harness's own comparison (benchmark/reference)."""
    import sys
    import time

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[1]))
    from benchmark import beams, run, spec, synth
    from benchmark.reference.search import Search
    from peasoup_tpu.cli import peasoup
    from peasoup_tpu.io.sigproc import read_filterbank
    from peasoup_tpu.ops.dedisperse import output_scale
    from peasoup_tpu.parallel.sharded_dedisperse import (
        dedisperse_sharded, packed_input,
    )
    from peasoup_tpu.pipeline.search import PeasoupSearch
    from peasoup_tpu.plan.dm_plan import DMPlan

    config = {
        "geometry": {"nchans": 64, "nbits": 2, "tsamp": 0.00032,
                     "nsamps": 33280, "fch1": 1510.0, "foff": -1.09},
        "flags": ["--dm_end", "60", "-t", "4"],
        "dm_end": 60.0, "acc_list": [0.0],
        "spectral": {"nharmonics": 4, "boundary_5_freq": 0.05,
                     "boundary_25_freq": 0.5, "min_snr": 9.0,
                     "min_freq": 0.1, "max_freq": 1100.0, "freq_tol": 1e-4,
                     "max_harm": 16, "min_gap": 30},
        "dm_trials": 19, "accel_trials": 19,
        # the limit of tests/bench's tiny cell
        "check": {"beams": 1, "pairs": 3, "pair_window": 8,
                  "freq_tol_bins": 1.0, "match_bins": 1.5, "snr_cut": 9.45,
                  "snr_gap_limit": 0.001},
    }
    traffic = {"beams": 1, "noise_only": 0, "draw": "grid",
               "period_s": [0.05, 0.5], "dm": [20.0, 40.0], "duty": 0.02,
               "amp": 0.5}
    seed = 2**31 + 25
    geom = config["geometry"]
    [beam] = beams.draw(traffic, seed)
    path = synth.write_beam(str(tmp_path / "b.fil"), geom, beam)

    fil = read_filterbank(path)
    plan = DMPlan.create(
        nsamps=fil.nsamps, nchans=fil.nchans, tsamp=fil.tsamp,
        fch1=fil.fch1, foff=fil.foff, dm_start=0.0, dm_end=60.0,
        pulse_width=64.0, tol=1.10,
    )
    samples, nbits = packed_input(fil)
    assert nbits == 2 and samples is fil.raw
    trials = np.asarray(dedisperse_sharded(
        samples, plan.delay_samples(), plan.killmask, plan.out_nsamps,
        make_mesh({"dm": 4}, devices=jax.devices()[:4]), nbits=2, scale=output_scale(2, 64),
    ))
    ref = Search(synth.read_channels(path, 64), geom, config["spectral"])
    for k in (0, plan.ndm // 2, plan.ndm - 1):
        np.testing.assert_array_equal(
            trials[k, : ref.size], ref.trial(float(plan.dm_list[k]))
        )

    monkeypatch.setattr(
        PeasoupSearch, "_pick_devices", lambda self: jax.local_devices()[:4]
    )
    out = str(tmp_path / "out")
    assert peasoup.main(["-i", path, "-o", out, *config["flags"]]) == 0
    record = {"fil": path, "outdir": out, "beam": 0, "wall": 1.0,
              "error": None}
    cell = spec.Cell("tiny.t4", config, traffic, 4, [], [])
    chk = run.check(cell, [beam], [record], seed)
    assert chk["n_detections"] > 0 and not chk["bad"]
    assert chk["gap"] <= chk["limit"]
