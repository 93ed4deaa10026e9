"""Resilience-layer tests: the error classification, the retry policy, the
degradation ladder, unified corrupt-artifact recovery, the fault
registry's grammar/determinism/zero-cost contract, the threaded call
sites (filterbank reads, queue claims, sqlite ingest, checkpoint
writes, OOM rungs), and the background-thread crash guard satellites.
"""

import errno
import json
import multiprocessing
import os
import sqlite3
import time

import numpy as np
import pytest

from peasoup_tpu import resilience as R
from peasoup_tpu.obs import RunTelemetry
from peasoup_tpu.resilience import faults
from peasoup_tpu.resilience.stats import STATS


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts fault-free with zeroed accounting."""
    faults.configure(None)
    STATS.reset()
    yield
    faults.configure(None)
    STATS.reset()


# --------------------------------------------------------------------------
# classification
# --------------------------------------------------------------------------

class TestClassification:
    @pytest.mark.parametrize(
        "exc,want",
        [
            (R.TransientIOError(errno.EIO, "x"), R.TRANSIENT),
            (OSError(errno.EIO, "x"), R.TRANSIENT),
            (OSError(errno.EAGAIN, "x"), R.TRANSIENT),
            (sqlite3.OperationalError("database is locked"), R.TRANSIENT),
            (sqlite3.OperationalError("database table is busy"),
             R.TRANSIENT),
            (TimeoutError("t"), R.TRANSIENT),
            (MemoryError(), R.RESOURCE_EXHAUSTED),
            (RuntimeError("RESOURCE_EXHAUSTED: oom"),
             R.RESOURCE_EXHAUSTED),
            (R.CorruptArtifactError("torn"), R.CORRUPT),
            (EOFError(), R.CORRUPT),
            (FileNotFoundError(2, "gone"), R.FATAL),  # protocol state
            (PermissionError(13, "denied"), R.FATAL),
            (ValueError("bad input"), R.FATAL),
            (sqlite3.OperationalError("no such table: x"), R.FATAL),
        ],
    )
    def test_classify(self, exc, want):
        assert R.classify(exc) == want

    def test_json_decode_is_corrupt(self):
        with pytest.raises(json.JSONDecodeError) as ei:
            json.loads("{torn")
        assert R.classify(ei.value) == R.CORRUPT

    def test_bad_zipfile_is_corrupt(self):
        import zipfile

        assert R.classify(zipfile.BadZipFile("torn npz")) == R.CORRUPT

    def test_worker_killed_is_not_an_exception(self):
        """The simulated SIGKILL must bypass every `except Exception`
        recovery path, like the real thing."""
        assert not isinstance(R.WorkerKilled("x"), Exception)
        assert isinstance(R.WorkerKilled("x"), BaseException)


# --------------------------------------------------------------------------
# retry policy
# --------------------------------------------------------------------------

class TestRetryPolicy:
    def test_recovers_and_emits_events(self):
        pol = R.RetryPolicy(max_attempts=3, base_delay_s=0.001)
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise R.TransientIOError(errno.EIO, "flaky")
            return "ok"

        tel = RunTelemetry()
        with tel.activate():
            assert pol.call(flaky, site="t.site") == "ok"
        kinds = [e["kind"] for e in tel.events]
        assert kinds.count("resilience_retry") == 2
        assert "resilience_recovered" in kinds
        retry = next(e for e in tel.events if e["kind"] == "resilience_retry")
        assert retry["site"] == "t.site"
        assert retry["error_class"] == R.TRANSIENT
        snap = STATS.snapshot()
        assert snap["retries"]["t.site"] == 2
        assert snap["recoveries"]["t.site"] == 1

    def test_gives_up_after_budget(self):
        pol = R.RetryPolicy(max_attempts=2, base_delay_s=0.001)
        tel = RunTelemetry()
        with tel.activate(), pytest.raises(R.TransientIOError):
            pol.call(
                lambda: (_ for _ in ()).throw(
                    R.TransientIOError(errno.EIO, "always")
                ),
                site="t.giveup",
            )
        assert any(
            e["kind"] == "resilience_giveup" for e in tel.events
        )
        snap = STATS.snapshot()
        assert snap["giveups"]["t.giveup"] == 1
        assert snap["degraded"] is True

    def test_fatal_raises_immediately(self):
        pol = R.RetryPolicy(max_attempts=5, base_delay_s=0.001)
        calls = {"n": 0}

        def fatal():
            calls["n"] += 1
            raise ValueError("broken program")

        with pytest.raises(ValueError):
            pol.call(fatal, site="t.fatal")
        assert calls["n"] == 1  # no retries burned on a fatal class

    def test_deterministic_jitter(self):
        a = R.RetryPolicy(jitter=0.5)
        b = R.RetryPolicy(jitter=0.5)
        assert [a.delay(k, "s") for k in (1, 2, 3)] == [
            b.delay(k, "s") for k in (1, 2, 3)
        ]
        # and distinct sites get distinct (but stable) schedules
        assert a.delay(1, "s1") != a.delay(1, "s2")


# --------------------------------------------------------------------------
# degradation ladder
# --------------------------------------------------------------------------

class TestDegradationLadder:
    def test_steps_in_order_with_events(self):
        tel = RunTelemetry()
        with tel.activate():
            lad = R.DegradationLadder("t.lad", ("shrink", "subband", "cpu"))
            lad.step("shrink", dm_block=64)
            lad.step("shrink", dm_block=32)  # same rung repeats fine
            lad.step("subband")
            with pytest.raises(ValueError):
                lad.step("shrink")  # never climbs back up
            lad.exhausted()
        degs = [e for e in tel.events if e["kind"] == "degradation"]
        assert [d["rung"] for d in degs] == ["shrink", "shrink", "subband"]
        assert [d["rung_index"] for d in degs] == [0, 0, 1]
        assert any(
            e["kind"] == "degradation_exhausted" for e in tel.events
        )
        assert STATS.snapshot()["degradations"]["t.lad:shrink"] == 2

    def test_unknown_rung_is_a_programming_error(self):
        lad = R.DegradationLadder("t.lad2", ("a",))
        with pytest.raises(ValueError):
            lad.step("nope")


# --------------------------------------------------------------------------
# load_or_recover (the unified corrupt-artifact policy)
# --------------------------------------------------------------------------

class TestLoadOrRecover:
    def test_missing_returns_default(self, tmp_path):
        out = R.load_or_recover(
            str(tmp_path / "nope.json"),
            lambda p: json.load(open(p)),
            default={"fresh": True},
            kind="test artifact",
        )
        assert out == {"fresh": True}
        # absence is normal, not corruption
        assert STATS.snapshot()["corrupt_artifacts"] == {}

    def test_corrupt_quarantines_not_deletes(self, tmp_path, caplog):
        path = tmp_path / "art.json"
        path.write_text("{torn")
        tel = RunTelemetry()
        with caplog.at_level("WARNING", logger="peasoup_tpu"):
            with tel.activate():
                out = R.load_or_recover(
                    str(path), lambda p: json.load(open(p)),
                    default=None, kind="test artifact",
                    action="regenerating",
                )
        assert out is None
        assert not path.exists()
        q = tmp_path / "art.json.corrupt"
        assert q.exists() and q.read_text() == "{torn"  # forensics kept
        assert any(
            "discarding unreadable test artifact" in r.message
            for r in caplog.records
        )
        ev = next(e for e in tel.events if e["kind"] == "corrupt_artifact")
        assert ev["quarantined_to"] == str(q)
        assert STATS.snapshot()["corrupt_artifacts"]["test artifact"] == 1

    def test_quarantine_false_keeps_file(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text("{torn")
        out = R.load_or_recover(
            str(path), lambda p: json.load(open(p)),
            default=None, kind="baseline", quarantine=False,
        )
        assert out is None
        assert path.exists()  # checked-in files are never renamed


# --------------------------------------------------------------------------
# fault registry
# --------------------------------------------------------------------------

class TestFaultRegistry:
    def test_grammar_rejects_unknown_site_and_bad_kv(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            faults.parse_faults("nope.site:n=1")
        with pytest.raises(ValueError, match="malformed"):
            faults.parse_faults("fil.read:n")
        with pytest.raises(ValueError, match="unknown fault option"):
            faults.parse_faults("fil.read:zz=1")

    def test_bare_site_fires_once(self):
        faults.configure("fil.read")
        with pytest.raises(R.TransientIOError, match="injected"):
            faults.fire("fil.read", "a")
        faults.fire("fil.read", "b")  # budget spent: silent

    def test_at_ordinal_and_at_context(self):
        faults.configure("db.ingest:at=2,worker.kill:at=jobX")
        faults.fire("db.ingest", "first")
        with pytest.raises(sqlite3.OperationalError, match="locked"):
            faults.fire("db.ingest", "second")
        faults.fire("worker.kill", "jobA")  # no context match
        with pytest.raises(R.WorkerKilled):
            faults.fire("worker.kill", "jobX-77")
        faults.fire("worker.kill", "jobX-77")  # fires once

    def test_probability_schedule_is_seed_deterministic(self):
        a = faults.parse_faults("fil.read:p=0.4:n=999", seed=11)
        b = faults.parse_faults("fil.read:p=0.4:n=999", seed=11)
        c = faults.parse_faults("fil.read:p=0.4:n=999", seed=12)
        draw = lambda pl: [
            pl.rules["fil.read"].should_fire("") for _ in range(64)
        ]
        da, db_, dc = draw(a), draw(b), draw(c)
        assert da == db_
        assert da != dc
        assert any(da) and not all(da)

    def test_injected_exception_is_attributable(self):
        faults.configure("checkpoint.write:n=1")
        with pytest.raises(R.TransientIOError) as ei:
            faults.fire("checkpoint.write", "ck")
        assert "[injected:checkpoint.write#1]" in str(ei.value)

    def test_env_var_activation_and_explicit_override(self, monkeypatch):
        monkeypatch.setenv("PEASOUP_FAULTS", "fil.read:n=1")
        faults._ENV_CHECKED = False  # simulate a fresh process
        assert faults.active_plan() is not None
        faults.configure(None)  # explicit wins over env
        assert faults.active_plan() is None
        faults.fire("fil.read", "x")  # disabled: no raise

    def test_disabled_fire_is_cheap_and_silent(self):
        faults.configure(None)
        t0 = time.perf_counter()
        for _ in range(10000):
            faults.fire("fil.read", "hot")
        dt = time.perf_counter() - t0
        assert dt < 0.5  # ~tens of ns/call; generous CI bound
        assert STATS.snapshot()["faults_injected"] == {}


# --------------------------------------------------------------------------
# threaded call sites
# --------------------------------------------------------------------------

def _write_tiny_fil(path, nsamps=256, nchans=4):
    from peasoup_tpu.io.sigproc import (
        Filterbank,
        SigprocHeader,
        write_filterbank,
    )

    hdr = SigprocHeader(
        source_name="T", tsamp=1e-3, fch1=1400.0, foff=-16.0,
        nchans=nchans, nbits=8, nifs=1, data_type=1,
    )
    data = np.zeros((nsamps, nchans), np.uint8) + 32
    write_filterbank(path, Filterbank(header=hdr, data=data))
    return path


class TestCallSites:
    def test_read_filterbank_survives_flaky_reads(self, tmp_path):
        from peasoup_tpu.io.sigproc import read_filterbank

        path = _write_tiny_fil(str(tmp_path / "a.fil"))
        faults.configure("fil.read:n=2")
        fil = read_filterbank(path)
        assert fil.nsamps == 256
        snap = STATS.snapshot()
        assert snap["faults_injected"]["fil.read"] == 2
        assert snap["recoveries"]["fil.read"] == 1

    def test_read_filterbank_gives_up_when_budget_spent(self, tmp_path):
        from peasoup_tpu.io.sigproc import read_filterbank

        path = _write_tiny_fil(str(tmp_path / "b.fil"))
        faults.configure("fil.read:n=99")
        with pytest.raises(R.TransientIOError):
            read_filterbank(path)
        assert STATS.snapshot()["giveups"]["fil.read"] == 1

    def test_short_read_is_transient_then_fatal(self, tmp_path):
        """A payload shorter than the header's declared nsamples (a
        recorder still appending, or a torn copy) is transient: it
        retries, then raises when the budget is spent. Needs an
        explicit nsamples header keyword — without one the reader
        derives nsamples from the file size and can't see the tear."""
        import struct

        from peasoup_tpu.io.sigproc import read_filterbank

        def ws(f, s):
            b = s.encode()
            f.write(struct.pack("<i", len(b)))
            f.write(b)

        path = str(tmp_path / "c.fil")
        with open(path, "wb") as f:
            ws(f, "HEADER_START")
            for key, val in (
                ("nchans", 4), ("nbits", 8), ("nsamples", 256),
                ("nifs", 1), ("data_type", 1),
            ):
                ws(f, key)
                f.write(struct.pack("<i", val))
            for key, val in (
                ("tsamp", 1e-3), ("fch1", 1400.0), ("foff", -16.0),
            ):
                ws(f, key)
                f.write(struct.pack("<d", val))
            ws(f, "HEADER_END")
            f.write(b"\x20" * (256 * 4 - 64))  # 64 bytes short
        with pytest.raises(R.TransientIOError, match="short read"):
            read_filterbank(path)
        assert STATS.snapshot()["retries"]["fil.read"] >= 1

    def test_queue_claim_survives_injected_io_failure(self, tmp_path):
        from peasoup_tpu.campaign.queue import Job, JobQueue

        q = JobQueue(str(tmp_path))
        q.add_job(Job(job_id="j1", input="x.fil"))
        faults.configure("queue.claim:n=1")
        claim = q.try_claim("j1", "w1")
        assert claim is not None  # retried through the injection
        assert STATS.snapshot()["recoveries"]["queue.claim"] == 1

    def test_checkpoint_write_retries_and_load_quarantines(self, tmp_path):
        from peasoup_tpu.pipeline.checkpoint import SearchCheckpoint

        base = str(tmp_path / "s.ckpt")
        payload = {
            0: (
                np.zeros((2, 4), np.int32),
                np.zeros((4,), np.float32),
                np.asarray(0, np.int32),
            )
        }
        ck = SearchCheckpoint(base, "k")
        faults.configure("checkpoint.write:n=1")
        ck.save(payload)  # survives the injected write failure
        assert sorted(ck.load()) == [0]
        assert STATS.snapshot()["recoveries"]["checkpoint.write"] == 1
        # now corrupt on disk: load quarantines (satellite migration of
        # the old discard-with-warning contract)
        faults.configure(None)
        with open(base, "r+b") as f:
            f.truncate(20)
        assert ck.load() == {}
        assert os.path.exists(base + ".corrupt")
        assert not os.path.exists(base)
        # a fresh save over the damage fully recovers
        ck.save(payload)
        assert sorted(ck.load()) == [0]

    def test_checkpoint_slice_corrupt_sibling_quarantined(self, tmp_path):
        """A damaged per-slice store must not poison the union load,
        and its .corrupt quarantine must not re-enter _store_files."""
        from peasoup_tpu.pipeline.checkpoint import SearchCheckpoint

        base = str(tmp_path / "m.ckpt")

        def payload(k):
            return {
                0: (
                    np.full((2, 4), k, np.int32),
                    np.zeros((4,), np.float32),
                    np.asarray(0, np.int32),
                )
            }

        SearchCheckpoint(base, "k", slice_bounds=(0, 4)).save(payload(0))
        SearchCheckpoint(base, "k", slice_bounds=(4, 8)).save(payload(4))
        with open(base + ".dm4-8", "r+b") as f:
            f.truncate(10)
        union = SearchCheckpoint(base, "k").load()
        assert sorted(union) == [0]
        assert os.path.exists(base + ".dm4-8.corrupt")
        # and a second load does not trip over the quarantined file
        assert sorted(SearchCheckpoint(base, "k").load()) == [0]

    def test_cache_corrupt_fault_drills_tuning_recovery(self, tmp_path):
        from peasoup_tpu.perf import tuning

        path = str(tmp_path / "tc.json")
        tuning.save_cache(path, {
            "schema": tuning.TUNING_SCHEMA,
            "version": tuning.TUNING_VERSION,
            "devices": {},
        })
        faults.configure("cache.corrupt:n=1")
        doc = tuning.load_cache(path)  # injected corruption -> empty
        assert doc["devices"] == {}
        assert os.path.exists(path + ".corrupt")
        snap = STATS.snapshot()
        assert snap["faults_injected"]["cache.corrupt"] == 1
        assert snap["corrupt_artifacts"]["tuning cache"] == 1

    def test_db_ingest_retries_through_injected_lock(self, tmp_path):
        """The injected SQLITE_BUSY drill: the ingest transaction is
        retried whole and lands exactly once."""
        from peasoup_tpu.campaign.db import CandidateDB

        job_dir = tmp_path / "job"
        _make_overview(str(job_dir))
        faults.configure("db.ingest:n=2")
        with CandidateDB(str(tmp_path / "c.sqlite")) as db:
            counts = db.ingest_job("j1", str(job_dir), "in.fil")
            assert counts["single_pulse"] == 1
            assert len(db.candidates_for("j1")) == 1
        snap = STATS.snapshot()
        assert snap["retries"]["db.ingest"] == 2
        assert snap["recoveries"]["db.ingest"] == 1


def _make_overview(job_dir):
    """A minimal real overview.xml via the production writer."""
    from peasoup_tpu.core.candidates import SinglePulseCandidate
    from peasoup_tpu.io.output import OutputFileWriter
    from peasoup_tpu.io.sigproc import SigprocHeader
    from peasoup_tpu.pipeline.single_pulse import SinglePulseConfig

    os.makedirs(job_dir, exist_ok=True)
    hdr = SigprocHeader(
        source_name="T", tsamp=1e-3, fch1=1400.0, foff=-16.0,
        nchans=4, nbits=8, nifs=1, data_type=1, nsamples=256,
    )
    cand = SinglePulseCandidate(
        dm=10.0, dm_idx=3, snr=9.5, time_s=0.1, sample=100, width=4,
        width_idx=2, members=5,
    )
    w = OutputFileWriter()
    w.add_misc_info()
    w.add_header(hdr)
    w.add_dm_list(np.asarray([0.0, 5.0, 10.0]))
    w.add_single_pulse_section(
        SinglePulseConfig(), "in.fil", (1, 2, 4), [cand]
    )
    w.to_file(os.path.join(job_dir, "overview.xml"))


class TestTwoProcessDBContention:
    def test_racing_ingesters_both_land(self, tmp_path):
        """Satellite regression: a second PROCESS holding the write
        lock must surface as busy/locked and be absorbed by the retry
        layer, with both writes landing (tiny busy_timeout forces the
        contention through OUR policy instead of sqlite's wait)."""
        from peasoup_tpu.campaign.db import CandidateDB

        db_path = str(tmp_path / "c.sqlite")
        job_dir = str(tmp_path / "job")
        _make_overview(job_dir)
        # schema init up front so the subprocess needs no setup
        CandidateDB(db_path).close()
        ctx = multiprocessing.get_context("spawn")
        started = ctx.Event()
        proc = ctx.Process(
            target=_hold_write_lock, args=(db_path, started, 0.2)
        )
        proc.start()
        try:
            assert started.wait(10.0)
            with CandidateDB(db_path, busy_timeout_ms=20) as db:
                db.ingest_job("j1", job_dir, "in.fil")
        finally:
            proc.join(10.0)
        assert proc.exitcode == 0
        with CandidateDB(db_path) as db:
            assert len(db.candidates_for("j1")) == 1
            rows = db._query(
                "SELECT COUNT(*) AS n FROM candidates "
                "WHERE job_id = 'locker'"
            )
            assert rows[0]["n"] == 1
        assert STATS.snapshot()["retries"].get("db.ingest", 0) >= 1


def _hold_write_lock(db_path, started, hold_s):
    conn = sqlite3.connect(db_path, timeout=10.0)
    conn.execute("PRAGMA busy_timeout=10000")
    conn.execute("BEGIN IMMEDIATE")
    conn.execute(
        "INSERT INTO observations (job_id, input) VALUES ('locker', 'x')"
    )
    conn.execute(
        "INSERT INTO candidates (job_id, kind, dm, snr) "
        "VALUES ('locker', 'single_pulse', 1.0, 9.0)"
    )
    started.set()
    time.sleep(hold_s)
    conn.commit()
    conn.close()


# --------------------------------------------------------------------------
# degradation rungs fire in order, bitwise-equal where guaranteed
# --------------------------------------------------------------------------

class TestDegradationRungs:
    def test_sp_oom_rung_fires_and_results_match(self, tmp_path):
        """device.oom injection at the single-pulse wave dispatch:
        the shrink rung fires, emits its ladder event, and the
        candidate set is bitwise-equal to the fault-free run (the
        ladder's guarantee for the shrink rung)."""
        from test_campaign import make_obs

        from peasoup_tpu.io.sigproc import read_filterbank
        from peasoup_tpu.pipeline.single_pulse import (
            SinglePulseConfig,
            SinglePulseSearch,
        )

        path = make_obs(str(tmp_path / "o.fil"))
        fil = read_filterbank(path)
        cfg = SinglePulseConfig(
            dm_end=20.0, min_snr=7.0, n_widths=6, dm_block=8,
            outdir=str(tmp_path),
        )
        want = SinglePulseSearch(cfg).run(fil)

        faults.configure("device.oom:at=1")
        tel = RunTelemetry()
        with tel.activate():
            got = SinglePulseSearch(cfg).run(fil)
        degs = [e for e in tel.events if e["kind"] == "degradation"]
        assert degs and degs[0]["ladder"] == "spsearch.memory"
        assert degs[0]["rung"] == "dm_block_shrink"
        assert any(
            e["kind"] == "sp_oom_shrink_retry" for e in tel.events
        )
        assert len(got.candidates) == len(want.candidates) > 0
        for a, b in zip(want.candidates, got.candidates):
            assert (a.dm_idx, a.sample, a.width) == (
                b.dm_idx, b.sample, b.width
            )
            assert a.snr == b.snr  # bitwise: same shapes per trial


# --------------------------------------------------------------------------
# background-thread crash guard (satellite)
# --------------------------------------------------------------------------

class TestThreadCrashGuard:
    def test_guard_thread_emits_event_and_degrades(self):
        tel = RunTelemetry()

        def boom():
            raise RuntimeError("thread bug")

        exc = R.guard_thread("t-thread", boom, telemetry=tel)
        assert isinstance(exc, RuntimeError)
        ev = next(e for e in tel.events if e["kind"] == "thread_crashed")
        assert ev["thread"] == "t-thread"
        snap = STATS.snapshot()
        assert snap["thread_crashes"]["t-thread"] == 1
        assert snap["degraded"] is True
        # ... which every run's status section now reports
        assert tel.snapshot_sections()["resilience"]["degraded"] is True

    def test_warmer_crash_does_not_kill_the_job(self, tmp_path, monkeypatch):
        """Satellite: a crashing _BucketWarmer thread must emit
        thread_crashed on the job's telemetry and leave the campaign
        job runnable (warmup is an optimisation, not a dependency)."""
        from peasoup_tpu.campaign import runner as runner_mod
        from peasoup_tpu.campaign.runner import _BucketWarmer

        def explode(*a, **k):
            raise RuntimeError("warmup bug")

        monkeypatch.setattr(
            "peasoup_tpu.perf.warmup.warm_bucket", explode
        )
        tel = RunTelemetry()
        w = _BucketWarmer(
            (4, 8, 256, 1e-3, 1400.0, -16.0), "spsearch", {},
            str(tmp_path / "scratch"), "dryrun", telemetry=tel,
        )
        w.start()
        stats = w.result(timeout=30.0)
        assert "crashed" in stats["error"]
        assert any(
            e["kind"] == "thread_crashed"
            and e["thread"] == "campaign-warmup"
            for e in tel.events
        )
        assert STATS.snapshot()["thread_crashes"]["campaign-warmup"] == 1
        assert runner_mod is not None  # keep the import referenced

    def test_stream_reader_crash_is_structured(self, tmp_path):
        """Satellite: the stream reader thread emits thread_crashed
        (plus the existing stream_reader_error) instead of dying
        invisibly."""
        from peasoup_tpu.stream.driver import StreamConfig, StreamingSearch

        self._outdir = tmp_path

        from peasoup_tpu.io.stream_source import StreamFormat

        class ExplodingSource:
            format = StreamFormat(
                nchans=4, nbits=8, tsamp=1e-3, fch1=1400.0, foff=-16.0
            )
            block_samples = 64

            def blocks(self):
                raise RuntimeError("reader bug")
                yield  # pragma: no cover

            def close(self):
                pass

        cfg = StreamConfig(
            outdir=str(self._outdir), dm_end=5.0, chunk_samples=128,
            n_widths=3, decimate=8, warmup=False,
        )
        tel = RunTelemetry()
        with tel.activate(), pytest.raises(RuntimeError):
            StreamingSearch(cfg).run(ExplodingSource())
        kinds = [e["kind"] for e in tel.events]
        assert "thread_crashed" in kinds
        assert "stream_reader_error" in kinds
        assert STATS.snapshot()["thread_crashes"][
            "peasoup-stream-reader"
        ] == 1

    def test_clock_skew_reap_degrades_to_extra_attempt(self, tmp_path):
        """clock.skew drill: a reaper whose clock runs fast reaps a
        live claim early — the job burns one attempt but is never
        lost (it re-queues claimable), and the injection is
        attributable in the stats."""
        from peasoup_tpu.campaign.queue import Job, JobQueue

        q = JobQueue(str(tmp_path), lease_s=30.0, backoff_base_s=0.0)
        q.add_job(Job(job_id="j1", input="x.fil"))
        claim = q.try_claim("j1", "w1")
        assert claim is not None
        faults.configure("clock.skew:skew=3600")
        reaped = q.reap_stale()
        assert reaped == ["j1"]  # skewed clock saw the lease expired
        faults.configure(None)
        job = q.get_job("j1")
        assert job.attempts == 1  # one attempt burned, job not lost
        assert q.state("j1") in ("pending", "backoff")
        assert q.try_claim("j1", "w2") is not None  # still claimable
        assert STATS.snapshot()["faults_injected"]["clock.skew"] == 1


# --------------------------------------------------------------------------
# multihost fault sites (barrier / merge)
# --------------------------------------------------------------------------

class TestMultihostFaultSites:
    def test_barrier_injection_is_transient(self):
        """A host dying at the allgather barrier must fail the step
        classified TRANSIENT (fast, retryable) — never hang."""
        from peasoup_tpu.parallel.multihost import _allgather_pickled

        faults.configure("multihost.barrier:n=1")
        with pytest.raises(R.TransientIOError) as ei:
            _allgather_pickled(b"payload", context="search:candidates")
        assert R.classify(ei.value) == R.TRANSIENT
        assert "[injected:multihost.barrier#1]" in str(ei.value)
        # budget spent: the single-process identity path works again
        assert _allgather_pickled(b"payload", context="x") == [b"payload"]
        assert STATS.snapshot()["faults_injected"]["multihost.barrier"] == 1

    def test_merge_injection_is_transient(self):
        import pickle

        from peasoup_tpu.parallel.multihost import _unpickle_all

        blob = pickle.dumps({"cands": [1, 2]})
        faults.configure("multihost.merge:n=1")
        with pytest.raises(R.TransientIOError) as ei:
            _unpickle_all([blob], context="spsearch:events")
        assert R.classify(ei.value) == R.TRANSIENT
        assert _unpickle_all([blob], context="x") == [{"cands": [1, 2]}]
        assert STATS.snapshot()["faults_injected"]["multihost.merge"] == 1

    def test_real_collective_error_reclassified_transient(self):
        """A distributed-runtime failure signature (coordinator
        deadline, dropped connection) re-raises as TransientIOError;
        a programming error propagates unchanged."""
        from peasoup_tpu.parallel.multihost import (
            _classify_collective_error,
        )

        with pytest.raises(R.TransientIOError):
            _classify_collective_error(
                RuntimeError("DEADLINE_EXCEEDED: barrier timed out"),
                "search:candidates",
            )
        with pytest.raises(ValueError, match="bad shape"):
            _classify_collective_error(ValueError("bad shape"), "x")

    def test_sites_zero_cost_when_off(self):
        faults.configure(None)
        t0 = time.perf_counter()
        for _ in range(10000):
            faults.fire("multihost.barrier", "hot")
            faults.fire("multihost.merge", "hot")
        assert time.perf_counter() - t0 < 0.5
        assert STATS.snapshot()["faults_injected"] == {}


# --------------------------------------------------------------------------
# cache.corrupt through the persistent XLA compilation cache
# --------------------------------------------------------------------------

class TestCacheCorruptWarmup:
    @pytest.fixture()
    def scratch_cache(self, tmp_path, monkeypatch):
        """Point the persistent compilation cache at a scratch dir for
        the duration (resetting jax's lazily-initialised cache object
        so the dir change takes effect mid-process), restoring the
        suite's shared cache after."""
        import jax

        def _reset():
            try:
                from jax._src import compilation_cache as _cc

                _cc.reset_cache()
            except Exception:
                pass

        cache = str(tmp_path / "xla_cache")
        old = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
        _reset()
        yield cache
        jax.config.update("jax_compilation_cache_dir", old)
        _reset()

    def test_garbled_entry_quarantines_and_recompiles(self, scratch_cache):
        """The acceptance drill: a cache.corrupt injection during
        warmup quarantines the persistent cache's entries to
        ``*.corrupt`` and the program recompiles — warmup reports NO
        error, and the quarantine is attributable."""
        import glob as _glob

        from peasoup_tpu.ops.registry import registered_programs
        from peasoup_tpu.perf.warmup import warm_registry
        from peasoup_tpu.utils.cache import cache_entry_paths

        name = registered_programs()[0].name
        cold = warm_registry(programs=[name])
        assert cold.programs[0].error is None
        entries = cache_entry_paths(scratch_cache)
        assert entries  # the cold compile populated the cache
        # garble a real entry's bytes, then schedule the injection
        faults.configure("cache.corrupt:n=1")
        faults.maybe_corrupt_file(entries[0], context="xla-cache-entry")
        faults.configure("cache.corrupt:n=1")  # re-arm for the seam
        rep = warm_registry(programs=[name])
        assert rep.programs[0].error is None  # recovered, not crashed
        corrupt = _glob.glob(os.path.join(scratch_cache, "*.corrupt"))
        assert corrupt  # forensics kept aside
        assert cache_entry_paths(scratch_cache) == []  # all quarantined
        snap = STATS.snapshot()
        assert snap["corrupt_artifacts"]["xla cache"] >= 1
        assert snap["faults_injected"]["cache.corrupt"] >= 1
        # and a clean pass repopulates the cache from scratch
        faults.configure(None)
        again = warm_registry(programs=[name])
        assert again.programs[0].error is None

    def test_quarantine_helper_renames_not_deletes(self, tmp_path):
        from peasoup_tpu.utils.cache import (
            cache_entry_paths,
            quarantine_cache_entries,
        )

        d = tmp_path / "cache"
        d.mkdir()
        (d / "entry1").write_bytes(b"\x00CHAOS-CORRUPT\x00")
        (d / "entry2").write_bytes(b"fine")
        q = quarantine_cache_entries(str(d))
        assert len(q) == 2
        assert (d / "entry1.corrupt").read_bytes().startswith(b"\x00CHAOS")
        assert cache_entry_paths(str(d)) == []
        assert STATS.snapshot()["corrupt_artifacts"]["xla cache"] == 1

    def test_non_corrupt_compile_error_still_reported(self, scratch_cache):
        """A genuine trace/compile failure must NOT trigger the cache
        quarantine — it is a finding, not a torn artifact."""
        from peasoup_tpu.perf.warmup import _compile_with_cache_recovery

        import jax

        def broken(x):
            raise ValueError("genuine trace bug")

        err = _compile_with_cache_recovery(
            jax, broken, (jax.ShapeDtypeStruct((4,), "float32"),), {},
            "broken", scratch_cache,
        )
        assert err is not None and "genuine trace bug" in err
        assert STATS.snapshot()["corrupt_artifacts"] == {}


# --------------------------------------------------------------------------
# device.oom fall-through: shrink -> (subband ->) CPU instead of raising
# --------------------------------------------------------------------------

class TestOOMFallThrough:
    def test_sp_exhaustion_falls_through_to_cpu_bitwise(self, tmp_path):
        """Single-pulse driver: exhausting the shrink rung
        (dm_block=4 -> 2 -> 1, three injections) steps the cpu_backend
        rung instead of raising, and the candidates are bitwise-equal
        to the fault-free run."""
        from test_campaign import make_obs

        from peasoup_tpu.io.sigproc import read_filterbank
        from peasoup_tpu.pipeline.single_pulse import (
            SinglePulseConfig,
            SinglePulseSearch,
        )

        path = make_obs(str(tmp_path / "o.fil"))
        fil = read_filterbank(path)
        cfg = SinglePulseConfig(
            dm_end=20.0, min_snr=7.0, n_widths=6, dm_block=4,
            outdir=str(tmp_path),
        )
        want = SinglePulseSearch(cfg).run(fil)
        faults.configure("device.oom:n=3")
        tel = RunTelemetry()
        with tel.activate():
            got = SinglePulseSearch(cfg).run(fil)
        rungs = [
            (e["ladder"], e["rung"]) for e in tel.events
            if e["kind"] == "degradation"
        ]
        assert rungs == [
            ("spsearch.memory", "dm_block_shrink"),
            ("spsearch.memory", "dm_block_shrink"),
            ("spsearch.memory", "cpu_backend"),
        ]
        assert not any(
            e["kind"] == "degradation_exhausted" for e in tel.events
        )
        assert len(got.candidates) == len(want.candidates) > 0
        for a, b in zip(want.candidates, got.candidates):
            assert (a.dm_idx, a.sample, a.width) == (
                b.dm_idx, b.sample, b.width
            )
            assert a.snr == b.snr  # bitwise
        assert STATS.snapshot()["degradations"][
            "spsearch.memory:cpu_backend"
        ] == 1

    def test_search_falls_through_subband_then_cpu_bitwise(self, tmp_path):
        """Periodicity driver: three injections exhaust the shrink
        rung into the exact-subband rung; a fourth OOMs the subband
        attempt into the CPU rung. Both paths must produce candidates
        bitwise-equal to the fault-free run (max_smear=0 subbanding is
        the direct sum; the CPU rung re-runs the identical programs)."""
        import numpy as np

        from peasoup_tpu.io.sigproc import read_filterbank
        from peasoup_tpu.perf.warmup import synthetic_bucket_observation
        from peasoup_tpu.pipeline.search import PeasoupSearch, SearchConfig

        bucket = (8, 8, 4096, 0.000256, 1400.0, -16.0)
        fil = synthetic_bucket_observation(
            bucket, str(tmp_path / "o.fil")
        )
        cfg = SearchConfig(
            dm_end=20.0, min_snr=7.0, dm_block=4, outdir=str(tmp_path),
            limit=50,
        )
        want = PeasoupSearch(cfg).run(fil)
        assert len(want.candidates) > 0  # the pulse train is periodic

        def sig(res):
            return [
                (c.dm_idx, c.nh, c.acc, c.freq, c.snr)
                for c in res.candidates
            ]

        # n=3: shrink x2 -> subband rung runs clean
        faults.configure("device.oom:n=3")
        tel = RunTelemetry()
        with tel.activate():
            got = PeasoupSearch(cfg).run(fil)
        rungs = [
            e["rung"] for e in tel.events if e["kind"] == "degradation"
        ]
        assert rungs == ["dm_block_shrink", "dm_block_shrink", "subband"]
        assert sig(got) == sig(want)

        # n=6: the subband rung's own shrink sequence (restarted at
        # the full block) OOMs to the floor too -> CPU rung
        faults.configure("device.oom:n=6")
        tel = RunTelemetry()
        with tel.activate():
            got2 = PeasoupSearch(cfg).run(fil)
        rungs2 = [
            e["rung"] for e in tel.events if e["kind"] == "degradation"
        ]
        # in-rung shrinks after the subband step are events, not
        # ladder steps (a ladder never climbs back up)
        assert rungs2 == [
            "dm_block_shrink", "dm_block_shrink", "subband", "cpu_backend",
        ]
        assert sum(
            1 for e in tel.events if e["kind"] == "oom_shrink_retry"
        ) == 4
        assert sig(got2) == sig(want)
        assert np.isfinite([c.snr for c in got2.candidates]).all()

    def test_degraded_flag_lands_in_done_record(self, tmp_path):
        """A campaign job that descended a ladder completes with
        degraded=true in its done record (and the rollup tallies it)."""
        from test_campaign import make_obs

        from peasoup_tpu.campaign.queue import JobQueue, job_id_for
        from peasoup_tpu.campaign.rollup import build_status
        from peasoup_tpu.campaign.runner import (
            CampaignConfig,
            bucket_for_input,
            enqueue_entries,
            run_worker,
            save_campaign_config,
        )

        root = str(tmp_path / "camp")
        obs = make_obs(str(tmp_path / "o.fil"))
        save_campaign_config(
            root,
            CampaignConfig(
                warmup=False,
                config={
                    "dm_end": 20.0, "min_snr": 7.0, "n_widths": 6,
                    "dm_block": 4,
                },
            ),
        )
        q = JobQueue(root)
        enqueue_entries(q, [{"input": obs}], "spsearch")
        faults.configure("device.oom:n=3")  # exhausts into the cpu rung
        tally = run_worker(root, worker_id="w1", poll_s=0.05)
        faults.configure(None)
        assert tally["done"] == 1
        [done] = q.done_records()
        assert done["degraded"] is True
        assert done["resilience"]["degradations"][
            "spsearch.memory:cpu_backend"
        ] == 1
        st = build_status(root, q)
        assert st["degraded_jobs"] == 1
