"""Live observability tests: the status.json heartbeat + stall
watchdog, the crash flight recorder (including real-SIGTERM abort
forensics in a subprocess), per-host manifest merging with straggler
statistics, manifest schema validation, and the report tool's
older-schema tolerance."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from argparse import Namespace

import pytest

jax = pytest.importorskip("jax")

from peasoup_tpu import obs
from peasoup_tpu.cli import live_observability
from peasoup_tpu.utils import Stopwatch
from test_pipeline import make_synthetic_fil


def _args(**kw):
    base = dict(
        status_json=None, heartbeat_interval=0.02,
        no_flight_recorder=False,
    )
    base.update(kw)
    return Namespace(**base)


# --------------------------------------------------------------------------
# telemetry live-state plumbing
# --------------------------------------------------------------------------

def test_stage_and_progress_tracking():
    t = obs.RunTelemetry()
    assert t.current_stage is None
    t.set_stage("plan")
    assert t.current_stage == "plan"
    t.set_stage("plan")  # idempotent: no duplicate event
    assert [e["kind"] for e in t.events] == ["stage"]
    with t.stage("searching"):
        assert t.current_stage == "searching"
        with t.stage("inner"):
            assert t.current_stage == "inner"
        assert t.current_stage == "searching"
    t.set_progress(3, 10, unit="chunks")
    assert t.progress_state["done"] == 3.0
    assert t.progress_state["total"] == 10.0
    assert t.progress_state["unit"] == "chunks"
    # NOOP absorbs both without state
    obs.NOOP.set_stage("x")
    obs.NOOP.set_progress(1, 2)
    assert obs.NOOP.current_stage is None
    assert obs.NOOP.progress_state == {}


def test_event_listeners():
    t = obs.RunTelemetry()
    seen = []
    t.add_listener(seen.append)
    t.event("a", x=1)

    def boom(rec):
        raise RuntimeError("listener bug")

    t.add_listener(boom)
    t.event("b")  # a broken listener must not break recording
    t.remove_listener(seen.append)
    t.event("c")
    assert [r["kind"] for r in seen] == ["a", "b"]
    assert [r["kind"] for r in t.events] == ["a", "b", "c"]


def test_manifest_v2_tags_and_aborted(tmp_path):
    t = obs.RunTelemetry(run_id="v2")
    t.set_stage("searching")
    t.set_progress(2, 8, unit="chunks")
    man = t.write(str(tmp_path / "m.json"))
    assert man["version"] == obs.MANIFEST_VERSION >= 2
    assert man["process_index"] == 0
    assert man["process_count"] >= 1
    assert "aborted" not in man
    aborted = t.write(
        str(tmp_path / "a.json"), aborted=True, abort_reason="signal:TERM"
    )
    assert aborted["aborted"] is True
    assert aborted["abort_reason"] == "signal:TERM"
    assert aborted["stage_at_abort"] == "searching"
    assert aborted["progress_at_abort"]["done"] == 2.0
    assert obs.load_manifest(str(tmp_path / "a.json"))["aborted"] is True


# --------------------------------------------------------------------------
# heartbeat + stall watchdog
# --------------------------------------------------------------------------

def test_heartbeat_snapshots_progress(tmp_path):
    t = obs.RunTelemetry(run_id="hb")
    path = str(tmp_path / "status.json")
    hb = obs.Heartbeat(t, path, interval=0.02, stall_timeout=100.0)
    with hb:
        t.set_stage("searching")
        t.set_progress(1, 10, unit="chunks")
        time.sleep(0.1)
        s1 = obs.load_status(path)
        t.set_progress(6, 10, unit="chunks")
        time.sleep(0.1)
        s2 = obs.load_status(path)
    final = obs.load_status(path)
    assert s1["schema"] == obs.STATUS_SCHEMA
    assert s2["seq"] > s1["seq"]
    assert s2["progress"]["done"] > s1["progress"]["done"]
    assert s2["progress"]["frac"] == pytest.approx(0.6)
    assert s2["progress"]["rate_per_s"] > 0
    assert s2["progress"]["eta_s"] is not None
    assert s2["stage"] == "searching"
    assert final["done"] is True
    assert final["run_id"] == "hb"
    # stopping twice is harmless
    hb.stop()


def test_heartbeat_stall_watchdog(tmp_path):
    t = obs.RunTelemetry(run_id="stall")
    path = str(tmp_path / "status.json")
    hb = obs.Heartbeat(t, path, interval=0.02, stall_timeout=0.08)
    with hb:
        t.set_stage("searching")
        t.set_progress(1, 10)
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            if any(e["kind"] == "stall" for e in t.events):
                break
            time.sleep(0.02)
        st = obs.load_status(path)
        assert st["stalled"] is True
        stall = next(e for e in t.events if e["kind"] == "stall")
        assert stall["stage"] == "searching"
        assert stall["stalled_for_s"] >= 0.08
        # exactly one stall event per episode (no oscillation)
        time.sleep(0.2)
        assert sum(e["kind"] == "stall" for e in t.events) == 1
        # progress resumes -> recovery event, stalled clears
        t.set_progress(2, 10)
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            if any(e["kind"] == "stall_recovered" for e in t.events):
                break
            time.sleep(0.02)
        assert any(e["kind"] == "stall_recovered" for e in t.events)
        time.sleep(0.06)
        assert obs.load_status(path)["stalled"] is False


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------

def test_flight_ring_is_bounded_and_dump_writes_both(tmp_path):
    t = obs.RunTelemetry(run_id="fr")
    t.set_context(command="unit")
    t.set_stage("searching")
    t.set_progress(4, 9, unit="chunks")
    fpath = str(tmp_path / "flight.json")
    mpath = str(tmp_path / "telemetry.json")
    fr = obs.FlightRecorder(t, fpath, manifest_path=mpath, ring=64)
    for i in range(200):
        t.event("tick", i=i)
    doc = fr.dump("unit-test")
    fr.close()
    assert fr.dump("again") is None  # at most once
    flight = obs.load_flight(fpath)
    assert flight["schema"] == obs.FLIGHT_SCHEMA
    assert flight["reason"] == "unit-test"
    assert flight["stage"] == "searching"
    assert flight["progress"]["done"] == 4.0
    ticks = [e for e in flight["events"] if e["kind"] == "tick"]
    assert len(flight["events"]) == 64  # ring bound
    assert ticks[-1]["i"] == 199  # ... keeping the most recent
    man = obs.load_manifest(mpath)
    assert man["aborted"] is True
    assert man["abort_reason"] == "unit-test"
    assert doc["run_id"] == "fr"


def test_live_observability_dumps_on_exception(tmp_path):
    t = obs.RunTelemetry(run_id="exc")
    prev_term = signal.getsignal(signal.SIGTERM)
    mpath = str(tmp_path / "telemetry.json")
    with pytest.raises(RuntimeError, match="boom"):
        with live_observability(
            t,
            _args(status_json=str(tmp_path / "status.json")),
            str(tmp_path),
            mpath,
        ):
            t.event("before_crash")
            raise RuntimeError("boom")
    flight = obs.load_flight(str(tmp_path / "flight.json"))
    assert flight["reason"] == "exception:RuntimeError"
    assert "boom" in flight["exception"]
    assert any(e["kind"] == "before_crash" for e in flight["events"])
    assert obs.load_manifest(mpath)["aborted"] is True
    # heartbeat left a final snapshot; handlers were restored
    assert obs.load_status(str(tmp_path / "status.json"))["done"] is True
    assert signal.getsignal(signal.SIGTERM) is prev_term


def test_live_observability_clean_exit_leaves_no_flight(tmp_path):
    t = obs.RunTelemetry(run_id="clean")
    with live_observability(t, _args(), str(tmp_path), None):
        t.event("fine")
    assert not (tmp_path / "flight.json").exists()


# --------------------------------------------------------------------------
# end-to-end: heartbeat through the peasoup CLI, SIGTERM forensics
# --------------------------------------------------------------------------

def test_e2e_status_json_snapshots(tmp_path):
    """Acceptance: a tiny end-to-end run with --status-json produces at
    least two distinct snapshots with progress advancing between them."""
    from peasoup_tpu.cli.peasoup import main as peasoup_main

    path, _, _ = make_synthetic_fil(tmp_path)
    outdir = tmp_path / "out"
    status = tmp_path / "status.json"
    snaps: dict[int, dict] = {}
    stop = threading.Event()

    def watcher():
        while not stop.is_set():
            try:
                with open(status) as f:
                    st = json.load(f)
                snaps[st["seq"]] = st
            except (OSError, json.JSONDecodeError, KeyError):
                pass
            time.sleep(0.005)

    th = threading.Thread(target=watcher, daemon=True)
    th.start()
    try:
        rc = peasoup_main(
            ["-i", str(path), "-o", str(outdir), "--dm_end", "40",
             "-n", "2", "--limit", "20",
             "--status-json", str(status),
             "--heartbeat-interval", "0.02"]
        )
    finally:
        stop.set()
        th.join(timeout=5)
    assert rc == 0
    final = obs.load_status(str(status))
    snaps[final["seq"]] = final
    assert len(snaps) >= 2, "expected at least two distinct snapshots"
    first = snaps[min(snaps)]
    last = snaps[max(snaps)]
    assert last["done"] is True
    # progress advanced between the snapshots: the first beat fires
    # before the search loop (no/zero progress), the last carries the
    # completed chunk counter
    assert last["progress"] is not None
    assert last["progress"]["done"] == last["progress"]["total"] > 0
    assert (
        first.get("progress") is None
        or first["progress"]["done"] < last["progress"]["done"]
        or first["stage"] != last["stage"]
    )
    # the searching stage was visible live in at least one snapshot
    stages = {s.get("stage") for s in snaps.values()}
    assert "searching" in stages or "done" in stages
    # clean exit: no flight dump, manifest not marked aborted
    assert not (outdir / "flight.json").exists()
    man = obs.load_manifest(str(outdir / "telemetry.json"))
    assert "aborted" not in man
    kinds = [e["kind"] for e in man["events"]]
    assert "stage" in kinds


def test_sigterm_leaves_flight_and_aborted_manifest(tmp_path):
    """Acceptance: a SIGTERM'd run leaves flight.json + a partial
    manifest marked aborted (real process, real signal)."""
    path, _, _ = make_synthetic_fil(tmp_path)
    outdir = tmp_path / "out"
    outdir.mkdir()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    worker = os.path.join(os.path.dirname(__file__), "abort_worker.py")
    proc = subprocess.Popen(
        [sys.executable, worker, str(path), str(outdir)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        # the heartbeat's first snapshot lands only after the flight
        # recorder is armed (live_observability orders it so): once
        # status.json exists, SIGTERM forensics are guaranteed
        status = outdir / "status.json"
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and proc.poll() is None:
            if status.exists():
                break
            time.sleep(0.05)
        assert status.exists(), "run never wrote a heartbeat"
        time.sleep(0.2)  # let the run get properly underway
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    stderr = proc.stderr.read().decode("utf-8", "replace")
    assert proc.returncode == -signal.SIGTERM, (
        f"expected SIGTERM death, got rc={proc.returncode}; "
        f"stderr tail: {stderr[-800:]}"
    )
    flight = obs.load_flight(str(outdir / "flight.json"))
    assert flight["reason"] == "signal:SIGTERM"
    assert flight["signum"] == int(signal.SIGTERM)
    man = obs.load_manifest(str(outdir / "telemetry.json"))
    assert man["aborted"] is True
    assert man["abort_reason"] == "signal:SIGTERM"
    # the partial manifest is schema-valid and renders like any other
    obs.validate_manifest(man)
    from peasoup_tpu.tools.report import render

    assert "ABORTED" in render(man)


# --------------------------------------------------------------------------
# multi-host shard merging + straggler stats
# --------------------------------------------------------------------------

def _shard(tmp_path, idx, hostname, timers, run_id="merge-run"):
    t = obs.RunTelemetry(run_id=f"{run_id}-p{idx}")
    t.set_context(command="peasoup", process_index=idx)
    for k, v in timers.items():
        t.add_timer(k, v)
    t.incr("search.dm_trials_done", 50 + idx)
    t.gauge("memory.peak_bytes", 1e9 * (1 + idx))
    t.event("multihost_slice", process=idx)
    man = t.to_manifest()
    man["process_index"] = idx
    man["process_count"] = 2
    man["hostname"] = hostname
    man["duration_s"] = timers.get("searching", 1.0) + 1.0
    p = tmp_path / f"telemetry.proc{idx}.json"
    p.write_text(json.dumps(man))
    return str(p)


def test_report_merge_straggler_stats(tmp_path, capsys):
    """Acceptance: merging >=2 per-host shards produces one manifest
    with per-host straggler statistics."""
    from peasoup_tpu.tools.report import main as report_main

    a = _shard(tmp_path, 0, "host-a",
               {"searching": 10.0, "dedispersion": 2.0, "total": 13.0})
    b = _shard(tmp_path, 1, "host-b",
               {"searching": 14.0, "dedispersion": 2.5, "total": 17.5})
    merged_path = tmp_path / "merged.json"
    rc = report_main(["--merge", a, b, "-o", str(merged_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "straggler" in out
    assert "host-b" in out

    merged = obs.load_manifest(str(merged_path))
    obs.validate_manifest(merged)
    assert merged["merged"] is True
    assert merged["n_hosts"] == 2
    assert [h["process_index"] for h in merged["hosts"]] == [0, 1]
    # timers: max across hosts (a stage finishes with its slowest host)
    assert merged["timers"]["searching"] == 14.0
    # counters sum, gauges high-water
    assert merged["counters"]["search.dm_trials_done"] == 101
    assert merged["gauges"]["memory.peak_bytes"] == 2e9
    strag = merged["straggler"]["timers"]["searching"]
    assert strag["min"] == 10.0 and strag["max"] == 14.0
    assert strag["spread"] == pytest.approx(4.0)
    assert strag["mean"] == pytest.approx(12.0)
    assert strag["slowest"] == {
        "process_index": 1, "hostname": "host-b",
    }
    imb = merged["straggler"]["imbalance"]
    assert imb["slowest"]["hostname"] == "host-b"
    assert imb["ratio"] > 1.0
    # merged events carry their host tag, in time order
    assert all("process_index" in e for e in merged["events"])
    # the merged manifest renders like any other
    rc = report_main([str(merged_path)])
    assert rc == 0
    assert "hosts (2)" in capsys.readouterr().out


def test_report_merge_skips_shards_missing_a_stage(tmp_path):
    """Satellite regression: a shard that never reached a stage
    (aborted early, older writer, partial manifest) is SKIPPED in that
    stage's straggler entry — no KeyError, no phantom 0.0 ranked as
    the fastest host — and recorded as missing; a shard without
    duration_s stays out of the imbalance ranking; non-numeric timer
    values are dropped rather than poisoning the math."""
    from peasoup_tpu.tools.report import merge_manifests

    a = json.loads(open(_shard(
        tmp_path, 0, "host-a",
        {"searching": 10.0, "dedispersion": 2.0})).read())
    b = json.loads(open(_shard(
        tmp_path, 1, "host-b",
        {"searching": 14.0, "dedispersion": 2.5})).read())
    c = json.loads(open(_shard(
        tmp_path, 2, "host-c",
        {"dedispersion": 1.0})).read())
    # host-c aborted before the searching stage: no timer, no duration,
    # and one corrupted timer value
    del c["duration_s"]
    c["timers"]["plan"] = "corrupt"
    c["aborted"] = True

    merged = merge_manifests([a, b, c])
    obs.validate_manifest(merged)

    strag = merged["straggler"]["timers"]["searching"]
    assert strag["n_hosts"] == 2
    assert strag["min"] == 10.0  # NOT 0.0 from the missing shard
    assert strag["slowest"] == {"process_index": 1, "hostname": "host-b"}
    assert strag["missing"] == [
        {"process_index": 2, "hostname": "host-c"}
    ]
    # all three hosts carry dedispersion: no missing list there
    ded = merged["straggler"]["timers"]["dedispersion"]
    assert ded["n_hosts"] == 3 and "missing" not in ded
    # the corrupt value neither crashes nor appears anywhere
    assert "plan" not in merged["timers"]
    assert "plan" not in merged["hosts"][2]["timers"]
    # imbalance ranks only hosts that reported a duration
    imb = merged["straggler"]["imbalance"]
    assert imb["slowest"]["hostname"] == "host-b"
    assert imb["mean_s"] == pytest.approx((11.0 + 15.0) / 2)
    # the merged manifest still renders
    from peasoup_tpu.tools.report import render

    assert "host-c" in render(merged)


def test_report_merge_all_shards_partial(tmp_path):
    """Degenerate hardening case: EVERY shard lacks duration_s — the
    merge must still succeed with a zeroed imbalance block."""
    from peasoup_tpu.tools.report import merge_manifests

    shards = []
    for i in range(2):
        man = json.loads(
            open(_shard(tmp_path, i, f"h{i}", {"plan": 0.1 * (i + 1)})).read()
        )
        del man["duration_s"]
        shards.append(man)
    merged = merge_manifests(shards)
    obs.validate_manifest(merged)
    assert merged["straggler"]["imbalance"]["ratio"] == 1.0
    assert merged["straggler"]["timers"]["plan"]["n_hosts"] == 2


def test_report_merge_needs_two_shards(tmp_path):
    from peasoup_tpu.tools.report import main as report_main

    a = _shard(tmp_path, 0, "host-a", {"searching": 1.0})
    with pytest.raises(SystemExit):
        report_main(["--merge", a])


# --------------------------------------------------------------------------
# schema validation + older-manifest tolerance
# --------------------------------------------------------------------------

FIXTURE_V1 = os.path.join(
    os.path.dirname(__file__), "data", "manifest_v1.json"
)


def test_schema_validates_fresh_and_fixture(tmp_path):
    t = obs.RunTelemetry(run_id="schema")
    t.incr("c")
    t.gauge("g", 1.0)
    with t.stage("s"):
        pass
    t.event("e", a=1)
    obs.validate_manifest(t.to_manifest())
    obs.validate_manifest(
        t.to_manifest(aborted=True, abort_reason="x")
    )
    obs.validate_manifest(obs.load_manifest(FIXTURE_V1))


def test_schema_rejects_malformed():
    t = obs.RunTelemetry(run_id="bad")
    man = t.to_manifest()
    man["timers"] = {"searching": "fast"}  # must be numeric
    with pytest.raises(obs.SchemaError, match="searching"):
        obs.validate_manifest(man)
    man = t.to_manifest()
    del man["run_id"]
    with pytest.raises(obs.SchemaError, match="run_id"):
        obs.validate_manifest(man)
    with pytest.raises(obs.SchemaError, match="const"):
        obs.validate_manifest({**t.to_manifest(), "schema": "nope"})


def test_validate_manifest_cli(tmp_path, capsys):
    from peasoup_tpu.tools.validate_manifest import main as vmain

    assert vmain(["--fresh", FIXTURE_V1]) == 0
    assert "schema-valid" in capsys.readouterr().out
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": "peasoup_tpu.telemetry"}))
    assert vmain([str(bad)]) == 1


def test_report_tolerates_older_manifests(tmp_path, capsys):
    """Satellite: render/diff must .get() keys newer than a manifest's
    schema version instead of KeyError'ing."""
    from peasoup_tpu.tools.report import diff, main as report_main, render

    # the checked-in v1 fixture renders
    assert report_main([FIXTURE_V1]) == 0
    out = capsys.readouterr().out
    assert "legacy-v1-fixture" in out
    # a BARE minimal manifest (only the keys v1 required) renders and
    # diffs against a modern one without KeyError
    bare = {
        "schema": obs.MANIFEST_SCHEMA,
        "version": 1,
        "run_id": "bare",
        "created_unix": 0.0,
    }
    assert "bare" in render(bare)
    modern = obs.RunTelemetry(run_id="modern")
    modern.add_timer("searching", 1.0)
    text = diff(bare, modern.to_manifest())
    assert "bare" in text and "modern" in text and "(new)" in text
    # and load_manifest accepts v1 files (forward-compat stays rejected:
    # covered by test_obs.test_manifest_rejects_foreign_and_newer)
    assert obs.load_manifest(FIXTURE_V1)["version"] == 1


# --------------------------------------------------------------------------
# watch tool
# --------------------------------------------------------------------------

def test_watch_once_renders(tmp_path, capsys):
    from peasoup_tpu.tools.watch import main as watch_main

    t = obs.RunTelemetry(run_id="watched")
    t.set_stage("searching")
    t.set_progress(3, 12, unit="chunks")
    t.event("wave_plan", n_waves=2)
    path = str(tmp_path / "status.json")
    hb = obs.Heartbeat(t, path, interval=60.0, stall_timeout=0)
    hb.start()
    hb.stop()
    assert watch_main(["--once", path]) == 0
    out = capsys.readouterr().out
    assert "watched" in out
    assert "stage=searching" in out
    assert "chunks" in out
    assert "wave_plan" in out
    assert "run complete" in out  # final snapshot carries done
    # missing file: --once fails fast
    assert watch_main(["--once", str(tmp_path / "nope.json")]) == 1


def test_watch_campaign_rollup_renders(tmp_path, capsys):
    """Satellite: watch pointed at a campaign directory (or its
    campaign_status.json) renders the survey rollup — queue depths,
    retrying jobs with errors, quarantine — and detects the snapshot
    kind by schema, so one invocation works on both."""
    from peasoup_tpu.campaign.queue import Job, JobQueue
    from peasoup_tpu.campaign.rollup import write_status
    from peasoup_tpu.tools.watch import main as watch_main

    root = str(tmp_path / "camp")
    q = JobQueue(root, lease_s=30.0, max_attempts=2, backoff_base_s=60.0)
    for i in range(3):
        q.add_job(Job(job_id=f"job{i}", input=f"obs{i}.fil"))
    q.complete(q.try_claim("job0", "w1"), n_candidates=5)
    q.fail(q.try_claim("job1", "w1"), "flaky io")
    q.fail(q.try_claim("job1", "w1", now=time.time() + 120), "flaky io")
    write_status(root, q)

    # directory argument resolves to the rollup inside it
    assert watch_main(["--once", root]) == 0
    out = capsys.readouterr().out
    assert "campaign" in out
    assert "1/3 done" in out
    assert "quarantined=1" in out
    assert "QUARANTINED job1" in out and "flaky io" in out

    # the explicit file path works too, and a drained campaign says so
    q.complete(q.try_claim("job2", "w2"), n_candidates=1)
    q.retry("job1")
    q.complete(q.try_claim("job1", "w2"), n_candidates=0)
    write_status(root, q)
    assert watch_main(
        ["--once", os.path.join(root, "campaign_status.json")]
    ) == 0
    out = capsys.readouterr().out
    assert "3/3 done" in out
    assert "campaign complete" in out


# --------------------------------------------------------------------------
# satellites: Stopwatch context manager, peaks probe resolution, flags
# --------------------------------------------------------------------------

def test_stopwatch_context_manager_and_named_double_stop():
    with Stopwatch("DM-Loop") as sw:
        time.sleep(0.001)
    assert sw.elapsed > 0.0
    with pytest.raises(RuntimeError, match="DM-Loop"):
        sw.stop()  # second stop: clear error naming the span
    # unnamed stopwatches still raise clearly
    with pytest.raises(RuntimeError, match="not running"):
        Stopwatch().stop()
    # accumulation across with-blocks is preserved
    first = sw.elapsed
    with sw:
        pass
    assert sw.elapsed >= first


def test_trace_span_names_its_stopwatch():
    from peasoup_tpu.utils import trace_span

    sw = Stopwatch()
    with trace_span("Acceleration-Loop", sw):
        pass
    assert sw.name == "Acceleration-Loop"
    with pytest.raises(RuntimeError, match="Acceleration-Loop"):
        sw.stop()


def test_peaks_stripe_height_is_fixed():
    """One stripe height, compiled for v5e in tests/test_tpu_compile.py;
    no environment knob or child-process probe chooses another."""
    from peasoup_tpu.ops.pallas import peaks

    assert peaks._SUB == 24
    assert not hasattr(peaks, "SUB_RESOLUTION")


@pytest.mark.parametrize("which", ["peasoup", "ffa", "coincidencer"])
def test_cli_live_flags_plumbed(which):
    if which == "peasoup":
        from peasoup_tpu.cli.peasoup import build_parser

        base = ["-i", "x.fil"]
    elif which == "ffa":
        from peasoup_tpu.cli.ffa import build_parser

        base = ["-i", "x.fil"]
    else:
        from peasoup_tpu.cli.coincidencer import build_parser

        base = ["a.fil", "b.fil"]
    args = build_parser().parse_args(
        base + ["--status-json", "s.json", "--heartbeat-interval",
                "0.5", "--no-flight-recorder"]
    )
    assert args.status_json == "s.json"
    assert args.heartbeat_interval == 0.5
    assert args.no_flight_recorder is True
    args = build_parser().parse_args(base)
    assert args.status_json is None
    assert args.heartbeat_interval == 5.0
    assert args.no_flight_recorder is False
