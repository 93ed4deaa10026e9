"""Observability subsystem tests: the telemetry.json manifest schema
(round-trip, versioned, stable keys), the library logger and its CLI
plumbing, and the tools/report.py renderer."""

import json
import logging

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from peasoup_tpu import obs
from peasoup_tpu.obs import telemetry as tele
from test_pipeline import make_synthetic_fil


# --------------------------------------------------------------------------
# RunTelemetry core
# --------------------------------------------------------------------------

def test_manifest_round_trip(tmp_path):
    t = obs.RunTelemetry(run_id="r1")
    t.set_context(command="unit-test")
    t.incr("widgets")
    t.incr("widgets", 2)
    t.gauge("level", 5.0)
    t.gauge_max("peak", 10)
    t.gauge_max("peak", 7)  # high-water: must stay 10
    with t.stage("phase_a"):
        pass
    t.add_timer("phase_a", 1.5)  # accumulates onto the stage timer
    t.event("adaptive_thing", old=1, new=2)
    t.record_jit("/jax/core/compile", 0.25)

    path = str(tmp_path / "telemetry.json")
    written = t.write(path)
    man = obs.load_manifest(path)
    assert man == json.loads(json.dumps(written))  # JSON round-trip
    assert man["schema"] == obs.MANIFEST_SCHEMA
    assert man["version"] == obs.MANIFEST_VERSION
    assert man["run_id"] == "r1"
    assert man["context"]["command"] == "unit-test"
    assert man["counters"]["widgets"] == 3
    assert man["gauges"]["level"] == 5.0
    assert man["gauges"]["peak"] == 10
    assert man["timers"]["phase_a"] >= 1.5
    assert man["jit"]["/jax/core/compile"] == {
        "count": 1, "seconds": 0.25,
    }
    ev = next(e for e in man["events"] if e["kind"] == "adaptive_thing")
    assert ev["old"] == 1 and ev["new"] == 2
    assert ev["t"] >= 0.0  # monotonic offset from run start
    # stable top-level key order: schema/version lead
    assert list(man)[:3] == ["schema", "version", "run_id"]


def test_manifest_rejects_foreign_and_newer(tmp_path):
    p = tmp_path / "x.json"
    p.write_text(json.dumps({"schema": "something_else", "version": 1}))
    with pytest.raises(ValueError, match="not a"):
        obs.load_manifest(str(p))
    p.write_text(json.dumps(
        {"schema": obs.MANIFEST_SCHEMA,
         "version": obs.MANIFEST_VERSION + 1}
    ))
    with pytest.raises(ValueError, match="newer"):
        obs.load_manifest(str(p))


def test_current_defaults_to_noop_and_activation_scopes():
    assert obs.current() is obs.NOOP
    assert not obs.NOOP.enabled
    # the noop sink absorbs everything without accumulating state
    obs.NOOP.incr("x")
    obs.NOOP.event("y", a=1)
    with obs.NOOP.stage("z"):
        pass
    assert obs.NOOP.counters == {} and obs.NOOP.events == []
    assert obs.NOOP.timers == {}

    t = obs.RunTelemetry()
    with t.activate():
        assert obs.current() is t
        obs.current().incr("seen")
    assert obs.current() is obs.NOOP
    assert t.counters == {"seen": 1}


def test_jit_listener_routes_to_active_telemetry_only():
    t = obs.RunTelemetry()
    with t.activate():
        jax.jit(lambda x: x * 2 + 1)(np.arange(4.0)).block_until_ready()
    # jax.monitoring names vary by version; anything compile/lowering
    # shaped must have landed while active, nothing after deactivation
    n_before = sum(c for c, _ in t.jit.values())
    jax.jit(lambda x: x * 3 - 1)(np.arange(4.0)).block_until_ready()
    assert sum(c for c, _ in t.jit.values()) == n_before
    if t.jit:  # compile events observed on this jax version
        assert all(
            "compile" in k or "lower" in k for k in t.jit
        )


def test_capture_device_memory_never_raises():
    t = obs.RunTelemetry()
    t.capture_device_memory("anywhere")  # CPU: memory_stats absent
    # either nothing recorded or a positive high-water mark
    for v in t.gauges.values():
        assert v > 0


# --------------------------------------------------------------------------
# logger + CLI plumbing
# --------------------------------------------------------------------------

def test_resolve_level_precedence(monkeypatch):
    monkeypatch.delenv("PEASOUP_LOG_LEVEL", raising=False)
    assert obs.resolve_level(None) == logging.WARNING
    assert obs.resolve_level(None, verbose=True) == logging.INFO
    assert obs.resolve_level("debug") == logging.DEBUG
    assert obs.resolve_level("ERROR", verbose=True) == logging.ERROR
    assert obs.resolve_level(logging.DEBUG) == logging.DEBUG
    monkeypatch.setenv("PEASOUP_LOG_LEVEL", "error")
    assert obs.resolve_level(None) == logging.ERROR
    assert obs.resolve_level(None, verbose=True) == logging.INFO
    with pytest.raises(ValueError, match="unknown log level"):
        obs.resolve_level("shout")


def test_configure_is_idempotent_and_gates_levels():
    import io

    buf = io.StringIO()
    logger = obs.configure_logging("info", stream=buf)
    n_handlers = len(logger.handlers)
    obs.configure_logging("debug", stream=buf)
    assert len(logger.handlers) == n_handlers  # no handler stacking

    obs.configure_logging("warning", stream=buf)
    child = obs.get_logger("pipeline.search")
    child.info("hidden")
    child.warning("visible %d", 7)
    out = buf.getvalue()
    assert "hidden" not in out
    assert "visible 7" in out
    assert "peasoup_tpu.pipeline.search" in out


def test_logger_follows_a_replaced_stderr(monkeypatch):
    """Configured without a stream, the handler writes to the current
    sys.stderr: one closed after configuration (a caller's capture) is
    neither written nor flushed again."""
    import io
    import sys

    from peasoup_tpu.obs import log as obs_log

    logger = obs.get_logger()
    old = obs_log._handler
    if old is not None:
        logger.removeHandler(old)
    monkeypatch.setattr(obs_log, "_handler", None)
    gone = io.TextIOWrapper(io.BytesIO())  # as a captured stderr
    monkeypatch.setattr(sys, "stderr", gone)
    try:
        obs.configure_logging("warning")
        gone.close()
        now = io.StringIO()
        monkeypatch.setattr(sys, "stderr", now)
        obs.get_logger("obs.heartbeat").warning("after close")
        assert "after close" in now.getvalue()
        obs.configure_logging("warning", stream=io.StringIO())  # no raise
    finally:
        logger.removeHandler(obs_log._handler)
        if old is not None:
            logger.addHandler(old)


def test_get_logger_naming():
    assert obs.get_logger().name == "peasoup_tpu"
    assert obs.get_logger("obs").name == "peasoup_tpu.obs"


@pytest.mark.parametrize("which", ["peasoup", "ffa", "coincidencer"])
def test_cli_flags_plumbed(which):
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
        base + ["--log-level", "debug", "--metrics-json", "m.json",
                "--capture-device-trace"]
    )
    assert args.log_level == "debug"
    assert args.metrics_json == "m.json"
    assert args.capture_device_trace is True
    # defaults: no level override, no manifest path, no tracing
    args = build_parser().parse_args(base)
    assert args.log_level is None
    assert args.metrics_json is None
    assert args.capture_device_trace is False


# --------------------------------------------------------------------------
# end-to-end: search run -> manifest -> report
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def searched(tmp_path_factory):
    """One CLI search shared by the manifest/report/xml assertions."""
    from peasoup_tpu.cli.peasoup import main as peasoup_main

    tmp_path = tmp_path_factory.mktemp("obs_e2e")
    path, period, dm = make_synthetic_fil(tmp_path)
    outdir = tmp_path / "out"
    metrics = tmp_path / "metrics.json"
    rc = peasoup_main(
        ["-i", str(path), "-o", str(outdir), "--dm_end", "40",
         "-n", "2", "--limit", "20", "--metrics-json", str(metrics)]
    )
    assert rc == 0
    return outdir, metrics


def test_search_manifest_contents(searched):
    outdir, metrics = searched
    man = obs.load_manifest(str(metrics))
    # stage timers: the superset of overview.xml execution_times
    for key in ("reading", "plan", "dedispersion", "searching",
                "search_device", "search_host", "distilling", "scoring",
                "folding", "writing", "total"):
        assert key in man["timers"], key
        assert man["timers"][key] >= 0.0
    # candidate counts per stage
    for key in ("candidates.per_dm_distill", "candidates.per_dm_total",
                "candidates.post_dm_distill",
                "candidates.post_harmonic_distill", "candidates.final",
                "candidates.written"):
        assert key in man["gauges"], key
    assert man["gauges"]["candidates.written"] > 0
    assert man["gauges"]["search.n_dm_trials"] > 0
    assert man["gauges"]["search.n_accel_trials"] > 0
    # the adaptive-event log records the wave/device geometry
    kinds = [e["kind"] for e in man["events"]]
    assert "device_plan" in kinds
    assert "wave_plan" in kinds
    wave = next(e for e in man["events"] if e["kind"] == "wave_plan")
    assert wave["n_chunks"] >= wave["n_waves"] >= 1
    # jit stats: may be empty when every program was already compiled
    # earlier in this process (jax's in-memory executable cache emits no
    # monitoring events on a hit) — but whatever landed must be
    # compile/lowering shaped
    for key, st in man["jit"].items():
        assert "compile" in key or "lower" in key
        assert st["count"] >= 1 and st["seconds"] >= 0.0
    assert man["platform"]["backend"] == "cpu"


def test_default_manifest_lands_next_to_overview(tmp_path):
    from peasoup_tpu.cli.peasoup import main as peasoup_main

    path, _, _ = make_synthetic_fil(tmp_path, nsamps=1 << 13)
    outdir = tmp_path / "out"
    rc = peasoup_main(
        ["-i", str(path), "-o", str(outdir), "--dm_end", "10", "-n", "1",
         "--limit", "5"]
    )
    assert rc == 0
    assert (outdir / "overview.xml").exists()
    man = obs.load_manifest(str(outdir / "telemetry.json"))
    assert man["context"]["command"] == "peasoup"


def test_overview_xml_gains_new_stage_keys(searched):
    outdir, _ = searched
    from peasoup_tpu.tools import OverviewFile

    ov = OverviewFile(str(outdir / "overview.xml"))
    for key in ("plan", "distilling", "scoring", "writing",
                "dedispersion", "searching", "total", "reading"):
        assert key in ov.execution_times, key


def test_report_renders_and_diffs(searched, tmp_path, capsys):
    from peasoup_tpu.tools.report import main as report_main

    _, metrics = searched
    assert report_main([str(metrics)]) == 0
    out = capsys.readouterr().out
    assert "stage timers" in out
    assert "dedispersion" in out
    assert "adaptive events" in out
    assert "wave_plan" in out

    # diff against a doctored copy: renamed run, slower dedispersion
    man = obs.load_manifest(str(metrics))
    man["run_id"] = "after"
    man["timers"]["dedispersion"] += 1.0
    other = tmp_path / "after.json"
    other.write_text(json.dumps(man))
    assert report_main([str(metrics), str(other)]) == 0
    out = capsys.readouterr().out
    assert "after" in out.splitlines()[0]
    assert "dedispersion" in out
    assert "+1" in out  # the delta column

    with pytest.raises(SystemExit):
        report_main([str(metrics), str(other), str(other)])


def test_ffa_cli_writes_manifest(tmp_path):
    from peasoup_tpu.cli.ffa import main as ffa_main

    path, _, _ = make_synthetic_fil(tmp_path, nsamps=1 << 13)
    out = tmp_path / "ffa.xml"
    metrics = tmp_path / "ffa_telemetry.json"
    rc = ffa_main(
        ["-i", str(path), "-o", str(out), "--dm_end", "5",
         "--p_start", "1.0", "--p_end", "1.3",
         "--metrics-json", str(metrics)]
    )
    assert rc == 0
    man = obs.load_manifest(str(metrics))
    for key in ("reading", "dedispersion", "ffa_search", "total"):
        assert key in man["timers"], key
    assert man["context"]["command"] == "peasoup-ffa"
    # the XML execution_times table mirrors the manifest's timers
    xml = out.read_text()
    assert "<ffa_search>" in xml and "<total>" in xml
