"""Telemetry spans: the span tree, its profiler twins, the timers and
counters one `peasoup` call records."""

import glob
import gzip
import json
import os
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from peasoup_tpu import obs
from peasoup_tpu.cli.peasoup import main as peasoup_main
from peasoup_tpu.obs import telemetry as tele
from peasoup_tpu.tools import OverviewFile
from peasoup_tpu.tools.report import render_timeline
from test_pipeline import make_synthetic_fil

# the stage timers overview.xml's <execution_times> carries
OVERVIEW_TIMERS = ("reading", "plan", "dedispersion", "searching",
                   "search_device", "search_host", "distilling", "scoring",
                   "folding", "writing")


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


# --------------------------------------------------------------------------
# the span tree
# --------------------------------------------------------------------------

def test_parents_follow_nesting_per_thread():
    t = obs.RunTelemetry()
    seen = {}
    with t.stage("outer") as outer:
        sp = t.begin("inner")
        with t.stage("leaf"):
            pass
        sp.end()

        def other():
            with t.stage("elsewhere") as e:
                seen["id"] = e.id

        th = threading.Thread(target=other)
        th.start()
        th.join()
    spans = _by_name(t.spans)
    assert spans["outer"][0]["parent"] is None
    assert spans["inner"][0]["parent"] == outer.id
    assert spans["leaf"][0]["parent"] == spans["inner"][0]["id"]
    # a span on another thread has no parent
    assert spans["elsewhere"][0]["parent"] is None
    assert spans["elsewhere"][0]["id"] == seen["id"]
    assert t.timers["outer"] == spans["outer"][0]["dur_ns"] / 1e9
    # stage events name the innermost open span, back to the parent
    stages = [e["name"] for e in t.events if e["kind"] == "stage"]
    assert stages[:5] == ["outer", "inner", "leaf", "inner", "outer"]


def test_threads_keep_their_own_parents():
    """Spans opened on many threads at once: every id is unique and
    every parent is a span of the same thread."""
    import sys

    t = obs.RunTelemetry()
    owner = {}

    def work(k):
        for _ in range(100):
            with t.stage(f"w{k}") as outer:
                with t.stage(f"w{k}.leaf") as leaf:
                    owner[leaf.id] = owner[outer.id] = k

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    assert len(t.spans) == 16 * 200
    assert len({s["id"] for s in t.spans}) == len(t.spans)
    for s in t.spans:
        if s["name"].endswith(".leaf"):
            assert owner[s["parent"]] == owner[s["id"]]
        else:
            assert s["parent"] is None


def test_span_cap_counts_the_rest():
    t = obs.RunTelemetry()
    n = tele.MAX_SPANS + 5
    for _ in range(n):
        t.begin("s").end()
    assert len(t.spans) == tele.MAX_SPANS
    assert t.counters["telemetry.spans_dropped"] == 5
    assert t.timers["s"] > 0  # timers keep accumulating


def test_failed_span_keeps_the_stage_and_closes_what_was_left_open():
    t = obs.RunTelemetry()
    with pytest.raises(RuntimeError):
        with t.stage("run"):
            t.begin("searching")
            t.begin("search.fetch")
            raise RuntimeError("boom")
    # the flight recorder reads where the run died
    assert t.current_stage == "search.fetch"
    names = [s["name"] for s in t.spans]
    assert names == ["search.fetch", "searching", "run"]
    man = t.to_manifest()
    assert man["version"] == 4
    obs.validate_manifest(man)


def test_disabled_sink_measures_and_records_nothing():
    sp = obs.NOOP.begin("x")
    assert sp.end() >= 0.0
    assert obs.NOOP.spans == [] and obs.NOOP.timers == {}


def test_merge_skips_timers_a_span_wrote():
    t = obs.RunTelemetry()
    with t.stage("searching") as sp:
        pass
    t.merge_timers({"searching": sp.seconds, "total": 2.0})
    assert t.timers == {"searching": sp.seconds, "total": 2.0}


def test_first_call_records_jit_compile_under_its_span():
    t = obs.RunTelemetry()
    with t.activate(), t.stage("search.dispatch") as sp:
        jax.jit(lambda x: x * 7 - 3)(np.arange(5.0)).block_until_ready()
    compiles = [s for s in t.spans if s["name"] == "jit.compile"]
    assert compiles and all(s["parent"] == sp.id for s in compiles)
    assert all(s["start_ns"] >= sp.start_ns for s in compiles)


# --------------------------------------------------------------------------
# one `peasoup` call
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def two_runs(tmp_path_factory):
    """The same input searched twice; the second run under the
    profiler. Returns (manifest 1, manifest 2, outdir 2, trace events)."""
    tmp = tmp_path_factory.mktemp("spans")
    path, _, _ = make_synthetic_fil(tmp, nsamps=1 << 13, nchans=8)

    def run(name):
        out = tmp / name
        assert peasoup_main([
            "-i", str(path), "-o", str(out), "--dm_end", "20", "-n", "2",
            "--npdmp", "2", "--limit", "20", "--acc_start", "-5",
            "--acc_end", "5",
        ]) == 0
        with open(out / "telemetry.json") as f:
            return json.load(f), out

    man1, _ = run("cold")
    tdir = str(tmp / "trace")
    with jax.profiler.trace(tdir):
        man2, out2 = run("warm")
    events = []
    for p in glob.glob(os.path.join(tdir, "**", "*.trace.json.gz"),
                       recursive=True):
        with gzip.open(p, "rt") as f:
            events += json.load(f).get("traceEvents", [])
    return man1, man2, out2, events


def test_manifest_carries_the_span_tree(two_runs):
    _, man, _, _ = two_runs
    obs.validate_manifest(man)
    spans = _by_name(man["spans"])
    ids = {s["id"]: s for s in man["spans"]}

    def parent(s):
        return ids[s["parent"]]["name"] if s["parent"] is not None else None

    assert parent(spans["run"][0]) is None
    for name in ("reading", "plan", "dedispersion", "searching",
                 "distilling", "scoring", "folding", "writing",
                 "write.overview"):
        assert parent(spans[name][0]) == "run", name
    for name in ("search.setup", "search.wave", "search_host"):
        assert all(parent(s) == "searching" for s in spans[name]), name
    for name in ("search.dispatch", "search.fetch", "search.unpack"):
        assert all(parent(s) == "search.wave" for s in spans[name]), name
    for name in ("fold.dispatch", "fold.fetch", "fold.optimise"):
        assert all(parent(s) == "folding" for s in spans[name]), name
    assert parent(spans["dedispersion.upload"][0]) == "dedispersion"
    # every span lies inside its parent
    for s in man["spans"]:
        if s["parent"] is not None:
            p = ids[s["parent"]]
            assert p["start_ns"] <= s["start_ns"]
            assert s["start_ns"] + s["dur_ns"] <= p["start_ns"] + p["dur_ns"]


def test_every_span_has_its_profiler_twin_on_one_offset(two_runs):
    _, man, _, events = two_runs
    host = _by_name([
        {"name": e["name"][len("peasoup."):], "ts": float(e["ts"]),
         "dur": float(e.get("dur", 0.0))}
        for e in events
        if e.get("ph") == "X" and str(e.get("name", "")).startswith("peasoup.")
    ])
    pairs = []
    for name, spans in _by_name(man["spans"]).items():
        if name.startswith("jit."):
            continue  # recorded after the fact: no annotation
        twins = sorted(host.get(name, []), key=lambda e: e["ts"])
        assert len(twins) == len(spans), name
        pairs += zip(sorted(spans, key=lambda s: s["start_ns"]), twins)
    offsets = [s["start_ns"] / 1e3 - e["ts"] for s, e in pairs]
    offset = float(np.median(offsets))
    for (s, e), off in zip(pairs, offsets):
        assert abs(off - offset) < 1e3, s["name"]  # microseconds
        assert abs(s["dur_ns"] / 1e3 - e["dur"]) < 1e3, s["name"]


def test_manifest_timers_equal_overview_timings(two_runs):
    _, man, out, _ = two_runs
    ov = OverviewFile(str(out / "overview.xml")).execution_times
    for key in OVERVIEW_TIMERS:
        assert man["timers"][key] == pytest.approx(float(ov[key]),
                                                   rel=1e-9), key


def test_counters_repeat_on_the_same_input(two_runs):
    man1, man2, _, _ = two_runs
    for key in ("device.fetches", "search.chunks", "search.waves",
                "fold.groups"):
        assert man1["counters"][key] == man2["counters"][key] > 0, key
    assert man2["counters"]["device.fetch_bytes"] > 0


def test_fold_counts_real_and_padding_rows(two_runs):
    """Every fold group pads its real candidate rows to the fold
    bucket; the two counters split the batch between them."""
    from peasoup_tpu.pipeline.folder import MultiFolder

    for man in two_runs[:2]:
        c = man["counters"]
        assert 1 <= c["fold.rows"] <= 2  # --npdmp 2
        assert c["fold.rows"] + c["fold.rows_padded"] == (
            MultiFolder.fold_bucket * c["fold.groups"])


RESAMPLE_PATHS = ("identity", "select", "gather")


def test_fold_counts_rows_by_resample_path(two_runs):
    """Each DM group folds on one resample path; the three path
    counters split the real rows between them. In the CLI run the
    +-5 m/s^2 grid over 2^13 samples is the identity everywhere; three
    groups that need each path count their own rows."""
    from peasoup_tpu.core.candidates import Candidate
    from peasoup_tpu.ops.resample import SPEED_OF_LIGHT
    from peasoup_tpu.pipeline.folder import MultiFolder, fold_geometry

    for man in two_runs[:2]:
        c = man["counters"]
        assert c["fold.resample_identity"] == c["fold.rows"]
        assert sum(c.get(f"fold.resample_{p}", 0)
                   for p in RESAMPLE_PATHS) == c["fold.rows"]

    n, tsamp = 4096, 0.000256
    size = fold_geometry(n, tsamp)[0]  # the folded series' length

    def accel(shift):  # largest quadratic shift rounds to `shift`
        return (shift + 0.25) / (size / 2) ** 2 * 2 * SPEED_OF_LIGHT / tsamp

    groups = {0: (0.0, -5.0, 5.0), 1: (accel(3), 0.0), 2: (accel(100),)}
    cands = [
        Candidate(dm=float(d), dm_idx=d, acc=a, snr=9.0, freq=1.0 / 0.013)
        for d, accs in groups.items() for a in accs
    ]
    trials = np.random.default_rng(2).integers(
        20, 45, size=(3, n), dtype=np.uint8)
    tel = obs.RunTelemetry()
    with tel.activate():
        out = MultiFolder(trials, n, tsamp).fold_outcomes(cands, len(cands))
    assert len(out) == len(cands)
    assert tel.counters["fold.rows"] == 6
    for d, path in enumerate(RESAMPLE_PATHS):
        assert tel.counters[f"fold.resample_{path}"] == len(groups[d])


def test_timeline_renders_span_self_times(two_runs):
    _, man, _, _ = two_runs
    lines = render_timeline(man).splitlines()
    assert lines[0].startswith("span tree:")
    rows = {ln.split()[0]: ln for ln in lines[2:]}
    assert rows["run"].endswith("self") and "search.fetch" in rows


def test_stage_stack_drains_after_threads():
    t = obs.RunTelemetry()

    def work(k):
        for _ in range(50):
            with t.stage(f"t{k}"):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads)
    assert t._stage_stack == []
