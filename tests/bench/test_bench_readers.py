"""The per-layer readers of the program's spans and counters, on beam
records and telemetry.json manifests as the harness keeps them."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402

# a beam's telemetry.json, trimmed to what the readers need
MANIFEST = {
    "schema": "peasoup_tpu.telemetry", "version": 4,
    "timers": {"reading": 0.05, "search.setup": 0.01,
               "search.dispatch": 0.02, "search.fetch": 0.03,
               "fold.fetch": 0.04, "writing": 0.001,
               "write.overview": 0.002, "searching": 0.2},
    "counters": {"device.fetches": 9, "search.chunks": 1},
}
# what the program wrote before it had spans: the old timers only
OLD_MANIFEST = {"schema": "peasoup_tpu.telemetry", "version": 3,
                "timers": {"reading": 0.05, "searching": 0.2,
                           "writing": 0.001, "folding": 0.1},
                "counters": {}}


def _beam(tmp_path, name, man, error=None):
    outdir = tmp_path / name
    outdir.mkdir()
    (outdir / "telemetry.json").write_text(json.dumps(man))
    return {"outdir": str(outdir), "error": error, "timers": man["timers"],
            "gauges": {}, "stages": []}


@pytest.mark.parametrize("metric,want", [
    ("search.fetch_wait_s", 0.03),
    ("dispatch.host_s", 0.03),
    ("dispatch.fetches", 9),
    ("fold.wait_s", 0.04),
    ("io.write_s", 0.003),
])
def test_new_readers_read_spans_and_counters(tmp_path, metric, want):
    read = spec.reader(metric)
    beams = [_beam(tmp_path, "a", MANIFEST),
             _beam(tmp_path, "b", MANIFEST),
             # a failed beam never counts, whatever it recorded
             _beam(tmp_path, "c", {**MANIFEST, "timers": {
                 k: 99.0 for k in MANIFEST["timers"]},
                 "counters": {"device.fetches": 99}}, error="boom")]
    assert read(None, beams, {}) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["search.fetch_wait_s", "dispatch.host_s",
                                    "dispatch.fetches", "fold.wait_s",
                                    "io.write_s"])
def test_new_readers_find_nothing_in_an_older_program(tmp_path, metric):
    beams = [_beam(tmp_path, "old", OLD_MANIFEST)]
    assert spec.reader(metric)(None, beams, {}) is None
    assert spec.reader(metric)(None, [], {}) is None


def test_new_metrics_are_read_in_their_cells():
    bench = spec.benchmark()
    new = {"search.fetch_wait_s", "dispatch.host_s", "dispatch.fetches",
           "fold.wait_s", "io.write_s"}
    for wl in ("htru_medlat.one_beam", "tutorial.beam_pool16"):
        names = {m["name"] for m in spec.cell(wl).per_layer}
        want = new - ({"fold.wait_s"} if wl == "htru_medlat.one_beam"
                      else set())
        assert want <= names, wl
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "search.fetch_wait_s", "dispatch.host_s", "dispatch.fetches",
        "fold.wait_s", "io.write_s"]
