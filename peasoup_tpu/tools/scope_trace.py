"""Per-scope device-time/bytes attribution from a jax.profiler trace.

Kernel work is measured from the profiler's device tracks, not the
host's wall clock: TPU-pid X events
carry ``args.tf_op`` (the jax named-scope path), ``hlo_category`` and
``raw_bytes_accessed`` — aggregating durations by tf_op prefix gives an
honest (time, bytes) breakdown per pipeline stage (NOTES.md "Roofline
re-measurement").

Library use:
    with scope_trace() as result: run()
    result.table()  # [(scope, seconds, gigabytes), ...]

CLI: ``python -m peasoup_tpu.tools.scope_trace`` runs the dense-grid
tutorial search (the official bench workload) once warm and prints the
table — the source of NOTES.md's per-scope numbers.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import tempfile


class ScopeResult:
    def __init__(self) -> None:
        self.events: list[tuple[str, float, int]] = []  # (tf_op, us, bytes)

    @property
    def device_s(self) -> float:
        return sum(e[1] for e in self.events) / 1e6

    def table(self, depth: int = 2, top: int = 20):
        """Aggregate by the first ``depth`` components of the tf_op
        scope path; returns [(scope, seconds, gigabytes)] sorted by
        time."""
        agg: dict[str, list[float]] = {}
        for op, us, nbytes in self.events:
            key = "/".join(op.split("/")[:depth]) if op else "<unscoped>"
            a = agg.setdefault(key, [0.0, 0.0])
            a[0] += us / 1e6
            a[1] += nbytes / 1e9
        rows = sorted(agg.items(), key=lambda kv: -kv[1][0])[:top]
        return [(k, v[0], v[1]) for k, v in rows]

    def print_table(self, depth: int = 2, top: int = 20) -> None:
        print(f"device busy: {self.device_s * 1e3:.1f} ms")
        for scope, s, gb in self.table(depth, top):
            print(f"  {s * 1e3:8.1f} ms  {gb:8.2f} GB  {scope}")

    # top-level jit names per pipeline phase (bench.py --survey's
    # device anchor): the driver's phases dispatch distinct jitted
    # programs, so the trace's tf_op head classifies device time even
    # though the phases share one traced run
    PHASES = (
        ("search", ("search_dm_block", "compact_peaks", "pack_chunk",
                    "resample_select", "search_trial")),
        ("dedisp", ("jit(run)", "dedisperse", "subband", "unpack_fil",
                    "_stage1", "_stage2", "tims")),
        ("fold", ("fold", "deredden", "_optimise", "pack_subints")),
    )

    def phase_seconds(self) -> dict:
        """Device-busy seconds per pipeline phase + 'other' for
        anything unclassified (kept visible so mis-attribution can't
        hide)."""
        out = {name: 0.0 for name, _ in self.PHASES}
        out["other"] = 0.0
        for op, us, _ in self.events:
            head = op.split("/")[0] if op else ""
            for name, pats in self.PHASES:
                if any(p in head for p in pats):
                    out[name] += us / 1e6
                    break
            else:
                out["other"] += us / 1e6
        return out

    # finer roofline classification (perf/roofline.py STAGES): matched
    # against the WHOLE tf_op path, so the named scopes the drivers
    # emit inside one jitted program ("Spectrum-Chain", "Resample",
    # "Harmonic summing", "Peaks") split the search program's device
    # time per stage. First match wins; order puts the scoped stages
    # before the top-level jit-name fallbacks.
    STAGE_RULES = (
        ("unpack", ("unpack_fil",)),
        ("spectrum_chain", ("Spectrum-Chain", "whiten", "deredden")),
        ("resample", ("Resample", "resample")),
        ("harmonics", ("Harmonic summing", "harmonic")),
        ("peaks", ("Peaks", "peaks", "compact", "cluster",
                   "single_pulse", "boxcar")),
        ("dedisperse", ("jit(run)", "dedisperse", "subband", "_stage1",
                        "_stage2", "matmul_block", "tims")),
        ("fold", ("fold", "_optimise", "pack_subints")),
    )

    def stage_profile(self) -> dict:
        """{stage: (device seconds, bytes accessed)} over the roofline
        classification, + 'other' for anything unclassified (visible, never
        hidden) — the measured half of perf.roofline.stage_roofline."""
        out: dict = {name: [0.0, 0] for name, _ in self.STAGE_RULES}
        out["other"] = [0.0, 0]
        for op, us, nbytes in self.events:
            path = op or ""
            for name, pats in self.STAGE_RULES:
                if any(p in path for p in pats):
                    out[name][0] += us / 1e6
                    out[name][1] += nbytes
                    break
            else:
                out["other"][0] += us / 1e6
                out["other"][1] += nbytes
        return {k: (v[0], v[1]) for k, v in out.items()}


def parse_trace_events(tr: dict) -> list[tuple[str, float, int]]:
    """(tf_op, duration us, bytes) rows from a loaded trace document's
    TPU device tracks (X events carrying ``hlo_category`` under a
    process whose name mentions TPU)."""
    pids = {
        e["pid"]
        for e in tr["traceEvents"]
        if e.get("ph") == "M"
        and e.get("name") == "process_name"
        and "TPU" in (e.get("args") or {}).get("name", "")
    }
    rows: list[tuple[str, float, int]] = []
    for e in tr["traceEvents"]:
        args = e.get("args") or {}
        if (
            e.get("ph") == "X"
            and e.get("pid") in pids
            and "hlo_category" in args
        ):
            rows.append(
                (
                    args.get("tf_op", ""),
                    float(e.get("dur", 0)),
                    int(args.get("raw_bytes_accessed", 0) or 0),
                )
            )
    return rows


def result_from_trace_file(path: str) -> ScopeResult:
    """Parse one ``*.trace.json.gz`` (as written by jax.profiler) into a
    ScopeResult — no TPU needed, just the file."""
    res = ScopeResult()
    with gzip.open(path, "rt") as f:
        res.events = parse_trace_events(json.load(f))
    return res


@contextlib.contextmanager
def scope_trace():
    """Trace the with-block and populate a ScopeResult from the TPU
    device tracks of the resulting trace.json.gz."""
    import jax

    res = ScopeResult()
    with tempfile.TemporaryDirectory() as tdir:
        with jax.profiler.trace(tdir):
            yield res
        paths = glob.glob(tdir + "/**/*.trace.json.gz", recursive=True)
        if not paths:
            return
        res.events = result_from_trace_file(
            max(paths, key=os.path.getmtime)
        ).events


def main() -> int:
    import sys

    from peasoup_tpu.io import read_filterbank
    from peasoup_tpu.pipeline import PeasoupSearch, SearchConfig

    fil = read_filterbank(
        os.environ.get(
            "PEASOUP_BENCH_FIL", "/root/reference/example_data/tutorial.fil"
        )
    )
    dedupe = "--dedupe" in sys.argv
    search = PeasoupSearch(
        SearchConfig(
            dm_end=250.0, acc_start=-5.0, acc_end=5.0, acc_pulse_width=0.064,
            npdmp=0, limit=1000, dedupe_accel=dedupe,
        )
    )
    search.run(fil)
    search.run(fil)  # second warm-up locks adaptive sizes
    with scope_trace() as res:
        search.run(fil)
    res.print_table(depth=int(os.environ.get("SCOPE_DEPTH", "2")))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
