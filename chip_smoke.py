#!/usr/bin/env python
"""Bring-up check: the peasoup search end to end on a TPU chip.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --multichip   # four chips: phase B, -t 4 vs -t 1

Everything runs in this one process; nothing it starts imports JAX. It
stops with a non-zero exit, and prints no result, when JAX finds no TPU.

- Phase A (tutorial shape): 64 channels x 187,520 2-bit samples at
  320 us, a P = 250 ms pulsar at DM 30, searched with the README's Quick
  start flags. The top candidate must recover the period.
- Phase B (survey beam): 1024 channels x (2^21 + 2048) 2-bit samples at
  256 us, seed 42, a P = 50.03 ms pulsar at DM 60, searched to DM 120.
  The top candidate must recover the period near DM 60.

Both pulsars are narrow (2% duty) and weak per channel, so the S/N peaks
sharply at the true DM: a bright, broad pulse smears into a periodic
hump that wins at a wrong DM just as well.
- --multichip: phase B with the DM axis sharded over four chips and on
  one chip, in this process. The candidate lists must be bitwise equal
  (the sharded search runs the one-chip program on every chip) and
  every chip must have held trials.

Inputs are made from fixed seeds (peasoup_tpu.io.synth) and searched
through ``peasoup_tpu.cli.peasoup.main``, the entry point users call.
Each phase prints one JSON line: wall time, compiles and persistent
cache hits/misses, device memory high-water and the kernel route. Every
Pallas kernel the route expects on a TPU must have run. The last line is
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, "chiprun_out", "smoke")  # git-ignored
MIN_SNR = 9.0  # the peasoup CLI's default threshold


@dataclass(frozen=True)
class Phase:
    name: str
    nchans: int
    nsamps: int
    tsamp: float
    fch1: float
    foff: float
    period: float
    dm: float
    seed: int
    duty: float  # on-pulse fraction of the period
    amp: float  # chance an on-pulse sample gains 1 (pulsar strength)
    flags: tuple[str, ...]
    # allowed |DM - injected DM| of the top candidate; None where the
    # band is too narrow for the pulse width to pin the DM (the
    # tutorial's own golden top candidate sits anywhere in DM 19.8-30)
    dm_tol: float | None


TUTORIAL = Phase(
    "A_tutorial", nchans=64, nsamps=187_520, tsamp=320e-6, fch1=1510.0,
    foff=-1.09, period=0.25, dm=30.0, seed=1, duty=0.02, amp=0.25,
    flags=("--dm_end", "250", "--acc_start", "-5", "--acc_end", "5",
           "--npdmp", "10"),
    dm_tol=None,
)
SURVEY = Phase(
    "B_survey", nchans=1024, nsamps=(1 << 21) + 2048, tsamp=256e-6,
    fch1=1500.0, foff=-300.0 / 1024, period=0.05003, dm=60.0, seed=42,
    duty=0.02, amp=0.02,
    flags=("--dm_end", "120"),
    dm_tol=5.0,
)


def require_tpu(count: int):
    """The devices, or exit non-zero: this check never runs on a CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        sys.stderr.write(
            f"chip_smoke: needs {count} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)\n"
        )
        sys.exit(2)
    return devs


def build_native() -> str:
    from peasoup_tpu import native
    from peasoup_tpu.native.build import build

    lib = build()
    if lib is None or not native.available():
        raise RuntimeError("libpeasoup_host.so did not build")
    return lib


class MemoryWatch:
    """Highest ``bytes_in_use`` each device reported while running."""

    def __init__(self, devices, period: float = 0.05):
        self.devices = devices
        self.period = period
        self.high = [0] * len(devices)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _poll(self) -> None:
        while not self._stop.is_set():
            for i, d in enumerate(self.devices):
                used = (d.memory_stats() or {}).get("bytes_in_use", 0)
                self.high[i] = max(self.high[i], int(used))
            self._stop.wait(self.period)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


def route_failures(route: dict, engines: list[dict]) -> list[str]:
    """The Pallas kernels the TPU route expected that did not run."""
    bad = [k for k in ("pallas_peaks", "mega_harm", "fused_spec")
           if not route[k]]
    if route["interbin_fits"] and not route["fused_interbin"]:
        bad.append("fused_interbin")
    if route["dftspec_fits"] and not route["fused_dft"]:
        bad.append("fused_dft")
    if route["resample_fits"] and not route["resample_block"]:
        bad.append("resample")
    bad += [
        f"dedisperse({e['ndm']}x{e['nchans']})" for e in engines
        if e["engine"] != "pallas" and e.get("fits") is not False
    ]
    return bad


def run_phase(
    ph: Phase, threads: int = 1, tag: str = "", expect_tpu: bool = True,
    keep_fil: bool = False,
) -> dict:
    """Synthesise ``ph``'s input, search it through the peasoup CLI and
    check what came out. Returns the phase's report (raises on failure)."""
    from peasoup_tpu.cli import peasoup
    from peasoup_tpu.io.synth import pulsar_fil
    from peasoup_tpu.tools.parsers import OverviewFile

    t0 = time.perf_counter()
    fil = pulsar_fil(
        WORK, nchans=ph.nchans, nsamps=ph.nsamps, tsamp=ph.tsamp,
        fch1=ph.fch1, foff=ph.foff, period=ph.period, dm=ph.dm,
        seed=ph.seed, duty=ph.duty, amp=ph.amp,
    )
    synth_s = time.perf_counter() - t0
    outdir = os.path.join(WORK, ph.name + tag)
    shutil.rmtree(outdir, ignore_errors=True)
    argv = ["-i", fil, "-o", outdir, *ph.flags, "-t", str(threads)]
    t0 = time.perf_counter()
    rc = peasoup.main(argv)
    wall = time.perf_counter() - t0
    if not keep_fil:
        os.unlink(fil)
    if rc != 0:
        raise RuntimeError(f"{ph.name}: peasoup exited {rc}")
    for f in ("overview.xml", "candidates.peasoup"):
        if not os.path.getsize(os.path.join(outdir, f)):
            raise RuntimeError(f"{ph.name}: {f} is empty")

    with open(os.path.join(outdir, "telemetry.json")) as f:
        man = json.load(f)
    events = man["events"]
    [route] = [
        {k: v for k, v in e.items() if k not in ("kind", "t", "ts")}
        for e in events if e["kind"] == "search_route"
    ]
    engines = [e for e in events if e["kind"] == "dedisp_engine"]
    compiles = sum(
        v["count"] for k, v in man["jit"].items() if "backend_compile" in k
    )
    hits = int(man["counters"].get("jax.compilation_cache.cache_hits", 0))
    misses = int(
        man["counters"].get("jax.compilation_cache.cache_misses", 0)
    )
    ov = OverviewFile(os.path.join(outdir, "overview.xml"))
    if not len(ov.candidates):
        raise RuntimeError(f"{ph.name}: no candidates")
    top = ov.candidates[0]
    report = {
        "phase": ph.name + tag,
        "threads": threads,
        "synth_s": synth_s,
        "wall_s": wall,
        "timers_s": man["timers"],
        "compiles": compiles,
        "cache_hits": hits,
        "cache_misses": misses,
        "device_peak_bytes": man["gauges"].get("memory.peak_bytes"),
        "ndm": len(ov.dm_list),
        "route": route,
        "dedisp_engines": sorted({e["engine"] for e in engines}),
        "top": {
            "period": float(top["period"]), "dm": float(top["dm"]),
            "acc": float(top["acc"]), "nh": int(top["nh"]),
            "snr": float(top["snr"]),
        },
        "n_candidates": int(len(ov.candidates)),
    }
    rel = abs(float(top["period"]) - ph.period) / ph.period
    if rel > 2e-3:
        raise RuntimeError(
            f"{ph.name}: top candidate P={float(top['period'])!r} misses "
            f"the injected {ph.period} (rel {rel:.2e})"
        )
    if ph.dm_tol is not None and abs(float(top["dm"]) - ph.dm) > ph.dm_tol:
        raise RuntimeError(
            f"{ph.name}: top candidate DM {float(top['dm'])} is not near "
            f"the injected {ph.dm}"
        )
    if float(top["snr"]) < 2 * MIN_SNR:
        raise RuntimeError(f"{ph.name}: top S/N {float(top['snr'])} is weak")
    if expect_tpu:
        bad = route_failures(route, engines)
        if bad:
            raise RuntimeError(
                f"{ph.name}: TPU kernels that did not run: {bad}"
            )
    return report


def compare_candidates(a: str, b: str) -> dict:
    """Bitwise comparison of two phase outputs' candidate lists."""
    from peasoup_tpu.tools.parsers import OverviewFile

    ca = OverviewFile(os.path.join(WORK, a, "overview.xml"))
    cb = OverviewFile(os.path.join(WORK, b, "overview.xml"))
    fields = ("period", "dm", "acc", "nh", "snr")
    rows = [tuple(c[f] for f in fields) for c in ca.candidates]
    same_rows = rows == [tuple(c[f] for f in fields) for c in cb.candidates]
    with open(os.path.join(WORK, a, "candidates.peasoup"), "rb") as f:
        ba = f.read()
    with open(os.path.join(WORK, b, "candidates.peasoup"), "rb") as f:
        bb = f.read()
    return {
        "n_candidates": [len(ca.candidates), len(cb.candidates)],
        "same_dm_list": bool((ca.dm_list == cb.dm_list).all()),
        "same_rows": bool(same_rows),
        "same_candidates_file": ba == bb,
    }


def multichip(devs) -> None:
    with MemoryWatch(devs[:4]) as watch:
        sharded = run_phase(SURVEY, threads=4, tag="_t4", keep_fil=True)
    print(json.dumps(sharded), flush=True)
    single = run_phase(SURVEY, threads=1, tag="_t1")
    print(json.dumps(single), flush=True)
    cmp = compare_candidates(SURVEY.name + "_t4", SURVEY.name + "_t1")
    cmp["bytes_in_use_high"] = watch.high
    print(json.dumps({"multichip": cmp}), flush=True)
    if not (cmp["same_dm_list"] and cmp["same_rows"]
            and cmp["same_candidates_file"]):
        raise RuntimeError(f"-t 4 and -t 1 disagree: {cmp}")
    idle = [i for i, b in enumerate(watch.high) if b < (64 << 20)]
    if idle:
        raise RuntimeError(f"chips {idle} held no trials: {watch.high}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--multichip", action="store_true",
        help="phase B sharded over four chips against one chip, only",
    )
    args = ap.parse_args(argv)
    count = 4 if args.multichip else 1
    devs = require_tpu(count)
    lib = build_native()
    print(json.dumps({"native": os.path.basename(lib)}), flush=True)
    if args.multichip:
        multichip(devs)
    else:
        for ph in (TUTORIAL, SURVEY):
            print(json.dumps(run_phase(ph)), flush=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
