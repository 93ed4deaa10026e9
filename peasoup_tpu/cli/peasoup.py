"""`peasoup` CLI: flag-compatible with the reference binary
(reference: include/utils/cmdline.hpp:69-209 TCLAP spec).

Usage mirrors the CUDA original:
  peasoup -i data.fil --dm_end 250 --acc_start -5 --acc_end 5 --npdmp 10 -p
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from . import (
    add_observability_args,
    add_version_arg,
    init_observability,
    live_observability,
)


def default_outdir() -> str:
    return time.strftime("./%Y-%m-%d-%H:%M_peasoup/", time.gmtime())


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="peasoup",
        description="Peasoup-TPU - a TPU pulsar search pipeline",
    )
    p.add_argument("-i", "--inputfile", required=True, help="File to process (.fil)")
    p.add_argument("-o", "--outdir", default=None, help="The output directory")
    p.add_argument("-k", "--killfile", default="", help="Channel mask file")
    p.add_argument("-z", "--zapfile", default="", help="Birdie list file")
    p.add_argument(
        "-t", "--num_threads", type=int, default=14,
        help="Number of device workers (reference: number of GPUs)",
    )
    p.add_argument("--limit", type=int, default=1000,
                   help="upper limit on number of candidates to write out")
    p.add_argument("--fft_size", type=int, default=0,
                   help="Transform size to use (defaults to lower power of two)")
    p.add_argument("--dm_start", type=float, default=0.0)
    p.add_argument("--dm_end", type=float, default=100.0)
    p.add_argument("--dm_tol", type=float, default=1.10,
                   help="DM smearing tolerance (1.11=10%%)")
    p.add_argument("--dm_pulse_width", type=float, default=64.0,
                   help="Minimum pulse width (us) for which dm_tol is valid")
    p.add_argument("--acc_start", type=float, default=0.0)
    p.add_argument("--acc_end", type=float, default=0.0)
    p.add_argument("--acc_tol", type=float, default=1.10)
    p.add_argument("--acc_pulse_width", type=float, default=64.0)
    p.add_argument("--boundary_5_freq", type=float, default=0.05)
    p.add_argument("--boundary_25_freq", type=float, default=0.5)
    p.add_argument("-n", "--nharmonics", type=int, default=4)
    p.add_argument("--npdmp", type=int, default=0,
                   help="Number of candidates to fold and pdmp")
    p.add_argument("-m", "--min_snr", type=float, default=9.0)
    p.add_argument("--min_freq", type=float, default=0.1)
    p.add_argument("--max_freq", type=float, default=1100.0)
    p.add_argument("--max_harm_match", type=int, default=16, dest="max_harm")
    p.add_argument("--freq_tol", type=float, default=0.0001)
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("-p", "--progress_bar", action="store_true")
    p.add_argument(
        "--subbands", type=int, default=0,
        help="two-stage subband dedispersion with N subbands "
        "(~sqrt(nchans)-fold less arithmetic at high channel counts; "
        "0 = direct, exact)",
    )
    p.add_argument(
        "--subband_smear", type=float, default=1.0,
        help="max extra smear (samples) allowed per DM-trial group "
        "when --subbands is set (0 = exact)",
    )
    p.add_argument(
        "--dedisp_engine", default="", choices=("", "exact", "matmul"),
        help="force one dedispersion engine: the gather channel scan "
        "(exact) or the MXU banded matmul (matmul) — bitwise-equal "
        "outputs; default lets the plan/tuner decide (subband is "
        "forced via --subbands)",
    )
    p.add_argument(
        "--tune", action=argparse.BooleanOptionalAction, default=False,
        help="auto-select exact-vs-subband dedispersion and load "
        "per-device tuned shape knobs from the tuning cache "
        "(plan/dedisp_plan.py + perf/tuning.py); an explicit "
        "--subbands overrides the planner",
    )
    p.add_argument(
        "--tuning-cache", default="",
        help="tuning_cache.json path (default: the per-user cache, "
        "or PEASOUP_TUNING_CACHE)",
    )
    p.add_argument(
        "--checkpoint", default="",
        help="Checkpoint file for resumable searches (TPU extension; "
        "the reference has no checkpointing)",
    )
    p.add_argument(
        "--hbm_bytes", type=int, default=0,
        help="device memory budget in bytes (0 = ask the device; set "
        "on chips that report no limit — also PEASOUP_HBM_BYTES)",
    )
    p.add_argument(
        "--no_accel_dedupe", action="store_true",
        help="dispatch every accel trial even when trials provably "
        "share their entire rounded resample-shift map (the dedupe is "
        "bitwise-output-equal; this flag exists for timing comparisons)",
    )
    add_version_arg(p)
    add_observability_args(p)
    return p


def apply_platform_env() -> None:
    """Enable JAX's persistent compilation cache (fresh CLI invocations
    would otherwise pay the full XLA compile every run — measured 10x
    on repeat FFA searches)."""
    from ..utils.cache import enable_compilation_cache

    enable_compilation_cache()


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    outdir = args.outdir or default_outdir()
    apply_platform_env()
    tel = init_observability(args)
    tel.set_context(
        command="peasoup", inputfile=args.inputfile, outdir=outdir
    )
    manifest_path = args.metrics_json or os.path.join(
        outdir.rstrip("/"), "telemetry.json"
    )

    # Heavy imports after arg parsing so --help stays fast
    from ..io.output import CandidateFileWriter, OutputFileWriter
    from ..io.sigproc import read_filterbank
    from ..pipeline.search import SearchConfig

    cfg = SearchConfig(
        outdir=outdir,
        killfilename=args.killfile,
        zapfilename=args.zapfile,
        max_num_threads=args.num_threads,
        limit=args.limit,
        size=args.fft_size,
        dm_start=args.dm_start,
        dm_end=args.dm_end,
        dm_tol=args.dm_tol,
        dm_pulse_width=args.dm_pulse_width,
        acc_start=args.acc_start,
        acc_end=args.acc_end,
        acc_tol=args.acc_tol,
        acc_pulse_width=args.acc_pulse_width,
        boundary_5_freq=args.boundary_5_freq,
        boundary_25_freq=args.boundary_25_freq,
        nharmonics=args.nharmonics,
        npdmp=args.npdmp,
        min_snr=args.min_snr,
        min_freq=args.min_freq,
        max_freq=args.max_freq,
        max_harm=args.max_harm,
        freq_tol=args.freq_tol,
        verbose=args.verbose,
        progress_bar=args.progress_bar,
        checkpoint_file=args.checkpoint,
        hbm_bytes=args.hbm_bytes,
        dedupe_accel=not args.no_accel_dedupe,
        subbands=args.subbands,
        subband_smear=args.subband_smear,
        dedisp_engine=args.dedisp_engine,
        tune=args.tune,
        tuning_cache=args.tuning_cache,
    )
    # multi-host aware (JAX_COORDINATOR_ADDRESS & co.): each process
    # searches its DM slice; single-process this is PeasoupSearch.run
    from ..parallel.multihost import run_search

    with tel.activate(), live_observability(
        tel, args, outdir, manifest_path
    ):
        t0 = time.perf_counter()
        tel.set_stage("reading")
        if args.progress_bar:
            print(f"Reading data from {args.inputfile}")
        fil = read_filterbank(args.inputfile)
        reading = time.perf_counter() - t0

        with tel.device_capture():
            result = run_search(fil, cfg)
        result.timers["reading"] = reading
        tel.merge_timers(result.timers)

        import jax

        if jax.process_count() > 1:
            # per-host manifest shard (stage timers here are this
            # host's own): telemetry.procN.json next to the main
            # manifest, merged with `tools.report --merge`
            base, ext = os.path.splitext(manifest_path)
            tel.write(f"{base}.proc{jax.process_index()}{ext or '.json'}")
        if jax.process_index() != 0:
            return 0  # every process holds the identical result; rank 0 writes

        tel.set_stage("writing")
        t0 = time.perf_counter()
        writer = CandidateFileWriter(outdir)
        writer.write_binary(result.candidates, "candidates.peasoup")
        result.timers["writing"] = time.perf_counter() - t0
        tel.add_timer("writing", result.timers["writing"])

        stats = OutputFileWriter()
        stats.add_misc_info()
        stats.add_header(fil.header)
        stats.add_search_parameters(cfg, args.inputfile)
        stats.add_dm_list(result.dm_list)
        stats.add_acc_list(result.acc_list_dm0)
        stats.add_device_info()
        stats.add_candidates(result.candidates, writer.byte_mapping)
        stats.add_timing_info(result.timers)
        stats.to_file(f"{outdir.rstrip('/')}/overview.xml")

        # the machine-readable twin of overview.xml, written beside it
        # unless --metrics-json redirects it
        tel.gauge("candidates.written", len(result.candidates))
        tel.set_stage("done")
        tel.write(manifest_path)
    if args.verbose or args.progress_bar:
        print(
            f"Done: {len(result.candidates)} candidates -> {outdir} "
            f"(total {result.timers['total']:.2f}s)"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
