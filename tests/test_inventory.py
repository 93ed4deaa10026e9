"""Inventory drift guard: every component PARITY.md claims (mapping
SURVEY.md §2 line by line) must actually exist under its documented
name, so a rename or removal that forgets the docs fails loudly
instead of leaving PARITY.md citing symbols that no longer exist."""

import importlib

import pytest

# (module, [symbols]) — the public names PARITY.md cites
INVENTORY = [
    # §2a device/kernel components
    ("peasoup_tpu.ops.harmonics", ["harmonic_sums"]),
    ("peasoup_tpu.ops.spectrum", [
        "form_power", "form_interpolated", "spectrum_stats", "normalise",
    ]),
    ("peasoup_tpu.ops.resample", [
        "resample_accel", "resample_select", "resample_accel_quadratic",
        "accel_factor",
    ]),
    ("peasoup_tpu.ops.pallas.resample", [
        "resample_block_pallas", "choose_block",
    ]),
    ("peasoup_tpu.ops.peaks", [
        "find_peaks_device", "cluster_peaks", "cluster_peaks_device",
    ]),
    ("peasoup_tpu.ops.pallas.harmpeaks", ["find_harmonic_cluster_peaks"]),
    ("peasoup_tpu.ops.fold", ["fold_time_series", "fold_bins_np"]),
    ("peasoup_tpu.ops.fold_optimise", ["FoldOptimiser"]),
    ("peasoup_tpu.ops.rednoise", [
        "median_scrunch5", "linear_stretch", "running_median", "deredden",
        "whiten_fseries",
    ]),
    ("peasoup_tpu.ops.zap", ["birdie_mask", "zap_birdies"]),
    ("peasoup_tpu.ops.coincidence", ["coincidence_mask"]),
    ("peasoup_tpu.ops.correlate", ["find_delays"]),
    ("peasoup_tpu.ops.dedisperse", [
        "dedisperse_block", "dedisperse_device", "dedisperse",
        "dedisperse_subband", "subband_groups", "unpack_fil_device",
        "fil_to_device", "output_scale",
    ]),
    ("peasoup_tpu.ops.pallas.dedisperse", [
        "dedisperse_pallas", "plan_spread", "pallas_hbm_bytes",
    ]),
    ("peasoup_tpu.ops.ffa", [
        "ffa_transform", "ffa_search_block", "ffa_search_series",
        "boxcar_snr", "collapse_periods",
    ]),
    # §2b host-side components
    ("peasoup_tpu.io.sigproc", [
        "read_sigproc_header", "write_sigproc_header", "SigprocHeader",
        "Filterbank", "read_filterbank", "write_filterbank",
        "read_timeseries",
    ]),
    ("peasoup_tpu.io.dada", ["DadaHeader"]),
    ("peasoup_tpu.io.masks", ["read_killfile", "read_zapfile"]),
    ("peasoup_tpu.io.output", ["OutputFileWriter", "CandidateFileWriter"]),
    ("peasoup_tpu.io.xml_writer", ["Element"]),
    ("peasoup_tpu.core.candidates", [
        "Candidate", "CandidateCollection",
    ]),
    ("peasoup_tpu.plan.dm_plan", [
        "DMPlan", "generate_dm_list", "delay_table", "max_delay_samples",
    ]),
    ("peasoup_tpu.plan.accel_plan", ["AccelerationPlan"]),
    ("peasoup_tpu.plan.fft_plan", ["choose_fft_size", "prev_power_of_two"]),
    ("peasoup_tpu.pipeline.search", [
        "PeasoupSearch", "SearchConfig", "SearchResult",
        "PartialSearchResult",
    ]),
    ("peasoup_tpu.pipeline.distill", [
        "HarmonicDistiller", "AccelerationDistiller", "DMDistiller",
    ]),
    ("peasoup_tpu.pipeline.score", ["CandidateScorer"]),
    ("peasoup_tpu.pipeline.folder", ["MultiFolder"]),
    ("peasoup_tpu.pipeline.checkpoint", ["SearchCheckpoint"]),
    # §2c application entry points
    ("peasoup_tpu.cli.peasoup", ["main", "build_parser"]),
    ("peasoup_tpu.cli.ffa", ["main"]),
    ("peasoup_tpu.cli.coincidencer", ["main"]),
    ("peasoup_tpu.cli.accmap", ["main"]),
    # §2d post-processing
    ("peasoup_tpu.tools.parsers", ["OverviewFile", "CandidateFileParser"]),
    ("peasoup_tpu.tools.plotting", ["CandidatePlotter"]),
    ("peasoup_tpu.tools.as_text", ["main"]),
    # §2e parallelism & communication
    ("peasoup_tpu.parallel.mesh", ["make_mesh", "device_count"]),
    ("peasoup_tpu.parallel.sharded_search", [
        "make_sharded_search_fn", "place_trials",
    ]),
    ("peasoup_tpu.parallel.coincidence", [
        "sharded_coincidence", "baseline_beam",
    ]),
    ("peasoup_tpu.parallel.distributed_fft", ["distributed_rfft"]),
    ("peasoup_tpu.parallel.multihost", [
        "initialize", "global_mesh", "process_local_slice",
        "dm_slice_for_process", "run_search",
    ]),
    # §5 auxiliary subsystems
    ("peasoup_tpu.utils.trace", ["trace_span", "Stopwatch"]),
    ("peasoup_tpu.utils.progress", ["ProgressBar"]),
    ("peasoup_tpu.utils.debug", ["dump_buffer"]),
    ("peasoup_tpu.native", ["available"]),
    # observability: run telemetry manifest + structured logging
    ("peasoup_tpu.obs.telemetry", [
        "RunTelemetry", "current", "load_manifest",
    ]),
    ("peasoup_tpu.obs.log", ["get_logger", "configure", "resolve_level"]),
    ("peasoup_tpu.tools.report", ["render", "diff"]),
    ("peasoup_tpu.tools.scope_trace", [
        "scope_trace", "parse_trace_events", "result_from_trace_file",
    ]),
]


@pytest.mark.parametrize(
    "module,symbols", INVENTORY, ids=[m for m, _ in INVENTORY]
)
def test_component_exists(module, symbols):
    mod = importlib.import_module(module)
    missing = [s for s in symbols if not hasattr(mod, s)]
    assert not missing, f"{module} is missing documented symbols: {missing}"


def test_collect_pods_method():
    """PARITY.md maps the reference's collect_candidates assoc-tree
    flattening (candidates.hpp:78-84) to Candidate.collect_pods."""
    from peasoup_tpu.core.candidates import Candidate

    assert callable(getattr(Candidate, "collect_pods"))
