"""End-to-end profiler smoke test.

Captures a jax.profiler.trace of a tiny search run and asserts the
spans that stand for the reference's NVTX ranges are present: the
telemetry spans ``peasoup.dedispersion`` and ``peasoup.search.wave``
("Dedisperse", "DM-Loop") as host annotations, and "Acceleration-Loop"
and "Harmonic summing" as named_scope op metadata inside the jitted
program.
"""

import glob
import gzip
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from peasoup_tpu.io import read_filterbank
from peasoup_tpu.obs import RunTelemetry
from peasoup_tpu.pipeline import PeasoupSearch, SearchConfig

from test_pipeline import make_synthetic_fil


def test_trace_contains_host_spans(tmp_path):
    """The telemetry spans' host annotations (``peasoup.dedispersion``,
    ``peasoup.search.wave``) appear in a captured jax.profiler trace of
    a real tiny run."""
    path, _, _ = make_synthetic_fil(tmp_path, nsamps=1 << 13, nchans=8)
    fil = read_filterbank(str(path))
    cfg = SearchConfig(dm_end=20.0, nharmonics=2, npdmp=0, limit=20)
    search = PeasoupSearch(cfg)
    search.run(fil)  # compile outside the trace

    tdir = str(tmp_path / "trace")
    with jax.profiler.trace(tdir), RunTelemetry().activate():
        search.run(fil)

    files = glob.glob(
        os.path.join(tdir, "**", "*.trace.json.gz"), recursive=True
    )
    assert files, f"no trace file captured under {tdir}"
    text = ""
    for f in files:
        events = json.load(gzip.open(f))
        text += json.dumps(events)

    for span in ("peasoup.dedispersion", "peasoup.search.wave"):
        assert span in text, f"span {span!r} missing from profiler trace"


def test_jitted_program_carries_device_scopes():
    """The in-jit named_scope spans ("Acceleration-Loop",
    "Harmonic summing", NVTX parity: pipeline_multi.cu:207,
    harmonicfolder.hpp:28) are baked into the program's op metadata —
    device profiles group the covered ops under them."""
    import jax.numpy as jnp

    from peasoup_tpu.pipeline.accel_search import search_block_core
    from peasoup_tpu.pipeline.search import _level_windows

    size, nharms = 2048, 2
    tims = jnp.zeros((2, size), jnp.uint8)
    afs = jnp.zeros((2, 2), jnp.float32)
    zap = jnp.zeros(size // 2 + 1, bool)
    win = jnp.asarray(_level_windows(size, nharms, 0.1, 1100.0, 0.000256))
    lowered = jax.jit(
        lambda t, a: search_block_core(
            t, a, zap, win, threshold=6.0, size=size, nsamps_valid=size,
            nharms=nharms, max_peaks=16, pos5=8, pos25=80,
        )
    ).lower(tims, afs)
    try:
        text = lowered.as_text(debug_info=True)
    except TypeError:
        # this toolchain predates the debug_info kwarg AND strips
        # location metadata from the plain rendering — the scope
        # names exist but are unobservable here
        pytest.skip("Lowered.as_text lacks debug_info on this jax")
    assert "Acceleration-Loop" in text
    assert "Harmonic summing" in text


def _op_paths(text: str, program: str) -> list[str]:
    """The op-name paths under ``jit(<program>)/`` in a lowered
    program's location table."""
    import re

    return re.findall(r'loc\("(jit\(%s\)/[^"]*)"' % re.escape(program), text)


@pytest.mark.parametrize("program", ["deredden", "profile", "optimise",
                                     "dedisperse_pallas"])
def test_fold_and_dedispersion_programs_carry_stable_scopes(program):
    """The fold programs and the Pallas dedispersion entry carry a
    named_scope each, and every op keeps the device stage the trace
    reduction's rules gave it before the scope existed."""
    import jax.numpy as jnp

    from benchmark.reduce import stage_of
    from peasoup_tpu.ops.fold import fold_time_series
    from peasoup_tpu.ops.fold_optimise import FoldOptimiser, _optimise_device
    from peasoup_tpu.ops.pallas.dedisperse import _jit_full
    from peasoup_tpu.pipeline.folder import _deredden_tim

    if program == "deredden":
        scope, before, name = "fold.deredden", "spectrum_chain", "_deredden_tim"
        text = _deredden_tim.lower(
            jnp.zeros(256, jnp.uint8), size=256, pos5=2, pos25=20,
        ).as_text(debug_info=True)
    elif program == "profile":
        scope, before, name = "fold.profile", "fold", "fold_time_series"
        text = fold_time_series.lower(
            jnp.zeros((2, 64), jnp.float32), jnp.zeros((2, 64), jnp.int32),
            nbins=8, nints=2,
        ).as_text(debug_info=True)
    elif program == "optimise":
        scope, before, name = "fold.optimise", "fold", "_optimise_device"
        opt = FoldOptimiser(8, 2)
        text = _optimise_device.lower(
            jnp.zeros((2, 2, 8), jnp.float32), opt.shiftar_re,
            opt.shiftar_im, opt.templates_re, opt.templates_im,
            nbins=8, nints=2,
        ).as_text(debug_info=True)
    else:
        scope, before, name = "dedisperse_pallas", "dedisperse", "run"
        run = _jit_full(8, 256, 16, 256, 4, 8, 16, 512, 200, True, 1.0, True)
        text = run.lower(
            jnp.zeros((512, 16), jnp.uint8), jnp.zeros((8, 16), jnp.int32),
            jnp.ones(16, bool),
        ).as_text(debug_info=True)
    paths = _op_paths(text, name)
    assert paths
    for p in paths:
        assert p.startswith(f"jit({name})/{scope}")
        assert stage_of(p) == stage_of(p.replace(scope, "", 1)) == before
