"""Per-program microbenchmarks over the ops registry.

Each registered program's ShapeDtypeStructs are materialised into
device arrays and the AOT-compiled executable is timed over
median-of-k ``block_until_ready`` executions — the same
device-anchored discipline as bench.py's steady-state runs (the
compiled object is invoked directly, so no tracing, dispatch-cache or
compile time pollutes an execute sample; compile time is measured
separately, with its persistent-cache hit/miss attribution). The
result is a schema-validated ``perf.json`` keyed by program name (the
registered representative shapes are part of the record) with the
backend/device identity at top level — the document the ratchet
(perf/ratchet.py) compares against ``perf_baseline.json``.
"""

from __future__ import annotations

import time

from .warmup import _sink_scope

PERF_SCHEMA = "peasoup_tpu.perf"
# v2: per-program "stage" + top-level "stages" totals (the roofline
# classification shared with BENCH, perf/roofline.py) and the resolved
# "dedisp" alternative record
PERF_VERSION = 2

DEFAULT_REPS = 5


def _materialise(arg, rng):
    """A device array for one build-thunk operand. ShapeDtypeStructs
    become deterministic pseudo-random floats in [0.5, 1.5) (safe for
    the div/sqrt/log in the stats programs) or zeros for integer/bool
    operands (always-valid indices/masks); concrete arrays (e.g. the
    fold templates) upload as-is."""
    import jax
    import numpy as np

    if isinstance(arg, jax.ShapeDtypeStruct):
        dt = np.dtype(arg.dtype)
        if np.issubdtype(dt, np.floating):
            x = rng.uniform(0.5, 1.5, size=arg.shape).astype(dt)
        elif np.issubdtype(dt, np.complexfloating):
            x = (
                rng.uniform(0.5, 1.5, size=arg.shape)
                + 1j * rng.uniform(-0.5, 0.5, size=arg.shape)
            ).astype(dt)
        else:
            x = np.zeros(arg.shape, dt)
        return jax.device_put(x)
    return jax.device_put(np.asarray(arg))


def _arg_sig(args) -> list[str]:
    """Compact shape/dtype signature, e.g. ``u8[256,8]``."""
    import jax
    import numpy as np

    out = []
    for a in args:
        if isinstance(a, jax.ShapeDtypeStruct):
            shape, dt = a.shape, np.dtype(a.dtype)
        else:
            arr = np.asarray(a)
            shape, dt = arr.shape, arr.dtype
        out.append(f"{dt.str.lstrip('<>|=')}[{','.join(map(str, shape))}]")
    return out


def bench_program(spec, reps: int = DEFAULT_REPS, ctx=None) -> dict:
    """Compile and time one registered program. Returns its perf.json
    record; failures come back as a record with ``error`` set."""
    import jax
    import numpy as np

    rec: dict = {"error": None}
    try:
        built = spec.build_for(ctx)
        if built is None:
            return {**rec, "error": "no parameterisation for ctx"}
        fn, args, kwargs = built
        rec["args"] = _arg_sig(args)
        if not hasattr(fn, "lower"):
            fn = jax.jit(fn)
        t0 = time.perf_counter()
        with _sink_scope() as sink:
            compiled = fn.lower(*args, **kwargs).compile()
        rec["compile_s"] = round(time.perf_counter() - t0, 6)
        rec["compile_cache_hit"] = sink["cache_hits"] > 0
        rec["backend_compile_s"] = round(sink["backend_compile_s"], 6)

        rng = np.random.default_rng(0)
        dev_args = [_materialise(a, rng) for a in args]
        # one untimed execution absorbs first-dispatch overhead
        jax.block_until_ready(compiled(*dev_args))

        def _restage():
            # donated operands are consumed per call: re-stage them
            # OUTSIDE the timed window
            nonlocal dev_args
            r = np.random.default_rng(0)
            dev_args = [_materialise(a, r) for a in args]

        # the shared measurement path (perf/measure.py): the same
        # median-of-k block_until_ready discipline bench.py uses
        from .measure import summarize, timed_samples

        samples = timed_samples(
            lambda: jax.block_until_ready(compiled(*dev_args)),
            reps,
            prepare=_restage if spec.donate else None,
        )
        rec.update(summarize(samples))
    except Exception as exc:
        rec["error"] = f"{type(exc).__name__}: {exc!s:.300}"
    return rec


def run_microbench(
    specs=None,
    reps: int = DEFAULT_REPS,
    programs: list[str] | None = None,
    ctx=None,
) -> dict:
    """Benchmark the registry into a perf.json document. Programs that
    fail keep a record (with ``error``) so the ratchet can tell a
    vanished program from a broken one."""
    import jax

    from ..utils.cache import enable_compilation_cache

    if specs is None:
        from ..ops.registry import registered_programs

        specs = registered_programs()
    if programs:
        wanted = set(programs)
        specs = [s for s in specs if s.name in wanted]
    from .roofline import stage_for_program

    cache_dir = enable_compilation_cache()
    devs = jax.local_devices()
    t0 = time.perf_counter()
    recs = {}
    for spec in specs:
        rec = bench_program(spec, reps=reps, ctx=ctx)
        rec["stage"] = stage_for_program(spec.name)
        recs[spec.name] = rec
    ok = [r for r in recs.values() if not r["error"]]
    # per-stage execute totals: the same classification BENCH's device trace
    # uses (perf/roofline.py STAGES), so a ratchet regression and a
    # BENCH round name the same bucket
    stages: dict = {}
    for r in ok:
        st = stages.setdefault(
            r["stage"], {"programs": 0, "execute_s": 0.0}
        )
        st["programs"] += 1
        st["execute_s"] += r["execute_median_s"]
    for st in stages.values():
        st["execute_s"] = round(st["execute_s"], 6)
    doc = {
        "schema": PERF_SCHEMA,
        "version": PERF_VERSION,
        "created_unix": time.time(),
        "backend": jax.default_backend(),
        "device_kind": str(devs[0].device_kind) if devs else "unknown",
        "jax_version": jax.__version__,
        "cache_dir": cache_dir,
        "reps": reps,
        "programs": recs,
        "stages": stages,
        # the selected dedispersion alternative this bench's ctx (if
        # any) implies — BENCH records the same field from its tuned
        # plan, so the two reports stay comparable
        "dedisp": {
            "engine": (ctx.dedisp_engine or "exact") if ctx else "exact",
            "subbands": int(ctx.subbands) if ctx else 0,
            "subband_matmul": bool(ctx.subband_matmul) if ctx else False,
        },
        "totals": {
            "programs": len(recs),
            "errors": len(recs) - len(ok),
            "compile_s": round(sum(r["compile_s"] for r in ok), 6),
            "compile_cache_hits": sum(r["compile_cache_hit"] for r in ok),
            "execute_s": round(sum(r["execute_median_s"] for r in ok), 6),
            "wall_s": round(time.perf_counter() - t0, 3),
        },
    }
    return doc


def validate_perf(doc: dict) -> None:
    """Validate a perf.json document against the checked-in schema
    (obs/schema.py's dependency-free draft-07 subset); raises
    SchemaError on violation."""
    import json
    import os

    from ..obs.schema import validate

    path = os.path.join(os.path.dirname(__file__), "perf.schema.json")
    with open(path) as f:
        schema = json.load(f)
    validate(doc, schema)


def load_perf(path: str) -> dict:
    """Load + validate a perf.json document."""
    import json

    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != PERF_SCHEMA:
        raise ValueError(
            f"{path}: not a {PERF_SCHEMA} document "
            f"(schema={doc.get('schema')!r})"
        )
    validate_perf(doc)
    return doc


def write_perf(doc: dict, path: str) -> None:
    """Schema-validate and atomically write a perf.json document."""
    import json
    import os
    import tempfile

    validate_perf(doc)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
