"""Engine 2: jaxpr/StableHLO contract checks over registered programs.

Each :class:`~peasoup_tpu.ops.registry.ProgramSpec` is abstract-evaled
(traced + lowered, never compiled or executed) on CPU over its
registered representative shapes, and the artefacts are linted:

* **PSC101 f64 op** — the trace runs under ``jax.experimental
  .enable_x64`` so float64 drift that the production x64-disabled
  config silently *downcasts* (np.float64 staging constants, Python
  float promotion through np scalars) becomes a visible f64 op in the
  jaxpr. The walk recurses into sub-jaxprs (scan/cond/pjit bodies).
* **PSC102 host callback / unexpected custom call** — any
  ``custom_call`` whose target is not allowlisted; callback targets
  (``xla_python_cpu_callback`` etc.) are called out specifically.
* **PSC103 oversized baked-in constant** — closure constants above a
  size threshold get burned into the executable: silent recompiles
  per distinct value and HBM bloat (the hazard the campaign shape
  buckets exist to avoid).
* **PSC104 donation mismatch** — buffer donation lowered
  (``tf.aliasing_output``) must match what the registry declares the
  driver relies on, in both directions.
* **PSC105 trace/lower failure** — a registered program that no
  longer traces over its registered shapes is itself a finding (the
  registry is the contract).

**Bucket-ladder mode** (:func:`audit_programs_ladder`): the same
artifact lints run at the shapes a CAMPAIGN would trace — each rung of
the padded-nsamps octave ladder (campaign.runner.bucket_nsamps) is
turned into production ShapeCtxs with the drivers' own plan machinery
(perf.warmup.shape_ctx_for_bucket, plus subband/matmul/streaming
variants so every hook family gets a ctx it accepts), and every
registered program is rebuilt through its ``param`` hook at every
rung. Rung-dependent drift — an f64 constant only materialised past a
shape threshold, a baked table that crosses the size gate at survey
lengths, a donation that vanishes in a ctx-built variant — surfaces
here before a campaign hits it. **PSC106** flags any program the
ladder fails to cover at the required number of rungs: ladder
coverage is part of the registration contract, not best-effort.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field, replace

from .findings import Finding, SEV_ERROR, SEV_WARNING

# custom-call targets that are expected in normal CPU/TPU lowerings
DEFAULT_CUSTOM_CALL_ALLOWLIST = frozenset(
    {
        "Sharding",
        "SPMDFullToShardShape",
        "SPMDShardToFullShape",
        "ducc_fft",
        "dynamic_ducc_fft",
        "LuDecomposition",
    }
)

_CALLBACK_MARKERS = ("callback", "python", "py_")

_CUSTOM_CALL_RE = re.compile(
    r'custom_call\s*@(\w+)|call_target_name\s*=\s*"([^"]+)"'
)


@dataclass
class ContractConfig:
    max_const_bytes: int = 1 << 20  # 1 MiB
    check_x64: bool = True
    allow_custom_calls: frozenset = DEFAULT_CUSTOM_CALL_ALLOWLIST
    severity_const: str = SEV_ERROR
    platform: str = "cpu"


def _program_finding(spec, rule, message, severity=SEV_ERROR, hint="",
                     tag=""):
    return Finding(
        rule=rule,
        severity=severity,
        path=f"ops-registry/{spec.name}{tag}",
        line=0,
        col=0,
        message=message,
        fix_hint=hint,
        source_line=f"{rule} {spec.name}{tag}",
    )


def _walk_jaxprs(jaxpr):
    """Yield a jaxpr and every sub-jaxpr reachable through eqn params
    (scan/while/cond bodies, pjit call_jaxprs, custom_* rules)."""
    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for val in eqn.params.values():
                stack.extend(_sub_jaxprs(val))


def _sub_jaxprs(val):
    out = []
    if hasattr(val, "jaxpr"):  # ClosedJaxpr
        out.append(val.jaxpr)
    elif hasattr(val, "eqns"):  # raw Jaxpr
        out.append(val)
    elif isinstance(val, (tuple, list)):
        for v in val:
            out.extend(_sub_jaxprs(v))
    return out


def _f64_eqns(closed_jaxpr):
    """(primitive_name, dtype) pairs for eqns PRODUCING f64/c128.

    Only outputs count: a ``convert_element_type(f64 -> f32)`` that
    immediately downcasts a staging constant is benign (the compiled
    program holds the f32 result), while any eqn whose *output* is f64
    means f64 arithmetic actually runs on device."""
    bad = []
    for j in _walk_jaxprs(closed_jaxpr.jaxpr):
        for eqn in j.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                dt = str(getattr(aval, "dtype", ""))
                if dt in ("float64", "complex128"):
                    bad.append((eqn.primitive.name, dt))
                    break
    return bad


def audit_program(spec, cfg: ContractConfig | None = None) -> list[Finding]:
    """Contract-check one registered program at its representative
    shapes; returns findings."""
    return _audit_built(spec, spec.build, cfg or ContractConfig())


def _audit_built(
    spec, build, cfg: ContractConfig, tag: str = ""
) -> list[Finding]:
    """Trace + lint one build thunk's artifacts. ``tag`` marks ladder
    builds (``@nsamps=<rung>``) so findings carry their rung."""
    import contextlib

    import jax

    findings: list[Finding] = []
    x64 = jax.enable_x64() if cfg.check_x64 else contextlib.nullcontext()
    try:
        fn, args, kwargs = build()
        if not hasattr(fn, "trace"):  # plain function: stage it
            fn = jax.jit(fn)
        with x64:
            traced = fn.trace(*args, **kwargs)
            closed = traced.jaxpr
            text = traced.lower().as_text()
    except Exception as e:  # registry drift is a finding, not a crash
        return [
            _program_finding(
                spec,
                "PSC105",
                f"failed to trace/lower over "
                f"{'ladder' if tag else 'registered'} shapes: "
                f"{type(e).__name__}: {e}",
                hint=(
                    "the registry build thunk no longer matches the "
                    "program; fix the registration next to the op"
                ),
                tag=tag,
            )
        ]

    # PSC101: f64 ops. The jaxpr walk (outputs only) is the source of
    # truth — the HLO text also shows f64 *operands* of the benign
    # f64->f32 staging converts, which are not drift.
    bad = _f64_eqns(closed)
    if bad:
        prims = sorted({p for p, _ in bad})
        findings.append(
            _program_finding(
                spec,
                "PSC101",
                f"float64 ops in jaxpr ({len(bad)} eqns: "
                f"{', '.join(prims[:6])}): f64 drift that the "
                "x64-disabled production config silently downcasts",
                hint=(
                    "pin the offending constants/intermediates to "
                    "float32 (np.float32 / jnp.float32)"
                ),
                tag=tag,
            )
        )

    # PSC102: custom calls / host callbacks
    targets = {t for pair in _CUSTOM_CALL_RE.findall(text) for t in pair if t}
    allowed = cfg.allow_custom_calls | set(spec.allow_custom_calls)
    for target in sorted(targets):
        low = target.lower()
        if any(m in low for m in _CALLBACK_MARKERS):
            findings.append(
                _program_finding(
                    spec,
                    "PSC102",
                    f"host callback in lowered program: {target} — a "
                    "device->host round trip per invocation",
                    hint=(
                        "move the host work out of the jitted program "
                        "(or io_callback it explicitly outside ops/)"
                    ),
                    tag=tag,
                )
            )
        elif target not in allowed:
            findings.append(
                _program_finding(
                    spec,
                    "PSC102",
                    f"unexpected custom call: {target}",
                    hint=(
                        "if intentional, add it to the program's "
                        "allow_custom_calls in its registration"
                    ),
                    tag=tag,
                )
            )

    # PSC103: oversized baked-in constants
    for const in closed.consts:
        nbytes = getattr(const, "nbytes", 0)
        if nbytes > cfg.max_const_bytes:
            shape = getattr(const, "shape", ())
            dtype = getattr(const, "dtype", "?")
            findings.append(
                _program_finding(
                    spec,
                    "PSC103",
                    f"baked-in constant {shape} {dtype} "
                    f"({nbytes / 1e6:.1f} MB > "
                    f"{cfg.max_const_bytes / 1e6:.1f} MB): burned into "
                    "the executable — every distinct value is a silent "
                    "recompile plus resident HBM",
                    severity=cfg.severity_const,
                    hint="pass it as a traced operand instead",
                    tag=tag,
                )
            )

    # PSC104: donation must match the registry declaration
    donated = text.count("tf.aliasing_output") + text.count(
        "jax.buffer_donor"
    )
    if spec.donate and donated == 0:
        findings.append(
            _program_finding(
                spec,
                "PSC104",
                f"registry declares donated args {list(spec.donate)} "
                "but the lowering aliases no buffers — the driver's "
                "memory budget assumes in-place reuse",
                hint="add donate_argnums to the jit wrapper",
                tag=tag,
            )
        )
    elif donated and not spec.donate:
        findings.append(
            _program_finding(
                spec,
                "PSC104",
                f"program donates {donated} buffer(s) the registry "
                "does not declare — callers may still be reading the "
                "donated operands",
                severity=SEV_WARNING,
                hint="declare donate=... in the registration",
                tag=tag,
            )
        )
    return findings


@dataclass
class ContractReport:
    findings: list[Finding] = field(default_factory=list)
    programs: list[str] = field(default_factory=list)


def audit_programs(
    specs=None, cfg: ContractConfig | None = None
) -> ContractReport:
    """Contract-check all (or the given) registered programs."""
    if specs is None:
        from peasoup_tpu.ops.registry import registered_programs

        specs = registered_programs()
    cfg = cfg or ContractConfig()
    report = ContractReport()
    for spec in specs:
        report.programs.append(spec.name)
        report.findings.extend(audit_program(spec, cfg))
    return report


# --------------------------------------------------------------------------
# bucket-ladder contracts
# --------------------------------------------------------------------------

# the synthetic campaign bucket the ladder contracts trace at: small
# band (tiny DM plan -> fast traces) with a 10 ms sample time so the
# whitening boundaries (pos5/pos25) land on nonzero bins even at the
# smallest rungs. (nchans, nbits, tsamp, fch1, foff) — nsamps is the
# rung.
LADDER_BASE_BUCKET = (8, 8, 0.01, 1400.0, -16.0)
LADDER_BASE_NSAMPS = 2048
LADDER_OVERRIDES = {"dm_end": 20.0, "n_widths": 6}
DEFAULT_LADDER_RUNGS = 2


def ladder_rungs(
    base_nsamps: int = LADDER_BASE_NSAMPS,
    count: int = DEFAULT_LADDER_RUNGS,
) -> list[int]:
    """The first ``count`` rungs >= ``base_nsamps`` of the campaign's
    padded-nsamps octave ladder ({2^k, 3*2^(k-1)} —
    campaign.runner.bucket_nsamps), so contracts walk the exact pad
    targets jobs bucket to."""
    from peasoup_tpu.campaign.runner import bucket_nsamps

    rungs: list[int] = []
    n = int(base_nsamps)
    while len(rungs) < count:
        r = bucket_nsamps(n)
        rungs.append(r)
        n = r + 1
    return rungs


def ladder_shape_ctxs(rung: int, overrides: dict | None = None) -> list:
    """Production ShapeCtx variants for one ladder rung: the spsearch
    and search pipelines via the drivers' own plan machinery, plus the
    streaming, subband and subband-matmul variants — one ctx family
    per hook family, so every registered program finds a ctx its hook
    accepts."""
    from peasoup_tpu.perf.warmup import shape_ctx_for_bucket

    nchans, nbits, tsamp, fch1, foff = LADDER_BASE_BUCKET
    bucket = (nchans, nbits, int(rung), tsamp, fch1, foff)
    ov = dict(LADDER_OVERRIDES if overrides is None else overrides)
    ctx_sp = shape_ctx_for_bucket(bucket, "spsearch", ov)
    ctx_search = shape_ctx_for_bucket(bucket, "search", ov)
    ctx_fdas = shape_ctx_for_bucket(bucket, "fdas", ov)
    return [
        ctx_sp,
        ctx_search,
        # FDAS correlation geometry: the fdas hooks decline every ctx
        # without a template batch, so they cover via this variant
        ctx_fdas,
        # streaming geometry: the chunk program's hook declines batch
        # ctxs, so give it the CLI-default chunk at this rung's plan
        replace(ctx_sp, stream_chunk=1024),
        # subband engine variants (gather-staged and matmul-staged)
        replace(ctx_search, subbands=4),
        replace(ctx_search, subbands=4, subband_matmul=True),
        # sub-byte bucket: the device unpacker declines byte data, so
        # its rung coverage rides a 2-bit variant of the same rung
        replace(ctx_sp, nbits=2),
    ]


@dataclass
class LadderReport:
    findings: list[Finding] = field(default_factory=list)
    rungs: list[int] = field(default_factory=list)
    # program name -> rungs at which a hook-built variant was traced
    coverage: dict[str, list[int]] = field(default_factory=dict)


def audit_programs_ladder(
    specs=None,
    rungs: list[int] | None = None,
    cfg: ContractConfig | None = None,
    min_rungs: int | None = None,
    overrides: dict | None = None,
) -> LadderReport:
    """Contract-check all (or the given) registered programs at every
    rung of the campaign bucket ladder. Each program is rebuilt
    through its ShapeCtx ``param`` hook with the first ctx variant
    that accepts it per rung; PSC106 flags programs the ladder covers
    at fewer than ``min_rungs`` rungs (default: every rung)."""
    if specs is None:
        from peasoup_tpu.ops.registry import registered_programs

        specs = registered_programs()
    cfg = cfg or ContractConfig()
    rungs = list(rungs) if rungs is not None else ladder_rungs()
    min_rungs = len(rungs) if min_rungs is None else min(
        min_rungs, len(rungs)
    )
    report = LadderReport(rungs=rungs)
    ctxs_by_rung = {r: ladder_shape_ctxs(r, overrides) for r in rungs}
    for spec in specs:
        covered: list[int] = []
        for rung in rungs:
            built = None
            for ctx in ctxs_by_rung[rung]:
                try:
                    built = spec.build_for(ctx)
                except Exception as exc:
                    report.findings.append(
                        _program_finding(
                            spec,
                            "PSC105",
                            f"ShapeCtx hook raised at rung {rung}: "
                            f"{type(exc).__name__}: {exc}",
                            hint=(
                                "hooks must DECLINE (return None) "
                                "ctxs they cannot build, never raise"
                            ),
                            tag=f"@nsamps={rung}",
                        )
                    )
                    built = None
                    break
                if built is not None:
                    break
            if built is None:
                continue
            covered.append(rung)
            built_spec = built
            report.findings.extend(
                _audit_built(
                    spec,
                    lambda b=built_spec: b,
                    cfg,
                    tag=f"@nsamps={rung}",
                )
            )
        report.coverage[spec.name] = covered
        if len(covered) < min_rungs:
            report.findings.append(
                _program_finding(
                    spec,
                    "PSC106",
                    f"bucket-ladder coverage {len(covered)}/"
                    f"{min_rungs} rungs (rungs {rungs}): the program "
                    "has no ShapeCtx hook (or its hook declines every "
                    "ladder ctx), so campaign-shape drift is invisible "
                    "to the contract engine",
                    hint=(
                        "give the registration a param= ShapeCtx hook "
                        "that builds at bucket geometry (see "
                        "_param_dedisperse_block)"
                    ),
                )
            )
    return report


__all__ = [
    "ContractConfig",
    "ContractReport",
    "DEFAULT_CUSTOM_CALL_ALLOWLIST",
    "LadderReport",
    "audit_program",
    "audit_programs",
    "audit_programs_ladder",
    "ladder_rungs",
    "ladder_shape_ctxs",
]
