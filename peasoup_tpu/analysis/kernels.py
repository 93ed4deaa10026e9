"""Engine 4: Mosaic-aware Pallas kernel contracts (PSK2xx).

Two halves over :mod:`peasoup_tpu.ops.pallas`:

* **Static rules** (registered in the shared AST engine, so they ride
  the same suppression syntax, ``--rules`` filter and fixtures):

  - PSK201 — a module calling ``pl.pallas_call`` with no entry in the
    kernel registry (``ops/pallas/registry.py``): unregistered kernels
    escape the twin/probe/fallback contract entirely.
  - PSK204 — literal BlockSpec tile shapes off the TPU lane/sublane
    quanta (last dim a multiple of 128, second-to-last of 8): Mosaic
    either rejects the tile or silently pads it, burning VMEM.
  - PSK205 — sub-f32 VMEM scratch whose literal sublane dim is below
    the dtype's quantum (bf16 -> 16, int8/fp8 -> 32).
  - PSK206 — ``num_scalar_prefetch`` out of step with the kernel
    registry declaration, or a kernel signature whose parameter count
    disagrees with the grid spec (scalar prefetch + in/out specs +
    scratch), when everything is statically countable.
  - PSK207 — a lane-retiling ``reshape`` inside a kernel body in a
    module whose registry entry declares no retile fallback: Mosaic
    support for lane retiles varies by toolchain, so such a kernel
    MUST sit behind a probe-gated fallback ladder (the spchain
    precedent).

* **Dynamic checks** (:func:`audit_kernels`, over the registry):

  - PSK202 — registry drift: missing entry point, deleted probe,
    or a probe that no longer references the declared jnp twin.
  - PSK203 — the kernel no longer traces/lowers in interpret mode at
    its registered geometry.
  - PSK208 — Mosaic lowering, attempted only where the toolchain
    allows (a real TPU backend): failure is an error (the TPU route
    raises for that kernel).
"""

from __future__ import annotations

import ast

from .astlint import ModuleContext, Rule, dotted_name, register_rule
from .findings import Finding, SEV_ERROR

_PALLAS_PATHS = ("peasoup_tpu/ops/pallas/",)
_PALLAS_EXCLUDE = (
    "peasoup_tpu/ops/pallas/__init__.py",
    "peasoup_tpu/ops/pallas/registry.py",
)

LANE = 128
SUBLANE_F32 = 8
# minimum sublane tile per sub-f32 dtype (pallas_guide.md: the
# second-to-last dim quantum grows as the element narrows)
_SUBLANE_QUANTA = {
    "bfloat16": 16,
    "float16": 16,
    "int8": 32,
    "uint8": 32,
    "float8_e4m3fn": 32,
    "float8_e5m2": 32,
}


def _module_stem(relpath: str) -> str:
    return relpath.rsplit("/", 1)[-1].removesuffix(".py")


def _registry_spec(relpath: str):
    try:
        from peasoup_tpu.ops.pallas.registry import spec_for_module

        return spec_for_module(_module_stem(relpath))
    except Exception:
        return None


def _calls_pallas_call(ctx: ModuleContext) -> ast.Call | None:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and (
            dotted_name(node.func) or ""
        ).endswith("pallas_call"):
            return node
    return None


@register_rule
class UnregisteredKernel(Rule):
    """``pl.pallas_call`` in a module with no kernel-registry entry."""

    id = "PSK201"
    severity = SEV_ERROR
    title = "Pallas kernel module missing from the kernel registry"
    fix_hint = (
        "add a KernelSpec (entry/probe/twin/fallback + interpret "
        "build) to ops/pallas/registry.py"
    )
    paths = _PALLAS_PATHS
    exclude = _PALLAS_EXCLUDE

    def check(self, ctx: ModuleContext):
        call = _calls_pallas_call(ctx)
        if call is None:
            return
        if _registry_spec(ctx.relpath) is None:
            yield self.finding(
                ctx, call,
                f"module {_module_stem(ctx.relpath)!r} builds a Pallas "
                "kernel but has no kernel-registry entry: it escapes "
                "the twin/probe/fallback contract",
            )


def _literal_dims(node: ast.AST) -> list[int | None] | None:
    """Tile-shape tuple -> dims (None for None/non-literal entries);
    None when the node is not a tuple/list literal at all."""
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    dims: list[int | None] = []
    for el in node.elts:
        if isinstance(el, ast.Constant) and isinstance(el.value, int):
            dims.append(el.value)
        elif isinstance(el, ast.Constant) and el.value is None:
            dims.append(None)
        else:
            dims.append(None)
    return dims


def _is_smem(call: ast.Call) -> bool:
    for kw in call.keywords:
        if kw.arg == "memory_space" and "SMEM" in (
            dotted_name(kw.value) or ""
        ):
            return True
    return any("SMEM" in (dotted_name(a) or "") for a in call.args)


@register_rule
class TileShapeQuanta(Rule):
    """Literal BlockSpec tiles off the (8, 128) f32 quanta.

    Only fully-literal dims are judged (symbolic tile maths is the
    probe's job); 1 is allowed anywhere (unit dims lower to scalar
    broadcast), SMEM blocks are exempt (scalars are untiled).
    """

    id = "PSK204"
    severity = SEV_ERROR
    title = "BlockSpec tile shape off the lane/sublane quanta"
    fix_hint = (
        "last tile dim a multiple of 128 (lane), second-to-last a "
        "multiple of 8 (f32 sublane) — or 1 for unit dims"
    )
    paths = _PALLAS_PATHS
    exclude = _PALLAS_EXCLUDE

    def check(self, ctx: ModuleContext):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            if not name.endswith("BlockSpec") or not node.args:
                continue
            if _is_smem(node):
                continue
            dims = _literal_dims(node.args[0])
            if not dims or len(dims) < 2:
                continue
            lane = dims[-1]
            sub = dims[-2]
            if lane is not None and lane != 1 and lane % LANE:
                yield self.finding(
                    ctx, node,
                    f"BlockSpec lane dim {lane} is not a multiple of "
                    f"{LANE}",
                )
            elif sub is not None and sub != 1 and sub % SUBLANE_F32:
                yield self.finding(
                    ctx, node,
                    f"BlockSpec sublane dim {sub} is not a multiple "
                    f"of {SUBLANE_F32}",
                )


@register_rule
class SubF32ScratchQuanta(Rule):
    """Sub-f32 VMEM scratch below its dtype's sublane quantum."""

    id = "PSK205"
    severity = SEV_ERROR
    title = "sub-f32 VMEM tile below the dtype's sublane quantum"
    fix_hint = (
        "bf16 tiles need sublane multiples of 16, int8/fp8 of 32 "
        "(pallas_guide: tiling constraints)"
    )
    paths = _PALLAS_PATHS
    exclude = _PALLAS_EXCLUDE

    def check(self, ctx: ModuleContext):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            if not name.endswith("VMEM") or len(node.args) < 2:
                continue
            dims = _literal_dims(node.args[0])
            dtype = (dotted_name(node.args[1]) or "").rsplit(".", 1)[-1]
            quantum = _SUBLANE_QUANTA.get(dtype)
            if quantum is None or not dims or len(dims) < 2:
                continue
            sub = dims[-2]
            if sub is not None and sub % quantum:
                yield self.finding(
                    ctx, node,
                    f"VMEM {dtype} scratch sublane dim {sub} is below "
                    f"the {quantum}-row quantum",
                )


def _kernel_defs(ctx: ModuleContext) -> list[ast.FunctionDef]:
    """Function defs passed (directly or through partial) as the first
    argument of a pallas_call in this module."""
    defs = {
        n.name: n
        for n in ast.walk(ctx.tree)
        if isinstance(n, ast.FunctionDef)
    }
    out = []
    partials: dict[str, str] = {}  # local name -> wrapped fn name
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Call
        ):
            callee = dotted_name(node.value.func) or ""
            if callee.split(".")[-1] == "partial" and node.value.args:
                inner = dotted_name(node.value.args[0])
                if inner and len(node.targets) == 1 and isinstance(
                    node.targets[0], ast.Name
                ):
                    partials[node.targets[0].id] = inner.split(".")[-1]
    for node in ast.walk(ctx.tree):
        if not (
            isinstance(node, ast.Call)
            and (dotted_name(node.func) or "").endswith("pallas_call")
            and node.args
        ):
            continue
        arg = node.args[0]
        name = dotted_name(arg)
        if isinstance(arg, ast.Call):
            callee = dotted_name(arg.func) or ""
            if callee.split(".")[-1] == "partial" and arg.args:
                name = dotted_name(arg.args[0])
        if name:
            leaf = name.split(".")[-1]
            leaf = partials.get(leaf, leaf)
            if leaf in defs:
                out.append(defs[leaf])
    return out


def _positional_param_count(fn: ast.FunctionDef) -> int:
    a = fn.args
    return len(a.posonlyargs) + len(a.args)


@register_rule
class ScalarPrefetchContract(Rule):
    """``num_scalar_prefetch`` vs the registry and the kernel arity.

    Scalar-prefetch refs arrive FIRST in the kernel signature; a
    miscounted ``num_scalar_prefetch`` shifts every later ref by one
    and Mosaic's error surfaces at lowering time, far from the edit.
    Checked statically when countable: the literal must equal the
    registry's ``scalar_prefetch`` declaration, and — when in/out
    specs and scratch_shapes are literal lists — the kernel's
    positional arity must equal prefetch + ins + outs + scratch.
    """

    id = "PSK206"
    severity = SEV_ERROR
    title = "scalar-prefetch count off the kernel registry/arity"
    fix_hint = (
        "keep num_scalar_prefetch, the KernelSpec.scalar_prefetch "
        "declaration, and the kernel's leading *_ref params in step"
    )
    paths = _PALLAS_PATHS
    exclude = _PALLAS_EXCLUDE

    def check(self, ctx: ModuleContext):
        spec = _registry_spec(ctx.relpath)
        kernels = _kernel_defs(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            if not name.endswith("PrefetchScalarGridSpec"):
                continue
            n_prefetch = None
            counts = {}
            for kw in node.keywords:
                if kw.arg == "num_scalar_prefetch":
                    if isinstance(kw.value, ast.Constant) and isinstance(
                        kw.value.value, int
                    ):
                        n_prefetch = kw.value.value
                elif kw.arg in ("in_specs", "out_specs", "scratch_shapes"):
                    if isinstance(kw.value, (ast.List, ast.Tuple)):
                        counts[kw.arg] = len(kw.value.elts)
            if n_prefetch is None:
                yield self.finding(
                    ctx, node,
                    "num_scalar_prefetch is not a literal int: the "
                    "scalar/ref split cannot be audited",
                )
                continue
            if spec is not None and spec.scalar_prefetch != n_prefetch:
                yield self.finding(
                    ctx, node,
                    f"num_scalar_prefetch={n_prefetch} disagrees with "
                    f"the kernel registry declaration "
                    f"({spec.scalar_prefetch})",
                )
                continue
            if len(counts) == 3 and len(kernels) == 1:
                want = (
                    n_prefetch
                    + counts["in_specs"]
                    + counts["out_specs"]
                    + counts["scratch_shapes"]
                )
                got = _positional_param_count(kernels[0])
                if got != want:
                    yield self.finding(
                        ctx, node,
                        f"kernel {kernels[0].name!r} takes {got} "
                        f"positional refs but the grid spec implies "
                        f"{want} (prefetch {n_prefetch} + ins "
                        f"{counts['in_specs']} + outs "
                        f"{counts['out_specs']} + scratch "
                        f"{counts['scratch_shapes']})",
                    )


@register_rule
class LaneRetileWithoutFallback(Rule):
    """Lane-retiling reshape in a kernel not declared as retiling.

    Reshapes that re-tile the minor (lane) dimension inside a kernel
    are refused by Mosaic in some forms (``(1, n) -> (n/32, 32)`` on
    JAX 0.9), so a kernel doing one must declare ``lane_retile=True``
    in its registry entry, which puts it among the kernels
    tests/test_tpu_compile.py compiles for v5e. Flat ``reshape(-1)``
    and unit-row ``reshape(1, n)`` are tile-preserving and exempt.
    """

    id = "PSK207"
    severity = SEV_ERROR
    title = "lane-retiling reshape in a kernel not declared lane_retile"
    fix_hint = (
        "declare lane_retile=True in the KernelSpec and compile the "
        "kernel for v5e in tests/test_tpu_compile.py, or restructure "
        "the kernel to avoid retiling the lane dim"
    )
    paths = _PALLAS_PATHS
    exclude = _PALLAS_EXCLUDE

    def _is_retile(self, call: ast.Call) -> bool:
        args = call.args
        if len(args) == 1 and isinstance(args[0], (ast.Tuple, ast.List)):
            args = list(args[0].elts)
        if len(args) < 2:
            return False  # flatten / 1-D
        first = args[0]
        if (
            len(args) == 2
            and isinstance(first, ast.Constant)
            and first.value == 1
        ):
            return False  # unit-row prepend keeps the lane layout
        return True

    def check(self, ctx: ModuleContext):
        spec = _registry_spec(ctx.relpath)
        if spec is not None and spec.lane_retile:
            return
        for fn in _kernel_defs(ctx):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                leaf = name.split(".")[-1]
                if leaf != "reshape":
                    continue
                if self._is_retile(node):
                    yield self.finding(
                        ctx, node,
                        f"kernel {fn.name!r} retiles the lane dim "
                        "in-kernel but its module declares no retile "
                        "fallback ladder",
                    )


# --------------------------------------------------------------------------
# dynamic checks over the kernel registry
# --------------------------------------------------------------------------


def _kernel_finding(spec, rule, message, severity=SEV_ERROR, hint=""):
    return Finding(
        rule=rule,
        severity=severity,
        path=f"pallas-registry/{spec.name}",
        line=0,
        col=0,
        message=message,
        fix_hint=hint,
        source_line=f"{rule} {spec.name}",
    )


def _probe_references_twin(probe_fn, twin: str) -> bool:
    import inspect
    import textwrap

    try:
        src = textwrap.dedent(inspect.getsource(probe_fn))
    except (OSError, TypeError):
        return False
    leaf = twin.rsplit(".", 1)[-1]
    return leaf in src


def audit_kernel(spec, mosaic: bool | None = None) -> list[Finding]:
    """Contract-check one registered kernel. ``mosaic=None`` probes
    the backend (TPU only); True forces the Mosaic lowering attempt,
    False skips it."""
    import importlib

    findings: list[Finding] = []
    # PSK202: registry drift — entry, probe, twin all resolvable and
    # the probe actually exercising the declared twin
    try:
        mod = importlib.import_module(spec.module)
    except Exception as exc:
        return [
            _kernel_finding(
                spec, "PSK202",
                f"kernel module {spec.module} failed to import: "
                f"{type(exc).__name__}: {exc!s:.200}",
            )
        ]
    if not hasattr(mod, spec.entry):
        findings.append(
            _kernel_finding(
                spec, "PSK202",
                f"entry point {spec.entry!r} missing from "
                f"{spec.module}",
                hint="fix the KernelSpec or restore the entry point",
            )
        )
    import peasoup_tpu.ops.pallas as pallas_pkg

    probe_fn = getattr(pallas_pkg, spec.probe, None)
    if probe_fn is None:
        findings.append(
            _kernel_finding(
                spec, "PSK202",
                f"probe {spec.probe!r} deleted from ops/pallas: the "
                "driver can no longer arbitrate this kernel's "
                "toolchain eligibility",
                hint=(
                    "restore the compile-and-run probe in "
                    "ops/pallas/__init__.py (oracle-checked against "
                    f"{spec.twin})"
                ),
            )
        )
    else:
        twin_mod, _, twin_attr = spec.twin.rpartition(".")
        try:
            twin_ok = hasattr(importlib.import_module(twin_mod), twin_attr)
        except Exception:
            twin_ok = False
        if not twin_ok:
            findings.append(
                _kernel_finding(
                    spec, "PSK202",
                    f"declared twin {spec.twin} is not importable",
                )
            )
        elif not _probe_references_twin(probe_fn, spec.twin):
            findings.append(
                _kernel_finding(
                    spec, "PSK202",
                    f"probe {spec.probe!r} no longer references the "
                    f"declared twin {spec.twin}: the oracle gate is "
                    "vacuous",
                )
            )
    if findings:
        return findings  # drifted registry: lowering would only noise

    # PSK203: interpret-mode trace/lower at the registered geometry
    import jax

    try:
        fn, args, kwargs = spec.build(True)
        jax.jit(lambda *a: fn(*a, **kwargs)).lower(*args)
    except Exception as exc:
        findings.append(
            _kernel_finding(
                spec, "PSK203",
                f"kernel no longer traces/lowers in interpret mode at "
                f"its registered geometry: {type(exc).__name__}: "
                f"{exc!s:.300}",
                hint=(
                    "the registry build thunk no longer matches the "
                    "kernel; fix the registration next to the kernel"
                ),
            )
        )
        return findings

    # PSK208: Mosaic lowering, where the toolchain allows
    if mosaic is None:
        try:
            mosaic = jax.default_backend() == "tpu"
        except Exception:
            mosaic = False
    if mosaic:
        try:
            fn, args, kwargs = spec.build(False)
            jax.jit(lambda *a: fn(*a, **kwargs)).lower(*args)
        except Exception as exc:
            findings.append(
                _kernel_finding(
                    spec, "PSK208",
                    f"Mosaic lowering failed on this toolchain: "
                    f"{type(exc).__name__}: {exc!s:.300}",
                    hint="the TPU route raises for this kernel on this "
                    "toolchain",
                )
            )
    return findings


class KernelReport:
    def __init__(self) -> None:
        self.findings: list[Finding] = []
        self.kernels: list[str] = []


def audit_kernels(specs=None, mosaic: bool | None = None) -> KernelReport:
    """Contract-check all (or the given) registered kernels. The
    interpret builds are closed over static args, so this traces and
    lowers but never executes device code."""
    if specs is None:
        from peasoup_tpu.ops.pallas.registry import kernel_specs

        specs = kernel_specs()
    report = KernelReport()
    for spec in specs:
        report.kernels.append(spec.name)
        report.findings.extend(audit_kernel(spec, mosaic=mosaic))
    return report


def kernel_rules() -> tuple[str, ...]:
    """The static PSK rule IDs (the runner's engine-4 filter)."""
    return ("PSK201", "PSK204", "PSK205", "PSK206", "PSK207")
