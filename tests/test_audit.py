"""Tests for the static-analysis subsystem (peasoup-audit).

Three layers:

* the AST engine against the fixture snippets in ``tests/data/audit/``
  — each fixture annotates its own expected hits (``expect[PSAxxx]``)
  and misses (``ok:`` comments), so every rule is exercised positively
  AND negatively from one source of truth;
* the baseline ratchet + suppression mechanics;
* the contract engine against toy registered programs with injected
  hazards (f64 op, oversized constant, donation mismatch, host
  callback, trace failure) plus the real ops registry.
"""

import functools
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from peasoup_tpu.analysis.astlint import ModuleContext, lint_source
from peasoup_tpu.analysis.contracts import (
    ContractConfig,
    audit_program,
    audit_programs,
)
from peasoup_tpu.analysis.findings import Baseline, Finding
from peasoup_tpu.analysis.rules import all_rules
from peasoup_tpu.ops.pallas.registry import kernel_specs
from peasoup_tpu.analysis.runner import (
    AUDIT_SCHEMA_PATH,
    render_text,
    run_audit,
    write_report,
)
from peasoup_tpu.obs.schema import SchemaError, validate
from peasoup_tpu.ops.registry import ProgramSpec, registered_programs, sds
from peasoup_tpu.tools.audit import main as audit_main

REPO_ROOT = Path(__file__).resolve().parent.parent
FIXTURE_DIR = Path(__file__).parent / "data" / "audit"
FIXTURES = sorted(FIXTURE_DIR.glob("ps[apk]*.py"))

_PATH_RE = re.compile(r"#\s*audit-path:\s*(\S+)")
_EXPECT_RE = re.compile(r"expect\[([A-Z]{3}\d{3})\]")


def _load_fixture(path: Path):
    """(source, lint-relpath, expected {(line, rule), ...})."""
    source = path.read_text()
    m = _PATH_RE.search(source)
    assert m, f"{path.name}: missing '# audit-path:' header"
    expected = set()
    for lineno, line in enumerate(source.splitlines(), 1):
        for rule in _EXPECT_RE.findall(line):
            expected.add((lineno, rule))
    return source, m.group(1), expected


class TestFixtureRules:
    """Every fixture's expect[] annotations match the engine exactly."""

    @pytest.mark.parametrize(
        "fixture", FIXTURES, ids=[p.stem for p in FIXTURES]
    )
    def test_fixture(self, fixture):
        source, relpath, expected = _load_fixture(fixture)
        assert expected, f"{fixture.name}: no expect[] annotations"
        findings, _ = lint_source(source, relpath)
        got = {(f.line, f.rule) for f in findings}
        missing = expected - got
        surprise = got - expected
        assert not missing, f"{fixture.name}: rules not raised: {missing}"
        assert not surprise, (
            f"{fixture.name}: unexpected findings: "
            f"{[(f.line, f.rule, f.message) for f in findings if (f.line, f.rule) in surprise]}"
        )

    def test_every_rule_has_positive_and_negative_coverage(self):
        """Each of the >=10 rule IDs appears in some fixture with at
        least one expected hit, and every fixture also contains clean
        lines (negative cases) the engine must NOT flag."""
        rules = set(all_rules())
        assert len(rules) >= 10
        covered = set()
        for fixture in FIXTURES:
            source, relpath, expected = _load_fixture(fixture)
            covered |= {rule for _, rule in expected}
            assert "# ok:" in source, (
                f"{fixture.name}: needs negative (ok) cases too"
            )
        assert rules <= covered, f"rules without fixtures: {rules - covered}"

    def test_syntax_error_is_a_finding_not_a_crash(self):
        findings, _ = lint_source(
            "def broken(:\n", "peasoup_tpu/ops/x.py"
        )
        assert [f.rule for f in findings] == ["PSA000"]

    def test_rules_are_path_scoped(self):
        # print() is fine in tools/, flagged in pipeline/
        src = "print('hi')\n"
        assert not lint_source(src, "peasoup_tpu/tools/x.py")[0]
        assert [
            f.rule
            for f in lint_source(src, "peasoup_tpu/pipeline/x.py")[0]
        ] == ["PSA007"]


class TestSuppressions:
    SRC = (
        "import time\n"
        "def f():\n"
        "    t0 = time.time(){comment}\n"
        "    return t0\n"
    )

    def test_reasoned_suppression_drops_finding(self):
        src = self.SRC.format(
            comment="  # audit: ignore[PSA006] -- epoch for the lease"
        )
        findings, suppressed = lint_source(src, "peasoup_tpu/obs/x.py")
        assert not findings
        assert suppressed == 1

    def test_bare_suppression_is_inactive_and_reported(self):
        src = self.SRC.format(comment="  # audit: ignore[PSA006]")
        findings, suppressed = lint_source(src, "peasoup_tpu/obs/x.py")
        assert suppressed == 0
        rules = sorted(f.rule for f in findings)
        assert rules == ["PSA000", "PSA006"]  # finding + inactive note

    def test_own_line_suppression_covers_next_code_line(self):
        src = (
            "import time\n"
            "def f():\n"
            "    # audit: ignore[PSA006] -- epoch timestamp\n"
            "    t0 = time.time()\n"
            "    return t0\n"
        )
        findings, suppressed = lint_source(src, "peasoup_tpu/obs/x.py")
        assert not findings and suppressed == 1

    def test_suppression_is_rule_specific(self):
        src = self.SRC.format(
            comment="  # audit: ignore[PSA001] -- wrong rule"
        )
        findings, _ = lint_source(src, "peasoup_tpu/obs/x.py")
        assert [f.rule for f in findings] == ["PSA006"]


class TestBaseline:
    def _findings(self, n=2, line=7):
        return [
            Finding(
                rule="PSA006",
                severity="warning",
                path="peasoup_tpu/obs/x.py",
                line=line + i,
                col=4,
                message="m",
                source_line=f"t{i} = time.time()",
            )
            for i in range(n)
        ]

    def test_round_trip(self, tmp_path):
        findings = self._findings()
        path = str(tmp_path / "baseline.json")
        Baseline.from_findings(findings).save(path)
        loaded = Baseline.load(path)
        new, old, resolved = loaded.apply(findings)
        assert not new and not resolved
        assert len(old) == 2 and all(f.baselined for f in old)

    def test_fingerprint_survives_line_shift(self):
        a = self._findings(1, line=7)[0]
        b = self._findings(1, line=99)[0]
        assert a.fingerprint == b.fingerprint

    def test_new_copy_of_baselined_hazard_still_fails(self):
        one = self._findings(1)
        baseline = Baseline.from_findings(one)
        # same stripped source line twice -> same fingerprint, count 1
        dupe = self._findings(1)[0]
        new, old, _ = baseline.apply(one + [dupe])
        assert len(old) == 1 and len(new) == 1

    def test_resolved_entries_reported(self):
        findings = self._findings(2)
        baseline = Baseline.from_findings(findings)
        new, old, resolved = baseline.apply(findings[:1])
        assert not new and len(old) == 1
        assert resolved == [findings[1].fingerprint]

    def test_rejects_foreign_schema(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"schema": "something.else"}))
        with pytest.raises(ValueError, match="not a"):
            Baseline.load(str(path))


class TestJitScopeAnalysis:
    """The shared machinery rules lean on."""

    def test_scan_body_is_a_jit_scope(self):
        src = (
            "import jax\n"
            "def outer(xs):\n"
            "    def body(c, x):\n"
            "        return c + x, None\n"
            "    return jax.lax.scan(body, 0.0, xs)\n"
        )
        ctx = ModuleContext(src, "peasoup_tpu/ops/x.py")
        bodies = [
            info.how
            for node, info in ctx.jit_scopes.items()
            if getattr(node, "name", "") == "body"
        ]
        assert bodies == ["traced-body"]

    def test_static_argnames_are_not_tracers(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('n',))\n"
            "def f(x, n):\n"
            "    return x * n\n"
        )
        ctx = ModuleContext(src, "peasoup_tpu/ops/x.py")
        (info,) = [
            i for n, i in ctx.jit_scopes.items()
            if getattr(n, "name", "") == "f"
        ]
        assert info.static_names == {"n"}
        assert info.tracer_names() == {"x"}

    def test_metadata_reads_are_not_tracer_references(self):
        src = "def f(x):\n    return x.shape[0] + len(x)\n"
        ctx = ModuleContext(src, "peasoup_tpu/ops/x.py")
        import ast as _ast

        ret = ctx.tree.body[0].body[0].value
        assert isinstance(ret, _ast.BinOp)
        assert not ctx.references_tracer(ret, {"x"})
        src2 = "def f(x):\n    return x + 1\n"
        ctx2 = ModuleContext(src2, "peasoup_tpu/ops/x.py")
        ret2 = ctx2.tree.body[0].body[0].value
        assert ctx2.references_tracer(ret2, {"x"})


def _toy(name, fn, args, donate=(), allow=()):
    return ProgramSpec(
        name=name,
        build=lambda: (fn, args, {}),
        donate=donate,
        allow_custom_calls=allow,
    )


class TestContractEngine:
    def test_injected_f64_op_flagged(self):
        spec = _toy(
            "toy.f64",
            lambda x: x * np.float64(2.0),
            (sds((8,), "float32"),),
        )
        assert [f.rule for f in audit_program(spec)] == ["PSC101"]

    def test_oversized_constant_flagged_and_threshold_respected(self):
        big = jnp.arange(300_000, dtype=jnp.float32)  # 1.2 MB
        spec = _toy(
            "toy.const", lambda x: x + big.sum(), (sds((8,), "float32"),)
        )
        assert [f.rule for f in audit_program(spec)] == ["PSC103"]
        cfg = ContractConfig(max_const_bytes=2 << 20)
        assert not audit_program(spec, cfg)

    def test_host_callback_flagged(self):
        def cb(x):
            return jax.pure_callback(
                lambda a: np.asarray(a) * 2,
                jax.ShapeDtypeStruct((8,), np.float32),
                x,
            )

        spec = _toy("toy.callback", cb, (sds((8,), "float32"),))
        findings = audit_program(spec)
        assert findings and all(f.rule == "PSC102" for f in findings)
        assert "callback" in findings[0].message

    def test_donation_mismatch_both_directions(self):
        declared = _toy(
            "toy.nodonate",
            jax.jit(lambda x: x + 1),
            (sds((8,), "float32"),),
            donate=(0,),
        )
        (f,) = audit_program(declared)
        assert f.rule == "PSC104" and f.severity == "error"
        undeclared = _toy(
            "toy.donates",
            jax.jit(lambda x: x + 1, donate_argnums=(0,)),
            (sds((8,), "float32"),),
        )
        (f,) = audit_program(undeclared)
        assert f.rule == "PSC104" and f.severity == "warning"

    def test_trace_failure_is_a_finding(self):
        spec = _toy(
            "toy.broken",
            lambda x: jnp.dot(x, jnp.zeros((3, 3), jnp.float32)),
            (sds((8,), "float32"),),
        )
        (f,) = audit_program(spec)
        assert f.rule == "PSC105"

    def test_clean_program_passes(self):
        spec = _toy(
            "toy.clean",
            lambda x: (x * jnp.float32(2.0)).sum(),
            (sds((8,), "float32"),),
        )
        assert not audit_program(spec)

    def test_per_program_custom_call_allowlist(self):
        def cb(x):
            return jax.pure_callback(
                lambda a: np.asarray(a) * 2,
                jax.ShapeDtypeStruct((8,), np.float32),
                x,
            )

        # callbacks are flagged even when allowlisted by target name:
        # the marker check is deliberate (a host round trip is never a
        # benign custom call), so only non-callback targets can be
        # allowlisted. Verify allowlisting an ordinary target works by
        # relying on the default allowlist accepting the FFT target.
        spec = _toy(
            "toy.fft",
            lambda x: jnp.fft.rfft(x).real,
            (sds((32,), "float32"),),
        )
        assert not [
            f for f in audit_program(spec) if f.rule == "PSC102"
        ]
        spec2 = _toy("toy.cb", cb, (sds((8,), "float32"),))
        assert [f.rule for f in audit_program(spec2)] == ["PSC102"]


class TestOpsRegistry:
    def test_registry_enumerates_the_ops_programs(self):
        specs = registered_programs()
        names = [s.name for s in specs]
        assert len(names) == len(set(names))
        assert len(names) >= 15
        assert all(n.startswith("ops.") for n in names)
        # every ops module with jitted entry points contributes
        prefixes = {n.split(".")[1] for n in names}
        for mod in (
            "dedisperse", "spectrum", "rednoise", "resample",
            "harmonics", "peaks", "fold", "ffa", "singlepulse",
            "coincidence",
        ):
            assert mod in prefixes, f"no registered programs from {mod}"

    def test_real_registry_is_contract_clean(self):
        # asserted off the shared four-engine pass (one trace of the
        # registry per test session, not one per test)
        result = _full_audit_result()
        assert len(result.programs_checked) >= 15
        assert result.clean, render_text(result, verbose=True)


def render_text_findings(findings):
    return "\n".join(f.render() for f in findings)


class TestRunnerAndCLI:
    def _mini_repo(self, tmp_path, violate=True):
        pkg = tmp_path / "peasoup_tpu" / "pipeline"
        pkg.mkdir(parents=True)
        body = "print('hi')\n" if violate else "x = 1\n"
        (pkg / "mod.py").write_text(body)
        return tmp_path

    def test_exit_0_on_clean_tree(self, tmp_path, capsys):
        root = self._mini_repo(tmp_path, violate=False)
        rc = audit_main(
            ["--root", str(root), "--no-contracts", "--no-kernels", "--no-mc"]
        )
        assert rc == 0
        assert "0 new" in capsys.readouterr().out

    def test_exit_1_on_new_finding(self, tmp_path, capsys):
        root = self._mini_repo(tmp_path)
        rc = audit_main(
            ["--root", str(root), "--no-contracts", "--no-kernels", "--no-mc"]
        )
        assert rc == 1
        assert "PSA007" in capsys.readouterr().out

    def test_exit_2_on_internal_error(self, tmp_path, capsys):
        root = self._mini_repo(tmp_path)
        bad = tmp_path / "bad_baseline.json"
        bad.write_text("{not json")
        rc = audit_main(
            [
                "--root", str(root), "--no-contracts", "--no-kernels", "--no-mc",
                "--baseline", str(bad),
            ]
        )
        assert rc == 2

    def test_write_baseline_ratchet_cycle(self, tmp_path, capsys):
        root = self._mini_repo(tmp_path)
        baseline = tmp_path / "baseline.json"
        args = [
            "--root", str(root), "--no-contracts", "--no-kernels", "--no-mc",
            "--baseline", str(baseline),
        ]
        assert audit_main(args) == 1  # new finding
        assert audit_main(args + ["--write-baseline"]) == 0
        assert audit_main(args) == 0  # tolerated now
        # a second violation is NEW even with the first baselined
        mod = root / "peasoup_tpu" / "pipeline" / "mod.py"
        mod.write_text(mod.read_text() + "print('again')\n")
        assert audit_main(args) == 1
        # fix everything: stale baseline is fine unless --strict-resolved
        mod.write_text("x = 1\n")
        assert audit_main(args) == 0
        assert audit_main(args + ["--strict-resolved"]) == 1
        assert audit_main(args + ["--write-baseline"]) == 0
        data = json.loads(baseline.read_text())
        assert data["fingerprints"] == {}
        capsys.readouterr()

    def test_json_report_validates_against_checked_in_schema(
        self, tmp_path
    ):
        root = self._mini_repo(tmp_path)
        result = run_audit(str(root), contracts=False, kernels=False)
        out = tmp_path / "audit.json"
        write_report(result, str(out))
        doc = json.loads(out.read_text())
        assert doc["schema"] == "peasoup_tpu.audit"
        assert doc["summary"]["new"] == 1
        with open(AUDIT_SCHEMA_PATH) as f:
            schema = json.load(f)
        validate(doc, schema)  # double-check independently
        doc["summary"]["new"] = -1
        with pytest.raises(SchemaError):
            validate(doc, schema)

    def test_rule_filter(self, tmp_path):
        root = self._mini_repo(tmp_path)
        result = run_audit(
            str(root), contracts=False, kernels=False,
            rule_ids=["PSA006"],
        )
        assert not result.findings  # PSA007 filtered out
        with pytest.raises(ValueError, match="unknown rule ids"):
            run_audit(
                str(root), contracts=False, kernels=False,
                rule_ids=["NOPE"],
            )

    def test_list_rules(self, capsys):
        assert audit_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule_id in all_rules():
            assert rule_id in out
        assert "PSC101" in out

    def test_render_text_summarises_baselined(self):
        result = run_audit(
            str(REPO_ROOT), contracts=False, kernels=False,
            baseline_path=str(REPO_ROOT / "audit_baseline.json"),
        )
        text = render_text(result)
        assert "peasoup-audit:" in text


class TestRepoIsClean:
    """The acceptance gate: the tree audits clean with the checked-in
    baseline (AST engine here; the contract engine is covered by
    TestOpsRegistry.test_real_registry_is_contract_clean)."""

    def test_ast_engine_clean_on_repo(self):
        result = run_audit(
            str(REPO_ROOT),
            contracts=False,
            kernels=False,
            baseline_path=str(REPO_ROOT / "audit_baseline.json"),
        )
        assert result.clean, render_text(result, verbose=True)
        assert result.files_scanned > 50

    def test_cli_end_to_end_subprocess(self):
        """The exact command check.sh runs, exit code included."""
        proc = subprocess.run(
            [
                sys.executable, "-m", "peasoup_tpu.tools.audit",
                "--root", str(REPO_ROOT),
                "--baseline", str(REPO_ROOT / "audit_baseline.json"),
                "--no-contracts", "--no-kernels", "--no-mc",
            ],
            capture_output=True,
            text=True,
            cwd=str(REPO_ROOT),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


# --------------------------------------------------------------------------
# bucket-ladder contracts (engine 2, ladder mode)
# --------------------------------------------------------------------------


def _ladder_toy(name, leak_rung=None, hook=True):
    """Toy spec whose ShapeCtx hook builds a tiny program at the ctx's
    nsamps — optionally leaking f64 at exactly one ladder rung (the
    rung-dependent drift class the ladder pass exists to catch)."""

    def param(ctx):
        if ctx.fft_size <= 0:  # accept one ctx variant per rung
            return None
        n = int(ctx.nsamps)
        factor = (
            np.float64(2.0) if n == leak_rung else np.float32(2.0)
        )
        return (lambda x: x * factor, (sds((n,), "float32"),), {})

    return ProgramSpec(
        name=name,
        build=lambda: (
            lambda x: x * jnp.float32(2.0),
            (sds((64,), "float32"),),
            {},
        ),
        param=param if hook else None,
    )


class TestLadderContracts:
    def test_rungs_walk_the_campaign_ladder(self):
        from peasoup_tpu.analysis.contracts import ladder_rungs
        from peasoup_tpu.campaign.runner import bucket_nsamps

        rungs = ladder_rungs(2048, 3)
        assert rungs == [2048, 3072, 4096]
        assert all(bucket_nsamps(r) == r for r in rungs)

    def test_ladder_ctxs_cover_every_hook_family(self):
        from peasoup_tpu.analysis.contracts import ladder_shape_ctxs

        ctxs = ladder_shape_ctxs(2048)
        assert any(c.widths for c in ctxs)  # spsearch
        assert any(c.fft_size > 0 for c in ctxs)  # search
        assert any(c.stream_chunk > 0 for c in ctxs)  # streaming
        assert any(c.subbands > 0 for c in ctxs)  # subband
        assert any(c.subband_matmul for c in ctxs)  # subband matmul
        assert any(c.nbits < 8 for c in ctxs)  # sub-byte unpacker
        assert any(c.pos25 > c.pos5 >= 0 for c in ctxs)  # rednoise

    def test_clean_toy_covers_all_rungs(self):
        from peasoup_tpu.analysis.contracts import audit_programs_ladder

        rep = audit_programs_ladder(specs=[_ladder_toy("toy.clean")])
        assert not rep.findings
        assert rep.coverage["toy.clean"] == rep.rungs

    def test_rung_only_f64_leak_is_caught_and_tagged(self):
        """The acceptance fixture: clean at the representative shapes
        AND at rung 2048, f64 at rung 3072 only — invisible to the
        representative pass, pinned by the ladder."""
        from peasoup_tpu.analysis.contracts import audit_programs_ladder

        toy = _ladder_toy("toy.rung_leak", leak_rung=3072)
        assert not audit_program(toy)  # representative shapes: clean
        rep = audit_programs_ladder(specs=[toy], rungs=[2048, 3072])
        assert [f.rule for f in rep.findings] == ["PSC101"]
        assert rep.findings[0].path == (
            "ops-registry/toy.rung_leak@nsamps=3072"
        )

    def test_missing_hook_is_a_coverage_finding(self):
        from peasoup_tpu.analysis.contracts import audit_programs_ladder

        rep = audit_programs_ladder(
            specs=[_ladder_toy("toy.nohook", hook=False)]
        )
        assert [f.rule for f in rep.findings] == ["PSC106"]
        assert rep.coverage["toy.nohook"] == []

    def test_raising_hook_is_a_finding_not_a_crash(self):
        from peasoup_tpu.analysis.contracts import audit_programs_ladder

        def bad_hook(ctx):
            raise RuntimeError("boom")

        spec = ProgramSpec(
            name="toy.raises",
            build=lambda: (lambda x: x, (sds((8,), "float32"),), {}),
            param=bad_hook,
        )
        rep = audit_programs_ladder(specs=[spec], rungs=[2048])
        assert any(f.rule == "PSC105" for f in rep.findings)
        assert any(f.rule == "PSC106" for f in rep.findings)

    def test_real_registry_is_ladder_clean(self):
        """Every registered program is covered at >= 2 rungs and no
        rung-dependent drift exists (the check.sh gate's ladder half;
        asserted off the shared four-engine pass)."""
        result = _full_audit_result()
        assert result.clean
        assert len(result.ladder_rungs) >= 2
        assert set(result.ladder_coverage) == {
            s.name for s in registered_programs()
        }
        assert all(
            len(covered) >= 2
            for covered in result.ladder_coverage.values()
        ), result.ladder_coverage


# --------------------------------------------------------------------------
# Pallas kernel contracts (engine 4)
# --------------------------------------------------------------------------


class TestKernelEngine:
    def _spec(self, **overrides):
        import dataclasses

        from peasoup_tpu.ops.pallas.registry import kernel_specs

        spec = next(
            s for s in kernel_specs() if s.name == "pallas.boxcar"
        )
        return dataclasses.replace(spec, **overrides)

    def test_real_kernel_registry_is_clean(self):
        result = _full_audit_result()
        assert len(result.kernels_checked) == len(kernel_specs())
        assert result.clean

    def test_registry_covers_every_pallas_module(self):
        """Every ops/pallas module that builds a kernel has a spec —
        the PSK201 cross-reference from the registry side."""
        from peasoup_tpu.ops.pallas.registry import kernel_specs

        pallas_dir = REPO_ROOT / "peasoup_tpu" / "ops" / "pallas"
        modules = {
            p.stem
            for p in pallas_dir.glob("*.py")
            if p.stem not in ("__init__", "registry")
            and "pallas_call" in p.read_text()
        }
        registered = {
            s.module.rsplit(".", 1)[-1] for s in kernel_specs()
        }
        assert modules == registered

    def test_deleted_probe_is_flagged(self):
        """The acceptance fixture: a kernel whose probe was deleted
        must fail the gate (PSK202), and run_audit maps it to new
        findings (CLI exit 1)."""
        from peasoup_tpu.analysis.kernels import audit_kernels

        doctored = self._spec(probe="probe_pallas_deleted")
        rep = audit_kernels(specs=[doctored])
        assert [f.rule for f in rep.findings] == ["PSK202"]
        assert "deleted" in rep.findings[0].message
        result = run_audit(
            str(REPO_ROOT), ast_engine=False, contracts=False,
            kernel_specs=[doctored],
        )
        assert not result.clean  # exit 1 through the CLI mapping

    def test_unreferenced_twin_is_flagged(self):
        from peasoup_tpu.analysis.kernels import audit_kernels

        doctored = self._spec(
            twin="peasoup_tpu.ops.spectrum.spectrum_stats"
        )
        rep = audit_kernels(specs=[doctored])
        assert [f.rule for f in rep.findings] == ["PSK202"]
        assert "vacuous" in rep.findings[0].message

    def test_broken_build_is_flagged(self):
        from peasoup_tpu.analysis.kernels import audit_kernels

        def broken_build(interpret=True):
            raise ValueError("geometry drifted")

        doctored = self._spec(build=broken_build)
        rep = audit_kernels(specs=[doctored])
        assert [f.rule for f in rep.findings] == ["PSK203"]

    def test_mosaic_skipped_off_tpu_and_forced_flag(self):
        from peasoup_tpu.analysis.kernels import audit_kernel

        spec = self._spec()
        # mosaic=False: interpret-only (the CPU CI path) — clean
        assert not audit_kernel(spec, mosaic=False)


# --------------------------------------------------------------------------
# the four-engine acceptance gate
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _full_audit_result():
    """ONE four-engine pass over the repo, shared by the acceptance
    tests below (the engines are deterministic and read-only; running
    them once keeps the suite inside the tier-1 wall budget)."""
    return run_audit(
        str(REPO_ROOT),
        baseline_path=str(REPO_ROOT / "audit_baseline.json"),
    )


class TestFourEngineAcceptance:
    def test_full_audit_is_clean_with_empty_baseline(self):
        """The exact check.sh gate: all four engines over the repo,
        EMPTY checked-in baseline, exit 0."""
        baseline = REPO_ROOT / "audit_baseline.json"
        assert json.loads(baseline.read_text())["fingerprints"] == {}
        result = _full_audit_result()
        assert result.clean, render_text(result, verbose=True)
        assert result.files_scanned > 100
        assert len(result.programs_checked) >= 30
        assert len(result.kernels_checked) == len(kernel_specs())
        assert len(result.ladder_rungs) >= 2
        assert all(
            len(v) >= 2 for v in result.ladder_coverage.values()
        ), result.ladder_coverage
        # the manifest round-trips the checked-in v2 schema
        man = result.to_manifest()
        assert man["version"] >= 2
        with open(AUDIT_SCHEMA_PATH) as f:
            validate(man, json.load(f))

    def _mini_repo(self, tmp_path, relpath, body):
        mod = tmp_path / "peasoup_tpu" / relpath
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(body)
        return tmp_path

    def test_injected_nonatomic_queue_write_exits_1(self, tmp_path):
        root = self._mini_repo(
            tmp_path, "campaign/writer.py",
            "import os\n"
            "def publish(doc, root):\n"
            "    path = os.path.join(root, 'queue', 'jobs', 'j.json')\n"
            "    with open(path, 'w') as f:\n"
            "        f.write(doc)\n",
        )
        rc = audit_main(
            ["--root", str(root), "--no-contracts", "--no-kernels", "--no-mc"]
        )
        assert rc == 1

    def test_injected_unguarded_thread_exits_1(self, tmp_path):
        root = self._mini_repo(
            tmp_path, "obs/spawn.py",
            "import threading\n"
            "def tick():\n"
            "    pass\n"
            "def go():\n"
            "    threading.Thread(target=tick, daemon=True).start()\n",
        )
        rc = audit_main(
            ["--root", str(root), "--no-contracts", "--no-kernels", "--no-mc"]
        )
        assert rc == 1

    def test_engine_toggles_silence_their_rules(self, tmp_path):
        root = self._mini_repo(
            tmp_path, "obs/spawn.py",
            "import threading\n"
            "def tick():\n"
            "    pass\n"
            "def go():\n"
            "    threading.Thread(target=tick, daemon=True).start()\n",
        )
        rc = audit_main(
            [
                "--root", str(root), "--no-contracts", "--no-kernels", "--no-mc",
                "--no-protocol",
            ]
        )
        assert rc == 0  # PSP104 is engine 3's; toggled off

    def test_rung_only_f64_leak_fails_the_gate(self):
        toy = _ladder_toy("toy.gate_leak", leak_rung=3072)
        result = run_audit(
            str(REPO_ROOT), ast_engine=False, kernels=False,
            program_specs=[toy],
        )
        assert not result.clean
        assert any(
            f.rule == "PSC101" and f.path.endswith("@nsamps=3072")
            for f in result.new
        )
