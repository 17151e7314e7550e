"""Static-analysis subsystem (analytics_zoo_tpu.analysis): Tier-1 AST
lint per-rule fixtures, Tier-2 HLO cost extraction exactness, the
timed_compile hook, and the package-wide CI gate.

Tier-1 fixtures live in tests/resources/zoolint_fixtures/ — one module
per rule with positive lines (marked ``POSITIVE`` in comments) and
suppressed negatives, never imported, linted statically.

Tier-2 pins the analytic features against hand counts: exact matmul
FLOPs (2·M·K·N), collective count/bytes of a 2-device psum, a planted
f64 op and host callback each raising a finding, and the acceptance
check that ``timed_compile`` of the fused train step emits
``zoo_hlo_flops`` matching the analytic hand count for the test model.

``test_package_is_clean`` is the quick-tier gate: the full linter over
``analytics_zoo_tpu/`` must report zero unsuppressed findings (the same
check ``python tools/zoolint.py analytics_zoo_tpu/`` exits 0 on).
"""

import json
import os

import numpy as np
import pytest

import jax

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURES = os.path.join(REPO, "tests", "resources", "zoolint_fixtures")


def _lint_fixture(name, rule=None):
    from analytics_zoo_tpu.analysis import lint_file

    findings = lint_file(os.path.join(FIXTURES, name))
    if rule is not None:
        findings = [f for f in findings if f.rule == rule]
    return findings


def _active(findings):
    return [f for f in findings if not f.suppressed]


def _suppressed(findings):
    return [f for f in findings if f.suppressed]


def _line_of(name, marker):
    """1-based line of the first source line containing ``marker``."""
    with open(os.path.join(FIXTURES, name)) as f:
        for i, line in enumerate(f, start=1):
            if marker in line:
                return i
    raise AssertionError(f"{marker!r} not in {name}")


# ---------------------------------------------------------------------------
# Tier 1: one fixture per rule — positives found, negatives quiet,
# suppressions honored.
# ---------------------------------------------------------------------------


class TestJitSideEffectRule:
    FX = "fx_jit_side_effect.py"

    def test_positives(self):
        active = _active(_lint_fixture(self.FX, "jit-side-effect"))
        lines = {f.line for f in active}
        assert _line_of(self.FX, 'print("tracing", x)') in lines
        assert _line_of(self.FX, "time.time()") in lines
        assert _line_of(self.FX, "np.random.rand(3)") in lines
        # transitive: helper called FROM a traced function is traced too
        assert _line_of(self.FX, '"transitively traced"') in lines
        # the plain host function must NOT fire
        assert _line_of(self.FX, "plain host function") not in lines

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "jit-side-effect"))
        assert [f.line for f in sup] == [_line_of(self.FX, '"marker"')]

    def test_severity_is_error(self):
        assert all(str(f.severity) == "error"
                   for f in _lint_fixture(self.FX, "jit-side-effect"))

    def test_nested_traced_call_attributed_to_innermost(self):
        """A side effect in a nested traced def is reported once,
        against the INNERMOST function name — deterministically (the
        traced set is identity-hashed; attribution must not depend on
        set iteration order)."""
        from analytics_zoo_tpu.analysis import lint_source

        src = ("import jax\n"
               "@jax.jit\n"
               "def outer(x):\n"
               "    def inner(y):\n"
               "        print(y)\n"
               "        return y\n"
               "    return inner(x)\n")
        found = [f for f in lint_source(src, "t.py")
                 if f.rule == "jit-side-effect"]
        assert len(found) == 1
        assert found[0].data["function"] == "inner"


class TestPrngReuseRule:
    FX = "fx_prng_reuse.py"

    def test_positive_and_negatives(self):
        active = _active(_lint_fixture(self.FX, "prng-reuse"))
        assert [f.line for f in active] == \
            [_line_of(self.FX, "POSITIVE: same key")]

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "prng-reuse"))
        assert [f.line for f in sup] == \
            [_line_of(self.FX, "antithetic pair")]

    def test_nested_functions_have_separate_key_scopes(self):
        """Two sibling closures each consuming their own `key` param
        once must not read as a reuse in the enclosing function."""
        from analytics_zoo_tpu.analysis import lint_source

        src = ("import jax\n"
               "def outer():\n"
               "    def f(key):\n"
               "        return jax.random.normal(key, (2,))\n"
               "    def g(key):\n"
               "        return jax.random.uniform(key, (2,))\n"
               "    return f, g\n")
        assert not [f for f in lint_source(src, "t.py")
                    if f.rule == "prng-reuse"]

    def test_reuse_inside_nested_function_reported_once(self):
        from analytics_zoo_tpu.analysis import lint_source

        src = ("import jax\n"
               "def outer():\n"
               "    def f(key):\n"
               "        a = jax.random.normal(key, (2,))\n"
               "        b = jax.random.uniform(key, (2,))\n"
               "        return a + b\n"
               "    return f\n")
        found = [f for f in lint_source(src, "t.py")
                 if f.rule == "prng-reuse"]
        assert len(found) == 1 and found[0].line == 5


class TestHostSyncRule:
    FX = "fx_host_sync.py"

    def test_positives_only_inside_hot_path(self):
        active = _active(_lint_fixture(self.FX, "host-sync"))
        # float/asarray/block/device_get/int/.item() in the loop plus
        # the straight-line float()
        assert len(active) == 7
        cold = _line_of(self.FX, "not annotated hot-path")
        assert cold not in {f.line for f in active}

    def test_item_call_detected(self):
        active = _active(_lint_fixture(self.FX, "host-sync"))
        item_line = _line_of(self.FX, ".item()")
        hit = [f for f in active if f.line == item_line]
        assert len(hit) == 1 and hit[0].data["call"] == ".item()"

    def test_loop_context_changes_message(self):
        active = _active(_lint_fixture(self.FX, "host-sync"))
        by_line = {f.line: f for f in active}
        in_loop = by_line[_line_of(self.FX, "POSITIVE (in loop)")]
        assert in_loop.data.get("in_loop") is True
        assert "next feed" in in_loop.message
        straight = by_line[_line_of(self.FX, "not in a loop")]
        assert "in_loop" not in straight.data
        assert "next feed" not in straight.message

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "host-sync"))
        assert [f.line for f in sup] == \
            [_line_of(self.FX, "epoch-boundary sync")]


class TestNonDonatedCarryRule:
    FX = "fx_nondonated_carry.py"

    def test_decorator_and_call_site_positives(self):
        active = _active(_lint_fixture(self.FX, "nondonated-carry"))
        lines = {f.line for f in active}
        assert _line_of(self.FX, "POSITIVE (decorator)") in lines
        assert _line_of(self.FX, "POSITIVE (call site)") in lines
        assert len(active) == 2  # donated variants stay quiet

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "nondonated-carry"))
        assert [f.line for f in sup] == \
            [_line_of(self.FX, "reused across probes")]


class TestRawJitRule:
    FX = "fx_raw_jit.py"

    def test_raw_jit_positives(self):
        """Decorator, partial-decorator and call-site jits outside the
        compile plane are flagged; the timed_compile idiom and
        compile_step routing stay quiet."""
        active = _active(_lint_fixture(self.FX, "raw-jit"))
        lines = {f.line for f in active}
        assert _line_of(self.FX, "POSITIVE (decorator)") in lines
        assert _line_of(self.FX, "POSITIVE (partial decorator)") in lines
        assert _line_of(self.FX, "POSITIVE (call site)") in lines
        assert len(active) == 3  # choke-point negatives stay quiet

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "raw-jit"))
        assert [f.line for f in sup] == \
            [_line_of(self.FX, "deliberate bypass")]

    def test_package_train_steps_routed(self):
        """The rewired call sites the rule exists for: the estimator's
        train/eval steps and both explicit strategies now reach XLA only
        through compile_step — zero active raw-jit findings in those
        modules."""
        from analytics_zoo_tpu.analysis import lint_paths

        mods = [
            os.path.join(REPO, "analytics_zoo_tpu", p) for p in (
                "pipeline/estimator/estimator.py",
                "pipeline/estimator/local.py",
                "parallel/strategies.py",
            )
        ]
        active = [f for f in _active(lint_paths(mods))
                  if f.rule == "raw-jit"]
        assert not active, [str(f) for f in active]


class TestRawRematRule:
    FX = "fx_raw_remat.py"

    def test_raw_remat_positives(self):
        """Decorator, partial-decorator and call-site checkpoints outside
        apply_remat are flagged; the apply_remat routing stays quiet."""
        active = _active(_lint_fixture(self.FX, "raw-remat"))
        lines = {f.line for f in active}
        assert _line_of(self.FX, "POSITIVE (decorator)") in lines
        assert _line_of(self.FX, "POSITIVE (partial decorator)") in lines
        assert _line_of(self.FX, "POSITIVE (call site)") in lines
        assert len(active) == 3  # apply_remat negative stays quiet

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "raw-remat"))
        assert [f.line for f in sup] == \
            [_line_of(self.FX, "deliberate bypass")]

    def test_package_remat_routed(self):
        """The call sites the rule exists for: the transformer blocks and
        the pipeline stage bodies now checkpoint only through
        apply_remat/resolve_remat — zero active raw-remat findings."""
        from analytics_zoo_tpu.analysis import lint_paths

        mods = [
            os.path.join(REPO, "analytics_zoo_tpu", p) for p in (
                "pipeline/api/keras/layers/self_attention.py",
                "parallel/pipeline.py",
                "pipeline/estimator/estimator.py",
            )
        ]
        active = [f for f in _active(lint_paths(mods))
                  if f.rule == "raw-remat"]
        assert not active, [str(f) for f in active]


class TestRawPallasCallRule:
    FX = "fx_raw_pallas.py"

    def test_raw_pallas_positives(self):
        """Decorator, partial-decorator and call-site pallas_calls
        outside ops/pallas/ are flagged."""
        active = _active(_lint_fixture(self.FX, "raw-pallas-call"))
        lines = {f.line for f in active}
        assert _line_of(self.FX, "POSITIVE (decorator)") in lines
        assert _line_of(self.FX, "POSITIVE (partial decorator)") in lines
        assert _line_of(self.FX, "POSITIVE (call site)") in lines
        assert len(active) == 3

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "raw-pallas-call"))
        assert [f.line for f in sup] == \
            [_line_of(self.FX, "deliberate bypass")]

    def test_package_kernels_routed(self):
        """The kernel plane's contract: every pl.pallas_call in the
        package lives in ops/pallas/ (where the modules carry the
        disable-file justification) and the kernel CONSUMERS carry
        none at all — zero active raw-pallas-call findings."""
        import glob

        from analytics_zoo_tpu.analysis import lint_paths

        mods = sorted(glob.glob(os.path.join(
            REPO, "analytics_zoo_tpu", "ops", "pallas", "*.py")))
        mods += [
            os.path.join(REPO, "analytics_zoo_tpu", p) for p in (
                "ops/attention.py",
                "pipeline/api/keras/objectives.py",
                "pipeline/inference/quantize.py",
                "pipeline/estimator/estimator.py",
            )
        ]
        active = [f for f in _active(lint_paths(mods))
                  if f.rule == "raw-pallas-call"]
        assert not active, [str(f) for f in active]


class TestGuardedByRule:
    FX = "fx_guarded_by.py"

    def test_unguarded_writes_caught(self):
        """The lock-discipline checker catches every write shape against
        a `# guarded-by:` attribute outside the lock."""
        active = _active(_lint_fixture(self.FX, "guarded-by"))
        lines = {f.line for f in active}
        assert _line_of(self.FX, "item assignment, no lock") in lines
        assert _line_of(self.FX, "augmented assignment, no lock") in lines
        assert _line_of(self.FX, "mutating call, no lock") in lines
        assert _line_of(self.FX, "rebinding loses") in lines
        assert _line_of(self.FX, "tuple-unpacking write") in lines
        assert len(active) == 5  # locked + undeclared writes are quiet

    def test_finding_names_attr_and_lock(self):
        f = _active(_lint_fixture(self.FX, "guarded-by"))[0]
        assert f.data["lock"] == "_lock"
        assert "_items" in f.message or "count" in f.message

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "guarded-by"))
        assert [f.line for f in sup] == \
            [_line_of(self.FX, "before the worker threads start")]


class TestLockOrderRule:
    FX = "fx_lock_order.py"

    def test_abba_found_consistent_quiet(self):
        active = _active(_lint_fixture(self.FX, "lock-order"))
        assert len(active) == 1
        assert set(active[0].data["locks"]) == \
            {"AbbaPair._a_lock", "AbbaPair._b_lock"}


class TestBareExceptRule:
    FX = "fx_bare_except.py"

    def test_swallow_found_reraise_quiet(self):
        active = _active(_lint_fixture(self.FX, "bare-except"))
        assert [f.line for f in active] == \
            [_line_of(self.FX, "POSITIVE: eats SystemExit")]

    def test_suppressed_negative(self):
        sup = _suppressed(_lint_fixture(self.FX, "bare-except"))
        assert [f.line for f in sup] == \
            [_line_of(self.FX, "last-resort guard")]


class TestEngine:
    def test_file_level_suppression(self):
        from analytics_zoo_tpu.analysis import lint_source

        src = ("# zoolint: disable-file=bare-except -- fixture\n"
               "def f():\n"
               "    try:\n"
               "        pass\n"
               "    except:\n"
               "        pass\n")
        findings = lint_source(src, "t.py")
        assert all(f.suppressed for f in findings
                   if f.rule == "bare-except")

    def test_syntax_error_is_a_finding(self):
        from analytics_zoo_tpu.analysis import lint_source

        findings = lint_source("def broken(:\n", "bad.py")
        assert [f.rule for f in findings] == ["parse-error"]

    def test_render_json_shape(self):
        from analytics_zoo_tpu.analysis import lint_file, render_json

        doc = json.loads(render_json(lint_file(
            os.path.join(FIXTURES, "fx_bare_except.py"))))
        assert doc["summary"]["total"] == 1
        assert doc["summary"]["suppressed"] == 1
        assert doc["summary"]["by_rule"] == {"bare-except": 1}
        assert doc["findings"][0]["path"].endswith("fx_bare_except.py")


class TestCli:
    def test_exit_nonzero_on_findings_and_json(self, capsys):
        from analytics_zoo_tpu.analysis.cli import main

        rc = main([FIXTURES, "--format", "json"])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["summary"]["total"] > 0

    def test_exit_zero_on_clean_tree(self, capsys):
        from analytics_zoo_tpu.analysis.cli import main

        rc = main([os.path.join(REPO, "analytics_zoo_tpu", "analysis")])
        capsys.readouterr()
        assert rc == 0

    def test_unknown_rule_is_usage_error(self, capsys):
        from analytics_zoo_tpu.analysis.cli import main

        rc = main([FIXTURES, "--rules", "no-such-rule"])
        capsys.readouterr()
        assert rc == 2

    def test_missing_path_is_usage_error_not_clean(self, capsys):
        """A typo'd path must exit 2, not report '0 findings' — a CI
        step pointed at nothing would otherwise stay green forever."""
        from analytics_zoo_tpu.analysis.cli import main

        rc = main(["no/such/dir-anywhere"])
        capsys.readouterr()
        assert rc == 2

    def test_rule_subset(self, capsys):
        from analytics_zoo_tpu.analysis.cli import main

        rc = main([os.path.join(FIXTURES, "fx_bare_except.py"),
                   "--rules", "guarded-by"])
        capsys.readouterr()
        assert rc == 0  # bare-except exists there, but wasn't asked for


# ---------------------------------------------------------------------------
# The CI gate (acceptance): zero unsuppressed findings over the package.
# ---------------------------------------------------------------------------


def test_package_is_clean():
    """`python tools/zoolint.py --whole-program analytics_zoo_tpu/`
    must exit 0: every real violation the per-file detectors AND the
    interprocedural pass (cross-module lock-order, guarded-by
    inference) surface is either fixed or justified with a reviewed
    suppression comment."""
    from analytics_zoo_tpu.analysis import lint_paths, render_text
    from analytics_zoo_tpu.analysis.rules_interproc import lint_program

    pkg = os.path.join(REPO, "analytics_zoo_tpu")
    findings = lint_paths([pkg]) + lint_program(pkg)
    active = _active(findings)
    assert not active, "unsuppressed zoolint findings:\n" + \
        render_text(active)


# ---------------------------------------------------------------------------
# Tier 2: analytic cost extraction + HLO findings.
# ---------------------------------------------------------------------------


class TestHloCostExtraction:
    def test_matmul_flops_exact(self):
        """FLOPs of one [8,16]x[16,4] dot: 2*8*16*4 = 1024 exactly (the
        same figure XLA's own cost analysis reports)."""
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        text = jax.jit(lambda a, b: a @ b).lower(
            np.zeros((8, 16), np.float32),
            np.zeros((16, 4), np.float32)).as_text()
        rpt = analyze_hlo_text(text, "matmul")
        assert rpt.matmul_flops == 2 * 8 * 16 * 4
        assert rpt.op_count == 1
        assert rpt.collective_count == 0
        assert not rpt.findings

    def test_batched_dot_general_flops(self):
        """Batched dims count into output, contracted dims into depth:
        [2,8,16]x[2,16,4] einsum -> 2 * (2*8*4) * 16."""
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        import jax.numpy as jnp
        text = jax.jit(
            lambda a, b: jnp.einsum("bij,bjk->bik", a, b)).lower(
            np.zeros((2, 8, 16), np.float32),
            np.zeros((2, 16, 4), np.float32)).as_text()
        rpt = analyze_hlo_text(text, "bmm")
        assert rpt.matmul_flops == 2 * (2 * 8 * 4) * 16

    def test_psum_collective_count_and_bytes(self):
        """A psum over a 2-device CPU mesh is ONE all_reduce moving the
        [8]f32 result = 32 bytes."""
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        devices = jax.devices()[:2]
        assert len(devices) == 2, "conftest forces an 8-device CPU mesh"
        f = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i",
                     devices=devices)
        rpt = analyze_hlo_text(
            f.lower(np.zeros((2, 8), np.float32)).as_text(), "psum")
        assert rpt.collective_count == 1
        assert rpt.collectives == {"all_reduce": 1}
        assert rpt.collective_bytes == 8 * 4
        assert not rpt.findings  # all_reduce is an EXPECTED collective

    def test_planted_f64_raises_finding(self):
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        with jax.enable_x64():
            text = jax.jit(lambda x: x.astype("float64") * 2.0).lower(
                np.zeros((4,), np.float32)).as_text()
        rpt = analyze_hlo_text(text, "f64")
        assert "hlo-f64" in {f.rule for f in rpt.findings}

    def test_planted_host_callback_raises_finding(self):
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        def cb(x):
            return np.asarray(x)

        text = jax.jit(lambda x: jax.pure_callback(
            cb, jax.ShapeDtypeStruct((4,), np.float32), x)).lower(
            np.zeros((4,), np.float32)).as_text()
        rpt = analyze_hlo_text(text, "cb")
        rules = {f.rule for f in rpt.findings}
        assert "hlo-host-callback" in rules

    def test_unexpected_all_gather_raises_finding(self):
        from analytics_zoo_tpu.analysis import analyze_hlo_text
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
        g = jax.jit(shard_map(lambda x: jax.lax.all_gather(x, "d"),
                              mesh=mesh, in_specs=P("d"),
                              out_specs=P(None, "d")))
        rpt = analyze_hlo_text(
            g.lower(np.zeros((8,), np.float32)).as_text(), "ag")
        assert "hlo-all-gather" in {f.rule for f in rpt.findings}
        assert rpt.collectives.get("all_gather") == 1

    def test_large_baked_constant_raises_finding(self):
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        big = np.arange(1024 * 300, dtype=np.float32).reshape(1024, 300)
        text = jax.jit(lambda x: x + big).lower(
            np.zeros((1024, 300), np.float32)).as_text()
        rpt = analyze_hlo_text(text, "const")
        consts = [f for f in rpt.findings
                  if f.rule == "hlo-large-constant"]
        assert consts and consts[0].data["bytes"] == big.nbytes

    def test_splat_constant_not_flagged(self):
        """A big SPLAT constant (dense<0.0> broadcast) is cheap — only
        non-splat literals are 'baked arrays'."""
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        import jax.numpy as jnp
        text = jax.jit(
            lambda x: x + jnp.zeros((2048, 2048), jnp.float32)).lower(
            np.zeros((2048, 2048), np.float32)).as_text()
        rpt = analyze_hlo_text(text, "splat")
        assert "hlo-large-constant" not in {f.rule for f in rpt.findings}

    def test_scan_counts_fused_dispatch(self):
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        text = jax.jit(lambda c, xs: jax.lax.scan(
            lambda c, x: (c @ x, c.sum()), c, xs)).lower(
            np.zeros((3, 3), np.float32),
            np.zeros((5, 3, 3), np.float32)).as_text()
        rpt = analyze_hlo_text(text, "scan")
        assert rpt.fused_dispatch_count == 1
        # dot in the (outlined) body counted ONCE: static graph features
        assert rpt.matmul_flops == 2 * 3 * 3 * 3


# ---------------------------------------------------------------------------
# Tier 2 wiring: the timed_compile hook -> metrics / flight / report.
# ---------------------------------------------------------------------------


@pytest.fixture()
def fresh_telemetry():
    from analytics_zoo_tpu.metrics import (
        FlightRecorder,
        MetricsRegistry,
        set_flight_recorder,
        set_registry,
    )

    reg, flight = MetricsRegistry(), FlightRecorder()
    prev_reg = set_registry(reg)
    prev_flight = set_flight_recorder(flight)
    yield reg, flight
    set_registry(prev_reg)
    set_flight_recorder(prev_flight)


def _gauge_value(reg, name, label):
    for fam in reg.collect():
        if fam.name == name:
            for labels, child in fam.samples():
                if labels.get("label") == label:
                    return child.get()
    raise AssertionError(f"{name}{{label={label}}} not found")


class TestTimedCompileHook:
    def test_emits_metrics_flight_and_report(self, fresh_telemetry,
                                             tmp_path, monkeypatch):
        """timed_compile of a known matmul emits zoo_hlo_flops matching
        the 2*M*K*N hand count, records the hlo_lint flight event, and
        writes the per-compile JSON report."""
        from analytics_zoo_tpu.common.compile_cache import timed_compile

        reg, flight = fresh_telemetry
        monkeypatch.setenv("ZOO_HLO_REPORT_DIR", str(tmp_path))
        lowered = jax.jit(lambda a, b: a @ b).lower(
            np.zeros((8, 16), np.float32),
            np.zeros((16, 4), np.float32))
        timed_compile(lowered, "hlo_gate_test")

        assert _gauge_value(reg, "zoo_hlo_flops",
                            "hlo_gate_test") == 2 * 8 * 16 * 4
        assert _gauge_value(reg, "zoo_hlo_collective_bytes",
                            "hlo_gate_test") == 0
        assert _gauge_value(reg, "zoo_hlo_findings", "hlo_gate_test") == 0

        # the flight ring answers "what was compiled" after a crash
        evs = flight.events("hlo_lint")
        assert len(evs) == 1
        assert evs[0]["label"] == "hlo_gate_test"
        assert evs[0]["matmul_flops"] == 2 * 8 * 16 * 4
        assert evs[0]["findings"] == []

        # the JSON report (schema zoo-hlo-report/2: v1 payload plus
        # compile/config context — compile_seconds is stamped by the
        # timed_compile hook, the rest when the caller provides them)
        reports = [f for f in os.listdir(tmp_path)
                   if f.startswith("hlo-hlo_gate_test")]
        assert len(reports) == 1
        with open(tmp_path / reports[0]) as f:
            doc = json.load(f)
        assert doc["schema"] == "zoo-hlo-report/2"
        assert doc["compile_seconds"] is None or \
            doc["compile_seconds"] >= 0
        assert doc["features"]["matmul_flops"] == 2 * 8 * 16 * 4
        assert doc["findings"] == []

    def test_disabled_by_env(self, fresh_telemetry, monkeypatch):
        from analytics_zoo_tpu.common.compile_cache import timed_compile

        reg, flight = fresh_telemetry
        monkeypatch.setenv("ZOO_HLO_LINT", "0")
        lowered = jax.jit(lambda a: a + 1).lower(
            np.zeros((4,), np.float32))
        timed_compile(lowered, "hlo_disabled")
        assert not flight.events("hlo_lint")
        assert not any(fam.name.startswith("zoo_hlo")
                       for fam in reg.collect())

    def test_varz_surface(self, fresh_telemetry):
        """The zoo_hlo_* family rides the standard snapshot path, so
        /varz and /metrics expose it without extra wiring."""
        from analytics_zoo_tpu.analysis.hlo import lint_lowered
        from analytics_zoo_tpu.metrics import prometheus_text, snapshot

        reg, _ = fresh_telemetry
        lowered = jax.jit(lambda a, b: a @ b).lower(
            np.zeros((2, 3), np.float32), np.zeros((3, 2), np.float32))
        lint_lowered(lowered, "varz_probe")
        names = {s["name"] for s in snapshot(reg)["samples"]}
        assert "zoo_hlo_flops" in names
        assert 'zoo_hlo_flops{label="varz_probe"}' in prometheus_text(reg)


class TestFusedTrainStepAcceptance:
    @pytest.fixture(autouse=True)
    def _reset_compile_cache(self):
        from analytics_zoo_tpu.common import compile_cache

        yield
        # the warmup below enables the persistent cache at a tmp dir:
        # turn it back off so later tests don't compile into a deleted
        # directory
        compile_cache.disable_persistent_cache()

    def test_fused_train_step_flops_match_hand_count(
            self, fresh_telemetry, tmp_path, monkeypatch):
        """Acceptance: timed_compile of the FUSED train step (scan-K)
        emits zoo_hlo_flops/zoo_hlo_collective_bytes whose matmul-FLOPs
        value matches the analytic hand count for the test model.

        Model: one Dense(8 -> 4), no bias-matmul, MSE, batch 32.
        Matmuls per step: forward x@W = 2*B*I*O, grad dW = x^T@dy =
        2*I*O*B (dx is pruned — x is not differentiated).  Hand count =
        4*B*I*O = 4096.  The scan-K body is the SAME one_step closure,
        outlined once, so the fused program's static matmul FLOPs equal
        the K=1 program's."""
        import analytics_zoo_tpu as az
        from analytics_zoo_tpu.common.engine import ZooConfig
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

        reg, flight = fresh_telemetry
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("ZOO_COMPILE_CACHE", str(tmp_path / "cc"))
        az.init_zoo_context(ZooConfig(seed=3, mesh_shape={"data": 8},
                                      steps_per_dispatch=2))
        m = Sequential()
        m.add(Dense(4, input_shape=(8,)))
        m.compile(optimizer="sgd", loss="mse")
        est = m._make_estimator()
        batch = {
            "x": np.random.default_rng(0).normal(
                size=(32, 8)).astype(np.float32),
            "y": np.zeros((32, 4), np.float32),
        }
        est.warmup(batch, steps_per_dispatch=2)

        hand_count = 4 * 32 * 8 * 4  # fwd 2BIO + dW 2BIO
        assert _gauge_value(reg, "zoo_hlo_flops",
                            "train_step") == hand_count
        assert _gauge_value(reg, "zoo_hlo_flops",
                            "train_step_scan2") == hand_count
        # GSPMD inserts the gradient all-reduce AFTER lowering, so the
        # pre-partitioning module text carries no explicit collectives
        assert _gauge_value(reg, "zoo_hlo_collective_bytes",
                            "train_step_scan2") == 0
        # the fused program is one lax.scan = one while loop
        assert _gauge_value(reg, "zoo_hlo_fused_dispatches",
                            "train_step_scan2") == 1
        assert _gauge_value(reg, "zoo_hlo_fused_dispatches",
                            "train_step") == 0
        # flight carries one hlo_lint verdict per compiled program
        labels = [e["label"] for e in flight.events("hlo_lint")]
        assert "train_step" in labels and "train_step_scan2" in labels
