"""Kernel plane (ISSUE 19): ``kernel_rules`` as the FIFTH rule table on
:class:`ShardingPlan` and the hand-tuned Pallas kernels behind it —
``fused_adam`` (single HBM round trip per optimizer step),
``fused_softmax_xent`` (no (B, V) prob tensor in HBM), ``int8_matmul``
(weight-stationary int8) plus the flash wiring.

The core claims pinned here:

- every kernel's jnp fallback IS the numerical oracle: CPU runs it
  automatically, ``ZOO_KERNEL_INTERPRET=1`` forces the Pallas path in
  interpret mode and it agrees with the fallback within the recorded
  tolerance (fused_adam's fallback is BITWISE ``optax.adam``);
- an all-``"xla"`` kernel table is a true no-op — the training
  trajectory is bit-identical to a plan with no table at all;
- ``kernel_rules`` participate in the plan cache key and the
  ``+kernels`` name suffix round-trips through ``resolve_plan``;
- without ``ZOO_USE_PALLAS`` no kernel module is ever imported (the
  plane costs nothing when off); with it, the estimator swaps the
  optimizer/loss and the trajectory stays finite on CPU via fallbacks;
- eager kernels lower through the choke point under ``kernel_<name>``
  labels: a second process over a shared ``ZOO_COMPILE_CACHE``
  warm-starts every label with zero misses.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_NEW_KERNEL_MODULES = (
    "analytics_zoo_tpu.ops.pallas.fused_adam",
    "analytics_zoo_tpu.ops.pallas.fused_softmax_xent",
    "analytics_zoo_tpu.ops.pallas.int8_matmul",
)


# ---------------------------------------------------------------------------
# Rule table / plan vocabulary units
# ---------------------------------------------------------------------------


class TestKernelRules:
    def test_invalid_kernel_raises_at_construction(self):
        from analytics_zoo_tpu.parallel.plan import ShardingPlan

        with pytest.raises(ValueError, match="kernel"):
            ShardingPlan(name="t", kernel_rules=((".*", "turbo"),))

    def test_cache_key_participation_and_arity(self):
        from analytics_zoo_tpu.parallel.plan import (
            data_parallel,
            with_kernels,
        )

        dp = data_parallel()
        wk = with_kernels(dp)
        assert dp.cache_key() != wk.cache_key()
        # the five rule tables + the scalar knobs: the key grew when the
        # kernel table landed — pin the arity so a silently-dropped
        # table can't alias two different programs
        assert len(wk.cache_key()) == 11
        # per-scope tables differ too
        xla_only = with_kernels(dp, rules=((".*", "xla"),))
        assert xla_only.cache_key() != wk.cache_key()

    def test_name_suffix_round_trips_through_resolve_plan(self):
        from analytics_zoo_tpu.parallel.plan import (
            DEFAULT_KERNEL_RULES,
            resolve_plan,
            with_kernels,
        )

        p = resolve_plan("dp+kernels")
        assert p.name == "dp+kernels"
        assert p.kernel_rules == with_kernels("dp").kernel_rules
        assert [k for _, k in p.kernel_rules] \
            == [k for _, k in DEFAULT_KERNEL_RULES]
        # +kernels stacks LAST — after overlap and the dtype role
        q = resolve_plan("zero1+bf16+kernels")
        assert q.name == "zero1+bf16+kernels"
        assert q.dtype_rules == ((".*", "bf16"),)
        assert len(q.kernel_rules) == len(DEFAULT_KERNEL_RULES)
        # idempotent: with_kernels on a +kernels plan keeps one suffix
        assert with_kernels(q).name == "zero1+bf16+kernels"

    def test_kernel_policy_str_and_first_match_wins(self):
        from analytics_zoo_tpu.parallel.plan import ShardingPlan

        plan = ShardingPlan(
            name="t",
            kernel_rules=((r"^attention$", "xla"), (r".*", "flash")))
        assert plan.kernel_for("attention") == "xla"
        assert plan.kernel_for("anything.else") == "flash"
        assert "attention" in plan.kernel_policy_str()
        empty = ShardingPlan(name="e")
        assert empty.kernel_policy_str() == ""
        assert empty.kernel_for("attention") is None
        assert empty.kernel_for("attention", default="xla") == "xla"

    def test_resolve_kernel_consults_active_plan(self):
        from analytics_zoo_tpu.parallel.plan import (
            ShardingPlan,
            _active_plan,
            resolve_kernel,
        )

        # no active plan: the consumer's own default applies
        assert resolve_kernel("optimizer.adam") is None
        assert resolve_kernel("attention", default="flash") == "flash"
        plan = ShardingPlan(
            name="t",
            kernel_rules=((r"^optimizer\.adam$", "fused_adam"),
                          (r"^attention$", "xla")))
        with _active_plan(plan):
            assert resolve_kernel("optimizer.adam") == "fused_adam"
            # "xla" is an explicit pick, not a fall-through
            assert resolve_kernel("attention", default="flash") == "xla"
            # unmatched scope falls back to the default
            assert resolve_kernel("loss.softmax_xent") is None

    def test_env_knobs(self, monkeypatch):
        from analytics_zoo_tpu.common.engine import ZooConfig

        monkeypatch.delenv("ZOO_USE_PALLAS", raising=False)
        assert ZooConfig().use_pallas is False
        monkeypatch.setenv("ZOO_USE_PALLAS", "1")
        assert ZooConfig().use_pallas is True
        # the plan env accepts the +kernels suffix (validated eagerly)
        monkeypatch.setenv("ZOO_SHARDING_PLAN", "zero1+bf16+kernels")
        assert ZooConfig().sharding_plan == "zero1+bf16+kernels"
        monkeypatch.setenv("ZOO_SHARDING_PLAN", "zero1+kernelz")
        with pytest.raises(ValueError, match="ZOO_SHARDING_PLAN"):
            ZooConfig()


# ---------------------------------------------------------------------------
# Numerical parity: fallback oracle vs interpret-mode Pallas path
# ---------------------------------------------------------------------------


def _adam_steps(tx, params, grads_seq):
    state = tx.init(params)
    out = []
    for g in grads_seq:
        updates, state = tx.update(g, state, params)
        params = optax.apply_updates(params, updates)
        out.append(params)
    return out, state


def _grad_tree(rng, params):
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(
            rng.normal(size=p.shape).astype(np.float32)), params)


class TestKernelParity:
    def test_fused_adam_fallback_bitwise_vs_optax(self, monkeypatch):
        from analytics_zoo_tpu.ops.pallas import fused_adam as fa

        monkeypatch.delenv("ZOO_KERNEL_INTERPRET", raising=False)
        monkeypatch.delenv("ZOO_KERNEL_FORCE_PALLAS", raising=False)
        params = {"w": jnp.zeros((32, 16), jnp.float32),
                  "b": jnp.zeros((5,), jnp.float32)}
        rng = np.random.default_rng(0)
        grads = [_grad_tree(rng, params) for _ in range(3)]
        before = dict(fa.invocation_counts)
        ours, st = _adam_steps(fa.fused_adam(1e-3), params, grads)
        ref, st_ref = _adam_steps(optax.adam(1e-3), params, grads)
        assert fa.invocation_counts["fallback"] > before["fallback"]
        for a, b in zip(jax.tree_util.tree_leaves((ours, st)),
                        jax.tree_util.tree_leaves((ref, st_ref))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fused_adam_interpret_parity_and_schedule(self, monkeypatch):
        from analytics_zoo_tpu.ops.pallas import fused_adam as fa

        monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
        sched = optax.exponential_decay(1e-3, 10, 0.9)
        params = {"w": jnp.ones((64,), jnp.float32) * 0.5,
                  "b": jnp.ones((3, 7), jnp.float32)}
        rng = np.random.default_rng(1)
        grads = [_grad_tree(rng, params) for _ in range(3)]
        before = dict(fa.invocation_counts)
        ours, st = _adam_steps(fa.fused_adam(sched), params, grads)
        assert fa.invocation_counts["pallas"] > before["pallas"]
        monkeypatch.delenv("ZOO_KERNEL_INTERPRET")
        ref, st_ref = _adam_steps(optax.adam(sched), params, grads)
        for a, b in zip(jax.tree_util.tree_leaves((ours, st)),
                        jax.tree_util.tree_leaves((ref, st_ref))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)

    def test_softmax_xent_interpret_fwd_and_grad(self, monkeypatch):
        from analytics_zoo_tpu.ops.pallas import fused_softmax_xent as fx

        rng = np.random.default_rng(2)
        logits = jnp.asarray(
            rng.normal(size=(16, 384)).astype(np.float32) * 4.0)
        labels = jnp.asarray(
            rng.integers(0, 384, size=(16,)).astype(np.int32))

        def mean_loss(lg):
            return fx.softmax_xent(lg, labels).mean()

        monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
        before = dict(fx.invocation_counts)
        loss = fx.softmax_xent(logits, labels)
        grad = jax.grad(mean_loss)(logits)
        assert fx.invocation_counts["pallas"] > before["pallas"]
        monkeypatch.delenv("ZOO_KERNEL_INTERPRET")
        ref = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels)
        ref_grad = jax.grad(
            lambda lg: optax.softmax_cross_entropy_with_integer_labels(
                lg, labels).mean())(logits)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad),
                                   atol=1e-6, rtol=1e-5)

    def test_int8_matmul_interpret_parity(self, monkeypatch):
        from analytics_zoo_tpu.ops.pallas import int8_matmul as im

        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))
        w = jnp.asarray(
            rng.integers(-127, 128, size=(128, 64)).astype(np.int8))
        scale = jnp.asarray(
            rng.uniform(0.005, 0.02, size=(64,)).astype(np.float32))
        monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
        before = dict(im.invocation_counts)
        out = im.int8_matmul(x, w, scale)
        assert im.invocation_counts["pallas"] > before["pallas"]
        monkeypatch.delenv("ZOO_KERNEL_INTERPRET")
        ref = im._reference(x, w, scale)
        denom = float(np.linalg.norm(np.asarray(ref))) or 1.0
        rel = float(
            np.linalg.norm(np.asarray(out) - np.asarray(ref))) / denom
        assert rel < 1e-4, rel
        assert out.dtype == x.dtype


# ---------------------------------------------------------------------------
# Flash wiring: kernel_rules drive attention routing, composed with bf16
# ---------------------------------------------------------------------------


class TestFlashCompose:
    def test_attention_rule_routes_flash_and_xla(self, monkeypatch):
        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.ops.pallas import flash_attention as fl
        from analytics_zoo_tpu.parallel.plan import (
            _active_plan,
            data_parallel,
            with_dtype,
            with_kernels,
        )

        monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
        rng = np.random.default_rng(4)
        q, k, v = (jnp.asarray(
            rng.normal(size=(1, 2, 256, 64)).astype(np.float32) * 0.1)
            for _ in range(3))

        # bf16 dtype_rules + flash kernel_rules compose on one plan
        plan = with_kernels(with_dtype(data_parallel(), "bf16"),
                            rules=((r"^attention$", "flash"),))
        assert plan.name == "dp+bf16+kernels"
        assert plan.dtype_rules == ((".*", "bf16"),)
        before = dict(fl.invocation_counts)
        with _active_plan(plan):
            out_flash = dot_product_attention(q, k, v)
        assert fl.invocation_counts["pallas"] > before["pallas"]

        # the explicit "xla" pick pins the dense jnp path
        xla_plan = with_kernels(data_parallel(),
                                rules=((r"^attention$", "xla"),))
        before = dict(fl.invocation_counts)
        with _active_plan(xla_plan):
            out_xla = dot_product_attention(q, k, v)
        assert fl.invocation_counts["pallas"] == before["pallas"]
        np.testing.assert_allclose(np.asarray(out_flash),
                                   np.asarray(out_xla),
                                   atol=2e-3, rtol=2e-2)


# ---------------------------------------------------------------------------
# Training: all-"xla" table is bit-identical to no table at all
# ---------------------------------------------------------------------------


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(8, 4))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return x, y


def _fit(mesh_size, epochs, plan=None):
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    zoo.init_zoo_context(seed=3, mesh_shape={"data": mesh_size})
    x, y = _data()
    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    m.fit(x, y, batch_size=32, nb_epoch=epochs, plan=plan)
    return m


def test_all_xla_table_trajectory_bit_identical():
    """kernel_rules mapping every scope to "xla" must be a pure no-op:
    the estimator sees a different plan name/cache key, but every
    consumer takes the identical XLA path — so the losses are BITWISE
    equal to a plan with no kernel table."""
    from analytics_zoo_tpu.parallel.plan import data_parallel, with_kernels

    base = _fit(2, 2)
    xla = _fit(2, 2, plan=with_kernels(data_parallel(),
                                       rules=((r".*", "xla"),)))
    l_base = [h["loss"] for h in base._estimator.history]
    l_xla = [h["loss"] for h in xla._estimator.history]
    assert l_base == l_xla, (l_base, l_xla)
    assert xla._estimator._plan_record["name"] == "dp+kernels"


# ---------------------------------------------------------------------------
# Subprocess pins: import hygiene, end-to-end knob, cache warm start
# ---------------------------------------------------------------------------


def _run_child(script, env_overrides=None, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ("ZOO_USE_PALLAS", "ZOO_SHARDING_PLAN", "ZOO_COMPILE_CACHE",
              "JAX_COMPILATION_CACHE_DIR",
              "ZOO_KERNEL_INTERPRET", "ZOO_KERNEL_FORCE_PALLAS"):
        env.pop(k, None)
    env.update(env_overrides or {})
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


_FIT_CHILD = r"""
import json
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

zoo.init_zoo_context(seed=3, mesh_shape={"data": 2})
rng = np.random.default_rng(0)
x = rng.normal(size=(64, 8)).astype(np.float32)
y = rng.integers(0, 4, size=(64,)).astype(np.int32)
m = Sequential()
m.add(Dense(16, activation="relu", input_shape=(8,)))
m.add(Dense(4))
m.compile(optimizer="adam",
          loss="sparse_categorical_crossentropy_from_logits")
m.fit(x, y, batch_size=32, nb_epoch=1)

from analytics_zoo_tpu.ops.pallas import kernel_invocation_counts

out = {
    "modules": sorted(n for n in sys.modules
                      if n.startswith("analytics_zoo_tpu.ops.pallas.")),
    "plan": m._estimator._plan_record["name"],
    "losses": [float(h["loss"]) for h in m._estimator.history],
    "counts": kernel_invocation_counts(),
}
print("RESULT " + json.dumps(out))
"""


def test_no_use_pallas_imports_no_kernel_module():
    """The negative pin: a plain fit without ZOO_USE_PALLAS never
    imports a kernel module — the plane is free when off."""
    out = _run_child(_FIT_CHILD)
    for mod in _NEW_KERNEL_MODULES:
        assert mod not in out["modules"], out["modules"]
    assert not out["plan"].endswith("+kernels"), out["plan"]


def test_use_pallas_fit_swaps_consumers_and_stays_finite():
    """ZOO_USE_PALLAS=1 end to end on CPU: the resolved plan carries
    the kernel table, the estimator swap imports fused_adam and the
    loss routes through fused_softmax_xent — and every invocation takes
    the fallback (CPU has no Mosaic), so training just works."""
    out = _run_child(_FIT_CHILD, {"ZOO_USE_PALLAS": "1"})
    assert out["plan"].endswith("+kernels"), out["plan"]
    assert "analytics_zoo_tpu.ops.pallas.fused_adam" in out["modules"]
    assert "analytics_zoo_tpu.ops.pallas.fused_softmax_xent" \
        in out["modules"]
    assert all(np.isfinite(v) for v in out["losses"]), out["losses"]
    counts = out["counts"]
    assert counts["fused_adam"]["fallback"] > 0, counts
    assert counts["fused_adam"]["pallas"] == 0, counts


_KERNEL_WARM_CHILD = r"""
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.metrics import get_registry, snapshot
from analytics_zoo_tpu.ops.pallas import kernel_step
import analytics_zoo_tpu.ops.pallas.fused_adam as fa
import analytics_zoo_tpu.ops.pallas.fused_softmax_xent as fx
import analytics_zoo_tpu.ops.pallas.int8_matmul as im

zoo.init_zoo_context(seed=0)
rng = np.random.default_rng(0)

g = jnp.asarray(rng.normal(size=(512,)).astype(np.float32))
zeros = jnp.zeros((512,), jnp.float32)
scal = jnp.asarray([1e-3, 0.9, 0.999, 1e-8, 0.1, 0.001], jnp.float32)
kernel_step("fused_adam", fa._adam_leaf_reference)(g, zeros, zeros, scal)

logits = jnp.asarray(rng.normal(size=(32, 128)).astype(np.float32))
labels = jnp.asarray(rng.integers(0, 128, size=(32,)).astype(np.int32))
kernel_step("fused_softmax_xent", fx._reference_fwd)(logits, labels)

x = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
w = jnp.asarray(rng.integers(-127, 128, size=(64, 32)).astype(np.int8))
s = jnp.full((32,), 0.02, jnp.float32)
kernel_step("int8_matmul", im._reference)(x, w, s)

out = {"hits": {}, "misses": {}}
for smp in snapshot(get_registry())["samples"]:
    lab = smp.get("labels", {}).get("label", "")
    if not lab.startswith("kernel_"):
        continue
    if smp["name"] == "zoo_compile_cache_hits_total":
        out["hits"][lab] = out["hits"].get(lab, 0) + smp["value"]
    elif smp["name"] == "zoo_compile_cache_misses_total":
        out["misses"][lab] = out["misses"].get(lab, 0) + smp["value"]
print("RESULT " + json.dumps(out))
"""


def test_kernel_labels_warm_start_from_shared_cache(tmp_path):
    """Eager kernels compile through the choke point under their own
    kernel_<name> labels, so a second process over the same
    ZOO_COMPILE_CACHE warm-starts EVERY kernel label: zero misses."""
    cache = str(tmp_path / "cc")
    labels = {"kernel_fused_adam", "kernel_fused_softmax_xent",
              "kernel_int8_matmul"}
    cold = _run_child(_KERNEL_WARM_CHILD, {"ZOO_COMPILE_CACHE": cache})
    assert set(cold["misses"]) >= labels, cold
    for lab in labels:
        assert cold["misses"][lab] > 0, cold
        assert cold["hits"].get(lab, 0) == 0, cold
    warm = _run_child(_KERNEL_WARM_CHILD, {"ZOO_COMPILE_CACHE": cache})
    for lab in labels:
        assert warm["misses"].get(lab, 0) == 0, warm
        assert warm["hits"][lab] == cold["misses"][lab], (cold, warm)


# ---------------------------------------------------------------------------
# Cost model + oracle: analytic byte terms and the per-platform verdict
# ---------------------------------------------------------------------------


class TestKernelCostModel:
    def test_byte_models_match_verified_lowerings(self):
        """Pin the analytic formulas at the sizes the cross-lowering
        test below measures."""
        from analytics_zoo_tpu.analysis.costmodel import kernel_bytes

        assert kernel_bytes("fused_adam", n=4096)["kernel"] \
            == 24 * 4096 + 24
        assert kernel_bytes(
            "fused_softmax_xent", batch=128, vocab=2048)["kernel"] \
            == 4 * 128 * 2048 + 12 * 128
        assert kernel_bytes("int8_matmul", m=128, k=256, n=128)["kernel"] \
            == 4 * 128 * 256 + 256 * 128 + 4 * 128 + 4 * 128 * 128
        # and each kernel beats its XLA twin at realistic sizes
        for name, sizes in (
                ("fused_adam", {"n": 1 << 20}),
                ("fused_softmax_xent", {"batch": 256, "vocab": 32000}),
                ("int8_matmul", {"m": 128, "k": 4096, "n": 4096}),
                ("flash", {"batch": 8, "heads": 12, "seq": 2048,
                           "head_dim": 64})):
            b = kernel_bytes(name, **sizes)
            assert b["kernel"] < b["xla"], (name, b)
        with pytest.raises(ValueError, match="unknown kernel"):
            kernel_bytes("turbo", n=1)

    def test_choose_kernel_declines_on_cpu_picks_on_tpu(self):
        from analytics_zoo_tpu.analysis.costmodel import choose_kernel

        sizes = {"n": 1 << 20}
        cpu = choose_kernel("fused_adam", platform="cpu", **sizes)
        assert cpu["choice"] == "xla"
        tpu = choose_kernel("fused_adam", platform="tpu-v4", **sizes)
        assert tpu["choice"] == "fused_adam"
        # a size where the byte model predicts no win declines even
        # on TPU: flash at tiny L (the O(L²) term is negligible)
        small = choose_kernel("flash", platform="tpu-v4", batch=1,
                              heads=1, seq=0, head_dim=64)
        assert small["choice"] == "xla"

    def test_choose_plan_kernel_sweep(self):
        from analytics_zoo_tpu.analysis.costmodel import PeakTable
        from analytics_zoo_tpu.analysis.oracle import ConfigOracle

        feats = {"matmul_flops": 1e13, "bytes_accessed": 1e9}
        kwargs = dict(features=feats, activation_bytes=1 << 30)
        tpu = ConfigOracle(peaks=PeakTable(
            flops=1e12, hbm_bytes_per_s=1e11, link_bytes_per_s=1e10,
            dispatch_overhead_s=1e-5, hbm_bytes=64 << 30,
            source="tpu-test"))
        # default: no kernel options — the old candidate space exactly
        name, doc = tpu.choose_plan(1 << 30, 2 << 30, 8, **kwargs)
        assert doc.get("chosen_kernels") is None
        assert not any("+kernels" in c["config"]
                       for c in doc["candidates"])
        # swept on TPU peaks: the kernel variant wins the step factor
        name2, doc2 = tpu.choose_plan(
            1 << 30, 2 << 30, 8, kernel_options=(None, "kernels"),
            **kwargs)
        assert doc2["chosen_kernels"] == "kernels"
        assert doc2["chosen_config"].endswith("+kernels")
        # swept on CPU peaks: the factor is 1.0 and the tie breaks to
        # the plain candidate — the oracle DECLINES pallas off-TPU
        cpu = ConfigOracle(peaks=PeakTable(
            flops=1e12, hbm_bytes_per_s=1e11, link_bytes_per_s=1e10,
            dispatch_overhead_s=1e-5, hbm_bytes=64 << 30, source="cpu"))
        name3, doc3 = cpu.choose_plan(
            1 << 30, 2 << 30, 8, kernel_options=(None, "kernels"),
            **kwargs)
        assert doc3["chosen_kernels"] is None, doc3["chosen_config"]

    def test_choose_kernels_logs_to_prediction_plane(self):
        from analytics_zoo_tpu.analysis.costmodel import resolve_peaks
        from analytics_zoo_tpu.analysis.oracle import ConfigOracle

        oracle = ConfigOracle(peaks=resolve_peaks("cpu"))
        verdicts = oracle.choose_kernels(
            {"fused_adam": {"n": 1 << 20}}, platform="cpu")
        assert verdicts["fused_adam"]["choice"] == "xla"
        rows = [r for r in oracle.prediction_log()
                if r["consumer"] == "kernel_plane"]
        assert rows and rows[-1]["config"] == "kernel=fused_adam"

    def test_cross_lowered_bytes_match_the_model_and_cpu_declines(self):
        """Each Pallas variant is cross-lowered for TPU with no chip
        (``lower(lowering_platforms=("tpu",))``), ``lint_lowered``
        attributes the ``tpu_custom_call``'s operand and result bytes,
        and the measured number sits within 5% of ``kernel_bytes``'
        prediction.  The oracle over the same sizes declines every
        kernel on the CPU (Pallas lowers via Mosaic) and picks by the
        byte model under the tpu-v4 peaks."""
        from analytics_zoo_tpu.analysis.costmodel import (
            kernel_bytes,
            resolve_peaks,
        )
        from analytics_zoo_tpu.analysis.hlo import lint_lowered
        from analytics_zoo_tpu.analysis.oracle import ConfigOracle
        from analytics_zoo_tpu.ops.pallas import fused_adam as fa
        from analytics_zoo_tpu.ops.pallas import fused_softmax_xent as fx
        from analytics_zoo_tpu.ops.pallas import int8_matmul as im
        from analytics_zoo_tpu.ops.pallas import record_kernel_bytes

        rng = np.random.default_rng(11)
        g1 = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
        scal = jnp.asarray([1e-3, 0.9, 0.999, 1e-8, 0.1, 0.001],
                           jnp.float32)
        logits = jnp.asarray(rng.normal(size=(128, 2048)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 2048, size=(128,)), jnp.int32)
        x8 = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
        w8 = jnp.asarray(rng.integers(-127, 128, size=(256, 128)), jnp.int8)
        s8 = jnp.asarray(rng.uniform(0.01, 0.1, size=(128,)), jnp.float32)
        sizes = {
            "fused_adam": {"n": 4096},
            "fused_softmax_xent": {"batch": 128, "vocab": 2048},
            "int8_matmul": {"m": 128, "k": 256, "n": 128},
        }
        lowerings = {
            "fused_adam": (
                lambda g, m, n, s: fa._adam_leaf_pallas(g, m, n, s, False),
                (g1, g1 * 0, g1 * 0 + 1e-4, scal)),
            "fused_softmax_xent": (
                lambda x, l: fx._fwd_pallas(x, l, False), (logits, labels)),
            "int8_matmul": (
                lambda x, w, s: im._matmul_pallas(x, w, s, False),
                (x8, w8, s8)),
        }
        for name, (fn, args) in lowerings.items():
            lowered = jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",))
            rpt = lint_lowered(lowered, label=f"kernel_{name}_tpu")
            assert rpt.custom_kernel_count >= 1, name
            doc = record_kernel_bytes(
                f"kernel_{name}", int(rpt.custom_kernel_bytes),
                predicted_bytes=int(kernel_bytes(name, **sizes[name])
                                    ["kernel"]))
            assert doc["rel_error"] <= 0.05, (name, doc)

        sizes["flash"] = {"batch": 8, "heads": 12, "seq": 512,
                          "head_dim": 64}
        cpu = ConfigOracle(peaks=resolve_peaks("cpu")).choose_kernels(
            sizes, platform="cpu")
        assert all(v["choice"] == "xla" for v in cpu.values()), cpu
        tpu = ConfigOracle(peaks=resolve_peaks("tpu-v4")).choose_kernels(
            sizes, platform="tpu-v4")
        assert tpu["fused_adam"]["choice"] == "fused_adam"
