"""The complete memory plan (ISSUE 14): ZeRO-2/3 as sharding-plan
rules, the remat policy as plan rules resolved at trace time, and
pipeline schedules lowered through the compile choke point.

Acceptance: zero3 holds <= 0.25x replicated-DP per-chip param+opt bytes
at a bit-identical (or recorded-ulp) loss trajectory; a model whose
plan="dp" footprint exceeds the configured HBM budget trains under the
fit(plan="auto") oracle choice; every pipeline schedule compiles
through compile_step/timed_compile with zoo_hlo_* features and a
persistent-cache warm hit from a second process.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _data(n=512, feat=32, classes=10, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, feat)).astype(np.float32)
    y = np.argmax(x @ rng.normal(size=(feat, classes)),
                  axis=1).astype(np.int32)
    return x, y


def _model(width=256, feat=32, classes=10):
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    m = Sequential()
    m.add(Dense(width, activation="relu", input_shape=(feat,)))
    m.add(Dense(width, activation="relu"))
    m.add(Dense(classes, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    return m


def _fit(plan, epochs=2, width=256, seed=11):
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.parallel.plan import per_chip_bytes

    zoo.init_zoo_context(seed=seed, mesh_shape={"data": 8})
    x, y = _data()
    m = _model(width=width)
    m.fit(x, y, batch_size=64, nb_epoch=epochs, plan=plan)
    est = m._estimator
    losses = [h["loss"] for h in est.history]
    chip = per_chip_bytes((m.params, est._opt_state))
    return m, losses, chip


# ---------------------------------------------------------------------------
# ZeRO-2/3 as plan rules
# ---------------------------------------------------------------------------


class TestZeroPlanRules:
    def test_zero2_zero3_rule_tables(self):
        from analytics_zoo_tpu.parallel import plan as zp

        z2, z3 = zp.zero2(), zp.zero3()
        # zero2 = zero1's persistent layout + grads reduce-scattered
        assert not z2.shards_params and z2.shards_opt
        assert z2.grad_rules == ((r".*", P("data")),)
        # zero3 shards everything: params, opt state and the grad tree
        assert z3.shards_params and z3.shards_opt
        assert z3.grad_rules == ((r".*", P("data")),)
        assert zp.zero1().grad_rules is None
        assert zp.resolve_plan("zero2").name == "zero2"
        assert zp.resolve_plan("zero3").name == "zero3"

    def test_cache_key_carries_memory_fields(self):
        from analytics_zoo_tpu.parallel import plan as zp

        keys = {zp.zero1().cache_key(), zp.zero2().cache_key(),
                zp.zero3().cache_key(), zp.fsdp().cache_key(),
                zp.with_remat(zp.fsdp(), "full").cache_key(),
                zp.with_remat(zp.fsdp(), "dots").cache_key()}
        # grad_rules separate zero1/zero2 and fsdp/zero3; remat_rules
        # separate the rematted variants — six distinct programs
        assert len(keys) == 6

    def test_constrain_grads_shards_in_graph(self):
        from analytics_zoo_tpu.parallel import plan as zp

        mesh = zp.build_mesh({"data": 8})
        grads = {"k": jnp.ones((16, 4)), "ragged": jnp.ones((3, 4)),
                 "scalar": jnp.ones(())}
        out = jax.jit(
            lambda g: zp.zero3().constrain_grads(g, mesh))(grads)
        assert out["k"].sharding.spec == P("data")
        # the clamp discipline rides along: indivisible/0-D replicate
        assert out["ragged"].sharding.spec in (P(), P(None))
        # dp (grad_rules=None) is the identity — no constraint op
        same = zp.data_parallel().constrain_grads(grads, mesh)
        assert same is grads


class TestRematRules:
    def test_apply_remat_policies(self):
        from analytics_zoo_tpu.parallel import plan as zp

        def f(x):
            return jnp.sin(x) * x

        x = jnp.linspace(0.0, 1.0, 8)
        assert zp.apply_remat(f, None) is f
        assert zp.apply_remat(f, "none") is f
        for policy in zp.REMAT_POLICIES:
            g = zp.apply_remat(f, policy)
            np.testing.assert_array_equal(np.asarray(g(x)),
                                          np.asarray(f(x)))
            np.testing.assert_allclose(
                np.asarray(jax.grad(lambda v: jnp.sum(g(v)))(x)),
                np.asarray(jax.grad(lambda v: jnp.sum(f(v)))(x)))
        with pytest.raises(ValueError, match="remat policy"):
            zp.apply_remat(f, "not-a-policy")

    def test_resolve_remat_sees_plan_at_trace_time(self):
        """compile_step enters the plan for the duration of tracing, so
        resolve_remat inside the traced body returns the plan's policy;
        outside any plan it returns the caller's default."""
        from analytics_zoo_tpu.parallel import plan as zp

        zp.build_mesh({"data": 8})
        seen = {}

        def step(x):
            seen["policy"] = zp.resolve_remat("blocks", default="flag")
            return x * 2.0

        assert zp.resolve_remat("blocks", default="flag") == "flag"
        planned = zp.compile_step(
            step, zp.with_remat(zp.data_parallel(), "dots"),
            label="remat_probe_step")
        out = planned(jnp.ones(()))
        assert float(out) == 2.0
        assert seen["policy"] == "dots"
        # pattern must match the path: a non-matching rule falls back
        scoped = zp.with_remat(zp.data_parallel(), "full",
                               pattern=r"decoder")
        zp.compile_step(step, scoped,
                        label="remat_probe_scoped_step")(jnp.ones(()))
        assert seen["policy"] == "flag"

    def test_plan_rule_remat_reaches_the_pipelined_transformer(self):
        """One grad step of a 4-block transformer GPipe'd over
        ``pipe=4``, compiled through ``compile_step`` under a plan whose
        ``remat_rules`` carry the policy: it reaches ``apply_remat`` via
        ``resolve_remat`` inside the stage body at trace time.  The
        rematted step reproduces the un-rematted gradients, and its
        analytic FLOPs show the forward recomputed in the backward."""
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.analysis.hlo import last_features
        from analytics_zoo_tpu.parallel import plan as zp
        from analytics_zoo_tpu.parallel.pipeline import transformer_gpipe
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            TransformerLayer,
        )

        zoo.init_zoo_context(seed=3, mesh_shape={"data": 2, "pipe": 4},
                             mesh_axes=("data", "pipe"), platform="cpu")
        layer = TransformerLayer(vocab=64, seq_len=8, n_block=4, n_head=2,
                                 hidden_size=16, embedding_drop=0.0,
                                 hidden_drop=0.0, attn_drop=0.0)
        params = layer.init_params(jax.random.PRNGKey(0))
        h = jnp.asarray(np.random.default_rng(0).normal(
            size=(8, 8, 16)).astype(np.float32))

        def loss_fn(p, a):
            return jnp.mean(transformer_gpipe(layer, p, a,
                                              n_microbatch=4) ** 2)

        def grad_step(plan, label):
            step = zp.compile_step(jax.value_and_grad(loss_fn), plan,
                                   label=label)
            _, grads = step(params, h)
            return grads, last_features(label)["matmul_flops"]

        g_none, flops_none = grad_step(
            zp.resolve_plan("dp"), "pipeline_gpipe_noremat")
        g_full, flops_full = grad_step(
            zp.with_remat(zp.resolve_plan("dp"), "full"),
            "pipeline_gpipe_remat_full")
        for a, b in zip(jax.tree_util.tree_leaves(g_none),
                        jax.tree_util.tree_leaves(g_full)):
            assert float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) \
                < 1e-6
        assert flops_full > flops_none


# ---------------------------------------------------------------------------
# per-chip memory and trajectory acceptance
# ---------------------------------------------------------------------------


class TestZeroTraining:
    def test_zero3_quarter_memory_at_dp_trajectory(self):
        """The ISSUE 14 pin: zero3 per-chip param+opt bytes <= 0.25x
        replicated DP, loss trajectory bitwise dp's (the gather-on-use
        program computes the same sums in the same order); zero2 holds
        zero1-level persistent state (grads are transient in JAX) with
        the same trajectory."""
        _, dp_losses, dp_chip = _fit("dp")
        _, z3_losses, z3_chip = _fit("zero3")
        _, z2_losses, z2_chip = _fit("zero2")

        assert z3_chip / dp_chip <= 0.25, (z3_chip, dp_chip)
        assert z2_chip / dp_chip <= 0.5, (z2_chip, dp_chip)
        assert z3_losses == dp_losses
        # zero2 groups no reduction differently on this program; any
        # drift would be ulp-level, not a different trajectory
        assert max(abs(a - b)
                   for a, b in zip(z2_losses, dp_losses)) < 1e-6

    def test_zero_mem_gauges_close_the_loop(self):
        """Every planned fit publishes zoo_mem_* gauges: the cost
        model's predict_chip_bytes against the measured placement, with
        small relative error."""
        from analytics_zoo_tpu.metrics import get_registry, snapshot

        _fit("zero3", epochs=1)
        mem = {}
        for s in snapshot(get_registry())["samples"]:
            if s["name"].startswith("zoo_mem_") \
                    and s["labels"].get("label") == "train_step_zero3":
                mem[s["name"]] = s["value"]
        assert mem.get("zoo_mem_predicted_bytes", 0) > 0
        assert mem.get("zoo_mem_live_bytes", 0) > 0
        assert mem["zoo_mem_rel_error"] < 0.05, mem


class TestAutoPlanEscapesOOM:
    def test_model_oom_under_dp_trains_under_auto(self, monkeypatch):
        """A model whose replicated footprint exceeds the configured
        HBM budget: the oracle records dp as infeasible and plan="auto"
        resolves to a sharded (possibly rematted) config that fits —
        and the fit actually trains."""
        import analytics_zoo_tpu as zoo

        # small model: ~20KB params + ~40KB adam state + ~20KB
        # activation estimate; a 15KB budget rules out dp (~80KB) and
        # the zero1/zero2 tiers (replicated params alone exceed it) but
        # admits the param+opt-sharded plans once rematted
        monkeypatch.setenv("ZOO_ORACLE_PEAKS",
                           json.dumps({"hbm_bytes": 15_000}))
        zoo.init_zoo_context(seed=0, mesh_shape={"data": 8})
        x, y = _data(n=128, feat=8, classes=4, seed=0)
        m = _model(width=64, feat=8, classes=4)
        m.fit(x, y, batch_size=32, nb_epoch=2, plan="auto")
        est = m._estimator
        doc = est._auto_plan_record
        by_config = {c["config"]: c for c in doc["candidates"]}
        assert not by_config["plan=dp"]["fits_budget"]
        assert doc["feasible"], doc
        chosen = est._auto_plan
        assert chosen.name.split("+")[0] in ("fsdp", "zero3")
        losses = [h["loss"] for h in est.history]
        assert len(losses) == 2 and np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestChoosePlanRematSweep:
    def test_remat_widens_the_feasible_set(self):
        """A budget no un-rematted candidate fits: the sweep finds a
        plan x remat config that does, charges the recompute in
        predicted step time, and records both axes in the doc."""
        from analytics_zoo_tpu.analysis.costmodel import (
            PLATFORM_PEAKS,
            predict_chip_bytes,
        )
        from analytics_zoo_tpu.analysis.oracle import ConfigOracle

        p, o, n, act = 800_000, 1_600_000, 8, 800_000
        oracle = ConfigOracle(peaks=PLATFORM_PEAKS["cpu"])
        # zero3 without remat: (p+o)/n + act = 1.1M; with remat full:
        # (p+o)/n + 0.15*act = 420K — only the rematted tier fits 500K
        assert predict_chip_bytes(p, o, "zero3", n, activation_bytes=act) \
            > 500_000
        assert predict_chip_bytes(p, o, "zero3", n, activation_bytes=act,
                                  remat="full") <= 500_000
        name, doc = oracle.choose_plan(
            p, o, n, hbm_budget=500_000, activation_bytes=act,
            remat_options=(None, "full"))
        assert doc["feasible"]
        assert doc["chosen_remat"] == "full"
        assert doc["chosen_config"].endswith("+remat_full")
        assert name in ("fsdp", "zero3")
        # un-rematted configs are still in the doc, marked infeasible
        assert any(c["remat"] is None and not c["fits_budget"]
                   for c in doc["candidates"])


# ---------------------------------------------------------------------------
# pipeline schedules through the compile choke point
# ---------------------------------------------------------------------------


_PIPE_CHILD = r"""
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.metrics import get_registry, snapshot
from analytics_zoo_tpu.parallel.pipeline import (
    gpipe, gpipe_hetero, gpipe_1f1b_grads, gpipe_hetero_1f1b_grads,
)

zoo.init_zoo_context(seed=0, mesh_shape={"data": 2, "pipe": 4},
                     mesh_axes=("data", "pipe"))
rng = np.random.default_rng(0)


def stage(p, a):
    return jnp.tanh(a @ p["w"] + p["b"])


def params(v=1):
    return {"w": rng.normal(0, .5, (4 * v, 8, 8)).astype(np.float32),
            "b": rng.normal(0, .1, (4 * v, 8)).astype(np.float32)}


x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
y = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))

def loss(a, y_mb):
    return jnp.mean((a - y_mb) ** 2)

# every schedule called EAGERLY so _run_planned owns the choke point
gpipe(stage, params(), x, n_microbatch=8)
gpipe(stage, params(2), x, n_microbatch=8, circular_repeats=2)
edge = [{"w": rng.normal(0, .5, (8, 8)).astype(np.float32)}
        for _ in range(4)]
fns = [lambda e, s, a: jnp.tanh(a @ e["w"])] * 4
gpipe_hetero(fns, edge, {}, x, n_microbatch=8)
gpipe_1f1b_grads(stage, loss, params(), x, y, n_microbatch=8)
gpipe_hetero_1f1b_grads(fns, edge, {}, x, y, loss, n_microbatch=8)

out = {"hits": 0, "misses": 0, "hlo_flops": {}, "compiled": []}
for s in snapshot(get_registry())["samples"]:
    if s["name"] == "zoo_compile_cache_hits_total":
        out["hits"] += s["value"]
    elif s["name"] == "zoo_compile_cache_misses_total":
        out["misses"] += s["value"]
    elif s["name"] == "zoo_hlo_flops":
        out["hlo_flops"][s["labels"]["label"]] = s["value"]
    elif s["name"] == "zoo_compile_seconds":
        out["compiled"].append(s["labels"]["label"])
print("RESULT " + json.dumps(out))
"""

PIPELINE_LABELS = {
    "pipeline_gpipe_step", "pipeline_gpipe_circular_step",
    "pipeline_gpipe_hetero_step", "pipeline_1f1b_step",
    "pipeline_1f1b_hetero_step",
}


def _run_pipe_child(cache_dir):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        ZOO_COMPILE_CACHE=str(cache_dir),
    )
    env.pop("ZOO_SHARDING_PLAN", None)
    env.pop("ZOO_SHARD_OPTIMIZER", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", _PIPE_CHILD], env=env,
                       cwd=REPO, capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_every_pipeline_schedule_compiles_through_choke_point(tmp_path):
    """GPipe, circular/interleaved, hetero, 1F1B and hetero-1F1B all
    lower through compile_step → timed_compile as pipeline_* plans:
    every schedule label lands in zoo_compile_seconds with nonzero
    zoo_hlo_flops, and a second process over the same ZOO_COMPILE_CACHE
    compiles each as a persistent-cache HIT."""
    cache = tmp_path / "cc"
    cold = _run_pipe_child(cache)
    assert PIPELINE_LABELS <= set(cold["compiled"]), cold["compiled"]
    assert PIPELINE_LABELS <= set(cold["hlo_flops"]), cold["hlo_flops"]
    for label in PIPELINE_LABELS:
        assert cold["hlo_flops"][label] > 0, label
    assert cold["hits"] == 0
    assert cold["misses"] == len(PIPELINE_LABELS)

    warm = _run_pipe_child(cache)
    assert warm["misses"] == 0, warm
    assert warm["hits"] == len(PIPELINE_LABELS)
    assert PIPELINE_LABELS <= set(warm["hlo_flops"])
