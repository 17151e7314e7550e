"""Unified partitioner (parallel/plan.py): ShardingPlan rule tables,
canned plans, the hybrid mesh builder, and compile_step — the ONE
compile choke point every strategy lowers through.

Acceptance (ISSUE 10): every strategy (plain DP, shard_map, zero1,
fsdp, TP) compiles through compile_step → timed_compile — a
second-process warm start over a shared ZOO_COMPILE_CACHE shows cache
hits and zoo_hlo_* features for ALL plans — and the fsdp plan's
per-chip param+opt bytes are <= 0.6x replicated DP at a bit-identical
loss trajectory on the 8-device CPU mesh.
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


def _data(n=256, feat=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, feat)).astype(np.float32)
    y = np.argmax(x @ rng.normal(size=(feat, classes)),
                  axis=1).astype(np.int32)
    return x, y


def _model(feat=8, width=64, depth=1, classes=4):
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    m = Sequential()
    m.add(Dense(width, activation="relu", input_shape=(feat,)))
    for _ in range(depth - 1):
        m.add(Dense(width, activation="relu"))
    m.add(Dense(classes, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    return m


#: the estimator's plans train both: two layers, and three of four times
#: the width
SIZES = {"narrow": dict(feat=8, width=64, depth=1, classes=4),
         "wide": dict(feat=32, width=256, depth=2, classes=10)}


# ---------------------------------------------------------------------------
# ShardingPlan unit behavior
# ---------------------------------------------------------------------------


class TestShardingPlan:
    def test_canned_plans_and_rule_resolution(self):
        from analytics_zoo_tpu.parallel import plan as zp

        dp, fs, z1 = zp.data_parallel(), zp.fsdp(), zp.zero1()
        assert not dp.shards_params and not dp.shards_opt
        assert fs.shards_params and fs.shards_opt
        assert not z1.shards_params and z1.shards_opt
        tp = zp.tensor_parallel([(r"kernel", P(None, "model"))])
        assert tp.shards_params
        # catch-all appended so unmatched leaves replicate, not raise
        assert tp.param_rules[-1][0] == r".*"

    def test_specs_clamped_to_mesh_divisibility(self):
        from analytics_zoo_tpu.parallel import plan as zp

        mesh = zp.build_mesh({"data": 8})
        params = {"k": np.zeros((16, 4)), "ragged": np.zeros((3, 4)),
                  "scalar": np.zeros(())}
        specs = zp.fsdp().param_specs(params, mesh)
        assert specs["k"] == P("data")
        assert specs["ragged"] == P()   # 3 % 8 != 0 -> replicate
        assert specs["scalar"] == P()
        # axis absent from the mesh drops to None instead of erroring
        tp = zp.tensor_parallel([(r"k", P(None, "model"))])
        specs = tp.param_specs(params, mesh)  # mesh has no model axis
        assert specs["k"] == P()

    @pytest.mark.parametrize("shape,want", [
        ((3, 3, 64, 128), P(None, None, "data")),   # conv kernel: Cin
        ((7, 7, 3, 64), P(None, None, None, "data")),  # stem: Cout
        ((12, 32), P(None, "data")),
        ((16, 4), P("data")),                       # dim 0 still first
        ((3, 5), P()),                              # nothing divides
    ])
    def test_one_entry_spec_moves_to_a_dim_the_axis_divides(self, shape,
                                                            want):
        """``P(axis)`` under a GSPMD plan means "shard this leaf": a
        small dim 0 must not leave a conv kernel replicated (a ResNet-50
        under fsdp kept 94% of its state on every chip)."""
        from analytics_zoo_tpu.parallel import plan as zp

        mesh = zp.build_mesh({"data": 8})
        params = {"w": np.zeros(shape, np.float32)}
        assert zp.fsdp().param_specs(params, mesh)["w"] == want
        assert zp.zero1().opt_specs(params, mesh)["w"] == want
        # a spec that names its dims, and a shard_map plan's spec (the
        # program's contract), stay where they were written
        stays = P("data") if shape[0] % 8 == 0 else P()
        tp = zp.tensor_parallel([(r"w", P("data", None))])
        assert tp.param_specs(params, mesh)["w"] == stays
        sm = zp.ShardingPlan(name="sm", mode="shard_map",
                             param_rules=((r".*", P("data")),))
        assert sm.param_specs(params, mesh)["w"] == stays

    def test_resolve_plan_precedence(self, monkeypatch):
        from analytics_zoo_tpu.common.engine import ZooConfig
        from analytics_zoo_tpu.parallel import plan as zp

        monkeypatch.delenv("ZOO_SHARDING_PLAN", raising=False)
        monkeypatch.delenv("ZOO_SHARD_OPTIMIZER", raising=False)
        assert zp.resolve_plan(None, ZooConfig()).name == "dp"
        # env tier
        monkeypatch.setenv("ZOO_SHARDING_PLAN", "fsdp")
        assert zp.resolve_plan(None, ZooConfig()).name == "fsdp"
        # explicit beats env
        assert zp.resolve_plan("zero1", ZooConfig()).name == "zero1"
        # legacy ZOO_SHARD_OPTIMIZER maps to zero1
        monkeypatch.delenv("ZOO_SHARDING_PLAN")
        monkeypatch.setenv("ZOO_SHARD_OPTIMIZER", "1")
        assert zp.resolve_plan(None, ZooConfig()).name == "zero1"
        # a plan object passes through untouched
        tp = zp.tensor_parallel([("kernel", P(None, "model"))])
        assert zp.resolve_plan(tp, ZooConfig()) is tp

    def test_bad_plan_name_fails_eagerly(self, monkeypatch):
        from analytics_zoo_tpu.common.engine import ZooConfig
        from analytics_zoo_tpu.parallel import plan as zp

        with pytest.raises(ValueError, match="fsdp"):
            zp.resolve_plan("fsdqqp")
        # the env knob fails at config init naming itself
        monkeypatch.setenv("ZOO_SHARDING_PLAN", "nope")
        with pytest.raises(ValueError, match="ZOO_SHARDING_PLAN"):
            ZooConfig()

    def test_bare_string_spec_rejected(self):
        """P(*"model") would splat into per-character axes that all
        clamp to replicate — a silent no-op plan; rejected loudly."""
        from analytics_zoo_tpu.parallel import plan as zp

        with pytest.raises(TypeError, match="bare string"):
            zp.tensor_parallel([(r"kernel", "model")])

    def test_batch_specs(self):
        from analytics_zoo_tpu.parallel import plan as zp

        p = zp.fsdp()
        assert p.batch_spec(2) == P("data", None)
        assert p.batch_spec(0) == P()
        assert p.batch_spec(3, stacked=True) == P(None, "data", None)
        assert p.batch_spec(1, stacked=True) == P()
        hy = zp.ShardingPlan(name="hybrid", batch_axes=("dcn", "data"))
        assert hy.batch_spec(2) == P(("dcn", "data"), None)

    def test_spec_serialization_roundtrip(self):
        from analytics_zoo_tpu.parallel import plan as zp

        specs = {"a": P("data"), "b": {"c": P(None, ("dcn", "data")),
                                       "d": P()}}
        ser = zp.serialize_specs(specs)
        assert all(isinstance(e, list) for e in ser)  # safe_load clean
        flat = zp.deserialize_specs(json.loads(json.dumps(ser)))
        assert flat == [P("data"), P(None, ("dcn", "data")), P()]


class TestBuildMesh:
    def test_single_slice_falls_back_to_plain_mesh(self):
        from analytics_zoo_tpu.parallel import plan as zp

        mesh = zp.build_mesh({"data": 4, "model": 2})
        assert dict(mesh.shape) == {"data": 4, "model": 2}

    def test_hybrid_dcn_outer_axis(self, monkeypatch):
        from analytics_zoo_tpu.parallel import plan as zp

        devs = jax.devices()
        mesh = zp.build_mesh({"data": 2, "model": 2}, dcn_shape=2,
                             dcn_axis="dcn",
                             slice_groups=[devs[:4], devs[4:]])
        assert mesh.axis_names[0] == "dcn"  # crossing axis outermost
        assert dict(mesh.shape) == {"dcn": 2, "data": 2, "model": 2}
        # ZOO_DCN_AXIS names the crossing axis when not passed
        monkeypatch.setenv("ZOO_DCN_AXIS", "data")
        mesh = zp.build_mesh({"data": 4}, dcn_shape=2,
                             slice_groups=[devs[:4], devs[4:]])
        assert dict(mesh.shape) == {"data": 8}


# ---------------------------------------------------------------------------
# compile_step: the choke point's dispatch semantics
# ---------------------------------------------------------------------------


class TestCompileStep:
    def test_compiles_once_per_signature_through_timed_compile(self):
        from analytics_zoo_tpu.metrics import (
            MetricsRegistry,
            set_registry,
            snapshot,
        )
        from analytics_zoo_tpu.parallel.plan import compile_step

        reg = MetricsRegistry(enabled=True)
        prev = set_registry(reg)
        try:
            calls = []
            step = compile_step(lambda a: a * 2.0, label="probe_cs")
            for _ in range(3):
                calls.append(np.asarray(step(jnp.ones((4,)))))
            # new shape => new lowering, same wrapper
            step(jnp.ones((8,)))
            hist = [s for s in snapshot(reg)["samples"]
                    if s["name"] == "zoo_compile_seconds"
                    and s["labels"] == {"label": "probe_cs"}]
            assert hist and hist[0]["count"] == 2  # 2 signatures, 3 calls
            np.testing.assert_array_equal(calls[0], 2.0 * np.ones(4))
        finally:
            set_registry(prev)

    def test_python_scalar_retype_recompiles(self):
        """An int and a float at the same position are different
        programs (int32 vs f32 weak avals): the signature must key on
        the scalar's TYPE, or the cached executable rejects the
        mismatched aval instead of recompiling."""
        from analytics_zoo_tpu.parallel.plan import compile_step

        step = compile_step(lambda a, s: a * s, label="probe_scalar")
        out_i = step(jnp.ones((4,)), 2)
        out_f = step(jnp.ones((4,)), 2.5)
        assert float(out_i[0]) == 2.0
        assert float(out_f[0]) == 2.5

    def test_shard_map_mode_requires_specs(self):
        from analytics_zoo_tpu.parallel.plan import (
            ShardingPlan,
            compile_step,
        )

        with pytest.raises(ValueError, match="in_specs"):
            compile_step(lambda x: x,
                         ShardingPlan(name="sm", mode="shard_map"))


# ---------------------------------------------------------------------------
# Estimator integration: plans end to end
# ---------------------------------------------------------------------------


def _fit_under(plan, nb_epoch=3, size="narrow", **fit_kw):
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.parallel.plan import per_chip_bytes

    zoo.init_zoo_context(seed=3, mesh_shape={"data": 8})
    shape = SIZES[size]
    x, y = _data(feat=shape["feat"], classes=shape["classes"])
    m = _model(**shape)
    m.fit(x, y, batch_size=32, nb_epoch=nb_epoch, plan=plan, **fit_kw)
    est = m._estimator
    return {
        "losses": [h["loss"] for h in est.history],
        "bytes": per_chip_bytes((m.params, est._opt_state)),
        "spec0": jax.tree_util.tree_leaves(m.params)[0].sharding.spec,
        "model": m,
    }


class TestEstimatorPlans:
    @pytest.mark.parametrize("size", list(SIZES))
    def test_fsdp_bitwise_trajectory_and_memory(self, size):
        """The headline contract: fsdp trains bit-identically to
        replicated DP while holding <= 0.6x (measured ~0.13x) the
        per-chip param+opt bytes."""
        dp = _fit_under(None, size=size)
        fs = _fit_under("fsdp", size=size)
        assert fs["losses"] == dp["losses"]  # BITWISE
        assert fs["spec0"] == P("data")
        assert dp["spec0"] == P()
        assert fs["bytes"] <= 0.6 * dp["bytes"], (fs["bytes"], dp["bytes"])

    @pytest.mark.parametrize("size", list(SIZES))
    def test_zero1_plan_shards_opt_only(self, size):
        """zero1's program groups the gradient's reduction differently
        (reduce-scatter into the moments, all-gather of the updates), so
        its losses are dp's to an ulp, not to the bit."""
        dp = _fit_under(None, size=size)
        z1 = _fit_under("zero1", size=size)
        assert z1["spec0"] == P()  # params pinned replicated
        assert z1["bytes"] <= 0.6 * dp["bytes"], (z1["bytes"], dp["bytes"])
        np.testing.assert_allclose(z1["losses"], dp["losses"],
                                   rtol=1e-5, atol=1e-6)

    def test_env_knob_selects_plan(self, monkeypatch):
        monkeypatch.setenv("ZOO_SHARDING_PLAN", "fsdp")
        got = _fit_under(None, nb_epoch=1)
        assert got["spec0"] == P("data")

    def test_tensor_parallel_plan_through_estimator(self):
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.parallel.plan import tensor_parallel

        dp = _fit_under(None)
        zoo.init_zoo_context(seed=3, mesh_shape={"data": 2, "model": 4})
        x, y = _data()
        m = _model()
        tp = tensor_parallel([(r"kernel", P(None, "model"))])
        m.fit(x, y, batch_size=32, nb_epoch=3, plan=tp)
        k0 = m.params["dense_1"]["kernel"]
        assert k0.sharding.spec == P(None, "model")
        # same global math on a different mesh topology: the schedule
        # depends only on (seed, epoch), so the trajectory matches the
        # 8-way DP run to float tolerance
        np.testing.assert_allclose(
            [h["loss"] for h in m._estimator.history], dp["losses"],
            rtol=1e-5, atol=1e-6)

    def test_checkpoint_saves_plan_spec_tree(self, tmp_path):
        from analytics_zoo_tpu.common.safe_pickle import safe_load

        import analytics_zoo_tpu as zoo

        zoo.init_zoo_context(seed=3, mesh_shape={"data": 8})
        x, y = _data()
        m = _model()
        m.set_checkpoint(str(tmp_path))
        m.fit(x, y, batch_size=32, nb_epoch=1, plan="fsdp")
        files = [f for f in os.listdir(tmp_path) if f.endswith(".pkl")]
        assert files
        with open(os.path.join(tmp_path, sorted(files)[-1]), "rb") as f:
            payload = safe_load(f)
        rec = payload["plan"]
        assert rec["name"] == "fsdp"
        assert rec["mesh"] == {"data": 8, "model": 1}
        assert ["data"] in rec["param_specs"]  # sharded leaves recorded
        assert len(rec["opt_specs"]) == len(payload["opt_flat"])


# ---------------------------------------------------------------------------
# Acceptance: ALL plans through the choke point, cross-process warm start
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.metrics import get_registry, snapshot
from analytics_zoo_tpu.parallel import (
    make_shard_map_train_step, make_zero1_train_step, tensor_parallel,
)
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.objectives import get_loss


def model():
    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    return m


rng = np.random.default_rng(0)
x = rng.normal(size=(64, 8)).astype(np.float32)
y = rng.integers(0, 4, size=(64,)).astype(np.int32)
batch = {"x": x[:32], "y": y[:32]}

# jit-mode plans through the estimator's warmup: ONE choke-point
# compile + dispatch per plan
for plan in ("dp", "fsdp", "zero1"):
    zoo.init_zoo_context(seed=0, mesh_shape={"data": 8})
    model()._make_estimator().warmup(batch, plan=plan)

# tensor parallelism on a {data: 2, model: 4} mesh
zoo.init_zoo_context(seed=0, mesh_shape={"data": 2, "model": 4})
tp = tensor_parallel([(r"kernel", P(None, "model"))])
model()._make_estimator().warmup(batch, plan=tp)

# explicit shard_map strategies (mode="shard_map" plans)
zoo.init_zoo_context(seed=0, mesh_shape={"data": 8})
m = model()
loss = get_loss("sparse_categorical_crossentropy")
opt = optax.adam(1e-2)
db = {"x": jnp.asarray(x[:32]), "y": jnp.asarray(y[:32])}
params, state = m.build_params()
step = make_shard_map_train_step(m, loss, opt)
step(params, opt.init(params), state, jax.random.PRNGKey(0), db)
m2 = model()  # fresh buffers: the step above donated m's
zstep, zinit = make_zero1_train_step(m2, loss, opt)
params2, state2 = m2.build_params()
zstep(params2, zinit(params2), state2, jax.random.PRNGKey(0), db)

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

ALL_PLAN_LABELS = {
    "train_step", "train_step_fsdp", "train_step_zero1", "train_step_tp",
    "shard_map_step", "zero1_step", "zero1_init_opt_state",
}


def _run_child(cache_dir):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        ZOO_COMPILE_CACHE=str(cache_dir),
    )
    env.pop("ZOO_SHARDING_PLAN", None)
    env.pop("ZOO_SHARD_OPTIMIZER", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_every_plan_compiles_through_choke_point_and_warm_starts(tmp_path):
    """The acceptance pin: plain DP, fsdp, zero1, TP, explicit
    shard_map and explicit zero1 ALL lower through compile_step →
    timed_compile.  Evidence: (a) every plan label lands in
    zoo_compile_seconds AND carries zoo_hlo_* features (the HLO lint
    rides the choke point), (b) a SECOND process over the same
    ZOO_COMPILE_CACHE compiles every one of those programs as a
    persistent-cache HIT (zero misses)."""
    cache = tmp_path / "cc"
    cold = _run_child(cache)
    assert ALL_PLAN_LABELS <= set(cold["compiled"]), cold["compiled"]
    assert ALL_PLAN_LABELS <= set(cold["hlo_flops"]), cold["hlo_flops"]
    # every compiled program extracted nonzero analytic FLOPs except the
    # collective-free init (its program is gather/pad, not matmul)
    for label in ALL_PLAN_LABELS - {"zero1_init_opt_state"}:
        assert cold["hlo_flops"][label] > 0, label
    assert cold["hits"] == 0
    assert cold["misses"] == len(ALL_PLAN_LABELS)

    warm = _run_child(cache)
    assert warm["misses"] == 0, warm
    assert warm["hits"] == len(ALL_PLAN_LABELS)
    assert ALL_PLAN_LABELS <= set(warm["hlo_flops"])
