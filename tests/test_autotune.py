"""Closed-loop autotuning (feature/autotune.py): resizable pipeline
byte-identity, controller convergence on both synthetics, K hill-climb
trajectory bit-identity, RAM budget, disabled-mode zero overhead, the
ZooConfig knob validation satellite, and the /varz + metrics_dump
decision-log surfaces."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.feature.autotune import AutotuneController
from analytics_zoo_tpu.feature.common import FnPreprocessing
from analytics_zoo_tpu.feature.dataset import FeatureSet, ShardedFeatureSet
from analytics_zoo_tpu.feature.prefetch import (
    PrefetchFeatureSet,
    PrefetchPipeline,
    worth_prefetching,
)


def _sleepy_sharded(n_shards=4, records=32, load_sleep=0.01,
                    transform_sleep=0.001):
    def loader(path):
        i = int(path.rsplit("-", 1)[-1])
        time.sleep(load_sleep)
        rng = np.random.default_rng(1234 + i)
        return {"x": rng.standard_normal((records, 16)).astype("float32"),
                "y": rng.integers(0, 10, size=(records,)).astype("int32")}

    base = ShardedFeatureSet(
        [f"synth://shard-{i}" for i in range(n_shards)],
        n_slices=n_shards, loader=loader, sizer=lambda p: records)

    def slow(r):
        time.sleep(transform_sleep)
        return r

    return base.transform(FnPreprocessing(slow))


def _streams_equal(a_batches, b_batches):
    if len(a_batches) != len(b_batches):
        return False
    for a, b in zip(a_batches, b_batches):
        if set(a) != set(b):
            return False
        for k in a:
            if not np.array_equal(a[k], b[k]):
                return False
    return True


# ---------------------------------------------------------------------------
# resizable pipeline primitives
# ---------------------------------------------------------------------------

def test_pipeline_resize_preserves_byte_identical_stream():
    """The acceptance pin: aggressive concurrent grow/shrink of BOTH
    knobs while the stream is consumed must not reorder, drop, or
    duplicate a single batch."""
    x = np.arange(4000, dtype=np.float32).reshape(1000, 4)
    fs = FeatureSet.of(x).transform(FnPreprocessing(lambda r: r * 2.0))
    serial = list(fs.batches(8, shuffle=True, seed=5, epoch=2))

    # controller-style attach exposes the live pipeline so a second
    # thread can churn its knobs mid-iteration
    live = {}

    class Grabber:
        data_metrics = None

        def pipeline_config(self, w, d):
            return w, d

        def attach_pipeline(self, pipe, sharded=None):
            live["pipe"] = pipe

        def detach_pipeline(self, pipe):
            pass

    pre = PrefetchFeatureSet(fs, depth=1, workers=1,
                             controller=Grabber())
    gen = pre.batches(8, shuffle=True, seed=5, epoch=2)
    got = [next(gen)]
    stop = threading.Event()

    def churn():
        sizes = [(1, 1), (4, 8), (2, 3), (8, 16), (1, 2), (3, 8)]
        i = 0
        while not stop.is_set():
            w, d = sizes[i % len(sizes)]
            live["pipe"].resize(workers=w, depth=d)
            i += 1
            time.sleep(0.001)

    churner = threading.Thread(target=churn, daemon=True)
    churner.start()
    try:
        got.extend(gen)
    finally:
        stop.set()
        churner.join(timeout=5)
    assert _streams_equal(serial, got)


def test_worker_pool_grows_and_shrinks():
    from analytics_zoo_tpu.feature.prefetch import _WorkerPool

    pool = _WorkerPool(1, thread_name_prefix="zoo-test-pool")
    try:
        def live():
            return sum(t.name.startswith("zoo-test-pool") and t.is_alive()
                       for t in threading.enumerate())

        assert live() == 1
        pool.resize(3)
        deadline = time.monotonic() + 5
        while live() < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert live() == 3
        # shrink is lazy: workers exit between tasks
        pool.resize(1)
        deadline = time.monotonic() + 5
        while live() > 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert live() == 1
        # futures still work after resizing
        assert pool.submit(lambda a: a + 1, 41).result(timeout=5) == 42
    finally:
        pool.shutdown()
    with pytest.raises(RuntimeError):
        pool.submit(lambda: None)


def test_resizable_queue_blocks_and_unblocks_on_resize():
    import queue as _q

    from analytics_zoo_tpu.feature.prefetch import _ResizableQueue

    q = _ResizableQueue(1)
    q.put("a")
    with pytest.raises(_q.Full):
        q.put("b", timeout=0.05)
    q.resize(2)
    q.put("b", timeout=0.5)  # grow admitted it without a drain
    assert q.get() == "a" and q.get() == "b"  # FIFO preserved
    q.resize(1)
    with pytest.raises(_q.Empty):
        q.get_nowait()


def test_read_ahead_count_knob(shard_paths=None, tmp_path=None):
    fs = _sleepy_sharded(n_shards=5, load_sleep=0.0, transform_sleep=0.0)
    inner = fs.base
    inner.set_read_ahead_count(3)
    assert inner._ra_ahead == 3
    with pytest.raises(ValueError):
        inner.set_read_ahead_count(0)
    # read-ahead=3 still loads each shard exactly once
    pre = PrefetchFeatureSet(fs, depth=2, workers=2)
    serial = list(fs.batches(8, shuffle=True, seed=3, epoch=0))
    got = list(pre.batches(8, shuffle=True, seed=3, epoch=0))
    assert _streams_equal(serial, got)
    assert inner.last_shard_nbytes > 0


# ---------------------------------------------------------------------------
# controller: data plane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("load_sleep, epochs, interval", [
    (0.01, 4, 0.03),
    (0.015, 5, 0.04),  # a slower disk, a slower controller
], ids=["10ms-shards", "15ms-shards"])
def test_controller_grows_pipeline_and_stays_byte_identical(
        load_sleep, epochs, interval):
    """From the worst-case start (one worker, depth one) the controller
    ends above both, and resizing a live pipeline changes no byte of the
    stream."""
    fs = _sleepy_sharded(load_sleep=load_sleep)
    serial = [list(fs.batches(8, shuffle=True, seed=7, epoch=e))
              for e in range(epochs)]
    ctrl = AutotuneController(interval=interval, min_window=4)
    pre = PrefetchFeatureSet(fs, depth=1, workers=1, controller=ctrl)
    try:
        for e in range(epochs):
            got = list(pre.batches(8, shuffle=True, seed=7, epoch=e))
            assert _streams_equal(serial[e], got)
    finally:
        ctrl.stop()
    log = ctrl.decision_log()
    assert any(d["knob"] == "workers" and d["new"] > d["old"]
               for d in log), log
    cur = ctrl.current()
    assert cur["workers"] > 1 and cur["depth"] > 1, cur
    # every decision also landed in the flight ring
    from analytics_zoo_tpu.metrics import get_flight_recorder
    flight_autotune = get_flight_recorder().events(kind="autotune")
    assert len(flight_autotune) >= len(log) > 0


def test_ram_budget_caps_depth_growth():
    """A budget of ~4 batches: the controller must keep
    batch_bytes x (depth + workers) under it instead of growing depth
    toward 2x workers."""
    fs = _sleepy_sharded(records=64)
    batch = next(iter(fs.batches(8, shuffle=True, seed=1, epoch=0)))
    batch_bytes = sum(v.nbytes for v in batch.values())
    budget = batch_bytes * 6
    ctrl = AutotuneController(interval=0.02, min_window=3,
                              ram_budget=budget, max_read_ahead=1)
    pre = PrefetchFeatureSet(fs, depth=1, workers=1, controller=ctrl)
    try:
        for e in range(4):
            list(pre.batches(8, shuffle=True, seed=1, epoch=e))
    finally:
        ctrl.stop()
    cur = ctrl.current()
    est = batch_bytes * (cur["depth"] + cur["workers"])
    assert est <= budget * 2, (cur, batch_bytes, budget)
    assert cur["depth"] <= 8, cur


# ---------------------------------------------------------------------------
# controller: K hill-climb (trajectory bit-identity is the contract)
# ---------------------------------------------------------------------------

def _fit_tiny(autotune=None, epochs=2, n=1024, **cfg_kwargs):
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.engine import ZooConfig
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    zoo.init_zoo_context(ZooConfig(seed=3, mesh_shape={"data": 8},
                                   **cfg_kwargs))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(n,)).astype(np.int32)
    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    m.fit(x, y, batch_size=32, nb_epoch=epochs, autotune=autotune)
    return [h["loss"] for h in m._estimator.history]


def test_k_hill_climb_policy_on_synthetic_costs():
    """Deterministic policy pin (no timing noise): per-dispatch wall
    modeled as nk x step + fixed overhead must climb the whole ladder;
    a cost curve whose optimum is K=2 must settle exactly there."""
    ctrl = AutotuneController(k_samples=2, k_warm_skip=0)
    for _ in range(100):
        if ctrl.k_settled:
            break
        k = ctrl.current_k()
        ctrl.observe_dispatch(k, k * 0.0005 + 0.005)  # overhead-bound
    assert ctrl.k_settled and ctrl.current_k() == 16
    assert ctrl.current()["k_settle_dispatch"] is not None

    ctrl2 = AutotuneController(k_samples=2, k_warm_skip=0)
    costs = {1: 0.0011, 2: 0.00100, 4: 0.0015, 8: 0.002, 16: 0.003}
    for _ in range(100):
        if ctrl2.k_settled:
            break
        k = ctrl2.current_k()
        ctrl2.observe_dispatch(k, k * costs[k])
    assert ctrl2.k_settled and ctrl2.current_k() == 2
    # stale chunks from before a switch never pollute a window
    ctrl2.observe_dispatch(4, 99.0)
    assert ctrl2.current_k() == 2


def test_k_hill_climb_explores_and_trajectory_is_bitwise_identical():
    """The online contract: exploring K during a REAL fit leaves the
    loss trajectory bit-for-bit unchanged (which K it settles on is
    timing-dependent, and no test holds it)."""
    l1 = _fit_tiny(autotune=False, epochs=2, n=2048)
    ctrl = AutotuneController(k_samples=3, k_warm_skip=2)
    try:
        la = _fit_tiny(autotune=ctrl, epochs=2, n=2048)
    finally:
        ctrl.stop()
    # the climb probed beyond K=1, and the trajectory did not move
    assert any(d["knob"] == "k" for d in ctrl.decision_log())
    assert la == l1  # bitwise float equality, no tolerance


def test_autotune_env_knob_via_config(monkeypatch):
    monkeypatch.setenv("ZOO_AUTOTUNE", "1")
    monkeypatch.setenv("ZOO_AUTOTUNE_INTERVAL", "0.05")
    l1 = _fit_tiny(autotune=False, epochs=1)
    la = _fit_tiny(epochs=1)  # autotune=None defers to the env tier
    assert la == l1
    # the estimator's own controller was stopped when fit returned
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and any(
            t.name == "zoo-autotune" and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.05)
    assert not any(t.name == "zoo-autotune" and t.is_alive()
                   for t in threading.enumerate())


# ---------------------------------------------------------------------------
# disabled mode: zero threads, zero import (the ZOO_SAN pattern)
# ---------------------------------------------------------------------------

def test_autotune_unset_means_no_thread_and_no_import():
    """ZOO_AUTOTUNE unset ⇒ a plain fit never imports feature.autotune
    and never starts a controller thread (subprocess so other tests'
    imports can't contaminate sys.modules)."""
    code = """
import os, sys, threading
os.environ.pop("ZOO_AUTOTUNE", None)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
zoo.init_zoo_context(seed=0)
rng = np.random.default_rng(0)
x = rng.normal(size=(64, 4)).astype(np.float32)
y = (x.sum(1) > 0).astype(np.int32)
m = Sequential()
m.add(Dense(2, activation="softmax", input_shape=(4,)))
m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
m.fit(x, y, batch_size=8, nb_epoch=1)
assert "analytics_zoo_tpu.feature.autotune" not in sys.modules, \\
    "autotune imported on the disabled path"
assert not [t.name for t in threading.enumerate()
            if t.name == "zoo-autotune"]
print("CLEAN")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CLEAN" in r.stdout


def test_worth_prefetching_heuristic():
    x = np.zeros((32, 4), np.float32)
    plain = FeatureSet.of(x)
    assert not worth_prefetching(plain)  # resident, nothing to hide
    assert worth_prefetching(plain.transform(
        FnPreprocessing(lambda r: r)))  # pooled map stage
    assert worth_prefetching(_sleepy_sharded())  # shard loads
    assert worth_prefetching(
        FeatureSet.array(x, memory_type="PMEM"))  # page-cache reads


# ---------------------------------------------------------------------------
# ZooConfig satellite: eager validation naming the env var
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("var,val,msg", [
    ("ZOO_PREFETCH_WORKERS", "two", "ZOO_PREFETCH_WORKERS"),
    ("ZOO_PREFETCH_WORKERS", "-1", "ZOO_PREFETCH_WORKERS"),
    ("ZOO_PREFETCH_DEPTH", "0", "ZOO_PREFETCH_DEPTH"),
    ("ZOO_PREFETCH_DEPTH", "4.5", "ZOO_PREFETCH_DEPTH"),
    ("ZOO_STEPS_PER_DISPATCH", "0", "ZOO_STEPS_PER_DISPATCH"),
    ("ZOO_STEPS_PER_DISPATCH", "x", "ZOO_STEPS_PER_DISPATCH"),
    ("ZOO_AUTOTUNE_RAM_BUDGET", "lots", "ZOO_AUTOTUNE_RAM_BUDGET"),
    ("ZOO_AUTOTUNE_MAX_WORKERS", "0", "ZOO_AUTOTUNE_MAX_WORKERS"),
])
def test_env_knobs_validated_eagerly_with_clear_errors(
        monkeypatch, var, val, msg):
    from analytics_zoo_tpu.common.engine import ZooConfig

    monkeypatch.setenv(var, val)
    with pytest.raises(ValueError, match=msg):
        ZooConfig()


def test_explicit_knobs_validated_naming_the_field():
    from analytics_zoo_tpu.common.engine import ZooConfig

    with pytest.raises(ValueError, match="prefetch_workers"):
        ZooConfig(prefetch_workers=-2)
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        ZooConfig(steps_per_dispatch=0)


def test_ram_budget_suffix_parsing(monkeypatch):
    from analytics_zoo_tpu.common.engine import ZooConfig

    monkeypatch.setenv("ZOO_AUTOTUNE_RAM_BUDGET", "512M")
    assert ZooConfig().autotune_ram_budget == 512 << 20
    monkeypatch.setenv("ZOO_AUTOTUNE_RAM_BUDGET", "2G")
    assert ZooConfig().autotune_ram_budget == 2 << 30
    monkeypatch.setenv("ZOO_AUTOTUNE_RAM_BUDGET", "65536")
    assert ZooConfig().autotune_ram_budget == 65536


# ---------------------------------------------------------------------------
# map-fusion satellite: one _preprocess_batch pass per batch
# ---------------------------------------------------------------------------

def test_transform_chain_fuses_to_one_pass_per_batch(monkeypatch):
    import analytics_zoo_tpu.feature.prefetch as prefetch_mod

    calls = []
    real = prefetch_mod._preprocess_batch

    def counting(pre, batch):
        calls.append(type(pre).__name__)
        return real(pre, batch)

    monkeypatch.setattr(prefetch_mod, "_preprocess_batch", counting)
    x = np.arange(120, dtype=np.float32).reshape(40, 3)
    fs = FeatureSet.of(x).transform(
        FnPreprocessing(lambda r: r + 1.0)).transform(
        FnPreprocessing(lambda r: r * 3.0)).transform(
        FnPreprocessing(lambda r: r - 0.5))
    serial = list(fs.batches(8, shuffle=True, seed=2, epoch=1))
    got = list(fs.prefetch(depth=2, workers=2).batches(
        8, shuffle=True, seed=2, epoch=1))
    assert _streams_equal(serial, got)
    # 5 batches, 3 transforms: ONE fused pass per batch, not 15
    assert len(calls) == 5, calls
    assert all(c == "FusedPreprocessing" for c in calls)


def test_fused_stages_see_materialized_rows_like_serial():
    """Review pin: stage N receives an ndarray row (the serial np.stack
    boundary shape), not stage N-1's raw Python return — a stage-1
    transform returning a LIST must not break (or change the bytes of)
    a stage-2 transform that uses ndarray methods."""
    x = np.arange(60, dtype=np.float32).reshape(20, 3)
    fs = FeatureSet.of(x).transform(
        FnPreprocessing(lambda r: list(r * 2.0))).transform(  # raw list!
        FnPreprocessing(lambda r: r.mean() * np.ones(3, r.dtype)))
    serial = list(fs.batches(4, shuffle=False))
    got = list(fs.prefetch(depth=2, workers=2).batches(4, shuffle=False))
    assert _streams_equal(serial, got)


def test_autotune_false_does_not_resurrect_fit_controller():
    """Review pin: train(autotune=True) on a caller-owned
    PrefetchFeatureSet must not leave its fit-local controller attached —
    a later train(autotune=False) on the SAME set spawns no thread."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.engine import ZooConfig
    from analytics_zoo_tpu.feature.dataset import FeatureSet
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    zoo.init_zoo_context(ZooConfig(seed=3, mesh_shape={"data": 8}))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(256,)).astype(np.int32)
    pre_fs = FeatureSet.of(x, y).prefetch(depth=2, workers=1)
    m = Sequential()
    m.add(Dense(4, activation="softmax", input_shape=(8,)))
    m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    est = m._make_estimator()
    m._estimator = est
    est.train(pre_fs, batch_size=32, nb_epoch=1, autotune=True)
    assert pre_fs._controller is None  # fit-scoped attachment undone
    est.train(pre_fs, batch_size=32, nb_epoch=1, autotune=False)
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and any(
            t.name == "zoo-autotune" and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.05)
    assert not any(t.name == "zoo-autotune" and t.is_alive()
                   for t in threading.enumerate())


def test_zoo_autotune_env_rejects_non_boolean(monkeypatch):
    from analytics_zoo_tpu.common.engine import ZooConfig

    monkeypatch.setenv("ZOO_AUTOTUNE", "false")
    assert ZooConfig().autotune is False  # 'false' DISABLES, never enables
    monkeypatch.setenv("ZOO_AUTOTUNE", "maybe")
    with pytest.raises(ValueError, match="ZOO_AUTOTUNE"):
        ZooConfig()


# ---------------------------------------------------------------------------
# observability surfaces: /varz + metrics_dump decision table
# ---------------------------------------------------------------------------

def test_varz_and_metrics_dump_render_decisions():
    import urllib.request

    from analytics_zoo_tpu.metrics import MetricsServer

    fs = _sleepy_sharded()
    ctrl = AutotuneController(interval=0.02, min_window=3)
    pre = PrefetchFeatureSet(fs, depth=1, workers=1, controller=ctrl)
    try:
        for e in range(3):
            list(pre.batches(8, shuffle=True, seed=7, epoch=e))
    finally:
        ctrl.stop()
    assert ctrl.decision_log(), "controller made no decisions"
    srv = MetricsServer(port=0).start()
    try:
        with urllib.request.urlopen(srv.url + "/varz", timeout=10) as r:
            doc = json.load(r)
    finally:
        srv.stop()
    auto = doc.get("autotune")
    assert auto and auto["decisions"], auto
    d0 = auto["decisions"][0]
    assert {"ts", "knob", "old", "new", "reason"} <= set(d0)

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import metrics_dump

    lines = []
    metrics_dump.render_autotune(doc, out=lines)
    text = "\n".join(lines)
    assert "autotune:" in text
    assert d0["knob"] in text and d0["reason"] in text


def test_zoo_autotune_metrics_family_exported():
    from analytics_zoo_tpu.metrics import MetricsRegistry, snapshot

    reg = MetricsRegistry(enabled=True)
    ctrl = AutotuneController(registry=reg, interval=0.02, min_window=3)
    fs = _sleepy_sharded()
    pre = PrefetchFeatureSet(fs, depth=1, workers=1, controller=ctrl)
    try:
        for e in range(3):
            list(pre.batches(8, shuffle=True, seed=7, epoch=e))
    finally:
        ctrl.stop()
    names = {s["name"] for s in snapshot(reg)["samples"]}
    assert {"zoo_autotune_workers", "zoo_autotune_depth",
            "zoo_autotune_read_ahead", "zoo_autotune_k",
            "zoo_autotune_ram_budget_bytes",
            "zoo_autotune_decisions_total"} <= names, sorted(names)
