"""Benchmark driver: prints ONE JSON line with the headline metric.

Headline (BASELINE.md): ResNet-50 ImageNet training throughput,
images/sec/chip.  The reference publishes no absolute numbers (its story is
scaling factors on Xeon clusters, docs/docs/wp-bigdl.md); the BASELINE.json
north star is ">= A100-class images/sec/chip", so vs_baseline is reported
against a 2500 img/s A100 figure.

The measurement itself lives in examples/resnet/train_imagenet.run() — the
example IS the bench (the role of the reference's Perf.scala harness,
examples/vnni/bigdl/Perf.scala:53-66).  It reports the end-to-end number
AND the decomposition the end-to-end number hides:

- value / *_e2e: wall-clock fit() throughput (host batch assembly + uint8
  H2D infeed + compiled step);
- pure_step_*: the jitted train step on a device-resident batch — the
  framework's compute celling;
- infeed_fraction: how much of e2e the infeed fails to hide;
- compiles_timed: XLA compilations during the timed epoch (0 = no
  per-step retracing).

The headline is a device metric, so it needs the device: without a TPU
``python bench.py`` prints one line saying so and exits non-zero — there
is no reduced-size CPU run under the same metric name.  JAX is
initialised once, in this process; a chip belongs to one process at a
time, so ``main()`` starts no child.  The persistent compile cache goes
where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``<repo>/.jax_cache``.
"""

import json
import os
import subprocess
import sys
import time

A100_IMAGES_PER_SEC = 2500.0

# ResNet-50 training FLOPs per image at 224x224: ~4.09 GFLOP forward,
# ~3x forward for fwd+bwd (standard accounting).
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 4.09e9

# Peak bf16 matmul FLOP/s per chip by device_kind substring (public specs).
# Ordered most-specific first: "TPU v5 lite" (the v5e device_kind string)
# must match the 197 TF v5e entry, never the 459 TF v5p one.
TPU_PEAK_FLOPS = (
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12),  # Trillium
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

PROBE_CODE = "import jax; d = jax.devices(); print(d[0].platform, len(d))"


# ---------------------------------------------------------------------------
# --data-pipeline: host data-plane bench (feature/prefetch.py).  No jax —
# it measures the HOST side: serial FeatureSet.batches() vs the parallel
# prefetch pipeline on a synthetic loader/transform whose cost is pure
# sleep (IO-shaped: releases the GIL, like real file reads and cv2).
# Emits BENCH_DATA_*.json so the gain is pinned, not asserted.
# ---------------------------------------------------------------------------

def _sleepy_loader(load_sleep_s: float, shard_records: int, feat: int = 16):
    import numpy as np

    def load(path: str) -> dict:
        i = int(path.rsplit("-", 1)[-1])
        time.sleep(load_sleep_s)
        rng = np.random.default_rng(1234 + i)
        return {
            "x": rng.standard_normal((shard_records, feat))
                    .astype("float32"),
            "y": rng.integers(0, 10, size=(shard_records,))
                    .astype("int32"),
        }

    return load


def data_pipeline_bench(workers: int = 4, depth: int = 8,
                        n_shards: int = 6, shard_records: int = 64,
                        batch_size: int = 16,
                        load_sleep_ms: float = 40.0,
                        transform_sleep_ms: float = 2.0,
                        seed: int = 7, out_path: str | None = None) -> dict:
    """Serial vs prefetched host-pipeline throughput + wait breakdown.

    The synthetic loader sleeps per shard (disk/decode IO) and the
    per-record transform sleeps per record (host preprocessing), so the
    measured speedup isolates the pipeline machinery from numpy noise.
    Also verifies the determinism contract: the prefetched stream must be
    byte-identical to the serial one for the same seed/epoch.
    """
    import numpy as np

    from analytics_zoo_tpu.feature.common import FnPreprocessing
    from analytics_zoo_tpu.feature.dataset import ShardedFeatureSet
    from analytics_zoo_tpu.feature.prefetch import PrefetchFeatureSet
    from analytics_zoo_tpu.metrics import (
        DataPipelineMetrics,
        MetricsRegistry,
        snapshot,
    )

    t_sleep = transform_sleep_ms / 1e3
    paths = [f"synth://shard-{i}" for i in range(n_shards)]
    base = ShardedFeatureSet(
        paths, n_slices=n_shards,
        loader=_sleepy_loader(load_sleep_ms / 1e3, shard_records),
        sizer=lambda p: shard_records)

    def slow_identity(record):
        time.sleep(t_sleep)
        return record

    fs = base.transform(FnPreprocessing(slow_identity))

    def drain(feature_set):
        """Iterate one epoch; returns (batches, wall_s, waits list)."""
        out, waits = [], []
        it = feature_set.batches(batch_size, shuffle=True, seed=seed,
                                 epoch=0)
        t_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            batch = next(it, None)
            if batch is None:
                break
            waits.append(time.perf_counter() - t0)
            out.append(batch)
        return out, time.perf_counter() - t_start, waits

    def pcts(waits):
        return {"p50": round(float(np.percentile(waits, 50)), 6),
                "p99": round(float(np.percentile(waits, 99)), 6)}

    serial_batches, serial_s, serial_waits = drain(fs)
    # fresh registry so the artifact's zoo_data_prefetch_* series cover
    # exactly this run (the process-global one may hold training noise)
    reg = MetricsRegistry(enabled=True)
    pre = PrefetchFeatureSet(fs, depth=depth, workers=workers,
                             metrics=DataPipelineMetrics(registry=reg))
    pre_batches, pre_s, pre_waits = drain(pre)

    def batch_equal(a, b):
        if set(a) != set(b):
            return False
        return all(np.array_equal(a[k], b[k]) for k in a)

    deterministic = len(serial_batches) == len(pre_batches) and all(
        batch_equal(a, b) for a, b in zip(serial_batches, pre_batches))

    n_batches = len(serial_batches)
    prefetch_series = {}
    for s in snapshot(reg)["samples"]:
        if s["name"].startswith("zoo_data_prefetch") \
                and s.get("kind") == "histogram":
            prefetch_series[s["name"]] = {
                k: round(float(s[k]), 6)
                for k in ("count", "p50", "p99") if k in s}
    doc = {
        "metric": "data_pipeline_host_throughput",
        "unit": "batches/sec",
        "serial_batches_per_sec": round(n_batches / max(serial_s, 1e-9), 2),
        "prefetched_batches_per_sec": round(
            n_batches / max(pre_s, 1e-9), 2),
        "speedup": round(serial_s / max(pre_s, 1e-9), 3),
        "deterministic": bool(deterministic),
        "batches": n_batches,
        "workers": workers, "depth": depth, "batch_size": batch_size,
        "n_shards": n_shards, "shard_records": shard_records,
        "load_sleep_ms": load_sleep_ms,
        "transform_sleep_ms": transform_sleep_ms,
        # the fit-loop data_wait analogue: time the consumer blocked per
        # next() — what zoo_train_data_wait_seconds would see
        "consumer_wait_s": {"serial": pcts(serial_waits),
                            "prefetched": pcts(pre_waits)},
        "prefetch_metrics": prefetch_series,
    }
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_DATA_r06.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


# ---------------------------------------------------------------------------
# --autotune: closed-loop autotuning bench (feature/autotune.py).  Both
# synthetics start from the WORST-CASE defaults (workers=1, depth=1, K=1)
# and must converge to >= 0.9x the best hand-tuned throughput from
# BENCH_DATA_r06 (workers=4, depth=8) / BENCH_DISPATCH_r07 (K=16), with
# the stream byte-identical under resizing and the loss trajectory
# bit-identical to the fixed-K run.  Emits BENCH_AUTOTUNE_r08.json.
# ---------------------------------------------------------------------------

def autotune_data_plane_bench(quick: bool = False) -> dict:
    """Sleep-bound host-pipeline synthetic (the BENCH_DATA_r06 shape):
    serial vs untuned-default (1,1) vs hand-tuned (4,8) vs the
    controller starting at (1,1).  Returns the data_plane section."""
    import numpy as np

    from analytics_zoo_tpu.feature.autotune import AutotuneController
    from analytics_zoo_tpu.feature.common import FnPreprocessing
    from analytics_zoo_tpu.feature.dataset import ShardedFeatureSet
    from analytics_zoo_tpu.feature.prefetch import PrefetchFeatureSet

    if quick:
        cfg = dict(n_shards=4, shard_records=32, batch_size=8,
                   load_sleep_ms=15.0, transform_sleep_ms=1.0)
        epochs, interval = 5, 0.04
    else:
        cfg = dict(n_shards=6, shard_records=64, batch_size=16,
                   load_sleep_ms=40.0, transform_sleep_ms=2.0)
        epochs, interval = 6, 0.1
    seed = 7
    t_sleep = cfg["transform_sleep_ms"] / 1e3
    base = ShardedFeatureSet(
        [f"synth://shard-{i}" for i in range(cfg["n_shards"])],
        n_slices=cfg["n_shards"],
        loader=_sleepy_loader(cfg["load_sleep_ms"] / 1e3,
                              cfg["shard_records"]),
        sizer=lambda p: cfg["shard_records"])

    def slow_identity(record):
        time.sleep(t_sleep)
        return record

    fs = base.transform(FnPreprocessing(slow_identity))

    def drain(feature_set, epoch):
        t0 = time.perf_counter()
        out = list(feature_set.batches(cfg["batch_size"], shuffle=True,
                                       seed=seed, epoch=epoch))
        return out, time.perf_counter() - t0

    def bps(n, s):
        return round(n / max(s, 1e-9), 2)

    serial = [drain(fs, e)[0] for e in range(epochs)]
    n_batches = len(serial[0])
    _, untuned_s = drain(PrefetchFeatureSet(fs, depth=1, workers=1), 0)
    _, hand_s = drain(PrefetchFeatureSet(fs, depth=8, workers=4), 0)

    ctrl = AutotuneController(interval=interval, min_window=4)
    pre = PrefetchFeatureSet(fs, depth=1, workers=1, controller=ctrl)
    epoch_bps, deterministic = [], True
    for e in range(epochs):
        got, dt = drain(pre, e)
        epoch_bps.append(bps(len(got), dt))
        deterministic = deterministic and len(got) == len(serial[e]) \
            and all(set(a) == set(b)
                    and all(np.array_equal(a[k], b[k]) for k in a)
                    for a, b in zip(serial[e], got))
    ctrl.stop()
    final_bps = epoch_bps[-1]
    cur = ctrl.current()
    return {
        "synthetic": cfg,
        "epochs": epochs,
        "batches_per_epoch": n_batches,
        "untuned_default_batches_per_sec": bps(n_batches, untuned_s),
        "hand_tuned_batches_per_sec": bps(n_batches, hand_s),
        "autotuned_epoch_batches_per_sec": epoch_bps,
        "autotuned_final_batches_per_sec": final_bps,
        "vs_hand_tuned": round(final_bps * hand_s / n_batches, 3),
        "vs_untuned_default": round(final_bps * untuned_s / n_batches, 3),
        "deterministic_under_resizing": bool(deterministic),
        "converged": {k: cur[k] for k in
                      ("workers", "depth", "read_ahead")},
        "hand_tuned_config": {"workers": 4, "depth": 8},
        "decisions": [
            {k: d[k] for k in ("knob", "old", "new", "reason")}
            for d in ctrl.decision_log()],
    }


def autotune_dispatch_bench(quick: bool = False) -> dict:
    """Dispatch-bound synthetic (the BENCH_DISPATCH_r07 shape): fixed
    K=1 (untuned default) and K=16 (hand-tuned) vs the controller's
    hill-climb starting at K=1.  Returns the dispatch section."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.engine import ZooConfig
    from analytics_zoo_tpu.feature.autotune import AutotuneController

    n_batches = 192 if quick else 384
    batch_size = 16
    warm_epochs = 2  # the climb's ladder (~250-350 steps) lives here
    x, y = _dispatch_data(n_batches * batch_size)

    def fixed(k):
        zoo.init_zoo_context(ZooConfig(seed=11, steps_per_dispatch=k))
        m = _dispatch_model()
        # warm epochs match the autotuned leg so the trajectory
        # comparison covers the same step count
        m.fit(x, y, batch_size=batch_size, nb_epoch=warm_epochs)
        t0 = time.perf_counter()
        m.fit(x, y, batch_size=batch_size, nb_epoch=1)
        dt = time.perf_counter() - t0
        return (round(n_batches / dt, 1),
                [h["loss"] for h in m._estimator.history])

    k1_sps, k1_losses = fixed(1)
    k16_sps, _ = fixed(16)

    zoo.init_zoo_context(ZooConfig(seed=11))
    ctrl = AutotuneController()
    m = _dispatch_model()
    # warm epochs host the hill-climb (each K's first dispatch pays its
    # compile); the final epoch is the timed steady state at settled K
    m.fit(x, y, batch_size=batch_size, nb_epoch=warm_epochs,
          autotune=ctrl)
    t0 = time.perf_counter()
    m.fit(x, y, batch_size=batch_size, nb_epoch=1, autotune=ctrl)
    dt = time.perf_counter() - t0
    ctrl.stop()
    auto_losses = [h["loss"] for h in m._estimator.history]
    auto_sps = round(n_batches / dt, 1)
    cur = ctrl.current()
    return {
        "steps_per_epoch": n_batches,
        "batch_size": batch_size,
        "untuned_default_steps_per_sec": k1_sps,
        "hand_tuned_k16_steps_per_sec": k16_sps,
        "autotuned_steady_steps_per_sec": auto_sps,
        "vs_hand_tuned": round(auto_sps / max(k16_sps, 1e-9), 3),
        "vs_untuned_default": round(auto_sps / max(k1_sps, 1e-9), 3),
        "converged_k": cur["k"],
        "k_settled": cur["k_settled"],
        "k_cost_per_step_s": cur["k_cost_per_step_s"],
        "dispatches_to_converge": cur["k_settle_dispatch"],
        "loss_trajectory_bitwise_equal_to_k1": auto_losses == k1_losses,
        "decisions": [
            {k: d[k] for k in ("knob", "old", "new", "reason")}
            for d in ctrl.decision_log()],
    }


def autotune_bench(quick: bool = False, out_path: str | None = None) -> dict:
    """Both autotune synthetics; writes BENCH_AUTOTUNE_r08.json."""
    doc = {
        "metric": "autotune_convergence_vs_hand_tuned",
        "unit": "throughput ratio",
        "platform": "cpu",
        "quick": bool(quick),
        "data_plane": autotune_data_plane_bench(quick=quick),
        "dispatch": autotune_dispatch_bench(quick=quick),
    }
    doc["value"] = min(doc["data_plane"]["vs_hand_tuned"],
                       doc["dispatch"]["vs_hand_tuned"])
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_AUTOTUNE_r08.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _autotune_main(argv):
    # host/dispatch overhead bench: the CPU backend is the point
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(autotune_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --partition: unified-partitioner bench (parallel/plan.py).  Replicated
# data parallelism vs the fsdp plan (params + optimizer state sharded
# over `data`) on the 8-device CPU mesh: per-chip param+opt-state bytes
# measured from the LIVE arrays (one device's resident shards), HLO
# bytes_accessed from the compile plane's zoo_hlo_* features, steps/sec,
# and the trajectory-equality flag — the fsdp memory win must be free
# (placement changes bytes and collectives, never the math).  Emits
# BENCH_PARTITION_r10.json.
# ---------------------------------------------------------------------------


def _partition_model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    m = Sequential()
    m.add(Dense(256, activation="relu", input_shape=(32,)))
    m.add(Dense(256, activation="relu"))
    m.add(Dense(10, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    return m


def _partition_data(n=512, feat=32, classes=10, seed=7):
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, feat)).astype(np.float32)
    w = rng.normal(size=(feat, classes))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return x, y


def _partition_leg(plan_name, epochs, batch_size=64):
    """One training leg under a named plan; returns losses, per-chip
    bytes (live arrays), steps/sec and the plan's HLO features."""
    import jax

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.metrics import get_registry, snapshot
    from analytics_zoo_tpu.parallel.plan import per_chip_bytes

    zoo.init_zoo_context(seed=11, mesh_shape={"data": 8}, platform="cpu")
    x, y = _partition_data()
    m = _partition_model()
    t0 = time.perf_counter()
    m.fit(x, y, batch_size=batch_size, nb_epoch=epochs, plan=plan_name)
    dt = time.perf_counter() - t0
    est = m._estimator
    steps = est.global_step
    params, opt_state = m.params, est._opt_state
    chip_bytes = per_chip_bytes((params, opt_state))
    total_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            (params, opt_state)) if hasattr(leaf, "nbytes"))
    label = "train_step" if plan_name in (None, "dp") \
        else f"train_step_{plan_name}"
    hlo = {}
    for s in snapshot(get_registry())["samples"]:
        if s["name"].startswith("zoo_hlo_") \
                and s.get("labels", {}).get("label") == label:
            hlo[s["name"]] = s["value"]
    spec0 = jax.tree_util.tree_leaves(params)[0].sharding.spec
    return {
        "plan": plan_name or "dp",
        "losses": [h["loss"] for h in est.history],
        "per_chip_param_opt_bytes": int(chip_bytes),
        "global_param_opt_bytes": int(total_bytes),
        "steps": int(steps),
        "steps_per_sec": round(steps / max(dt, 1e-9), 2),
        "param0_spec": str(spec0),
        "hlo": hlo,
    }


def partition_bench(quick: bool = False,
                    out_path: str | None = None) -> dict:
    """Replicated DP vs the fsdp (and zero1) plans: memory ratio at
    trajectory equality; writes BENCH_PARTITION_r10.json."""
    epochs = 2 if quick else 4
    legs = {name: _partition_leg(name, epochs)
            for name in ("dp", "fsdp", "zero1")}
    repl, fs = legs["dp"], legs["fsdp"]
    ratio = fs["per_chip_param_opt_bytes"] \
        / max(repl["per_chip_param_opt_bytes"], 1)
    doc = {
        "metric": "fsdp_per_chip_param_opt_bytes_vs_replicated",
        "unit": "ratio (lower is better; target <= 0.6)",
        "value": round(ratio, 4),
        "zero1_ratio": round(
            legs["zero1"]["per_chip_param_opt_bytes"]
            / max(repl["per_chip_param_opt_bytes"], 1), 4),
        # the acceptance flag: fsdp must be FREE — the gather-on-use /
        # reduce-scatter program computes the same sums in the same
        # order, so the trajectory is bitwise dp's.  zero1's sharded-
        # moment program groups the gradient reduction differently
        # (reduce-scatter into moments, all-gather of updates) — ulp
        # drift, reported as max|Δ| rather than pretending bitwise.
        "trajectory_bitwise_equal": repl["losses"] == fs["losses"],
        "zero1_trajectory_max_abs_diff": max(
            abs(a - b) for a, b in zip(repl["losses"],
                                       legs["zero1"]["losses"])),
        "devices": 8,
        "platform": "cpu",
        "quick": bool(quick),
        "legs": legs,
        "note": ("per_chip bytes counted from live arrays (one device's "
                 "resident shards); hlo features from the compile "
                 "plane's zoo_hlo_* extraction at the choke point"),
    }
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_PARTITION_r10.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _partition_main(argv):
    # the 8-device CPU mesh is the point (memory layout, not FLOPs)
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(partition_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --memory: the complete memory plan (parallel/plan.py) — per-chip
# param+opt bytes under dp/zero1/zero2/zero3/fsdp on the 8-device CPU
# mesh, each leg closing the predicted-vs-measured loop through the
# estimator's zoo_mem_* gauges, plus transformer-GPipe legs where the
# remat policy arrives as a PLAN rule (with_remat → resolve_remat at
# trace time), not a layer flag.  Emits BENCH_MEMORY_r12.json.  The
# quick tier is the acceptance guard (tests/test_memory_plan.py):
# zero3 <= 0.25x dp per-chip state at a bit-identical (or recorded-ulp)
# loss trajectory, and the remat leg reproduces the un-remated grads.
# ---------------------------------------------------------------------------

_MEMORY_PLANS = ("dp", "zero1", "zero2", "zero3", "fsdp")


def _memory_leg(plan_name, epochs):
    """:func:`_partition_leg` plus the closed loop: the estimator's
    ``zoo_mem_*`` gauges (cost-model prediction vs measured placement)
    harvested for the leg's compile label."""
    from analytics_zoo_tpu.metrics import get_registry, snapshot

    leg = _partition_leg(plan_name, epochs)
    label = "train_step" if plan_name in (None, "dp") \
        else f"train_step_{plan_name}"
    mem = {}
    for s in snapshot(get_registry())["samples"]:
        if s["name"].startswith("zoo_mem_") \
                and s.get("labels", {}).get("label") == label:
            mem[s["name"]] = s["value"]
    leg["mem_gauges"] = mem
    if "zoo_mem_predicted_bytes" in mem:
        leg["predicted_chip_bytes"] = int(mem["zoo_mem_predicted_bytes"])
        leg["predicted_rel_error"] = round(
            float(mem.get("zoo_mem_rel_error", 0.0)), 4)
    return leg


def _memory_pipeline_leg(remat_policy):
    """One grad step of a 4-block transformer GPipe'd over ``pipe=4``,
    compiled through ``compile_step`` under a plan whose ``remat_rules``
    carry ``remat_policy`` — the policy reaches ``apply_remat`` via
    ``resolve_remat`` inside the stage body at trace time, overriding
    the layer's own flag.  Returns (doc, loss, grads) so the caller can
    pin remat == no-remat numerics."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.metrics import get_registry, snapshot
    from analytics_zoo_tpu.parallel.pipeline import transformer_gpipe
    from analytics_zoo_tpu.parallel.plan import (
        live_bytes,
        resolve_plan,
        with_remat,
        compile_step,
    )
    from analytics_zoo_tpu.pipeline.api.keras.layers import TransformerLayer

    zoo.init_zoo_context(seed=3, mesh_shape={"data": 2, "pipe": 4},
                         mesh_axes=("data", "pipe"), platform="cpu")
    layer = TransformerLayer(vocab=64, seq_len=8, n_block=4, n_head=2,
                             hidden_size=16, embedding_drop=0.0,
                             hidden_drop=0.0, attn_drop=0.0)
    params = layer.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(8, 8, 16)).astype(np.float32))

    plan = resolve_plan("dp")
    label = "pipeline_gpipe_noremat"
    if remat_policy:
        plan = with_remat(plan, remat_policy)
        label = f"pipeline_gpipe_remat_{remat_policy}"

    def loss_fn(p, a):
        return jnp.mean(transformer_gpipe(layer, p, a,
                                          n_microbatch=4) ** 2)

    step = compile_step(jax.value_and_grad(loss_fn), plan, label=label)
    t0 = time.perf_counter()
    loss, grads = step(params, h)
    loss = float(loss)
    dt = time.perf_counter() - t0
    hlo = {}
    for s in snapshot(get_registry())["samples"]:
        if s["name"].startswith("zoo_hlo_") \
                and s.get("labels", {}).get("label") == label:
            hlo[s["name"]] = s["value"]
    doc = {
        "remat": remat_policy,
        "label": label,
        "loss": loss,
        "compile_plus_step_s": round(dt, 3),
        "live": live_bytes(),
        "hlo": hlo,
    }
    return doc, loss, grads


def memory_bench(quick: bool = False, out_path: str | None = None) -> dict:
    """The full sharding×remat memory plan: per-chip state ratios vs
    replicated DP with predicted-vs-measured closure, and plan-rule
    remat equivalence on the pipelined transformer; writes
    BENCH_MEMORY_r12.json."""
    import jax
    import numpy as np

    epochs = 2 if quick else 4
    legs = {name: _memory_leg(name, epochs) for name in _MEMORY_PLANS}
    dp = legs["dp"]

    def ratio(name):
        return round(legs[name]["per_chip_param_opt_bytes"]
                     / max(dp["per_chip_param_opt_bytes"], 1), 4)

    def traj_max_diff(name):
        return max(abs(a - b) for a, b in zip(dp["losses"],
                                              legs[name]["losses"]))

    pipe_none, loss_none, g_none = _memory_pipeline_leg(None)
    pipe_full, loss_full, g_full = _memory_pipeline_leg("full")
    grad_diffs = jax.tree_util.tree_map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))),
        g_none, g_full)
    grad_max_diff = max(jax.tree_util.tree_leaves(grad_diffs) or [0.0])

    doc = {
        "metric": "zero3_per_chip_param_opt_bytes_vs_replicated",
        "unit": "ratio (lower is better; acceptance <= 0.25)",
        "value": ratio("zero3"),
        "ratios": {name: ratio(name) for name in _MEMORY_PLANS},
        # zero3/fsdp keep the gather-on-use program's reduction order,
        # so the trajectory is bitwise dp's; zero1/zero2 group the
        # moment update differently — ulp drift recorded, not hidden
        "zero3_trajectory_bitwise_equal":
            dp["losses"] == legs["zero3"]["losses"],
        "zero3_trajectory_max_abs_diff": traj_max_diff("zero3"),
        "zero2_trajectory_max_abs_diff": traj_max_diff("zero2"),
        "zero1_trajectory_max_abs_diff": traj_max_diff("zero1"),
        "pipeline_remat": {
            "legs": [pipe_none, pipe_full],
            "loss_abs_diff": abs(loss_none - loss_full),
            "grad_max_abs_diff": grad_max_diff,
        },
        "devices": 8,
        "platform": "cpu",
        "quick": bool(quick),
        "legs": legs,
        "note": ("per_chip bytes counted from live placed arrays; "
                 "predicted bytes from analysis/costmodel.py "
                 "predict_chip_bytes via the estimator's zoo_mem_* "
                 "gauges; remat legs compile through compile_step with "
                 "the policy as a plan rule (with_remat), resolved by "
                 "resolve_remat at trace time"),
    }
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_MEMORY_r12.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _memory_main(argv):
    # the 8-device CPU mesh is the point (memory layout, not FLOPs)
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(memory_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --precision: the precision plane (parallel/plan.py dtype_rules — the
# FOURTH rule table).  f32 vs mixed_precision() training legs on the
# 8-device CPU mesh: bf16 loss trajectory pinned within tolerance of
# f32, the per-leg zoo_hlo_* features plus the zoo-hlo-report dtype
# histogram showing the MEASURED bf16 shift, predicted-vs-measured
# steps/sec per dtype (DTYPE_PEAK_FACTORS closing the loop), the
# predicted fsdp param-gather collective-bytes reduction (grad
# collectives stay f32 per the accumulation contract), and the int8
# serving leg's bytes ratio + predict parity.  CPU has no bf16 MXU, so
# throughput wins are RECORDED, not required — the byte/feature deltas
# are the asserted invariants (tests/test_precision.py).  Emits
# BENCH_PRECISION_r16.json.
# ---------------------------------------------------------------------------


def _precision_leg(plan, epochs, report_dir, batch_size=64):
    """One training leg under ``plan`` (a ShardingPlan or name); returns
    losses, steps/sec, the compile plane's zoo_hlo_* features, the
    leg's zoo-hlo-report row (dtype histogram + declared policy) and
    the roofline's predicted steps/sec at the leg's compute dtype."""
    import jax
    import numpy as np

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.analysis.costmodel import (
        histogram_compute_dtype,
        load_report_rows,
        predict_steps_per_sec,
    )
    from analytics_zoo_tpu.metrics import get_registry, snapshot
    from analytics_zoo_tpu.parallel.plan import resolve_plan

    os.environ["ZOO_HLO_REPORT_DIR"] = report_dir
    try:
        zoo.init_zoo_context(seed=11, mesh_shape={"data": 8},
                             platform="cpu")
        plan = resolve_plan(plan)
        x, y = _partition_data()
        m = _partition_model()
        t0 = time.perf_counter()
        m.fit(x, y, batch_size=batch_size, nb_epoch=epochs, plan=plan)
        dt = time.perf_counter() - t0
    finally:
        os.environ.pop("ZOO_HLO_REPORT_DIR", None)
    est = m._estimator
    steps = est.global_step
    label = "train_step" if plan.name == "dp" \
        else f"train_step_{plan.name}"
    hlo = {}
    for s in snapshot(get_registry())["samples"]:
        if s["name"].startswith("zoo_hlo_") \
                and s.get("labels", {}).get("label") == label:
            hlo[s["name"]] = s["value"]
    row = next((r for r in load_report_rows(report_dir)
                if r["label"] == label), None)
    hist = (row or {}).get("dtype_histogram") or {}
    dtype = plan.compute_cast_dtype()
    dtype_name = {"bfloat16": "bf16", "float16": "f16"}.get(
        str(np.dtype(dtype)) if dtype is not None else "", None)
    predicted = None
    if row and row["features"]:
        predicted = predict_steps_per_sec(
            row["features"], k=1, plan=plan.name,
            dtype_histogram=hist or None)
    measured = steps / max(dt, 1e-9)
    return {
        "plan": plan.name,
        "dtype": dtype_name or "f32",
        "dtype_policy": plan.dtype_policy_str(),
        "losses": [h["loss"] for h in est.history],
        "steps": int(steps),
        "steps_per_sec": round(measured, 2),
        "predicted_steps_per_sec": (round(predicted, 2)
                                    if predicted else None),
        "hlo": hlo,
        "dtype_histogram": hist,
        "measured_compute_dtype": histogram_compute_dtype(hist),
        "model": m,
    }


def precision_bench(quick: bool = False,
                    out_path: str | None = None) -> dict:
    """f32 vs mixed_precision() vs int8 serving; writes
    BENCH_PRECISION_r16.json."""
    import tempfile

    import numpy as np

    from analytics_zoo_tpu.analysis.costmodel import plan_collective_bytes
    from analytics_zoo_tpu.parallel.plan import int8_serving, mixed_precision
    from analytics_zoo_tpu.pipeline.inference.quantize import (
        dequantize_params,
        quantize_params_for_plan,
        quantized_bytes_ratio,
    )

    epochs = 2 if quick else 4
    legs = {}
    with tempfile.TemporaryDirectory() as rd:
        legs["f32"] = _precision_leg("dp", epochs, os.path.join(rd, "f32"))
        legs["bf16"] = _precision_leg(mixed_precision(), epochs,
                                      os.path.join(rd, "bf16"))
    f32, bf16 = legs["f32"], legs["bf16"]
    max_rel = max(
        abs(a - b) / max(abs(a), 1e-9)
        for a, b in zip(f32["losses"], bf16["losses"]))

    # int8 serving: quantize the f32 leg's trained weights under the
    # plan's int8 role, compare predict outputs and weight bytes
    m = f32.pop("model")
    bf16.pop("model")
    x, _ = _partition_data()
    params = m.params
    qparams = quantize_params_for_plan(params, int8_serving())
    base = np.asarray(m.predict(x[:64]))
    m._estimator.model.params = dequantize_params(qparams)
    served = np.asarray(m.predict(x[:64]))
    m._estimator.model.params = params
    int8_leg = {
        "plan": "dp+int8",
        "bytes_ratio": round(quantized_bytes_ratio(params, qparams), 4),
        "predict_max_abs_diff": float(np.max(np.abs(base - served))),
    }
    legs["int8_serving"] = int8_leg

    # predicted collective reduction: only the fsdp param-GATHER
    # traffic shrinks at bf16 — grad collectives are charged f32 per
    # the accumulation contract, so the predicted ratio is 2/3, the
    # number a real-TPU profile should reproduce
    pb = 4 * 1024 * 1024
    coll_f32 = plan_collective_bytes(pb, "fsdp", 8)
    coll_bf16 = plan_collective_bytes(pb, "fsdp", 8, dtype="bf16")
    doc = {
        "metric": "bf16_mixed_loss_trajectory_max_rel_diff_vs_f32",
        "unit": "ratio (lower is better; target <= 0.05)",
        "value": round(max_rel, 6),
        "bf16_hlo_shift": {
            "f32_leg_bf16_ops": int(f32["dtype_histogram"].get("bf16", 0)),
            "bf16_leg_bf16_ops": int(
                bf16["dtype_histogram"].get("bf16", 0)),
            "bf16_leg_compute_dtype": bf16["measured_compute_dtype"],
        },
        "predicted_fsdp_collective_bytes": {
            "f32": int(coll_f32), "bf16": int(coll_bf16),
            "ratio": round(coll_bf16 / max(coll_f32, 1), 4),
        },
        "int8_serving_bytes_ratio": int8_leg["bytes_ratio"],
        "devices": 8,
        "platform": "cpu",
        "quick": bool(quick),
        "legs": legs,
        "note": ("CPU mesh: no bf16 MXU, so steps/sec parity is "
                 "recorded (predicted-vs-measured per dtype), not "
                 "gated; the asserted invariants are the trajectory "
                 "tolerance, the measured bf16 histogram shift, the "
                 "f32 masters, and the int8 bytes/parity numbers"),
    }
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_PRECISION_r16.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _precision_main(argv):
    # the 8-device CPU mesh: dtype layout and lowering, not FLOPs
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(precision_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --kernels: the Pallas kernel plane (ops/pallas/ behind kernel_rules —
# the FIFTH rule table).  Per kernel: (a) PARITY — the jnp fallback is
# the oracle; fused_adam's fallback is BITWISE optax.adam, and the
# interpret-mode Pallas path (ZOO_KERNEL_INTERPRET=1) is compared
# against it fwd and bwd; (b) BYTES — the kernel is cross-lowered for
# TPU with no chip (trace + lower(platforms=("tpu",))), hlo.py
# attributes the tpu_custom_call's operand+result bytes, and the
# measured number must sit within rel_error <= 0.05 of
# costmodel.kernel_bytes' analytic prediction; (c) the fallback leg
# compiles under its kernel_* label through compile_step/timed_compile
# (persistent cache + compile metering), and its CPU steps/sec is
# recorded; (d) VERDICTS — ConfigOracle.choose_kernels per platform:
# the CPU tier must DECLINE every kernel ("xla" — Pallas lowers via
# Mosaic), the tpu-v4 peaks pick by the byte model.  Emits
# BENCH_KERNEL_r17.json (tests/test_kernels.py pins the invariants).
# ---------------------------------------------------------------------------


def _kernel_lowered_bytes(name, fn, args, predicted):
    """Cross-lower the Pallas variant for TPU (no chip needed), run the
    HLO lint pipe on it, and return measured-vs-predicted custom-call
    bytes.  ``predicted`` is costmodel.kernel_bytes' "kernel" term."""
    import jax

    from analytics_zoo_tpu.analysis.hlo import lint_lowered
    from analytics_zoo_tpu.ops.pallas import record_kernel_bytes

    lowered = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",))
    rpt = lint_lowered(lowered, label=f"kernel_{name}_tpu")
    measured = int(rpt.custom_kernel_bytes)
    doc = record_kernel_bytes(f"kernel_{name}", measured,
                              predicted_bytes=int(predicted))
    doc["custom_kernel_count"] = int(rpt.custom_kernel_count)
    return doc


def _kernel_timed_leg(name, fn, args, iters):
    """Compile ``fn`` under the ``kernel_<name>`` label through the
    choke point (kernel_step -> compile_step -> timed_compile: the
    persistent cache and zoo_compile_seconds see it) and time the
    compiled fallback on CPU."""
    import jax

    from analytics_zoo_tpu.ops.pallas import kernel_step

    step = kernel_step(name, fn)
    out = step(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(*args)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return {"label": f"kernel_{name}",
            "steps_per_sec": round(iters / max(dt, 1e-9), 2)}


def kernels_bench(quick: bool = False,
                  out_path: str | None = None) -> dict:
    """Kernel-plane A/B: parity, cross-lowered bytes, verdicts; writes
    BENCH_KERNEL_r17.json."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from analytics_zoo_tpu.analysis.costmodel import (
        kernel_bytes,
        resolve_peaks,
    )
    from analytics_zoo_tpu.analysis.oracle import ConfigOracle
    from analytics_zoo_tpu.ops.pallas import fused_adam as fa
    from analytics_zoo_tpu.ops.pallas import fused_softmax_xent as fx
    from analytics_zoo_tpu.ops.pallas import int8_matmul as im
    from analytics_zoo_tpu.ops.pallas import kernel_invocation_counts

    iters = 10 if quick else 50
    steps = 2 if quick else 3
    rng = np.random.default_rng(11)
    kernels = {}

    # -- fused_adam: fallback bitwise vs optax, interpret vs optax -----
    params = {"w": jnp.asarray(rng.normal(size=(256, 128)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32),
        params)

    def run(tx, n):
        state = tx.init(params)
        p = params
        for _ in range(n):
            upd, state = tx.update(grads, state, p)
            p = optax.apply_updates(p, upd)
        return p

    p_ref = run(optax.adam(1e-3), steps)
    p_fb = run(fa.fused_adam(1e-3), steps)
    bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                        jax.tree_util.tree_leaves(p_fb)))
    os.environ["ZOO_KERNEL_INTERPRET"] = "1"
    try:
        p_int = run(fa.fused_adam(1e-3), steps)
    finally:
        os.environ.pop("ZOO_KERNEL_INTERPRET", None)
    interp_err = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                        jax.tree_util.tree_leaves(p_int)))
    n_adam = 4096
    g1 = jnp.asarray(rng.normal(size=(n_adam,)), jnp.float32)
    scal = jnp.asarray([1e-3, 0.9, 0.999, 1e-8, 0.1, 0.001], jnp.float32)
    kernels["fused_adam"] = {
        "parity": {"fallback_bitwise_vs_optax": bool(bitwise),
                   "interpret_max_abs_err": interp_err,
                   "tolerance": 1e-5},
        "bytes": _kernel_lowered_bytes(
            "fused_adam",
            lambda g, m, n, s: fa._adam_leaf_pallas(g, m, n, s, False),
            (g1, g1 * 0, g1 * 0 + 1e-4, scal),
            kernel_bytes("fused_adam", n=n_adam)["kernel"]),
        "timing": _kernel_timed_leg(
            "fused_adam", fa._adam_leaf_reference,
            (g1, g1 * 0, g1 * 0 + 1e-4, scal), iters),
    }

    # -- fused_softmax_xent: interpret fwd+grad vs the jnp oracle ------
    bsz, vocab = 128, 2048
    logits = jnp.asarray(rng.normal(size=(bsz, vocab)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, vocab, size=(bsz,)), jnp.int32)

    def loss_mean(x):
        return fx.softmax_xent(x, labels).mean()

    ref_loss, ref_lse = fx._reference_fwd(logits, labels)
    ref_dx = fx._reference_bwd(logits, labels, ref_lse,
                               jnp.full((bsz,), 1.0 / bsz))
    os.environ["ZOO_KERNEL_INTERPRET"] = "1"
    try:
        int_loss = fx.softmax_xent(logits, labels)
        int_dx = jax.grad(loss_mean)(logits)
    finally:
        os.environ.pop("ZOO_KERNEL_INTERPRET", None)
    kernels["fused_softmax_xent"] = {
        "parity": {
            "interpret_fwd_max_abs_err": float(
                np.max(np.abs(np.asarray(int_loss - ref_loss)))),
            "interpret_bwd_max_abs_err": float(
                np.max(np.abs(np.asarray(int_dx - ref_dx)))),
            "tolerance": 1e-4},
        "bytes": _kernel_lowered_bytes(
            "fused_softmax_xent",
            lambda x, l: fx._fwd_pallas(x, l, False),
            (logits, labels),
            kernel_bytes("fused_softmax_xent", batch=bsz,
                         vocab=vocab)["kernel"]),
        "timing": _kernel_timed_leg(
            "fused_softmax_xent",
            lambda x, l: fx._reference_fwd(x, l)[0],
            (logits, labels), iters),
    }

    # -- int8_matmul: interpret vs dequantize-then-dot -----------------
    m_, k_, n_ = 128, 256, 128
    x8 = jnp.asarray(rng.normal(size=(m_, k_)), jnp.float32)
    w8 = jnp.asarray(rng.integers(-127, 128, size=(k_, n_)), jnp.int8)
    s8 = jnp.asarray(rng.uniform(0.01, 0.1, size=(n_,)), jnp.float32)
    ref_mm = im._reference(x8, w8, s8)
    os.environ["ZOO_KERNEL_INTERPRET"] = "1"
    try:
        int_mm = im.int8_matmul(x8, w8, s8)
    finally:
        os.environ.pop("ZOO_KERNEL_INTERPRET", None)
    denom = float(np.max(np.abs(np.asarray(ref_mm)))) or 1.0
    kernels["int8_matmul"] = {
        "parity": {
            "interpret_max_rel_err": float(
                np.max(np.abs(np.asarray(int_mm - ref_mm)))) / denom,
            "tolerance": 1e-4},
        "bytes": _kernel_lowered_bytes(
            "int8_matmul",
            lambda x, w, s: im._matmul_pallas(x, w, s, False),
            (x8, w8, s8),
            kernel_bytes("int8_matmul", m=m_, k=k_, n=n_)["kernel"]),
        "timing": _kernel_timed_leg(
            "int8_matmul", im._reference, (x8, w8, s8), iters),
    }

    # -- per-platform verdicts: CPU declines, TPU picks by bytes -------
    sizes = {
        "fused_adam": {"n": n_adam},
        "fused_softmax_xent": {"batch": bsz, "vocab": vocab},
        "int8_matmul": {"m": m_, "k": k_, "n": n_},
        "flash": {"batch": 8, "heads": 12, "seq": 512, "head_dim": 64},
    }
    verdicts = {}
    for platform in ("cpu", "tpu-v4"):
        oracle = ConfigOracle(peaks=resolve_peaks(platform))
        verdicts[platform] = {
            name: {"choice": v["choice"], "reason": v["reason"],
                   "predicted_bytes": v["predicted_bytes"]}
            for name, v in oracle.choose_kernels(
                sizes, platform=platform).items()}
    cpu_declines = sum(1 for v in verdicts["cpu"].values()
                      if v["choice"] == "xla")

    max_bytes_rel = max(
        kernels[k]["bytes"].get("rel_error", 1.0)
        for k in ("fused_adam", "fused_softmax_xent"))
    doc = {
        "metric": "cross_lowered_custom_call_bytes_max_rel_error",
        "unit": "ratio (lower is better; target <= 0.05)",
        "value": round(max_bytes_rel, 6),
        "kernels": kernels,
        "verdicts": verdicts,
        "cpu_xla_picks": int(cpu_declines),
        "invocation_counts": kernel_invocation_counts(),
        "platform": "cpu",
        "quick": bool(quick),
        "note": ("CPU tier: parity runs the Pallas kernels in interpret "
                 "mode against the jnp fallback oracle; bytes are "
                 "MEASURED from genuine Mosaic cross-lowering "
                 "(lower(platforms=('tpu',)), no chip) and must match "
                 "costmodel.kernel_bytes; throughput A/B on real TPU "
                 "HBM is future work — the verdicts record what the "
                 "oracle would pick there"),
    }
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_KERNEL_r17.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _kernels_main(argv):
    # single-process CPU: interpret-mode parity + cross-lowering need no
    # mesh, and the kernel_* labels must land in one compile cache
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(kernels_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --fleet: multi-replica serving fleet bench (serving/fleet.py).  No real
# model — the replicas serve the synthetic sleep model (per-RECORD
# GIL-releasing service time, like device inference), so the bench
# measures the CONTROL PLANE: the exactly-once claim protocol's
# scaling efficiency and the SLO autoscaler's response to a load step.
# Emits BENCH_FLEET_r09.json so the gains are pinned, not asserted.
# ---------------------------------------------------------------------------


def _fleet_controller(broker, replicas: int, service_ms: float,
                      batch_size: int = 8, budget_ms: float = 5.0,
                      scaler=None, interval: float = 0.5,
                      slo_p99_ms: float = 500.0):
    from analytics_zoo_tpu.serving import ClusterServingHelper
    from analytics_zoo_tpu.serving.fleet import (
        FleetController,
        _SyntheticModel,
    )
    from analytics_zoo_tpu.serving.scaler import SloScaler

    helper = ClusterServingHelper(
        model_path=None, batch_size=batch_size, batch_budget_ms=budget_ms,
        lease_ms=5_000, log_dir=os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "zoo-fleet-bench"))
    if scaler is None:  # fixed-size fleet: min == max pins the count
        scaler = SloScaler(slo_p99_ms=slo_p99_ms, min_replicas=replicas,
                           max_replicas=replicas)
    return FleetController(
        helper, broker, model_factory=lambda: _SyntheticModel(service_ms),
        scaler=scaler, interval=interval)


def fleet_scaling_bench(quick: bool = False) -> dict:
    """Saturated-backlog drain: wall-clock throughput of a 2-replica
    fleet vs 1 replica over ONE shared broker.  The claim protocol is
    the only coordination; >= 1.8x means leases + continuous batching
    cost < 10% of the doubled service capacity."""
    import numpy as np

    from analytics_zoo_tpu.serving import InMemoryBroker, InputQueue, \
        OutputQueue

    service_ms = 2.0
    n_records = 300 if quick else 1200
    out = {"service_ms_per_record": service_ms, "records": n_records,
           "throughput_rps": {}}
    for replicas in (1, 2):
        broker = InMemoryBroker()
        inq = InputQueue(broker=broker)
        rec = np.zeros((8,), np.float32)
        for i in range(n_records):
            inq.enqueue(f"u{i}", rec)
        ctrl = _fleet_controller(broker, replicas, service_ms)
        outq = OutputQueue(broker=broker)
        got = 0
        t0 = time.perf_counter()
        ctrl.start()
        deadline = t0 + 300.0
        while got < n_records and time.perf_counter() < deadline:
            got += len(outq.dequeue())
            time.sleep(0.005)
        wall = time.perf_counter() - t0
        ctrl.stop()
        if got != n_records:
            raise RuntimeError(
                f"fleet of {replicas} served {got}/{n_records}")
        out["throughput_rps"][str(replicas)] = round(n_records / wall, 1)
    out["scaling_2x_vs_1x"] = round(
        out["throughput_rps"]["2"] / out["throughput_rps"]["1"], 3)
    return out


def fleet_slo_bench(quick: bool = False) -> dict:
    """Offered-load step through the AUTOSCALING fleet: light traffic →
    overload (≈2.5x one replica's capacity) → light again.  Reports the
    client-observed p99 per load phase, the replica-count timeline, and
    the scaler's decision log — the acceptance story is p99 back under
    the SLO after scale-up, and replicas back at min after the load
    drops."""
    import threading

    import numpy as np

    from analytics_zoo_tpu.serving import InMemoryBroker, InputQueue, \
        OutputQueue
    from analytics_zoo_tpu.serving.scaler import SloScaler

    service_ms = 8.0  # one replica saturates at ~125 rec/s
    slo_p99_ms = 400.0
    interval = 0.25 if quick else 0.5
    phases = [("light", 2.0 if quick else 4.0, 30.0),
              ("overload", 6.0 if quick else 12.0, 300.0),
              ("light_again", 4.0 if quick else 8.0, 30.0)]
    # down_windows is the scale-down STABILIZATION window (the HPA
    # convention: minutes in production, seconds here): once the scaled-
    # up fleet drains the burst it reads slack, and the window must
    # outlast the rest of the overload phase or the fleet flaps down
    # into a marginal capacity that rebuilds the backlog
    scaler = SloScaler(slo_p99_ms=slo_p99_ms, min_replicas=1,
                       max_replicas=4, up_windows=2,
                       down_windows=18 if quick else 22)
    broker = InMemoryBroker()
    ctrl = _fleet_controller(broker, 1, service_ms, scaler=scaler,
                             interval=interval, slo_p99_ms=slo_p99_ms)
    inq = InputQueue(broker=broker)
    outq = OutputQueue(broker=broker)
    enq_ts: dict = {}
    lat: dict = {}  # uri -> (phase, latency_s)
    phase_of: dict = {}
    timeline = []
    stop = threading.Event()

    def collector():
        while not stop.is_set():
            now = time.perf_counter()
            for uri in outq.dequeue():
                t0 = enq_ts.get(uri)
                if t0 is not None:
                    lat[uri] = (phase_of[uri], now - t0)
            time.sleep(0.004)

    def sampler():
        t_start = time.perf_counter()
        while not stop.is_set():
            timeline.append({
                "t_s": round(time.perf_counter() - t_start, 2),
                "replicas": ctrl.replica_count(),
                "backlog": broker.unclaimed("image_stream"),
            })
            time.sleep(interval)

    ctrl.start()
    ct = threading.Thread(target=collector, daemon=True)
    st = threading.Thread(target=sampler, daemon=True)
    ct.start()
    st.start()
    rec = np.zeros((8,), np.float32)
    seq = 0
    phase_windows = {}
    for phase, duration, rate in phases:
        t_phase = time.perf_counter()
        phase_windows[phase] = [t_phase, t_phase + duration]
        while time.perf_counter() - t_phase < duration:
            uri = f"q{seq}"
            seq += 1
            phase_of[uri] = phase
            enq_ts[uri] = time.perf_counter()
            inq.enqueue(uri, rec)
            # paced offered load (sleep-based, so the achieved rate is
            # slightly under `rate` — the backlog signal is what counts)
            time.sleep(1.0 / rate)
    # drain: everything enqueued must come back before the report
    deadline = time.perf_counter() + 120.0
    while len(lat) < seq and time.perf_counter() < deadline:
        time.sleep(0.05)
    # let the scaler see the slack windows and come back down
    down_deadline = time.perf_counter() + (15.0 if quick else 30.0)
    while ctrl.replica_count() > 1 and time.perf_counter() < down_deadline:
        time.sleep(0.1)
    final_replicas = ctrl.replica_count()
    decisions = ctrl.decision_log()
    max_replicas_seen = max(
        [t["replicas"] for t in timeline] +
        [d["new"] for d in decisions if d["action"] == "up"] + [1])
    stop.set()
    ct.join(timeout=5)
    st.join(timeout=5)
    ctrl.stop()

    def p99(vals):
        if not vals:
            return None
        vals = sorted(vals)
        return round(vals[min(len(vals) - 1,
                              int(0.99 * len(vals)))] * 1e3, 1)

    by_phase = {}
    for phase, _, rate in phases:
        vals = [v for p, v in lat.values() if p == phase]
        by_phase[phase] = {"offered_rps": rate, "requests": len(vals),
                           "client_p99_ms": p99(vals)}
    # the SLO story: requests arriving in the LAST third of the overload
    # phase (post scale-up) vs the first third (pre scale-up)
    t0o, t1o = phase_windows["overload"]
    third = (t1o - t0o) / 3.0
    early, late = [], []
    for uri, (p, v) in lat.items():
        if p != "overload":
            continue
        ts = enq_ts[uri]
        if ts < t0o + third:
            early.append(v)
        elif ts > t1o - third:
            late.append(v)
    return {
        "service_ms_per_record": service_ms,
        "slo_p99_ms": slo_p99_ms,
        "phases": by_phase,
        "overload_early_p99_ms": p99(early),
        "overload_late_p99_ms": p99(late),
        "slo_held_after_scaleup": (p99(late) or 1e9) <= slo_p99_ms,
        "scaled_up": max_replicas_seen > 1,
        "scaled_down_after": final_replicas == 1,
        "max_replicas_seen": max_replicas_seen,
        "final_replicas": final_replicas,
        "replica_timeline": timeline,
        "decisions": [
            {k: d[k] for k in ("action", "old", "new", "reason",
                               "est_p99_ms", "queue_depth")}
            for d in decisions],
    }


def fleet_bench(quick: bool = False, out_path: str | None = None) -> dict:
    """Both fleet benches; writes BENCH_FLEET_r09.json."""
    doc = {
        "metric": "fleet_throughput_scaling_and_slo_step",
        "unit": "2-replica/1-replica throughput ratio",
        "platform": "cpu",
        "quick": bool(quick),
        "scaling": fleet_scaling_bench(quick=quick),
        "slo_step": fleet_slo_bench(quick=quick),
    }
    doc["value"] = doc["scaling"]["scaling_2x_vs_1x"]
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_FLEET_r09.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _fleet_main(argv):
    # control-plane bench: host threads + sleep models, CPU is the point
    os.environ["JAX_PLATFORMS"] = "cpu"
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(fleet_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --dispatch: fused multi-step dispatch + compile plane bench
# (ZOO_STEPS_PER_DISPATCH / ZOO_COMPILE_CACHE; docs/performance.md).
# Two measurements on a deliberately dispatch-bound synthetic model (tiny
# Dense net, small batch — per-step compute is microseconds, so the
# Python→device round-trip dominates):
#   1. steps/sec for K ∈ {1, 4, 16}: how much lax.scan fusion amortizes
#      the per-step host overhead, plus a bitwise trajectory-equality
#      check (the K>1 contract);
#   2. cold vs warm time-to-first-step in SUBPROCESSES sharing a
#      ZOO_COMPILE_CACHE dir (cold populates, warm deserializes), plus a
#      post-`estimator.warmup()` fit.
# Emits BENCH_DISPATCH_r07.json so the gain is pinned, not asserted.
# Forced to the CPU backend: this bench measures HOST dispatch overhead
# and compile persistence, not device compute.
# ---------------------------------------------------------------------------

DISPATCH_FEAT = 32
DISPATCH_CLASSES = 10


def _dispatch_model(width: int = 64, depth: int = 1):
    """The K-sweep uses the tiny default (dispatch-bound: per-step
    compute ≪ per-step host overhead).  The compile probe uses a DEEP
    stack (width 256 × 30) instead: there XLA compile is ~4× the
    trace+lower cost, which is the regime the persistent cache exists
    for — on a tiny model time-to-first-step is tracing-bound and no
    disk cache can help it."""
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    m = Sequential()
    m.add(Dense(width, activation="relu", input_shape=(DISPATCH_FEAT,)))
    for _ in range(depth - 1):
        m.add(Dense(width, activation="relu"))
    m.add(Dense(DISPATCH_CLASSES, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    return m


def _dispatch_data(n: int, seed: int = 5):
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, DISPATCH_FEAT)).astype("float32")
    y = rng.integers(0, DISPATCH_CLASSES, size=(n,)).astype("int32")
    return x, y


def dispatch_bench(ks=(1, 4, 16), n_batches: int = 384,
                   batch_size: int = 16, quick: bool = False,
                   compile_probe: bool = True,
                   out_path: str | None = None) -> dict:
    """K-sweep steps/sec + cold/warm compile seconds; writes the artifact.

    ``quick``: CI-sized run (fewer batches; also exercised by
    tests/test_dispatch.py so a fusion regression fails loudly).
    ``compile_probe=False`` skips the two compile-cache subprocesses
    (each pays a full jax import) — the quick-tier test does.
    """
    import tempfile

    import numpy as np

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.engine import ZooConfig

    if quick:
        n_batches = 128
    n_batches = max(ks) * (n_batches // max(ks))  # full chunks for every K
    x, y = _dispatch_data(n_batches * batch_size)

    results, trajectories = {}, {}
    for k in ks:
        zoo.init_zoo_context(ZooConfig(seed=11, steps_per_dispatch=k))
        m = _dispatch_model()
        # epoch 1 warms (trace + compile); epoch 2 is the timed
        # steady-state epoch (Keras continuation semantics)
        m.fit(x, y, batch_size=batch_size, nb_epoch=1)
        t0 = time.perf_counter()
        m.fit(x, y, batch_size=batch_size, nb_epoch=1)
        dt = time.perf_counter() - t0
        results[k] = {
            "steps_per_sec": round(n_batches / dt, 1),
            "dispatches_per_epoch": -(-n_batches // k),
            "epoch_s": round(dt, 4),
        }
        trajectories[k] = [h["loss"] for h in m._estimator.history]
    base = results[ks[0]]["steps_per_sec"]
    for k in ks:
        results[k]["speedup_vs_k1"] = round(
            results[k]["steps_per_sec"] / base, 3)

    doc = {
        "metric": "fused_dispatch_train_steps_per_sec",
        "unit": "steps/sec",
        "platform": "cpu",
        "batch_size": batch_size,
        "steps_per_epoch": n_batches,
        "sweep": {str(k): results[k] for k in ks},
        # the K>1 contract: identical loss trajectory, not just similar
        "loss_trajectory_bitwise_equal": all(
            trajectories[k] == trajectories[ks[0]] for k in ks),
    }

    if compile_probe:
        def probe_child(cache_dir, mode):
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env.pop("XLA_FLAGS", None)  # one stable cache key across runs
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--dispatch-child", cache_dir, mode],
                capture_output=True, text=True, timeout=600, env=env)
            if r.returncode != 0:
                raise RuntimeError(
                    f"dispatch child failed:\n{(r.stderr or '')[-2000:]}")
            return json.loads(r.stdout.strip().splitlines()[-1])

        with tempfile.TemporaryDirectory() as cache_dir:
            cold = probe_child(cache_dir, "fit")       # empty cache
            warm = probe_child(cache_dir, "fit")       # populated cache
        with tempfile.TemporaryDirectory() as cache_dir:
            warmed = probe_child(cache_dir, "warmup-fit")
        doc["compile_plane"] = {
            "cold_first_fit_s": cold["first_fit_s"],
            "warm_first_fit_s": warm["first_fit_s"],
            "warm_over_cold": round(
                warm["first_fit_s"] / max(cold["first_fit_s"], 1e-9), 3),
            "post_warmup_fit_s": warmed["first_fit_s"],
            "warmup_compile_s": warmed.get("warmup_compile_s"),
            "note": ("cold/warm: two fresh processes sharing one "
                     "ZOO_COMPILE_CACHE dir; warmup-fit: same-process "
                     "estimator.warmup() before the first fit"),
        }

    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_DISPATCH_r07.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _dispatch_child_main(argv):
    """Subprocess body for the cold/warm probe: time-to-first-step of a
    one-batch fit with the persistent compile cache at argv's dir."""
    cache_dir = argv[argv.index("--dispatch-child") + 1]
    mode = argv[argv.index("--dispatch-child") + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.engine import ZooConfig

    zoo.init_zoo_context(ZooConfig(seed=11, compile_cache=cache_dir))
    x, y = _dispatch_data(16)
    m = _dispatch_model(width=256, depth=30)
    out = {}
    if mode == "warmup-fit":
        m._estimator = m._make_estimator()
        t0 = time.perf_counter()
        secs = m._estimator.warmup({"x": x, "y": y})
        out["warmup_compile_s"] = round(time.perf_counter() - t0, 4)
        out["warmup_detail"] = {k: round(v, 4) for k, v in secs.items()}
    t0 = time.perf_counter()
    m.fit(x, y, batch_size=16, nb_epoch=1)
    out["first_fit_s"] = round(time.perf_counter() - t0, 4)
    print(json.dumps(out))


def _dispatch_main(argv):
    # measures host dispatch overhead; the CPU backend is the point, and
    # it also sidesteps the flaky TPU init entirely
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(dispatch_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --oracle: predictive compile plane bench (analysis/costmodel.py +
# analysis/oracle.py).  Two legs: (a) the oracle-primed K autotune on
# the dispatch-bound synthetic must settle within 5% of the best
# fixed-K throughput in <= 8 dispatches (the blind hill-climb needed
# ~53, BENCH_AUTOTUNE_r08) at a trajectory bitwise-equal to fixed K=1;
# (b) estimator.fit(plan="auto") under a pinned HBM budget must choose
# the same plan the exhaustive BENCH_PARTITION_r10 sweep measured as
# best-under-budget.  Every prediction is scored against its measured
# outcome.  Emits BENCH_ORACLE_r11.json.
# ---------------------------------------------------------------------------

#: per-chip budget (bytes) for the plan="auto" leg — between fsdp's
#: measured ~115 kB and zero1's ~384 kB per-chip footprint for the
#: partition model on 8 devices (BENCH_PARTITION_r10), so exactly one
#: plan fits and the exhaustive-vs-predicted comparison is
#: deterministic on a CPU host whose throughput ranking is noise
ORACLE_PLAN_HBM_BUDGET = 200_000


def _oracle_k_leg(quick: bool) -> tuple[dict, object]:
    """Prior-primed K autotune vs fixed K legs on the dispatch-bound
    synthetic; returns (section, the ConfigOracle) so the caller can
    merge its prediction log into the artifact."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.analysis.oracle import ConfigOracle
    from analytics_zoo_tpu.common.engine import ZooConfig
    from analytics_zoo_tpu.feature.autotune import AutotuneController

    n_batches = 192 if quick else 384
    batch_size = 16
    x, y = _dispatch_data(n_batches * batch_size)

    def fixed(k):
        zoo.init_zoo_context(ZooConfig(seed=11, steps_per_dispatch=k))
        m = _dispatch_model()
        m.fit(x, y, batch_size=batch_size, nb_epoch=1)  # warm/compile
        t0 = time.perf_counter()
        m.fit(x, y, batch_size=batch_size, nb_epoch=1)
        dt = time.perf_counter() - t0
        return (round(n_batches / dt, 1),
                [h["loss"] for h in m._estimator.history])

    # fixed K=1 pins the reference trajectory; fixed K=16 is the best
    # hand-tuned throughput (the blind climb's converged K, r08)
    k1_sps, k1_losses = fixed(1)
    k16_sps, _ = fixed(16)

    zoo.init_zoo_context(ZooConfig(seed=11))
    oracle = ConfigOracle.from_env()
    ctrl = AutotuneController(oracle=oracle)
    m = _dispatch_model()
    # epoch 1 hosts the prior jump + neighbor validation (the compile
    # of each visited K included); epoch 2 is the timed steady state
    m.fit(x, y, batch_size=batch_size, nb_epoch=1, autotune=ctrl)
    t0 = time.perf_counter()
    m.fit(x, y, batch_size=batch_size, nb_epoch=1, autotune=ctrl)
    dt = time.perf_counter() - t0
    ctrl.stop()
    auto_losses = [h["loss"] for h in m._estimator.history]
    auto_sps = round(n_batches / dt, 1)
    cur = ctrl.current()
    # close the prediction->outcome pairs the fixed legs measured; the
    # settled K's pair was already closed at settle time and the timed
    # steady state is the fresher measurement for it
    oracle.record_outcome("k=1", k1_sps, consumer="bench")
    oracle.record_outcome("k=16", k16_sps, consumer="bench")
    oracle.record_outcome(f"k={cur['k']}", auto_sps, consumer="bench")
    return {
        "steps_per_epoch": n_batches,
        "batch_size": batch_size,
        "untuned_default_steps_per_sec": k1_sps,
        "best_fixed_k16_steps_per_sec": k16_sps,
        "prior_tuned_steady_steps_per_sec": auto_sps,
        "vs_best_fixed": round(auto_sps / max(k16_sps, 1e-9), 3),
        "within_5pct_of_best": auto_sps >= 0.95 * k16_sps,
        "converged_k": cur["k"],
        "k_settled": cur["k_settled"],
        # tuning observations only — in-flight chunks queued before a
        # K switch keep their old size (pipeline latency, not search)
        "dispatches_to_converge": cur["k_settle_dispatch"],
        "total_dispatches_observed": cur["dispatches_observed"],
        "loss_trajectory_bitwise_equal_to_k1": auto_losses == k1_losses,
        "decisions": [
            {k: d[k] for k in ("knob", "old", "new", "reason")}
            for d in ctrl.decision_log()],
    }, oracle


def _oracle_blind_reference(quick: bool) -> dict:
    """Dispatches-to-converge without the prior.  The full tier
    re-measures the blind hill-climb; quick reuses the number the
    autotune bench already pinned (BENCH_AUTOTUNE_r08.json) instead of
    paying the ~53-dispatch climb again in CI."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_AUTOTUNE_r08.json")
    if quick:
        try:
            with open(path) as f:
                doc = json.load(f)
            return {
                "dispatches_to_converge":
                    doc["dispatch"]["dispatches_to_converge"],
                "source": os.path.basename(path),
            }
        except (OSError, ValueError, KeyError):
            return {"dispatches_to_converge": None,
                    "source": f"{os.path.basename(path)} (unreadable)"}
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.engine import ZooConfig
    from analytics_zoo_tpu.feature.autotune import AutotuneController

    n_batches = 384
    x, y = _dispatch_data(n_batches * 16)
    zoo.init_zoo_context(ZooConfig(seed=11))
    ctrl = AutotuneController()  # no oracle: the blind hill-climb
    m = _dispatch_model()
    m.fit(x, y, batch_size=16, nb_epoch=2, autotune=ctrl)
    ctrl.stop()
    cur = ctrl.current()
    return {"dispatches_to_converge": cur["k_settle_dispatch"],
            "converged_k": cur["k"], "source": "measured"}


def _oracle_plan_leg(epochs: int) -> dict:
    """estimator.fit(plan="auto") with the HBM budget pinned via
    ZOO_ORACLE_PEAKS; returns the resolved plan + the oracle's
    candidate table from the estimator's plan record."""
    import analytics_zoo_tpu as zoo

    prior = os.environ.get("ZOO_ORACLE_PEAKS")
    os.environ["ZOO_ORACLE_PEAKS"] = json.dumps(
        {"hbm_bytes": ORACLE_PLAN_HBM_BUDGET})
    try:
        zoo.init_zoo_context(seed=11, mesh_shape={"data": 8},
                             platform="cpu")
        x, y = _partition_data()
        m = _partition_model()
        t0 = time.perf_counter()
        m.fit(x, y, batch_size=64, nb_epoch=epochs, plan="auto")
        dt = time.perf_counter() - t0
        est = m._estimator
        return {
            "resolved_plan": est._plan_record["name"],
            "steps": int(est.global_step),
            "steps_per_sec": round(
                est.global_step / max(dt, 1e-9), 2),
            "auto": est._plan_record.get("auto"),
        }
    finally:
        if prior is None:
            os.environ.pop("ZOO_ORACLE_PEAKS", None)
        else:
            os.environ["ZOO_ORACLE_PEAKS"] = prior


def oracle_bench(quick: bool = False,
                 out_path: str | None = None) -> dict:
    """Both oracle legs + prediction scoring; writes
    BENCH_ORACLE_r11.json."""
    k_leg, oracle = _oracle_k_leg(quick)
    blind = _oracle_blind_reference(quick)
    plan_leg = _oracle_plan_leg(epochs=1 if quick else 2)

    # exhaustive reference: the measured per-plan sweep from the
    # partition bench — best-under-budget by measured steps/sec must
    # match what the oracle predicted without running the sweep
    budget = ORACLE_PLAN_HBM_BUDGET
    r10_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_PARTITION_r10.json")
    exhaustive_best, chip_bytes_error = None, {}
    try:
        with open(r10_path) as f:
            r10 = json.load(f)
        legs = r10.get("legs") or {}
        feasible = {name: leg for name, leg in legs.items()
                    if leg["per_chip_param_opt_bytes"] <= budget}
        if feasible:
            exhaustive_best = max(
                feasible, key=lambda n: feasible[n]["steps_per_sec"])
        from analytics_zoo_tpu.analysis.costmodel import predict_chip_bytes

        rec = plan_leg.get("auto") or {}
        for cand in rec.get("candidates", []):
            # r10 measured param+opt state only, so score it against the
            # activations-excluded prediction (the full-memory-plan
            # candidates above additionally carry the activation/remat
            # terms the sweep never measured)
            leg = legs.get(cand["plan"])
            if leg is None or cand["remat"] is not None:
                continue
            measured = leg["per_chip_param_opt_bytes"]
            predicted = predict_chip_bytes(
                rec["param_bytes"], rec["opt_bytes"], cand["plan"],
                rec["n_shards"])
            chip_bytes_error[cand["plan"]] = {
                "predicted_chip_bytes": predicted,
                "measured_chip_bytes": measured,
                "rel_error": round(
                    abs(predicted - measured) / max(measured, 1), 4),
            }
            oracle.record_outcome(f"plan={cand['plan']}",
                                  leg["steps_per_sec"], consumer="bench")
    except (OSError, ValueError, KeyError):
        r10_path = None

    # score the plan predictions on the bench's own oracle so the
    # artifact's prediction table covers both consumers (the estimator
    # leg used its own per-process oracle instance)
    auto_rec = plan_leg.get("auto") or {}
    if auto_rec:
        oracle.choose_plan(auto_rec["param_bytes"], auto_rec["opt_bytes"],
                           auto_rec["n_shards"], hbm_budget=budget)

    doc = {
        "metric": "oracle_prior_dispatches_to_converge",
        "unit": "dispatches to K-settle (target <= 8; blind ~53)",
        "value": k_leg["dispatches_to_converge"],
        "platform": "cpu",
        "quick": bool(quick),
        "k_prior": {**k_leg, "blind": blind},
        "plan_auto": {
            "hbm_budget_bytes": budget,
            "chosen": plan_leg["resolved_plan"],
            # the r10 sweep measured sharding only, so exhaustive
            # agreement is on the base plan; the remat suffix (swept
            # against the activation estimate, which r10 excludes) is
            # recorded in "chosen" above
            "chosen_base_plan": plan_leg["resolved_plan"].split("+")[0],
            "exhaustive_best_under_budget": exhaustive_best,
            "agrees_with_exhaustive": (
                None if exhaustive_best is None
                else plan_leg["resolved_plan"].split("+")[0]
                == exhaustive_best),
            "exhaustive_source": (os.path.basename(r10_path)
                                  if r10_path else None),
            "predicted_vs_measured_chip_bytes": chip_bytes_error,
            "leg": plan_leg,
        },
        "predictions": oracle.prediction_log(),
        "oracle": oracle.to_doc() | {"predictions": None},
    }
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_ORACLE_r11.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _oracle_main(argv):
    # CPU host, 8-device mesh: the K leg measures host dispatch
    # overhead and the plan leg needs the 8-way axis to shard over
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(oracle_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --overlap: the latency-hiding plane (ISSUE 15) — serial two-phase vs
# bucketed fused step on a comm-bound synthetic over the 8-device CPU
# mesh, checkpoint-stall sync vs async saves, and the overlap-aware
# roofline validated against the measured legs.  Emits
# BENCH_OVERLAP_r13.json.  The quick tier is the acceptance guard
# (tests/test_overlap.py): bucketed <= 0.85x serial at a BITWISE param
# trajectory, and async checkpoint stall p99 < 0.2x the synchronous
# save.
#
# What "serial" means on a 1-core emulated mesh: there is no device
# parallelism to overlap against, so the legs measure HOST-level
# latency hiding — the serial leg is the naive two-phase loop (backward
# dispatch, blocking host sync so the grads are materialized before the
# per-bucket reduction dispatches, sync again, THEN assemble the next
# feed: exactly the `host-sync` in-loop anti-pattern zoolint flags),
# while the bucketed leg issues ONE fused dispatch with the
# barrier-chained per-bucket psum_scatter and assembles the next feed
# while the device runs.  Both legs reduce over the SAME chunk
# boundaries with an elementwise update, so the parameter trajectory is
# bitwise identical and the time difference is pure dispatch/sync/feed
# stall.
# ---------------------------------------------------------------------------


def _overlap_comm_leg(plan_name, steps, dim=1 << 16, n_chunks=4,
                      lr=0.05):
    """Serial two-phase vs bucketed fused step for one plan family
    ("zero2": params replicated, grads bucket-reduce-scattered;
    "zero3": params stored sharded, gather-on-use with a
    prefetch-style barrier chain).  Returns measured p50s, the bitwise
    trajectory verdict and the fused program's HLO features."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.shard_map import shard_map
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from analytics_zoo_tpu.analysis.hlo import last_features
    from analytics_zoo_tpu.common.compile_cache import timed_compile

    n = 8
    mesh = jax.make_mesh((n,), ("data",))
    cm = dim // n_chunks
    m = cm // n                      # one device's slice of one bucket
    slices = [(i * cm, (i + 1) * cm) for i in range(n_chunks)]
    sharded = plan_name == "zero3"
    x_sharding = NamedSharding(mesh, P("data", None))
    meta = {"plan": plan_name, "mesh_shape": {"data": n},
            "steps_per_dispatch": 1,
            # these legs regather parameters by design (zero2 rebuilds
            # the replicated vector from its updated shard pieces,
            # zero3 gathers before backward), so all_gather is expected
            "expected_collectives": ("all_reduce", "all_gather",
                                     "collective_permute",
                                     "reduce_scatter")}

    base = np.arange(n * dim, dtype=np.float32).reshape(n, dim)

    def feed(step):
        # the per-step host data plane: deterministic batch assembly
        # on host, then the H2D put — the work the bucketed leg hides
        # behind the in-flight fused dispatch
        return jax.device_put(np.sin(base * 1e-3 + step * 0.13),
                              x_sharding)

    def local_grad(w, x):
        # analytic elementwise gradient of 0.5*mean((w-x)^2): no
        # cross-element reductions feed the update, so XLA cannot
        # reorder the math between the two differently-fused programs
        # — the bitwise pin is structural, not lucky
        return (w - x) * (2.0 / dim), jnp.sum((w - x) ** 2) / dim

    def gather_params(w_sh, chained):
        # zero3 forward: regather the per-bucket param pieces
        # (gather-on-use); the bucketed leg chains them with barriers —
        # the double-buffered prefetch schedule pinned at HLO level
        token, chunks = None, []
        for k in range(n_chunks):
            piece = w_sh[0, k * m:(k + 1) * m]
            if chained and token is not None:
                piece, token = jax.lax.optimization_barrier(
                    (piece, token))
            full = jax.lax.all_gather(piece, "data", tiled=True)
            token = full
            chunks.append(full)
        return jnp.concatenate(chunks)

    def reduce_chunk(chunk):
        return jax.lax.psum_scatter(
            chunk, "data", scatter_dimension=0, tiled=True) / n

    def updated_piece(w, w_sh, red, k, lo):
        # elementwise SGD on this device's slice of bucket k
        if sharded:
            return w_sh[0, k * m:(k + 1) * m] - lr * red
        idx = jax.lax.axis_index("data")
        return jax.lax.dynamic_slice(w, (lo + idx * m,), (m,)) \
            - lr * red

    # ---- serial (two-phase) programs -------------------------------
    def bwd_body(w, x):
        if sharded:
            w = gather_params(w, chained=False)
        g, loss = local_grad(w, x[0])
        return g[None], jax.lax.psum(loss, "data")[None] / n

    w_spec = P("data", None) if sharded else P()

    def make_red_chunk(k, lo, hi):
        def body(w, g):
            red = reduce_chunk(g[0][lo:hi])
            piece = updated_piece(w, w, red, k, lo)
            if sharded:
                return piece[None]
            return jax.lax.all_gather(piece, "data", tiled=True)
        out = P("data", None) if sharded else P()
        return shard_map(body, mesh=mesh, in_specs=(w_spec, P("data", None)),
                         out_specs=out, check_rep=False)

    def concat_fn(*chunks):
        return jnp.concatenate(chunks, axis=1 if sharded else 0)

    # ---- bucketed (fused) program ----------------------------------
    def fused_body(w_in, x):
        w = gather_params(w_in, chained=True) if sharded else w_in
        g, loss = local_grad(w, x[0])
        token, outs = None, []
        for k, (lo, hi) in enumerate(slices):
            c = g[lo:hi]
            if token is not None:
                # issue-order pin: bucket k's reduce-scatter is chained
                # behind bucket k-1's, matching the
                # backward-completion order plan.constrain_grads pins
                c, token = jax.lax.optimization_barrier((c, token))
            red = reduce_chunk(c)
            token = red
            piece = updated_piece(w, w_in, red, k, lo)
            outs.append(piece[None] if sharded else
                        jax.lax.all_gather(piece, "data", tiled=True))
        new_w = jnp.concatenate(outs, axis=1 if sharded else 0)
        return new_w, jax.lax.psum(loss, "data")[None] / n

    f_bwd = jax.jit(shard_map(
        bwd_body, mesh=mesh, in_specs=(w_spec, P("data", None)),
        out_specs=(P("data", None), P("data")), check_rep=False))
    f_red = [jax.jit(make_red_chunk(k, lo, hi))
             for k, (lo, hi) in enumerate(slices)]
    f_concat = jax.jit(concat_fn)
    f_fused = jax.jit(shard_map(
        fused_body, mesh=mesh, in_specs=(w_spec, P("data", None)),
        out_specs=((P("data", None) if sharded else P()), P("data")),
        check_rep=False))

    def w0():
        full = np.cos(np.arange(dim, dtype=np.float32) * 2e-3)
        if not sharded:
            return jax.device_put(jnp.asarray(full),
                                  NamedSharding(mesh, P()))
        # zero3 storage: device i's row = the concat of its m-slices
        # of each bucket (the strategies._shard_of chip layout)
        rows = np.stack([
            np.concatenate([full[lo + i * m: lo + (i + 1) * m]
                            for lo, _ in slices])
            for i in range(n)])
        return jax.device_put(jnp.asarray(rows), x_sharding)

    # every program through the one compile choke point, under its own
    # label — the gather-prefetch chain shows up in the fused report's
    # async/collective features
    x0, w_init = feed(0), w0()
    label = f"overlap_{plan_name}"
    exe_bwd = timed_compile(f_bwd.lower(w_init, x0),
                            f"{label}_serial_bwd", meta=meta)
    g0, _ = exe_bwd(w_init, x0)
    exe_red = [timed_compile(f.lower(w_init, g0),
                             f"{label}_serial_red{k}", meta=meta)
               for k, f in enumerate(f_red)]
    pieces0 = [e(w_init, g0) for e in exe_red]
    exe_concat = timed_compile(f_concat.lower(*pieces0),
                               f"{label}_serial_concat", meta=meta)
    exe_fused = timed_compile(
        f_fused.lower(w_init, x0), f"{label}_bucketed",
        meta=dict(meta, plan=f"{plan_name}+overlap"))

    warmup = 2

    def run_serial():
        w, x = w0(), feed(0)
        losses, times = [], []
        for s in range(steps + warmup):
            t0 = time.perf_counter()
            g, loss = exe_bwd(w, x)
            jax.block_until_ready(g)   # grads must land before the
            # per-bucket reduction dispatches can be issued
            pieces = [e(w, g) for e in exe_red]
            w = exe_concat(*pieces)
            jax.block_until_ready(w)   # naive loop: sync, THEN feed
            x = feed(s + 1)
            if s >= warmup:
                times.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(loss)[0]))
        return np.asarray(w), losses, times

    def run_bucketed():
        w, x = w0(), feed(0)
        losses, times = [], []
        for s in range(steps + warmup):
            t0 = time.perf_counter()
            w, loss = exe_fused(w, x)  # one fused dispatch
            x = feed(s + 1)            # next feed hides behind it
            jax.block_until_ready(w)
            if s >= warmup:
                times.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(loss)[0]))
        return np.asarray(w), losses, times

    # backward-only micro-leg: the calibrated roofline's compute term
    def measure_bwd():
        w, x = w0(), feed(0)
        ts = []
        for s in range(steps + warmup):
            t0 = time.perf_counter()
            g, _ = exe_bwd(w, x)
            jax.block_until_ready(g)
            if s >= warmup:
                ts.append(time.perf_counter() - t0)
        return ts

    def p50(vals):
        return sorted(vals)[len(vals) // 2]

    ws, ls, ts = run_serial()
    wb, lb, tb = run_bucketed()
    t_bwd = p50(measure_bwd())
    return {
        "plan": plan_name,
        "devices": n,
        "param_elements": dim,
        "bucket_count": n_chunks,
        "steps_timed": steps,
        "serial_step_p50_s": round(p50(ts), 6),
        "bucketed_step_p50_s": round(p50(tb), 6),
        "bucketed_vs_serial": round(p50(tb) / max(p50(ts), 1e-12), 4),
        "backward_only_p50_s": round(t_bwd, 6),
        "trajectory_bitwise_equal": bool(np.array_equal(ws, wb)),
        "loss_max_abs_diff": max(
            abs(a - b) for a, b in zip(ls, lb)),
        "losses_first_last": [ls[0], ls[-1]],
        "hlo_fused": last_features(f"{label}_bucketed") or {},
    }


def _overlap_roofline_row(leg):
    """Close the predicted-vs-measured loop for one comm leg: calibrate
    the peak table so the ADDITIVE model reproduces the serial
    measurement exactly, then compare both models against the measured
    BUCKETED step.  The overlap-aware prediction must not be further
    from the measurement than the additive one (and on serial legs the
    two coincide by construction — no regression on compute-bound
    legs)."""
    from analytics_zoo_tpu.analysis.costmodel import (
        PeakTable,
        predict_step_seconds,
    )

    feats = dict(leg["hlo_fused"])
    coll_bytes = feats.get("zoo_hlo_collective_bytes",
                           feats.get("collective_bytes", 0)) or 1.0
    bytes_acc = feats.get("zoo_hlo_bytes_accessed",
                          feats.get("bytes_accessed", 0)) or 1.0
    c = max(leg["backward_only_p50_s"], 1e-6)
    m_serial = leg["serial_step_p50_s"]
    m_bucketed = leg["bucketed_step_p50_s"]
    coll_s = max(m_serial - c, 1e-6)
    peaks = PeakTable(
        flops=1e30, hbm_bytes_per_s=bytes_acc / c,
        link_bytes_per_s=coll_bytes / coll_s,
        dispatch_overhead_s=0.0, hbm_bytes=int(4e9))
    norm = {"matmul_flops": feats.get("matmul_flops", 0),
            "bytes_accessed": bytes_acc,
            "collective_bytes": coll_bytes}
    t_additive = predict_step_seconds(norm, k=1, peaks=peaks,
                                      exposed_fraction=1.0)
    t_overlap = predict_step_seconds(norm, k=1, peaks=peaks,
                                     plan=f"{leg['plan']}+overlap")
    t_serial_model = predict_step_seconds(norm, k=1, peaks=peaks,
                                          plan=leg["plan"])
    rel = lambda pred, meas: abs(pred - meas) / max(meas, 1e-12)  # noqa: E731
    return {
        "plan": leg["plan"],
        "measured_serial_s": m_serial,
        "measured_bucketed_s": m_bucketed,
        "predicted_additive_s": round(t_additive, 6),
        "predicted_overlap_s": round(t_overlap, 6),
        # serial leg: the overlap-aware model with exposed=1.0 IS the
        # additive model — identical prediction, identical error
        "serial_rel_error_additive": round(rel(t_additive, m_serial), 4),
        "serial_rel_error_overlap": round(
            rel(t_serial_model, m_serial), 4),
        "bucketed_rel_error_additive": round(
            rel(t_additive, m_bucketed), 4),
        "bucketed_rel_error_overlap": round(
            rel(t_overlap, m_bucketed), 4),
    }


def _overlap_ckpt_leg(saves, payload_mb=48):
    """Checkpoint-stall comparison: the SAME save cadence (a work gap
    sized from the measured synchronous save) under
    ZOO_ASYNC_CHECKPOINT=0 (inline gather+serialize+rename) vs the
    async default (device snapshot on the caller thread, write on the
    daemon).  Returns per-mode stall percentiles."""
    import shutil
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.pipeline.estimator.estimator import (
        _Checkpointer,
    )

    elems = payload_mb * (1 << 20) // 4
    payload = {
        "params": jnp.asarray(
            np.arange(elems, dtype=np.float32) * 1e-3),
        "step": 7,
    }

    def pct(vals, q):
        s = sorted(vals)
        return s[min(int(q * len(s)), len(s) - 1)]

    def run(mode):
        prev = os.environ.get("ZOO_ASYNC_CHECKPOINT")
        os.environ["ZOO_ASYNC_CHECKPOINT"] = mode
        root = tempfile.mkdtemp(prefix=f"ovl-ckpt-{mode}-")
        try:
            ck = _Checkpointer(path=root, keep=2)
            # one untimed warmup save per mode: the first save pays
            # one-off costs (writer-thread spawn, cold fs paths) that a
            # training run amortizes over thousands of steps — they are
            # not the steady-state stall this leg measures
            ck.save("warm", dict(payload, step=-1))
            warm_pending = getattr(ck, "_pending", None)
            if warm_pending is not None:
                warm_pending.join()
            stalls = []
            for i in range(saves):
                t0 = time.perf_counter()
                ck.save(f"s{i}", dict(payload, step=i))
                stalls.append(time.perf_counter() - t0)
                time.sleep(run.gap)
            pending = getattr(ck, "_pending", None)
            if pending is not None:
                pending.join()
            assert ck.latest() is not None
            return stalls
        finally:
            if prev is None:
                os.environ.pop("ZOO_ASYNC_CHECKPOINT", None)
            else:
                os.environ["ZOO_ASYNC_CHECKPOINT"] = prev
            shutil.rmtree(root, ignore_errors=True)

    run.gap = 0.0
    sync_stalls = run("0")
    # the async leg's inter-save "compute" gap: big enough that the
    # previous write drains before the next save joins it (1.5x the
    # measured sync save), so the measured stall is the true
    # caller-visible cost, not a back-to-back writer queue
    run.gap = 1.5 * pct(sync_stalls, 0.5)
    async_stalls = run("1")
    sync_p99, async_p99 = pct(sync_stalls, 0.99), pct(async_stalls, 0.99)
    return {
        "saves_per_mode": saves,
        "payload_mb": payload_mb,
        "sync_stall_p50_s": round(pct(sync_stalls, 0.5), 6),
        "sync_stall_p99_s": round(sync_p99, 6),
        "async_stall_p50_s": round(pct(async_stalls, 0.5), 6),
        "async_stall_p99_s": round(async_p99, 6),
        "async_vs_sync_p99": round(async_p99 / max(sync_p99, 1e-12), 4),
    }


def overlap_bench(quick: bool = False,
                  out_path: str | None = None) -> dict:
    """The latency-hiding plane's number: serial two-phase vs bucketed
    fused step (zero2/zero3 families) at a bitwise-pinned trajectory,
    checkpoint stall sync vs async, and the overlap-aware roofline
    validated per leg; writes BENCH_OVERLAP_r13.json."""
    steps = 6 if quick else 16
    legs = {name: _overlap_comm_leg(name, steps)
            for name in ("zero2", "zero3")}
    roofline = [_overlap_roofline_row(leg) for leg in legs.values()]
    ckpt = _overlap_ckpt_leg(saves=6 if quick else 12)
    worst = max(leg["bucketed_vs_serial"] for leg in legs.values())
    doc = {
        "metric": "bucketed_overlap_step_time_vs_serial_two_phase",
        "unit": "ratio (lower is better; target <= 0.85)",
        "value": worst,
        "trajectory_bitwise_equal": all(
            leg["trajectory_bitwise_equal"] for leg in legs.values()),
        "checkpoint": ckpt,
        "checkpoint_target": "async_vs_sync_p99 < 0.2",
        "roofline": roofline,
        "roofline_target": ("bucketed_rel_error_overlap <= "
                            "bucketed_rel_error_additive on every leg; "
                            "serial errors coincide by construction"),
        "devices": 8,
        "platform": "cpu",
        "quick": bool(quick),
        "legs": legs,
        "note": ("host-level latency hiding on the emulated mesh: the "
                 "serial leg is the naive two-phase loop (backward "
                 "dispatch, host sync, per-bucket reduction "
                 "dispatches, sync, then next feed); the bucketed leg "
                 "is ONE fused dispatch with the barrier-chained "
                 "bucket schedule and the feed assembled while the "
                 "device runs.  Same bucket boundaries + elementwise "
                 "update => bitwise-equal trajectories; the delta is "
                 "pure dispatch/sync/feed stall"),
    }
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_OVERLAP_r13.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _overlap_main(argv):
    # the 8-device CPU mesh is the point (dispatch structure, not FLOPs)
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(overlap_bench(**kwargs)))


def probe_backend(timeout: float, env: dict | None = None) \
        -> tuple[bool, str]:
    """Try `jax.devices()` in a subprocess with a hard timeout.

    ``env`` overrides the child environment — the flag-adoption helpers
    below probe with candidate XLA_FLAGS applied, so a flag the backend
    would fatally reject aborts only the probe child.  ``main()`` does
    NOT call this: a chip belongs to one process at a time, and a child
    that initialises the TPU before or beside this process takes it.
    """
    try:
        r = subprocess.run(
            [sys.executable, "-c", PROBE_CODE],
            capture_output=True, text=True, timeout=timeout,
            env=dict(os.environ) if env is None else env,
        )
    except subprocess.TimeoutExpired:
        return False, f"probe timed out after {timeout:.0f}s"
    if r.returncode != 0:
        tail = (r.stderr or "").strip().splitlines()
        return False, (tail[-1] if tail else f"probe rc={r.returncode}")
    return True, r.stdout.strip()


def peak_flops_for(device_kind: str) -> float | None:
    kind = device_kind.lower()
    for key, val in TPU_PEAK_FLOPS:
        if key in kind:
            return val
    return None


def host_fingerprint() -> dict:
    """Provenance block stamped into every ``--out`` artifact: cpu
    count, jax/jaxlib versions, platform/device kind and the resolved
    peak table.  The cost model's training join (analysis/costmodel.py)
    reads accumulated artifacts — numbers measured on a different
    host or toolchain must be distinguishable, not silently mixed.

    jax is consulted only when ALREADY imported: the data-pipeline
    bench is deliberately jax-free.
    """
    import importlib.metadata

    def _ver(dist):
        try:
            return importlib.metadata.version(dist)
        except Exception:  # noqa: BLE001 - absent dist => null, not a crash
            return None

    from analytics_zoo_tpu.common.compile_cache import adopted_flags

    fp = {
        "cpu_count": os.cpu_count(),
        "jax_version": _ver("jax"),
        "jaxlib_version": _ver("jaxlib"),
        "platform": os.environ.get("JAX_PLATFORMS") or "unknown",
        "device_kind": "",
        "xla_flags_adopted": list(adopted_flags()),
    }
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            dev = jax.devices()[0]
            fp["platform"] = dev.platform
            fp["device_kind"] = getattr(dev, "device_kind", "") or ""
        except Exception:  # noqa: BLE001 - backend init failure
            pass
    try:
        from analytics_zoo_tpu.analysis.costmodel import resolve_peaks

        fp["peak_table"] = resolve_peaks(
            fp["platform"], fp["device_kind"]).to_doc()
    except Exception:  # noqa: BLE001 - bad ZOO_ORACLE_PEAKS etc.
        fp["peak_table"] = None
    return fp


def adopt_sweep_flags(probe=probe_backend, probe_timeout: float = 150.0,
                      path: str | None = None):
    """If an XLA flag sweep artifact (``FLAGSWEEP_r05.json``) names a
    combo beating baseline by >=1%, adopt its flags.  Must run BEFORE
    any jax import: XLA_FLAGS is read at backend init.  Returns the
    adopted combo name or None.

    The candidate flags are VALIDATED in a probe subprocess with
    XLA_FLAGS applied before this process commits to them: `xla_tpu_*`
    flags are a fatal 'Unknown flag' abort on the CPU backend, so if the
    flagged probe fails or lands on a non-TPU platform, adoption is
    skipped.  ``main()`` does not call this (one process per chip: the
    probe child would hold the TPU); kept with its tests for ROADMAP D1
    to judge."""
    if path is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "FLAGSWEEP_r05.json")
    try:
        with open(path) as f:
            sweep = json.load(f)
    except (OSError, ValueError):
        return None
    best, gain = sweep.get("best"), sweep.get("gain_pct")
    if not best or best == "baseline" or not gain or gain < 1.0:
        return None
    flags = sweep["results"][best]["flags"]
    candidate = (os.environ.get("XLA_FLAGS", "") + " " + flags).strip()
    ok, detail = probe(probe_timeout,
                       env=dict(os.environ, XLA_FLAGS=candidate))
    if not ok or not detail.startswith("tpu"):
        return None
    os.environ["XLA_FLAGS"] = candidate
    from analytics_zoo_tpu.common.compile_cache import (
        record_adopted_flags,
    )

    record_adopted_flags(flags.split())
    return f"{best} (+{gain}%)"


#: the XLA latency-hiding scheduler set (ISSUE 15): split collectives
#: into start/done pairs and let the scheduler hoist the starts behind
#: compute.  TPU-backend flags — a fatal 'Unknown flag' abort on CPU,
#: hence the same probe-validated, tpu-only adoption as the sweep
#: winners above.  Like them, not called by ``main()``.
LATENCY_HIDING_FLAGS = {
    "tpu": ("--xla_tpu_enable_latency_hiding_scheduler=true",
            "--xla_tpu_enable_async_collective_fusion=true"),
}


def adopt_latency_hiding_flags(probe=probe_backend,
                               probe_timeout: float = 150.0):
    """Adopt the async-collective / latency-hiding scheduler flag set,
    per-platform and only when a probe subprocess
    WITH the flags applied still initializes a TPU (the
    adopt_sweep_flags contract: a flag the backend rejects aborts only
    the probe child, never this process).  Must run BEFORE any jax
    import.  Adopted flags are registered with
    ``compile_cache.record_adopted_flags`` so every subsequent compile
    stamps them into its zoo-hlo-report (``xla_flags``) and the bench
    ``host_fingerprint`` — a cost-model row says WHICH scheduler
    produced its graph.  Returns the adopted flag tuple or None."""
    flags = LATENCY_HIDING_FLAGS.get("tpu", ())
    if not flags:
        return None
    already = os.environ.get("XLA_FLAGS", "")
    new = tuple(f for f in flags if f not in already)
    if not new:
        return flags  # inherited from the environment; still record
    candidate = (already + " " + " ".join(new)).strip()
    ok, detail = probe(probe_timeout,
                       env=dict(os.environ, XLA_FLAGS=candidate))
    if not ok or not detail.startswith("tpu"):
        return None
    os.environ["XLA_FLAGS"] = candidate
    from analytics_zoo_tpu.common.compile_cache import (
        record_adopted_flags,
    )

    record_adopted_flags(flags)
    return flags


def main():
    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, repo)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: the headline is a TPU metric and JAX found "
                 f"only {dev.platform!r} devices; nothing measured")

    from analytics_zoo_tpu.common.compile_cache import (
        maybe_enable_persistent_cache,
    )
    from examples.resnet.train_imagenet import run

    maybe_enable_persistent_cache(os.path.join(repo, ".jax_cache"))
    r = run(image_size=224, per_chip_batch=256, steps=30)
    ctx = r["ctx"]
    dp = max(ctx.data_parallel_size, 1)
    per_chip = r["e2e_ips"] / dp
    pure_per_chip = r["pure_ips"] / dp

    out = {
        "metric": "resnet50_imagenet_train_images_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / A100_IMAGES_PER_SEC, 3),
        "pure_step_images_per_sec_per_chip": round(pure_per_chip, 1),
        "pure_step_ms": round(r["pure_step_ms"], 1),
        "pure_step_vs_baseline": round(pure_per_chip / A100_IMAGES_PER_SEC,
                                       3),
        "infeed_fraction": round(r["infeed_fraction"], 3),
        "compiles_timed": r["compiles_timed"],
        "platform": ctx.platform,
        "device_kind": dev.device_kind,
        "devices": ctx.num_devices,
        "per_chip_batch": r["batch"] // dp,
        "image_size": r["image_size"],
        "steps_timed": r["steps_timed"],
    }
    peak = peak_flops_for(dev.device_kind)
    if peak:
        out["mfu_e2e"] = round(
            per_chip * RESNET50_TRAIN_FLOPS_PER_IMAGE / peak, 4)
        out["mfu_pure_step"] = round(
            pure_per_chip * RESNET50_TRAIN_FLOPS_PER_IMAGE / peak, 4)
        out["peak_flops_assumed"] = peak
    # Step-time breakdown from the metrics registry — the estimator's
    # built-in instrumentation (analytics_zoo_tpu.metrics), not a
    # bench-private timer: the same numbers a production scrape sees.
    from analytics_zoo_tpu.metrics import snapshot, write_jsonl

    breakdown = {}
    for s in snapshot()["samples"]:
        if s["name"] in ("zoo_train_data_wait_seconds",
                         "zoo_train_step_dispatch_seconds",
                         "zoo_train_step_seconds"):
            breakdown[s["name"]] = {
                k: round(float(s[k]), 6)
                for k in ("count", "p50", "p95", "p99")}
    if breakdown:
        out["step_breakdown"] = breakdown
    out["host_fingerprint"] = host_fingerprint()
    jsonl_path = os.environ.get("ZOO_METRICS_JSONL")
    if jsonl_path:
        write_jsonl(jsonl_path)
    print(json.dumps(out))


def _data_pipeline_main(argv):
    kwargs = {}
    if "--quick" in argv:
        # CPU-sized quick-tier configuration (also exercised by
        # tests/test_prefetch.py so pipeline regressions fail loudly)
        kwargs = dict(n_shards=4, shard_records=32, batch_size=8,
                      load_sleep_ms=15.0, transform_sleep_ms=1.0)
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(data_pipeline_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --elastic: unattended chaos recovery bench (elastic/; ISSUE 16).  One
# 4-worker TrainSupervisor run over a dir: broker loses TWO workers mid-
# run — one to kill -9 (lease expiry), one to SIGTERM (graceful leave) —
# and regains both via respawn.  Reported: rejoin wall-time per
# generation change, steps replayed per fault, the full generation/
# decision timeline, and the trajectory's max |Δ| of final parameters
# against an uninterrupted in-process run of the SAME spec (the
# resume-from-LATEST + bit-exact-resharding contract; expect 0.0).
# Emits BENCH_ELASTIC_r14.json so recovery cost is pinned, not asserted.
# ---------------------------------------------------------------------------


def elastic_bench(quick: bool = False,
                  out_path: str | None = None) -> dict:
    import shutil
    import tempfile

    import numpy as np

    from analytics_zoo_tpu.elastic import ChaosSchedule, TrainSupervisor

    work = tempfile.mkdtemp(prefix="zoo-elastic-bench-")
    try:
        ck = os.path.join(work, "ckpt")
        spec = dict(ckpt_dir=ck, nb_epoch=4 if quick else 6,
                    plan="fsdp", k=1, throttle_s=0.08)
        total_steps = (256 // 32) * spec["nb_epoch"]
        chaos = ChaosSchedule.parse(
            f"kill@{total_steps // 3}:w1,term@{total_steps // 2}:w2")
        sup = TrainSupervisor(
            "dir:" + os.path.join(work, "spool"), spec, workers=4,
            lease_ms=800, min_workers=1, interval=0.1, chaos=chaos)
        t0 = time.time()
        res = sup.run(timeout_s=420)
        if res is None:
            raise RuntimeError(
                "elastic bench: cohort never posted its result; "
                "decisions=%r" % sup.decision_log())

        log = sup.decision_log()
        timeline = [dict(d, t=round(d["ts"] - t0, 3)) for d in log]
        for d in timeline:
            d.pop("ts")
        rejoin_s = [d["seconds"] for d in log
                    if d["action"] == "rejoined"]
        steps_lost = [
            {"generation": d["generation"], "steps_replayed":
             d["steps_lost"]}
            for d in log
            if d["action"] == "rejoin" and d["reason"] == "leave"]

        # uninterrupted oracle: same spec, straight through in-process
        import pickle

        import jax

        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

        full = dict(TrainSupervisor.DEFAULT_SPEC, **spec)
        zoo.init_zoo_context(seed=full["seed"], mesh_shape={
            "data": min(4, len(jax.devices()))})
        m = Sequential()
        m.add(Dense(full["hidden"], activation="relu",
                    input_shape=(full["in_dim"],)))
        m.add(Dense(full["classes"], activation="softmax"))
        m.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy")
        rng = np.random.default_rng(full["seed"])
        x = rng.standard_normal(
            (full["n"], full["in_dim"])).astype(np.float32)
        y = rng.integers(0, full["classes"],
                         size=(full["n"],)).astype(np.int32)
        m.fit(x, y, batch_size=full["batch_size"],
              nb_epoch=full["nb_epoch"], plan=full["plan"])

        with open(os.path.join(ck, "LATEST")) as f:
            name = f.read().strip()
        with open(os.path.join(ck, name), "rb") as f:
            payload = pickle.load(f)
        diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(
                     jax.tree_util.tree_leaves(payload["params"]),
                     jax.tree_util.tree_leaves(m.params))]
        traj_max_diff = max(diffs) if diffs else float("nan")

        doc = {
            "metric": "elastic_chaos_recovery",
            "unit": "max |Δ| of final params vs uninterrupted run",
            "platform": "cpu",
            "quick": bool(quick),
            "value": traj_max_diff,
            "workers": 4,
            "chaos": chaos.to_doc(),
            "final_step": res["final_step"],
            "steps_per_sec": round(res["steps_per_sec"], 3),
            "generations": res["generation"],
            "rejoin_seconds": [round(s, 3) for s in rejoin_s],
            "steps_replayed_per_fault": steps_lost,
            "repicks": sup.repick_log(),
            "timeline": timeline,
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_ELASTIC_r14.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _elastic_main(argv):
    # the workers and the in-process oracle leg both need the forced
    # 8-device CPU mesh (the supervisor folds world sizes onto it)
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(elastic_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --federated: the zoowatch federation plane e2e (ISSUE 17).  Two legs:
#   1. federated_scaler_bench — a PROCESS-mode fleet whose replicas each
#      export /telemetryz on an ephemeral port; a VarzScraper discovers
#      them via the broker, feeds a TimeSeriesStore + SloEngine, and the
#      SloScaler runs ONLY on that federated view (the local registry is
#      never consulted) through a 10x offered-load step.  The story: the
#      burn-rate alert at /alertz fires BEFORE the estimated sojourn
#      hard-violates the serving SLO — the SLO spec's threshold is the
#      per-dispatch latency budget (batches filling up is the leading
#      indicator of saturation), so the multi-window burn crosses while
#      the client-visible p99 is still inside the SLO.
#   2. chaos_explainability_bench — a ChaosSchedule elastic run whose
#      per-process flight dumps are merged by tools/flight_merge.py onto
#      one wall-clock timeline; every generation change and respawn must
#      appear next to its cause event.
# Emits BENCH_FED_r15.json so both stories are pinned, not asserted.
# ---------------------------------------------------------------------------


def federated_scaler_bench(quick: bool = False) -> dict:
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from analytics_zoo_tpu.metrics import (
        MetricsServer, SloEngine, SloSpec, TimeSeriesStore,
        VarzScraper, fleet_varz_targets)
    from analytics_zoo_tpu.serving import (
        ClusterServingHelper, InputQueue, OutputQueue)
    from analytics_zoo_tpu.serving.broker import connect_broker
    from analytics_zoo_tpu.serving.fleet import FleetController
    from analytics_zoo_tpu.serving.scaler import (
        FederatedSignalSource, SloScaler)

    service_ms = 20.0          # one replica saturates at ~50 rec/s
    slo_p99_ms = 400.0         # the HARD serving SLO (sojourn estimate)
    dispatch_budget_s = 0.08   # SLO-spec threshold: per-dispatch budget
    light_rps, heavy_rps = 8.0, 80.0  # the 10x step
    light_s = 3.0 if quick else 5.0
    heavy_s = 10.0 if quick else 18.0

    work = tempfile.mkdtemp(prefix="zoo-fed-bench-")
    spool = os.path.join(work, "spool")
    broker_spec = "dir:" + spool
    db = connect_broker(broker_spec)
    store = TimeSeriesStore(capacity=1024)
    spec = SloSpec(
        "predict_latency", "zoo_serving_predict_seconds",
        threshold=dispatch_budget_s, objective=0.95,
        short_window=1.5, long_window=6.0, burn_threshold=1.0,
        description="per-dispatch latency budget (early-warning tier "
                    "under the %.0fms sojourn SLO)" % slo_p99_ms)
    engine = SloEngine(store, [spec])
    scraper = VarzScraper(
        store=store, engine=engine, interval=0.2, timeout=5.0,
        discover=fleet_varz_targets(db))
    srv = MetricsServer(port=0).start()  # the /alertz the bench polls
    fed = FederatedSignalSource(store, db, "image_stream",
                                scraper=scraper)
    ctrl = FleetController(
        ClusterServingHelper(
            model_path=None, batch_size=8, batch_budget_ms=10.0,
            lease_ms=5_000, log_dir=os.path.join(work, "logs")),
        broker_spec,
        scaler=SloScaler(slo_p99_ms=slo_p99_ms, min_replicas=1,
                         max_replicas=3, up_windows=2,
                         down_windows=10_000),
        interval=0.4, mode="process", signal_source=fed,
        replica_metrics=True,
        replica_extra_args=("--synthetic-sleep-ms", str(service_ms)))

    t_wall0 = time.time()
    marks = {"alert": None, "hard_violation": None, "scale_up": None}
    timeline = []
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            now = time.time()
            cur = ctrl.current()
            win = cur["window"]
            # the sojourn estimate the scaler acts on, recomputed from
            # the federated window: predict p99 + backlog drain time
            est_ms = win["predict_p99_ms"]
            if win["queue_depth"]:
                est_ms = est_ms + (
                    win["queue_depth"] / win["service_rate"] * 1e3
                    if win["service_rate"] > 0 else float("inf"))
            if marks["hard_violation"] is None and est_ms > slo_p99_ms:
                marks["hard_violation"] = now
            if marks["scale_up"] is None:
                ups = [d for d in ctrl.decision_log()
                       if d["action"] == "up"]
                if ups:
                    marks["scale_up"] = ups[0]["ts"]
            if marks["alert"] is None:
                try:
                    with urllib.request.urlopen(
                            srv.url + "/alertz", timeout=2) as r:
                        if _json.load(r).get("firing"):
                            marks["alert"] = now
                except (OSError, ValueError):
                    pass
            timeline.append({
                "t_s": round(now - t_wall0, 2),
                "replicas": cur["replicas"], "hosts": cur["hosts"],
                "est_p99_ms": (None if est_ms == float("inf")
                               else round(est_ms, 1)),
            })
            time.sleep(0.1)

    served = {}
    outq = OutputQueue(broker=db)

    def collector():
        while not stop.is_set():
            served.update(outq.dequeue())
            time.sleep(0.01)

    scraper.start()
    ctrl.start()
    seq = 0
    try:
        # wait for discovery: the scraper must see the first replica's
        # /telemetryz before load starts (the federated view is the
        # ONLY view the scaler has)
        deadline = time.time() + 120
        while time.time() < deadline:
            hz = scraper.healthz()
            if hz["healthy"] and hz["targets"]:
                break
            time.sleep(0.1)
        else:
            raise RuntimeError(
                "scraper never discovered a replica: %r"
                % scraper.healthz())
        threading.Thread(target=sampler, daemon=True).start()
        threading.Thread(target=collector, daemon=True).start()
        inq = InputQueue(broker=db)
        rec = np.zeros((8,), np.float32)
        for rate, duration in ((light_rps, light_s),
                               (heavy_rps, heavy_s)):
            t_phase = time.perf_counter()
            while time.perf_counter() - t_phase < duration:
                inq.enqueue(f"q{seq}", rec)
                seq += 1
                time.sleep(1.0 / rate)
        deadline = time.time() + 240
        while len(served) < seq and time.time() < deadline:
            time.sleep(0.1)
    finally:
        stop.set()
        ctrl.stop()
        scraper.stop()
        srv.stop()
        shutil.rmtree(work, ignore_errors=True)

    cur = ctrl.current()
    hz = scraper.healthz()
    rel = lambda ts: None if ts is None else round(ts - t_wall0, 2)  # noqa: E731
    alert, hard = marks["alert"], marks["hard_violation"]
    return {
        "service_ms_per_record": service_ms,
        "slo_p99_ms": slo_p99_ms,
        "dispatch_budget_ms": dispatch_budget_s * 1e3,
        "load_step": {"light_rps": light_rps, "heavy_rps": heavy_rps,
                      "factor": heavy_rps / light_rps},
        "federated": cur["federated"],
        "enqueued": seq, "served": len(served),
        "alert_t_s": rel(alert),
        "hard_violation_t_s": rel(hard),
        "scale_up_t_s": rel(marks["scale_up"]),
        "alert_before_hard_violation": (
            alert is not None and (hard is None or alert <= hard)),
        "scaled_up": any(d["action"] == "up"
                         for d in ctrl.decision_log()),
        "max_replicas_seen": max(
            [t["replicas"] for t in timeline] + [1]),
        "hosts_seen": sorted({t["hosts"] for t in timeline
                              if t["hosts"] is not None}),
        "slo_spec": spec.to_doc(),
        "scrape_targets_final": len(hz["targets"]),
        "decisions": [
            {k: d.get(k) for k in ("action", "old", "new", "reason",
                                   "est_p99_ms", "queue_depth",
                                   "hosts", "hosts_target")}
            for d in ctrl.decision_log()],
        "alerts": engine.alerts(),
        "timeline": timeline[:: 2 if quick else 1],
    }


def chaos_explainability_bench(quick: bool = False,
                               keep_artifacts_in: str | None = None) \
        -> dict:
    import shutil
    import tempfile

    from analytics_zoo_tpu.elastic import ChaosSchedule, TrainSupervisor
    from analytics_zoo_tpu.metrics import get_flight_recorder

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        import flight_merge
    finally:
        sys.path.pop(0)

    work = tempfile.mkdtemp(prefix="zoo-fed-chaos-")
    flight_dir = os.path.join(work, "flight")
    try:
        spec = dict(ckpt_dir=os.path.join(work, "ckpt"),
                    nb_epoch=3 if quick else 4, plan="dp", k=1,
                    throttle_s=0.08)
        total_steps = (256 // 32) * spec["nb_epoch"]
        chaos = ChaosSchedule.parse(f"kill@{total_steps // 2}:w1")
        sup = TrainSupervisor(
            "dir:" + os.path.join(work, "spool"), spec, workers=3,
            lease_ms=800, min_workers=1, interval=0.1, chaos=chaos,
            worker_env={"ZOO_FLIGHT_DIR": flight_dir})
        run_start = time.time()
        res = sup.run(timeout_s=420)
        if res is None:
            raise RuntimeError(
                "chaos run never finished; decisions=%r"
                % sup.decision_log())
        # the supervisor's own ring is the third process-perspective
        # (workers dumped theirs on exit/SIGTERM; the SIGKILLed
        # incarnation could not — its death is explained by the
        # supervisor's chaos event instead).  Written directly so the
        # global recorder's dump-dir/once-per-reason state is untouched.
        os.makedirs(flight_dir, exist_ok=True)
        sup_doc = get_flight_recorder().to_doc("bench")
        # the process-global ring may hold elastic events from EARLIER
        # runs in this interpreter (other benches, earlier tests) whose
        # worker dumps are not in this run's flight_dir — they would
        # show up as uncaused effects.  Keep only this run's events.
        sup_doc["events"] = [e for e in sup_doc["events"]
                             if e.get("ts", 0.0) >= run_start]
        with open(os.path.join(
                flight_dir, f"flight-{os.getpid()}-bench.json"),
                "w") as f:
            json.dump(sup_doc, f)

        docs = flight_merge.load_inputs([flight_dir])
        merged = flight_merge.merge_flight_docs(docs)
        narrative = flight_merge.narrative_lines(merged)
        out_trace = os.path.join(
            keep_artifacts_in or os.path.dirname(
                os.path.abspath(__file__)),
            "BENCH_FED_r15_chaos_trace.json")
        flight_merge.write_outputs(merged, out=out_trace)

        elastic = [e for e in merged["timeline"]
                   if e.get("kind") == "elastic"]
        rejoins = [e for e in elastic if e.get("event") == "rejoin"]
        respawns = [e for e in elastic if e.get("event") == "respawn"]
        chaos_evs = [e for e in elastic if e.get("event") == "chaos"]

        def cause_of(effect):
            """Nearest earlier event that explains `effect` — the
            chaos kill, a worker leave/join, or a respawn."""
            causes = [e for e in elastic
                      if e["t"] <= effect["t"] and e is not effect
                      and e.get("event") in ("chaos", "leave", "join",
                                             "respawn")]
            return causes[-1] if causes else None

        explained = [
            {"event": e.get("event"), "t_s": round(
                e["t"] - merged["timeline"][0]["t"], 3),
             "generation": e.get("generation"),
             "cause": (cause_of(e) or {}).get("event"),
             "cause_src": (cause_of(e) or {}).get("src")}
            for e in rejoins + respawns]
        return {
            "workers": 3,
            "chaos": chaos.to_doc(),
            "final_step": res["final_step"],
            "flight_dumps_merged": merged["sources"],
            "timeline_events": len(merged["timeline"]),
            "skew": merged["skew"],
            "skew_beyond_tolerance": [
                s for s, v in merged["skew"].items()
                if v["beyond_tolerance"]],
            "generation_changes": len(rejoins),
            "respawns": len(respawns),
            "chaos_events_seen": len(chaos_evs),
            "all_effects_have_causes": all(
                r["cause"] is not None for r in explained),
            "explained": explained,
            "narrative_head": narrative[:40],
            "merged_trace_artifact": out_trace,
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def federated_bench(quick: bool = False,
                    out_path: str | None = None) -> dict:
    doc = {
        "metric": "federated_slo_alert_lead_and_chaos_explainability",
        "unit": "alert fires before hard SLO violation (bool)",
        "platform": "cpu",
        "quick": bool(quick),
        "scaler": federated_scaler_bench(quick=quick),
        "explainability": chaos_explainability_bench(quick=quick),
    }
    doc["value"] = doc["scaler"]["alert_before_hard_violation"]
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_FED_r15.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _federated_main(argv):
    # control-plane bench: subprocess replicas + elastic workers need
    # the forced 8-device CPU mesh, same as the elastic bench
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(federated_bench(**kwargs)))


# ---------------------------------------------------------------------------
# --serving-predict: the predictive serving plane (ISSUE 20).  Three
# legs against the synthetic sleep model (the control-plane bench
# convention): (a) an oracle-primed fleet takes the BENCH_FED_r15 10x
# load step with zero hard SLO-violation windows where the reactive
# baseline accumulates seconds of violation, plus predicted-vs-measured
# predict-step latency per pad bucket; (b) a two-model router holds
# BOTH per-model p99 SLOs under skewed load; (c) under 20x overload the
# admission controller keeps accepted-work p99 under the SLO, sheds
# with typed retry-after, and the serve-log audit shows every accepted
# record served exactly once.  Emits BENCH_SERVE_r19.json.
# ---------------------------------------------------------------------------


def _serving_features(service_ms: float, buckets) -> dict:
    """Per-bucket cost-model features whose analytic predict time on
    the CPU peak table equals the synthetic model's service time
    (bucket * service_ms): flops = t * peak_flops, nothing else."""
    return {int(b): {"matmul_flops": int(b) * service_ms / 1e3 * 5e10,
                     "bytes_accessed": 0.0}
            for b in buckets}


def _p99(vals):
    if not vals:
        return None
    s = sorted(vals)
    return s[min(len(s) - 1, int(0.99 * len(s)))]


def _load_step_run(quick: bool, prior_target=None) -> dict:
    """One 10x load-step run (light -> heavy, then drain) against a
    1-min fleet; ``prior_target`` seeds the scaler (the oracle-primed
    leg).  Returns the violation-window count the acceptance compares."""
    import threading

    import numpy as np

    from analytics_zoo_tpu.serving import InMemoryBroker, InputQueue, \
        OutputQueue
    from analytics_zoo_tpu.serving.scaler import SloScaler

    service_ms = 20.0          # one replica saturates at ~50 rec/s
    slo_p99_ms = 400.0
    light_rps, heavy_rps = 8.0, 80.0  # the BENCH_FED_r15 10x step
    light_s = 3.0 if quick else 5.0
    heavy_s = 6.0 if quick else 12.0
    interval = 0.25

    scaler = SloScaler(slo_p99_ms=slo_p99_ms, min_replicas=1,
                       max_replicas=3, up_windows=2,
                       down_windows=10_000, prior_target=prior_target)
    broker = InMemoryBroker()
    ctrl = _fleet_controller(broker, 1, service_ms, scaler=scaler,
                             interval=interval, slo_p99_ms=slo_p99_ms)
    inq = InputQueue(broker=broker)
    outq = OutputQueue(broker=broker)
    served = {}
    stop = threading.Event()
    violations = [0]
    timeline = []
    t0 = time.time()

    def sampler():
        while not stop.is_set():
            cur = ctrl.current()
            win = cur["window"]
            est_ms = win["predict_p99_ms"]
            if win["queue_depth"]:
                est_ms = est_ms + (
                    win["queue_depth"] / win["service_rate"] * 1e3
                    if win["service_rate"] > 0 else float("inf"))
            if est_ms > slo_p99_ms:
                violations[0] += 1
            timeline.append({
                "t_s": round(time.time() - t0, 2),
                "replicas": cur["replicas"],
                "est_p99_ms": (None if est_ms == float("inf")
                               else round(est_ms, 1))})
            time.sleep(0.1)

    def collector():
        while not stop.is_set():
            served.update(outq.dequeue())
            time.sleep(0.01)

    ctrl.start()
    seq = 0
    try:
        threading.Thread(target=sampler, daemon=True).start()
        threading.Thread(target=collector, daemon=True).start()
        rec = np.zeros((8,), np.float32)
        for rate, duration in ((light_rps, light_s),
                               (heavy_rps, heavy_s)):
            t_phase = time.perf_counter()
            while time.perf_counter() - t_phase < duration:
                inq.enqueue(f"q{seq}", rec)
                seq += 1
                time.sleep(1.0 / rate)
        deadline = time.time() + 120
        while len(served) < seq and time.time() < deadline:
            time.sleep(0.05)
    finally:
        stop.set()
        ctrl.stop()
    return {
        "prior_target": prior_target,
        "slo_p99_ms": slo_p99_ms,
        "load_step": {"light_rps": light_rps, "heavy_rps": heavy_rps,
                      "factor": heavy_rps / light_rps},
        "enqueued": seq, "served": len(served),
        "violation_windows": violations[0],
        "violation_seconds": round(violations[0] * 0.1, 2),
        "max_replicas_seen": max(
            [t["replicas"] for t in timeline] + [1]),
        "decisions": [
            {k: d.get(k) for k in ("action", "old", "new", "reason")}
            for d in ctrl.decision_log()],
        "timeline": timeline[:: 4 if quick else 2],
    }


def serving_predict_primed_bench(quick: bool = False) -> dict:
    """Leg (a): the same 10x load step twice — reactive baseline
    (scaler starts at min_replicas, scales on observed violation) vs
    oracle-primed (``choose_serving`` predicts the replica target from
    the per-bucket serving cost model and SEEDS the scaler).  Also
    closes the oracle's prediction log with measured per-bucket predict
    latencies so the rel_error lands per bucket."""
    import numpy as np

    from analytics_zoo_tpu.analysis.costmodel import resolve_peaks
    from analytics_zoo_tpu.analysis.oracle import ConfigOracle
    from analytics_zoo_tpu.serving.fleet import _SyntheticModel

    service_ms = 20.0
    heavy_rps = 80.0
    slo_p99_ms = 400.0
    buckets = (8, 16)
    reactive = _load_step_run(quick)

    oracle = ConfigOracle(peaks=resolve_peaks("cpu"))
    feats = _serving_features(service_ms, buckets)
    verdict = oracle.choose_serving(
        feats, slo_p99_ms=slo_p99_ms, offered_rate=heavy_rps,
        model="step")
    primed = _load_step_run(quick, prior_target=verdict["replicas"])

    # close the prediction -> outcome loop: measure the synthetic
    # model's real per-bucket service time and hand it back to the
    # oracle, so rel_error lands per bucket like every oracle pick
    model = _SyntheticModel(service_ms)
    rel_errors = {}
    for b in buckets:
        arr = np.zeros((b, 8), np.float32)
        t0 = time.perf_counter()
        model.predict(arr)
        measured_s = time.perf_counter() - t0
        oracle.record_outcome(f"serving:step:b{b}", 1.0 / measured_s,
                              consumer="serving")
    for row in oracle.prediction_log():
        if row["config"].startswith("serving:step:b") \
                and row.get("rel_error") is not None:
            rel_errors[row["config"]] = round(row["rel_error"], 4)
    return {
        "service_ms_per_record": service_ms,
        "verdict": verdict,
        "reactive": reactive,
        "primed": primed,
        "primed_zero_violations": primed["violation_windows"] == 0,
        "predict_rel_error_by_bucket": rel_errors,
    }


def serving_multi_model_bench(quick: bool = False) -> dict:
    """Leg (b): a two-model router under skewed load — a fast
    high-rate model and a slow low-rate one share ONE broker on
    per-model streams, and BOTH client-observed p99s stay under their
    own SLOs."""
    import threading

    import numpy as np

    from analytics_zoo_tpu.analysis.costmodel import resolve_peaks
    from analytics_zoo_tpu.analysis.oracle import ConfigOracle
    from analytics_zoo_tpu.serving import InMemoryBroker, InputQueue, \
        OutputQueue
    from analytics_zoo_tpu.serving.fleet import _SyntheticModel
    from analytics_zoo_tpu.serving.modelspec import ModelSpec
    from analytics_zoo_tpu.serving.router import ModelRouter

    service = {"fast": 5.0, "slow": 20.0}           # ms per record
    specs = [ModelSpec("fast", slo_p99_ms=300.0, offered_rate=60.0),
             ModelSpec("slow", slo_p99_ms=800.0, offered_rate=10.0)]
    duration = 4.0 if quick else 8.0

    broker = InMemoryBroker()
    oracle = ConfigOracle(peaks=resolve_peaks("cpu"))
    router = ModelRouter(
        broker, specs,
        model_factory=lambda spec: _SyntheticModel(service[spec.name]),
        oracle=oracle,
        features={name: _serving_features(ms, (8, 16))
                  for name, ms in service.items()},
        max_replicas=3, interval=0.25)
    t_enq = {}
    lock = threading.Lock()
    latencies = {"fast": [], "slow": []}
    stop = threading.Event()
    outq = OutputQueue(broker=broker)

    def collector():
        while not stop.is_set():
            done = outq.dequeue()
            now = time.perf_counter()
            with lock:
                for uri in done:
                    if uri in t_enq:
                        latencies[uri.split(":", 1)[0]].append(
                            now - t_enq.pop(uri))
            time.sleep(0.01)

    def load(name, rate):
        inq = InputQueue(broker=broker, model=name)
        rec = np.zeros((8,), np.float32)
        i = 0
        t_phase = time.perf_counter()
        while time.perf_counter() - t_phase < duration:
            uri = f"{name}:{i}"
            with lock:
                t_enq[uri] = time.perf_counter()
            inq.enqueue(uri, rec)
            i += 1
            time.sleep(1.0 / rate)

    router.start()
    try:
        threading.Thread(target=collector, daemon=True).start()
        loaders = [threading.Thread(
            target=load, args=(s.name, s.offered_rate)) for s in specs]
        for t in loaders:
            t.start()
        for t in loaders:
            t.join()
        deadline = time.time() + 60
        while time.time() < deadline:
            with lock:
                if not t_enq:
                    break
            time.sleep(0.05)
    finally:
        stop.set()
        router.stop()

    out = {"models": {}}
    all_met = True
    for s in specs:
        p99 = _p99(latencies[s.name])
        met = p99 is not None and p99 * 1e3 < s.slo_p99_ms
        all_met = all_met and met
        out["models"][s.name] = {
            "slo_p99_ms": s.slo_p99_ms,
            "offered_rate": s.offered_rate,
            "served": len(latencies[s.name]),
            "client_p99_ms": (None if p99 is None
                              else round(p99 * 1e3, 1)),
            "slo_met": met,
            "verdict": router.verdict(s.name),
        }
    out["router_decisions"] = router.decision_log()
    out["both_slos_met"] = all_met
    return out


def serving_admission_bench(quick: bool = False) -> dict:
    """Leg (c): 20x overload through the admission-guarded router —
    the front door sheds with typed retry-after, accepted-work p99
    stays under the SLO, and the serve-log audit shows every accepted
    record served exactly once (trim is OFF on the guarded stream)."""
    import collections
    import tempfile
    import threading

    import numpy as np

    from analytics_zoo_tpu.analysis.costmodel import resolve_peaks
    from analytics_zoo_tpu.analysis.oracle import ConfigOracle
    from analytics_zoo_tpu.serving import InMemoryBroker, InputQueue, \
        OutputQueue, ServingRejected
    from analytics_zoo_tpu.serving.fleet import _SyntheticModel
    from analytics_zoo_tpu.serving.modelspec import ModelSpec
    from analytics_zoo_tpu.serving.router import ModelRouter

    service_ms = 10.0
    slo_p99_ms = 500.0
    light_rps, overload_rps = 12.5, 250.0  # the 20x overload
    light_s = 2.0
    overload_s = 4.0 if quick else 8.0

    broker = InMemoryBroker()
    oracle = ConfigOracle(peaks=resolve_peaks("cpu"))
    serve_log = tempfile.NamedTemporaryFile(
        prefix="zoo-admission-audit-", suffix=".log", delete=False)
    serve_log.close()
    router = ModelRouter(
        broker,
        [ModelSpec("gate", slo_p99_ms=slo_p99_ms,
                   offered_rate=overload_rps)],
        model_factory=lambda spec: _SyntheticModel(service_ms),
        oracle=oracle,
        features={"gate": _serving_features(service_ms, (8, 16))},
        admission=True, max_replicas=2, interval=0.25,
        serve_log=serve_log.name,
        admission_kwargs={"backlog_limit": 20, "interval": 0.05})
    t_enq = {}
    lock = threading.Lock()
    latencies = []
    rejections = []
    stop = threading.Event()
    outq = OutputQueue(broker=broker)

    def collector():
        while not stop.is_set():
            done = outq.dequeue()
            now = time.perf_counter()
            with lock:
                for uri in done:
                    if uri in t_enq:
                        latencies.append(now - t_enq.pop(uri))
            time.sleep(0.01)

    accepted = []
    router.start()
    try:
        threading.Thread(target=collector, daemon=True).start()
        inq = InputQueue(broker=broker, model="gate")
        rec = np.zeros((8,), np.float32)
        seq = 0
        phase_base = 0
        for rate, duration in ((light_rps, light_s),
                               (overload_rps, overload_s)):
            t_phase = time.perf_counter()
            while True:
                elapsed = time.perf_counter() - t_phase
                if elapsed >= duration:
                    break
                # rate-paced without per-record sleeps: catch the
                # enqueue count up to the offered-rate schedule
                due = phase_base + int(elapsed * rate)
                while seq < due:
                    uri = f"a{seq}"
                    seq += 1
                    try:
                        with lock:
                            t_enq[uri] = time.perf_counter()
                        inq.enqueue(uri, rec)
                        accepted.append(uri)
                    except ServingRejected as e:
                        with lock:
                            t_enq.pop(uri, None)
                        rejections.append(e.retry_after_s)
                time.sleep(0.002)
            phase_base = seq
        deadline = time.time() + 90
        while time.time() < deadline:
            with lock:
                if not t_enq:
                    break
            time.sleep(0.05)
    finally:
        stop.set()
        router.stop()

    with open(serve_log.name) as f:
        served_uris = [line.split()[-1] for line in f
                       if line.strip()]
    os.unlink(serve_log.name)
    counts = collections.Counter(served_uris)
    audit_ok = (set(counts) == set(accepted)
                and all(c == 1 for c in counts.values()))
    p99 = _p99(latencies)
    return {
        "service_ms_per_record": service_ms,
        "slo_p99_ms": slo_p99_ms,
        "overload": {"light_rps": light_rps,
                     "overload_rps": overload_rps,
                     "factor": overload_rps / light_rps},
        "offered": len(accepted) + len(rejections),
        "accepted": len(accepted),
        "rejected": len(rejections),
        "shed_fraction": round(
            len(rejections) / max(len(accepted) + len(rejections), 1),
            3),
        "accepted_p99_ms": (None if p99 is None
                            else round(p99 * 1e3, 1)),
        "accepted_p99_under_slo": (p99 is not None
                                   and p99 * 1e3 < slo_p99_ms),
        "retry_after_s": {
            "min": round(min(rejections), 3) if rejections else None,
            "max": round(max(rejections), 3) if rejections else None,
        },
        "all_rejections_carry_retry_after": (
            bool(rejections) and all(r > 0 for r in rejections)),
        "served": len(latencies),
        "audit_exactly_once": audit_ok,
        "admission_decisions": (
            router.admission("gate").decision_log()
            if router.admission("gate") is not None else []),
    }


def serving_predict_bench(quick: bool = False,
                          out_path: str | None = None) -> dict:
    doc = {
        "metric": "predictive_serving_primed_violations_and_admission",
        "unit": "primed fleet violation windows (0 = SLO held through "
                "the 10x step)",
        "platform": "cpu",
        "quick": bool(quick),
        "primed_vs_reactive": serving_predict_primed_bench(quick=quick),
        "multi_model": serving_multi_model_bench(quick=quick),
        "admission": serving_admission_bench(quick=quick),
    }
    leg_a = doc["primed_vs_reactive"]
    doc["value"] = leg_a["primed"]["violation_windows"]
    doc["acceptance"] = {
        "primed_no_worse_than_reactive": (
            leg_a["primed"]["violation_windows"]
            <= leg_a["reactive"]["violation_windows"]),
        "both_model_slos_met": doc["multi_model"]["both_slos_met"],
        "accepted_p99_under_slo":
            doc["admission"]["accepted_p99_under_slo"],
        "audit_exactly_once": doc["admission"]["audit_exactly_once"],
    }
    doc["host_fingerprint"] = host_fingerprint()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_SERVE_r19.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    doc["artifact"] = out_path
    return doc


def _serving_predict_main(argv):
    # control-plane bench: synthetic models, no mesh — plain CPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    kwargs = {}
    if "--quick" in argv:
        kwargs["quick"] = True
    if "--out" in argv:
        kwargs["out_path"] = argv[argv.index("--out") + 1]
    print(json.dumps(serving_predict_bench(**kwargs)))


if __name__ == "__main__":
    if "--partition" in sys.argv:
        _partition_main(sys.argv[1:])
    elif "--memory" in sys.argv:
        _memory_main(sys.argv[1:])
    elif "--precision" in sys.argv:
        _precision_main(sys.argv[1:])
    elif "--kernels" in sys.argv:
        _kernels_main(sys.argv[1:])
    elif "--data-pipeline" in sys.argv:
        _data_pipeline_main(sys.argv[1:])
    elif "--fleet" in sys.argv:
        _fleet_main(sys.argv[1:])
    elif "--autotune" in sys.argv:
        _autotune_main(sys.argv[1:])
    elif "--oracle" in sys.argv:
        _oracle_main(sys.argv[1:])
    elif "--overlap" in sys.argv:
        _overlap_main(sys.argv[1:])
    elif "--elastic" in sys.argv:
        _elastic_main(sys.argv[1:])
    elif "--federated" in sys.argv:
        _federated_main(sys.argv[1:])
    elif "--serving-predict" in sys.argv:
        _serving_predict_main(sys.argv[1:])
    elif "--dispatch-child" in sys.argv:
        _dispatch_child_main(sys.argv[1:])
    elif "--dispatch" in sys.argv:
        _dispatch_main(sys.argv[1:])
    else:
        main()
