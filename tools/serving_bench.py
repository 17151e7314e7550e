"""Cluster Serving latency benchmark: p50/p99 end-to-end latency at a fixed
offered load through InputQueue -> ClusterServing -> OutputQueue.

Mirrors the reference's serving data path (ClusterServing.scala:103-139:
stream read -> micro-batch -> predict -> write result hash -> xtrim
backpressure); the measured latency is enqueue-to-result-available per
record, i.e. queueing + decode + batch formation + jit inference + encode.

A client thread offers ``--rate`` records/sec (open-loop, so queueing delay
is visible, not hidden by back-to-back closed-loop pacing); the server runs
in its own thread on the in-memory broker; a collector polls result hashes
with a 1 ms tick and records completion times.

Writes SERVING_r05.json.  Usage:
  python tools/serving_bench.py [--rate 200] [--n 2000] [--batch 16]
                                [--shape 32,32,3]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def build_model(tmp, shape, classes=10):
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Convolution2D,
        Dense,
        Flatten,
        GlobalAveragePooling2D,
    )
    from analytics_zoo_tpu.pipeline.api.keras.topology import Sequential

    m = Sequential()
    m.add(Convolution2D(16, 3, 3, activation="relu", input_shape=shape))
    m.add(Convolution2D(32, 3, 3, activation="relu"))
    m.add(GlobalAveragePooling2D())
    m.add(Dense(classes, activation="softmax"))
    m.build_params()
    path = os.path.join(tmp, "model.zoo")
    m.save(path)
    return path


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--rate", type=float, default=200.0,
                   help="offered load, records/sec")
    p.add_argument("--n", type=int, default=2000)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--shape", default="32,32,3")
    p.add_argument("--out", default=None)
    a = p.parse_args()
    shape = tuple(int(v) for v in a.shape.split(","))

    import jax

    from analytics_zoo_tpu.serving import (
        ClusterServing,
        ClusterServingHelper,
        InMemoryBroker,
        InputQueue,
        OutputQueue,
    )

    tmp = tempfile.mkdtemp()
    model_path = build_model(tmp, shape)
    broker = InMemoryBroker()
    serving = ClusterServing(
        ClusterServingHelper(model_path=model_path, batch_size=a.batch,
                             top_n=1, data_shape=shape,
                             log_dir=os.path.join(tmp, "logs")),
        broker=broker)
    inq = InputQueue(broker=broker)
    outq = OutputQueue(broker=broker)

    # warm the jit caches (full and ragged-tail buckets) before timing
    rng = np.random.default_rng(0)
    img = rng.normal(size=shape).astype(np.float32)
    for i in range(a.batch + 1):
        inq.enqueue_image(f"warm-{i}", img)
    serving.run(max_records=a.batch + 1)

    enq_t = {}
    done_t = {}

    def producer():
        period = 1.0 / a.rate
        t_next = time.perf_counter()
        for i in range(a.n):
            uri = f"r-{i}"
            enq_t[uri] = time.perf_counter()
            inq.enqueue_image(uri, img)
            t_next += period
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)

    def collector():
        pending = set(f"r-{i}" for i in range(a.n))
        deadline = time.time() + a.n / a.rate + 120
        while pending and time.time() < deadline:
            for uri in list(pending):
                if outq.query(uri) is not None:
                    done_t[uri] = time.perf_counter()
                    pending.discard(uri)
            time.sleep(0.001)

    server = serving.start(idle_timeout=a.n / a.rate + 120)
    col = threading.Thread(target=collector)
    col.start()
    t0 = time.perf_counter()
    producer()
    col.join()
    wall = time.perf_counter() - t0
    serving.stop()

    # Per-record latencies go through the metrics registry (the same
    # substrate the server's own telemetry uses — ISSUE 1: no more
    # bench-private timers as the only signal).  The headline p50/p99
    # stay exact-from-samples; the registry section carries the
    # histogram summary plus the SERVER-side telemetry recorded by
    # ClusterServing.step() during this very run.
    from analytics_zoo_tpu.metrics import (
        get_registry, sample_key, snapshot)

    client_lat = get_registry().histogram(
        "zoo_serving_client_latency_seconds",
        "enqueue -> result-available latency per record")
    for u in done_t:
        client_lat.observe(done_t[u] - enq_t[u])

    lats = np.array(sorted(
        (done_t[u] - enq_t[u]) * 1e3 for u in done_t))
    completed = len(lats)
    if completed == 0:
        print(json.dumps({
            "error": "no records completed — server-side failure "
                     "(check model path / broker); see serving logs",
            "offered": a.n,
        }))
        sys.exit(1)
    d = jax.devices()[0]
    out = {
        "metric": "cluster_serving_latency_ms",
        "p50": round(float(np.percentile(lats, 50)), 2),
        "p90": round(float(np.percentile(lats, 90)), 2),
        "p99": round(float(np.percentile(lats, 99)), 2),
        "mean": round(float(lats.mean()), 2),
        "offered_rate_rps": a.rate,
        "achieved_rps": round(completed / wall, 1),
        "completed": completed,
        "offered": a.n,
        "batch_size": a.batch,
        "data_shape": shape,
        "broker": "in-memory",
        "platform": d.platform,
        "device_kind": d.device_kind,
        "semantics": "enqueue->result-available, open-loop offered load "
                     "(ClusterServing.scala:103-139 path)",
    }
    if out["achieved_rps"] < 0.95 * a.rate:
        out["note"] = ("SATURATED: offered load exceeds capacity, latency "
                       "is queueing delay, not service time — see a "
                       "stable-queue run for the latency number")
    # registry section: server-side serving telemetry + the client
    # latency histogram summary (same names a Prometheus scrape exposes)
    reg_doc = {}
    for s in snapshot()["samples"]:
        if not s["name"].startswith("zoo_serving"):
            continue
        key = sample_key(s)
        if s["kind"] == "histogram":
            reg_doc[key] = {k: round(float(s[k]), 6)
                            for k in ("count", "p50", "p95", "p99")}
        else:
            reg_doc[key] = round(float(s["value"]), 6)
    out["registry"] = reg_doc
    print(json.dumps(out))
    path = a.out or os.path.join(os.path.dirname(__file__), "..",
                                 "SERVING_r05.json")
    # Merge, don't clobber: the artifact keeps one run per
    # (platform, offered_rate) and fronts the best STABLE-queue run, so a
    # saturation probe can never replace the latency headline.
    runs = []
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        runs = old.get("runs") or ([{k: v for k, v in old.items()
                                     if k != "runs"}] if "p50" in old
                                   else [])
    runs = [r for r in runs
            if (r.get("platform"), r.get("offered_rate_rps"))
            != (out["platform"], out["offered_rate_rps"])]
    runs.append(out)

    def stable(r):
        return r.get("achieved_rps", 0) >= 0.95 * r.get(
            "offered_rate_rps", float("inf"))

    primary = max([r for r in runs if stable(r)] or runs,
                  key=lambda r: (r.get("platform") == "tpu",
                                 r.get("offered_rate_rps", 0)))
    doc = dict(primary)
    doc["runs"] = runs
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


if __name__ == "__main__":
    main()
