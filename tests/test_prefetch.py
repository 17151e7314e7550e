"""Parallel host data plane (feature/prefetch.py): ordered deterministic
delivery, worker-exception propagation, clean shutdown, shard read-ahead,
estimator composition, and the overlap of sleep-bound host work."""

import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.feature.common import FnPreprocessing
from analytics_zoo_tpu.feature.dataset import FeatureSet, ShardedFeatureSet
from analytics_zoo_tpu.feature.prefetch import (
    PrefetchFeatureSet,
    PrefetchPipeline,
)


def assert_streams_identical(a_batches, b_batches):
    assert len(a_batches) == len(b_batches)
    for a, b in zip(a_batches, b_batches):
        assert set(a) == set(b)
        for k in a:
            if isinstance(a[k], list):
                for ai, bi in zip(a[k], b[k]):
                    np.testing.assert_array_equal(ai, bi)
            else:
                np.testing.assert_array_equal(a[k], b[k])


@pytest.fixture()
def shard_dir(tmp_path):
    paths = []
    for i in range(5):
        p = tmp_path / f"shard{i}.npz"
        rng = np.random.default_rng(100 + i)
        np.savez(p, x=rng.standard_normal((13, 4)).astype(np.float32),
                 y=rng.integers(0, 3, size=(13,)).astype(np.int32))
        paths.append(str(p))
    return paths


def test_array_prefetch_byte_identical():
    x = np.arange(200 * 3, dtype=np.float32).reshape(200, 3)
    y = np.arange(200, dtype=np.int32)
    fs = FeatureSet.of(x, y)
    for kwargs in (
        dict(shuffle=True, seed=3, epoch=1),
        dict(shuffle=True, seed=3, epoch=1, start_batch=2),
        dict(shuffle=False, drop_last=False, pad_to_batch=8),
    ):
        serial = list(fs.batches(16, **kwargs))
        pre = list(fs.prefetch(depth=3, workers=2).batches(16, **kwargs))
        assert_streams_identical(serial, pre)


def test_transformed_prefetch_byte_identical_and_parallel():
    x = np.arange(120, dtype=np.float32).reshape(40, 3)
    seen_threads = set()

    def tf(record):
        seen_threads.add(threading.current_thread().name)
        return record * 2.0 + 1.0

    fs = FeatureSet.of(x).transform(FnPreprocessing(tf))
    serial = list(fs.batches(8, shuffle=True, seed=9, epoch=4))
    seen_threads.clear()
    pre = list(fs.prefetch(depth=4, workers=3).batches(
        8, shuffle=True, seed=9, epoch=4))
    assert_streams_identical(serial, pre)
    # the transform ran on pool workers, not the consumer thread
    assert all(t.startswith("zoo-prefetch") for t in seen_threads)


def test_nested_transforms_collapse_into_map_stage():
    x = np.arange(60, dtype=np.float32).reshape(20, 3)
    fs = FeatureSet.of(x).transform(
        FnPreprocessing(lambda r: r + 1.0)).transform(
        FnPreprocessing(lambda r: r * 3.0))
    serial = list(fs.batches(4, shuffle=True, seed=0, epoch=0))
    pre = list(fs.prefetch(depth=2, workers=2).batches(
        4, shuffle=True, seed=0, epoch=0))
    assert_streams_identical(serial, pre)


def test_sharded_prefetch_across_slice_boundary(shard_dir):
    # batch 8 over 13-record shards: every batch straddles shard
    # boundaries, and n_slices=5 keeps ONE shard resident, so the
    # resident slice advances (and read-ahead fires) mid-epoch
    fs = ShardedFeatureSet(shard_dir, n_slices=5)
    for kwargs in (dict(shuffle=True, seed=1, epoch=0),
                   dict(shuffle=True, seed=1, epoch=0, start_batch=3),
                   dict(shuffle=True, seed=2, epoch=5, drop_last=False,
                        pad_to_batch=4)):
        serial = list(fs.batches(8, **kwargs))
        pre = list(fs.prefetch(depth=3, workers=2).batches(8, **kwargs))
        assert_streams_identical(serial, pre)


def test_sharded_read_ahead_loads_next_shard_off_thread(shard_dir):
    load_threads = []

    def loader(path):
        load_threads.append(threading.current_thread().name)
        data = np.load(path)
        return {k: data[k] for k in data.files}

    fs = ShardedFeatureSet(shard_dir, n_slices=5, loader=loader,
                           sizer=lambda p: 13)
    pre = list(fs.prefetch(depth=3, workers=2).batches(
        8, shuffle=True, seed=1, epoch=0))
    assert pre  # consumed something
    # each shard loaded exactly once (read-ahead never duplicates work)
    assert len(load_threads) == len(shard_dir)
    # all but the first load were read-ahead submissions on the pool
    assert sum(t.startswith("zoo-prefetch") and "producer" not in t
               for t in load_threads) >= len(shard_dir) - 1
    # disabled again after iteration (no leaked pool reference)
    assert fs._ra_pool is None and fs._ra_futures == {}


def test_worker_exception_propagates_at_position_and_shuts_down():
    x = np.arange(64, dtype=np.float32).reshape(64, 1)

    def tf(record):
        if record[0] == 40.0:
            raise RuntimeError("boom at 40")
        return record

    fs = FeatureSet.of(x).transform(FnPreprocessing(tf))
    pre = fs.prefetch(depth=2, workers=2)
    it = pre.batches(8, shuffle=False)
    got = [next(it) for _ in range(5)]  # batches 0..4 are clean
    assert len(got) == 5
    with pytest.raises(RuntimeError, match="boom at 40"):
        next(it)  # batch 5 holds record 40
    # the pipeline shut down: no prefetch threads survive
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and any(
            t.name.startswith("zoo-prefetch") and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.05)
    assert not any(t.name.startswith("zoo-prefetch") and t.is_alive()
                   for t in threading.enumerate())


def test_source_exception_propagates():
    def bad_source():
        yield {"x": np.zeros((2, 2))}
        raise ValueError("source died")

    pipe = PrefetchPipeline(bad_source(), workers=1, depth=2)
    it = iter(pipe)
    next(it)
    with pytest.raises(ValueError, match="source died"):
        next(it)


def test_clean_shutdown_mid_stream():
    x = np.zeros((1000, 4), np.float32)
    fs = FeatureSet.of(x).transform(FnPreprocessing(lambda r: r))
    gen = fs.prefetch(depth=4, workers=2).batches(4, shuffle=False)
    next(gen)
    next(gen)
    gen.close()  # GeneratorExit -> pipeline.close()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and any(
            t.name == "zoo-prefetch-producer" and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.05)
    assert not any(t.name == "zoo-prefetch-producer" and t.is_alive()
                   for t in threading.enumerate())


def test_prefetch_metrics_and_health():
    from analytics_zoo_tpu.metrics import (
        DataPipelineMetrics,
        MetricsRegistry,
        get_health,
        snapshot,
    )

    reg = MetricsRegistry(enabled=True)
    x = np.zeros((40, 2), np.float32)
    fs = FeatureSet.of(x)
    pre = PrefetchFeatureSet(fs, depth=2, workers=1,
                             metrics=DataPipelineMetrics(registry=reg))
    n = len(list(pre.batches(8, shuffle=False)))
    by_name = {s["name"]: s for s in snapshot(reg)["samples"]}
    assert by_name["zoo_data_prefetch_batches_total"]["value"] == n
    # one wait per delivered batch plus the end-of-stream get
    assert by_name["zoo_data_prefetch_consumer_wait_seconds"]["count"] \
        == n + 1
    assert by_name["zoo_data_prefetch_workers"]["value"] == 1
    assert by_name.get("zoo_data_prefetch_errors_total",
                       {"value": 0})["value"] == 0
    # the infeed-style heartbeat component unregistered itself on exit
    assert "data_prefetch" not in get_health().status()["components"]


def test_estimator_composes_prefetch_with_infeed(zoo_ctx):
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    rng = np.random.default_rng(0)
    x = rng.standard_normal((128, 6)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)

    def fit(prefetch_workers):
        zoo_ctx.config.prefetch_workers = prefetch_workers
        zoo_ctx.config.prefetch_depth = 3
        model = Sequential()
        model.add(Dense(8, activation="relu", input_shape=(6,)))
        model.add(Dense(2, activation="softmax"))
        model.compile(optimizer="sgd",
                      loss="sparse_categorical_crossentropy")
        model.fit(x, y, batch_size=32, nb_epoch=2)
        return [h["loss"] for h in model._estimator.history]

    try:
        serial_losses = fit(0)
        prefetch_losses = fit(2)
    finally:
        zoo_ctx.config.prefetch_workers = 0
    # identical batch streams => identical training trajectories
    np.testing.assert_allclose(prefetch_losses, serial_losses, rtol=1e-6)


@pytest.mark.parametrize("bad", [{"depth": 0}, {"workers": 0}])
def test_pipeline_rejects_bad_knobs(bad):
    with pytest.raises(ValueError):
        PrefetchPipeline(iter([]), **bad)


def test_prefetched_host_work_overlaps_and_stream_is_identical():
    """A loader that sleeps a shard and a transform that sleeps a record
    (IO-shaped: both release the GIL) count their calls in flight.
    Serially there is one at a time; four workers hold at least two at
    once, and deliver the serial stream byte for byte."""
    lock = threading.Lock()
    in_flight = {"now": 0, "peak": 0}

    def sleeping(seconds):
        with lock:
            in_flight["now"] += 1
            in_flight["peak"] = max(in_flight["peak"], in_flight["now"])
        time.sleep(seconds)
        with lock:
            in_flight["now"] -= 1

    def load(path):
        sleeping(0.015)
        rng = np.random.default_rng(1234 + int(path.rsplit("-", 1)[-1]))
        return {"x": rng.standard_normal((32, 16)).astype("float32"),
                "y": rng.integers(0, 10, size=(32,)).astype("int32")}

    def slow_identity(record):
        sleeping(0.001)
        return record

    fs = ShardedFeatureSet(
        [f"synth://shard-{i}" for i in range(4)], n_slices=4,
        loader=load, sizer=lambda p: 32,
    ).transform(FnPreprocessing(slow_identity))

    def drain(feature_set):
        in_flight["peak"] = 0
        out = list(feature_set.batches(8, shuffle=True, seed=7, epoch=0))
        return out, in_flight["peak"]

    serial, serial_peak = drain(fs)
    prefetched, prefetched_peak = drain(
        PrefetchFeatureSet(fs, depth=8, workers=4))
    assert_streams_identical(serial, prefetched)
    assert serial_peak == 1
    assert prefetched_peak >= 2
