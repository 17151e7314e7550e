"""The host's account of a ``fit()`` call: one tree of spans a call, the
feeder's spans on their own thread under the epoch's, counters that agree
with the spans that feed them, and nothing recorded when both are off."""

import contextvars
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu import init_zoo_context
from analytics_zoo_tpu.metrics import (
    MetricsRegistry,
    Tracer,
    set_registry,
    set_tracer,
    span,
)

MAIN_UNDER_EPOCH = {
    "zoo.train.feeder_start", "zoo.train.data_wait",
    "zoo.train.step_dispatch", "zoo.train.on_iteration",
    "zoo.train.epoch_sync", "zoo.train.epoch_close"}
FEEDER = {"zoo.feed.batch", "zoo.feed.shard", "zoo.feed.blocked"}
UNDER_ENTER = {"zoo.fit.enter." + leaf for leaf in (
    "build", "place", "mem_gauges", "spec_record", "step_lookup", "resume")}
#: the histograms this account added, each with the span that feeds it
FED_BY = {"zoo_train_epoch_sync_seconds": "zoo.train.epoch_sync",
          "zoo_feed_host_batch_seconds": "zoo.feed.batch",
          "zoo_feed_shard_seconds": "zoo.feed.shard"}


def _model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    m = Sequential()
    m.add(Dense(2, activation="softmax", input_shape=(4,)))
    m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    return m


def _data(n=64):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    return x, (x.sum(1) > 0).astype(np.int32)


@pytest.fixture
def recorded():
    """Two ``fit`` calls of two epochs of eight steps, into a tracer and a
    registry of their own."""
    tracer, registry = Tracer(jax_bridge=False), MetricsRegistry()
    prev_t, prev_r = set_tracer(tracer), set_registry(registry)
    try:
        init_zoo_context(seed=0)
        m, (x, y) = _model(), _data()
        m.fit(x, y, batch_size=8, nb_epoch=2)
        m.fit(x, y, batch_size=8, nb_epoch=2)
    finally:
        set_tracer(prev_t)
        set_registry(prev_r)
    return tracer, registry


def _calls(events):
    by_fit = {}
    for e in events:
        if e["fit"] is not None:
            by_fit.setdefault(e["fit"], []).append(e)
    return [by_fit[k] for k in sorted(by_fit)]


def test_one_tree_a_call_with_the_table_s_nesting(recorded):
    events = recorded[0].events()
    assert len({e["id"] for e in events}) == len(events)
    calls = _calls(events)
    assert len(calls) == 2
    for call in calls:
        by_id = {e["id"]: e for e in call}
        name_of = lambda i: by_id[i]["name"] if i in by_id else None
        roots = [e for e in call if e["parent_id"] not in by_id]
        assert [e["name"] for e in roots] == ["zoo.keras.fit"]
        # the call's identifier is its outermost span's
        assert {e["fit"] for e in call} == {roots[0]["id"]}
        under = {}
        for e in call:
            under.setdefault(name_of(e["parent_id"]), set()).add(e["name"])
            # the name a test of PR 1 reads stays beside the identifier
            assert (e.get("args") or {}).get("parent") \
                == name_of(e["parent_id"])
        assert under["zoo.keras.fit"] == {"zoo.fit"}
        assert under["zoo.fit"] == {"zoo.fit.enter", "zoo.train.epoch",
                                    "zoo.fit.exit"}
        assert under["zoo.fit.enter"] == UNDER_ENTER
        assert under["zoo.train.epoch"] == MAIN_UNDER_EPOCH | FEEDER
        names = [e["name"] for e in call]
        assert names.count("zoo.fit") == 1
        assert names.count("zoo.train.epoch") == 2
        assert names.count("zoo.train.step_dispatch") == 16
        assert names.count("zoo.train.on_iteration") == 16
        assert names.count("zoo.train.data_wait") == 18
        assert names.count("zoo.train.epoch_sync") == 2


def test_the_feeder_s_spans_live_on_their_own_thread(recorded):
    for call in _calls(recorded[0].events()):
        main = {e["tid"] for e in call if e["name"] == "zoo.fit"}
        assert len(main) == 1
        epochs = {e["id"]: e for e in call if e["name"] == "zoo.train.epoch"}
        fed = [e for e in call if e["name"] in FEEDER]
        assert fed and all(e["tid"] not in main for e in fed)
        assert all(e["parent_id"] in epochs for e in fed)
        # one feeder thread an epoch, and only its spans off the main one
        assert len({e["tid"] for e in fed}) <= len(epochs)
        assert {e["name"] for e in call if e["tid"] not in main} <= FEEDER


def test_a_call_s_first_wait_and_every_step_are_marked(recorded):
    for call in _calls(recorded[0].events()):
        waits = [e for e in call if e["name"] == "zoo.train.data_wait"]
        assert sum(bool((e.get("args") or {}).get("first"))
                   for e in waits) == 2      # one an epoch
        steps = [e["args"]["step"] for e in call
                 if e["name"] == "zoo.train.step_dispatch"]
        assert steps == list(range(steps[0], steps[0] + 16))


def test_the_main_thread_s_leaves_tile_the_call(recorded):
    """Nothing of the main thread starts outside its parent, and the
    leaves under ``zoo.fit`` follow one another without overlap."""
    for call in _calls(recorded[0].events()):
        main = {e["tid"] for e in call if e["name"] == "zoo.fit"}
        spans = sorted((e for e in call if e["tid"] in main),
                       key=lambda e: e["ts"])
        by_id = {e["id"]: e for e in spans}
        for e in spans:
            parent = by_id.get(e["parent_id"])
            if parent is not None:
                assert parent["ts"] <= e["ts"]
                assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1
        parents = {e["parent_id"] for e in spans}
        leaves = [e for e in spans if e["id"] not in parents]
        for a, b in zip(leaves, leaves[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1


@pytest.mark.parametrize("family", sorted(FED_BY))
def test_a_new_histogram_agrees_with_its_spans(recorded, family):
    tracer, registry = recorded
    spans = [e for e in tracer.events() if e["name"] == FED_BY[family]]
    summary = registry.histogram(family, "").summary()
    assert summary["count"] == len(spans) > 0
    assert summary["sum"] == pytest.approx(
        sum(e["dur"] for e in spans) / 1e6, rel=1e-9)


def test_the_loop_s_two_histograms_keep_their_meaning(recorded):
    """A step each, as before; the dispatch's own clock reads lie around
    the span's, so it reads a little longer."""
    tracer, registry = recorded
    for family in ("zoo_train_data_wait_seconds",
                   "zoo_train_step_dispatch_seconds"):
        assert registry.histogram(family, "").summary()["count"] == 32
    spans = [e for e in tracer.events()
             if e["name"] == "zoo.train.step_dispatch"]
    assert registry.histogram("zoo_train_step_dispatch_seconds",
                              "").summary()["sum"] \
        >= sum(e["dur"] for e in spans) / 1e6


def test_context_init_is_a_span_and_a_gauge():
    tracer, registry = Tracer(jax_bridge=False), MetricsRegistry()
    prev_t, prev_r = set_tracer(tracer), set_registry(registry)
    try:
        init_zoo_context(seed=0)
    finally:
        set_tracer(prev_t)
        set_registry(prev_r)
    (event,) = [e for e in tracer.events()
                if e["name"] == "zoo.context.init"]
    assert event["fit"] is None and event["parent_id"] is None
    assert registry.gauge("zoo_context_init_seconds", "").get() \
        == pytest.approx(event["dur"] / 1e6, rel=1e-9)


@pytest.mark.parametrize("trace_on, metrics_on",
                         [(False, False), (False, True), (True, False)])
def test_switched_off_records_nothing(trace_on, metrics_on):
    """``ZOO_TRACE=0`` and ``ZOO_METRICS=0`` are a disabled tracer and a
    disabled registry: the fit runs, and neither records."""
    from analytics_zoo_tpu.metrics import snapshot

    tracer = Tracer(enabled=trace_on, jax_bridge=False)
    registry = MetricsRegistry(enabled=metrics_on)
    prev_t, prev_r = set_tracer(tracer), set_registry(registry)
    try:
        init_zoo_context(seed=0)
        _model().fit(*_data(), batch_size=8, nb_epoch=1)
    finally:
        set_tracer(prev_t)
        set_registry(prev_r)
    names = {e["name"] for e in tracer.events()}
    families = {s["name"] for s in snapshot(registry)["samples"]}
    assert bool(names) == trace_on
    assert bool(families) == metrics_on
    if trace_on:
        assert "zoo.feed.shard" in names and "zoo.fit" in names
    if metrics_on:
        # the spans' own counters count with the tracer off
        for family in FED_BY:
            assert registry.histogram(family, "").summary()["count"] > 0


def test_a_feeder_that_dies_mid_batch_leaves_a_closed_span():
    from analytics_zoo_tpu.pipeline.estimator.estimator import _DeviceFeeder

    def batches():
        yield {"x": 1}
        raise OSError("the shard went away")

    tracer = Tracer(jax_bridge=False)
    prev = set_tracer(tracer)
    try:
        with span("zoo.train.epoch"):
            feeder = _DeviceFeeder(batches(), lambda b: b, depth=2)
            got = []
            with pytest.raises(OSError, match="went away"):
                for item in feeder:
                    got.append(item)
            feeder._thread.join(timeout=10)
            assert not feeder._thread.is_alive()
    finally:
        set_tracer(prev)
    assert got == [{"x": 1}]
    events = tracer.events()
    (epoch,) = [e for e in events if e["name"] == "zoo.train.epoch"]
    batch_spans = [e for e in events if e["name"] == "zoo.feed.batch"]
    assert len(batch_spans) == 2
    assert "error" not in (batch_spans[0].get("args") or {})
    assert batch_spans[1]["args"]["error"] == "OSError"
    assert all(e["parent_id"] == epoch["id"] and e["tid"] != epoch["tid"]
               for e in batch_spans)


@pytest.mark.parametrize("where", ["shard", "blocked"])
def test_a_feeder_stopped_or_failing_later_closes_its_span_too(where):
    from analytics_zoo_tpu.pipeline.estimator.estimator import _DeviceFeeder

    def shard(b):
        if where == "shard":
            raise ValueError("bad batch")
        return b

    tracer = Tracer(jax_bridge=False)
    prev = set_tracer(tracer)
    try:
        feeder = _DeviceFeeder(iter([1, 2, 3, 4]), shard, depth=1)
        if where == "shard":
            with pytest.raises(ValueError, match="bad batch"):
                list(feeder)
        else:
            # never consumed: the thread sits in its put loop until told
            deadline = time.monotonic() + 10
            while feeder._q.qsize() < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.05)
            feeder.stop()
        feeder._thread.join(timeout=10)
        assert not feeder._thread.is_alive()
    finally:
        set_tracer(prev)
    last = [e for e in tracer.events() if e["name"] == "zoo.feed." + where]
    assert last, [e["name"] for e in tracer.events()]
    if where == "shard":
        assert last[-1]["args"]["error"] == "ValueError"
    else:
        assert last[-1]["dur"] >= 0 and "args" not in last[-1]


class TestSpan:
    def test_ids_parents_and_the_fit_identifier(self):
        t = Tracer(jax_bridge=False)
        with span("outside", tracer=t):
            pass
        with span("call", tracer=t, fit=True):
            with span("inner call", tracer=t, fit=True):   # a parent did
                with span("leaf", tracer=t):
                    pass
        with span("next call", tracer=t, fit=True):
            pass
        ev = {e["name"]: e for e in t.events()}
        assert ev["outside"]["fit"] is None
        assert ev["call"]["fit"] == ev["call"]["id"]
        assert ev["inner call"]["fit"] == ev["leaf"]["fit"] \
            == ev["call"]["id"]
        assert ev["leaf"]["parent_id"] == ev["inner call"]["id"]
        assert ev["inner call"]["parent_id"] == ev["call"]["id"]
        assert ev["call"]["parent_id"] is None
        assert ev["next call"]["fit"] == ev["next call"]["id"] \
            != ev["call"]["id"]

    def test_a_thread_under_a_copied_context_joins_the_tree(self):
        t = Tracer(jax_bridge=False)

        def work():
            with span("on the thread", tracer=t):
                pass

        with span("call", tracer=t, fit=True):
            copied = threading.Thread(
                target=contextvars.copy_context().run, args=(work,))
            bare = threading.Thread(target=work)
            for th in (copied, bare):
                th.start()
                th.join(timeout=10)
                assert not th.is_alive()
        call = next(e for e in t.events() if e["name"] == "call")
        joined, apart = [e for e in t.events()
                         if e["name"] == "on the thread"]
        assert joined["fit"] == call["id"] == joined["parent_id"]
        assert joined["tid"] != call["tid"]
        assert apart["fit"] is None and apart["parent_id"] is None

    def test_as_a_decorator_it_times_every_call(self):
        t = Tracer(jax_bridge=False)
        prev = set_tracer(t)
        try:
            @span("decorated", fit=True)
            def twice(x):
                """doc"""
                return 2 * x

            assert twice(2) == 4 and twice(3) == 6
        finally:
            set_tracer(prev)
        assert twice.__doc__ == "doc" and twice.__name__ == "twice"
        a, b = t.events()
        assert a["name"] == b["name"] == "decorated"
        assert a["fit"] == a["id"] != b["id"] == b["fit"]

    def test_a_block_that_raises_records_the_exception_s_type(self):
        t = Tracer(jax_bridge=False)
        with pytest.raises(KeyError):
            with span("outer", tracer=t):
                with span("inner", tracer=t, args={"k": 1}):
                    raise KeyError("x")
        ev = {e["name"]: e for e in t.events()}
        assert ev["inner"]["args"] == {"k": 1, "parent": "outer",
                                       "error": "KeyError"}
        assert ev["outer"]["args"] == {"error": "KeyError"}
        # and the next span starts from a clean stack
        with span("after", tracer=t):
            pass
        assert t.events()[-1]["parent_id"] is None

    @pytest.mark.parametrize("enabled", [True, False])
    def test_observe_gets_the_span_s_own_seconds(self, enabled):
        t = Tracer(enabled=enabled, jax_bridge=False)
        seen = []
        with span("timed", tracer=t, observe=seen.append):
            time.sleep(0.002)
        assert len(seen) == 1 and seen[0] >= 0.002
        if enabled:
            assert seen[0] == pytest.approx(t.events()[0]["dur"] / 1e6,
                                            rel=1e-9)
        else:
            assert t.events() == []

    def test_the_device_s_clock_is_the_epoch_in_nanoseconds(self):
        t = Tracer(jax_bridge=False)
        before = time.time_ns()
        with span("now", tracer=t):
            time.sleep(0.001)
        after = time.time_ns()
        start, end = t.device_clock_ns(t.events()[0])
        assert before - 2_000_000 <= start <= end <= after + 2_000_000
        assert end - start == pytest.approx(t.events()[0]["dur"] * 1e3,
                                            abs=2)
        assert t.clock_anchor()["epoch"] == pytest.approx(
            t._t0_epoch_ns / 1e9)
