"""Observability subsystem (ISSUE 1): registry semantics, exporters,
span tracing, serving/estimator telemetry wiring — plus regression tests
for the satellite fixes that rode the same PR (actor-worker auth,
ZeRO-1 reshard exact matching)."""

import json
import math
import os
import socket
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.metrics import (
    NULL,
    JsonlExporter,
    MetricsRegistry,
    TensorBoardExporter,
    Tracer,
    get_registry,
    prometheus_text,
    set_registry,
    set_tracer,
    snapshot,
    span,
)

# The `metrics` marker selects the observability-subsystem tests; the
# satellite-regression classes at the bottom of this file ride the same
# PR but are deliberately NOT tagged (they test actor auth / reshard,
# not telemetry).
metrics_mark = pytest.mark.metrics


@pytest.fixture()
def fresh_registry():
    """Swap in a private process-global registry; restore after."""
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


@metrics_mark
class TestRegistry:
    def test_counter_and_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("req_total", "requests", ("route",))
        c.labels(route="/a").inc()
        c.labels(route="/a").inc(2)
        c.labels(route="/b").inc(5)
        assert c.labels(route="/a").get() == 3
        assert c.labels(route="/b").get() == 5
        with pytest.raises(ValueError):
            c.labels(route="/a").inc(-1)  # counters only go up
        with pytest.raises(ValueError):
            c.labels(wrong="x")  # undeclared label name

    def test_gauge(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth", "")
        g.set(7)
        g.inc(3)
        g.dec(1)
        assert g.get() == 9

    def test_reregistration_conflicts(self):
        reg = MetricsRegistry()
        reg.counter("m", "")
        assert reg.counter("m", "") is reg.counter("m", "")  # idempotent
        with pytest.raises(ValueError):
            reg.gauge("m", "")  # kind conflict
        with pytest.raises(ValueError):
            reg.counter("m", "", ("l",))  # label conflict
        h = reg.histogram("h", "", buckets=(1, 2))
        assert reg.histogram("h", "") is h  # no buckets -> no check
        assert reg.histogram("h", "", buckets=(2, 1)) is h  # same bounds
        with pytest.raises(ValueError):
            reg.histogram("h", "", buckets=(1, 2, 4))  # bucket conflict

    def test_histogram_percentiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "", buckets=(1, 2, 4, 8, 16))
        for v in range(1, 9):  # uniform on (0, 8]
            h.observe(v)
        s = h.summary()
        assert s["count"] == 8 and s["sum"] == 36
        # p50 of uniform(0,8] sits in the (2,4] bucket; interpolation
        # keeps it within one bucket width of the true 4.0
        assert 2.0 <= s["p50"] <= 4.0
        # true p99 is 8; the estimate stays inside its (4, 8] bucket
        assert 4.0 <= s["p99"] <= 8.0
        # le= semantics are inclusive: value == bound lands in that bucket
        h2 = reg.histogram("lat2", "", buckets=(1, 2))
        h2.observe(1.0)
        assert dict(h2._default().buckets())[1.0] == 1
        # +Inf-bucket quantiles report the TAIL mean, not the overall
        # mean clamped to the last bound: 95 fast steps + 5 huge stalls
        # must surface the stall magnitude at p99
        h3 = reg.histogram("lat3", "", buckets=(1, 10))
        for _ in range(95):
            h3.observe(0.01)
        for _ in range(5):
            h3.observe(120.0)
        assert h3.percentile(0.99) == pytest.approx(120.0)

    def test_histogram_timer(self):
        reg = MetricsRegistry()
        h = reg.histogram("t", "")
        with h.time():
            pass
        assert h.summary()["count"] == 1

    def test_disabled_registry_is_allocation_free_noop(self):
        reg = MetricsRegistry(enabled=False)
        # every factory returns the ONE shared singleton: the hot path
        # never allocates children, label tuples, or timer objects
        assert reg.counter("a", "") is NULL
        assert reg.gauge("b", "") is NULL
        assert reg.histogram("c", "") is NULL
        assert NULL.labels(x="1") is NULL
        assert NULL.time() is NULL.time()  # shared no-op timer too
        NULL.inc()
        NULL.set(3)
        NULL.observe(0.1)  # all silently no-op
        assert reg.collect() == []
        # side-channel gate: work done ONLY to feed a metric (e.g. the
        # serving queue-depth xlen round-trip) keys off this flag
        from analytics_zoo_tpu.metrics import ServingMetrics

        assert ServingMetrics(reg).enabled is False

    def test_thread_safety(self):
        reg = MetricsRegistry()
        c = reg.counter("n", "")
        h = reg.histogram("h", "", buckets=(0.5,))

        def work():
            for _ in range(1000):
                c.inc()
                h.observe(0.1)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get() == 8000
        assert h.summary()["count"] == 8000


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("zoo_req_total", "requests", ("route",)).labels(
        route="/predict").inc(4)
    reg.gauge("zoo_depth", "queue depth").set(2)
    h = reg.histogram("zoo_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    return reg


@metrics_mark
class TestExporters:
    def test_prometheus_text(self):
        text = prometheus_text(_populated_registry())
        lines = text.splitlines()
        assert "# TYPE zoo_req_total counter" in lines
        assert 'zoo_req_total{route="/predict"} 4.0' in lines
        assert "# TYPE zoo_lat_seconds histogram" in lines
        # cumulative buckets end with the +Inf total == _count
        assert 'zoo_lat_seconds_bucket{le="0.1"} 1' in lines
        assert 'zoo_lat_seconds_bucket{le="1.0"} 2' in lines
        assert 'zoo_lat_seconds_bucket{le="+Inf"} 3' in lines
        assert "zoo_lat_seconds_count 3" in lines
        sum_line = [l for l in lines
                    if l.startswith("zoo_lat_seconds_sum")][0]
        assert math.isclose(float(sum_line.split()[-1]), 5.55)

    def test_jsonl_roundtrip(self, tmp_path):
        reg = _populated_registry()
        path = str(tmp_path / "m.jsonl")
        exp = JsonlExporter(path, reg)
        exp.write(step=1)
        reg.gauge("zoo_depth", "").set(9)
        exp.write(step=2)
        docs = [json.loads(l) for l in open(path)]
        assert len(docs) == 2 and docs[1]["step"] == 2
        by_name = {s["name"]: s for s in docs[1]["samples"]
                   if "labels" not in s}
        assert by_name["zoo_depth"]["value"] == 9
        assert by_name["zoo_lat_seconds"]["count"] == 3

    def test_metrics_dump_tool(self, tmp_path, capsys):
        import importlib.util
        import sys

        reg = _populated_registry()
        path = str(tmp_path / "m.jsonl")
        JsonlExporter(path, reg).write()
        spec = importlib.util.spec_from_file_location(
            "metrics_dump", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "metrics_dump.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        old_argv = sys.argv
        sys.argv = ["metrics_dump.py", path]
        try:
            mod.main()
        finally:
            sys.argv = old_argv
        out = capsys.readouterr().out
        assert "zoo_lat_seconds" in out and "zoo_depth" in out

    def test_tensorboard_bridge(self, tmp_path):
        from analytics_zoo_tpu.tensorboard import TrainSummary

        reg = _populated_registry()
        w = TrainSummary(str(tmp_path), "metrics-test")
        n = TensorBoardExporter(w, reg).export(step=3)
        w.close()
        assert n > 0
        scal = w.read_scalar("zoo_depth")
        assert scal and scal[0][0] == 3 and scal[0][1] == 2.0
        p50 = w.read_scalar("zoo_lat_seconds/p50")
        assert p50 and p50[0][1] > 0


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


@metrics_mark
class TestTracing:
    def test_nested_spans_chrome_trace(self, tmp_path):
        t = Tracer(jax_bridge=False)
        with span("outer", tracer=t):
            with span("inner", args={"k": 1}, tracer=t):
                time.sleep(0.001)
        doc = t.to_chrome_trace()
        json.dumps(doc)  # serializable
        evs = {e["name"]: e for e in doc["traceEvents"]}
        assert set(evs) == {"outer", "inner"}
        for e in evs.values():
            assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e \
                and "pid" in e and "tid" in e
        assert evs["inner"]["args"]["parent"] == "outer"
        assert evs["inner"]["args"]["k"] == 1
        # inner is contained in outer's interval
        assert evs["outer"]["ts"] <= evs["inner"]["ts"]
        assert (evs["inner"]["ts"] + evs["inner"]["dur"]
                <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-3)
        p = t.save(str(tmp_path / "trace.json"))
        assert json.load(open(p))["traceEvents"]

    def test_span_sync_blocks_on_device_values(self):
        import jax.numpy as jnp

        t = Tracer(jax_bridge=False)
        x = jnp.ones((8, 8))
        with span("compute", sync=x @ x, tracer=t):
            pass
        assert t.events()[0]["name"] == "compute"

    def test_event_cap_keeps_newest_counts_drops(self):
        t = Tracer(jax_bridge=False, max_events=2)
        for i in range(5):
            with span(f"s{i}", tracer=t):
                pass
        # ring buffer: the NEWEST window survives (a day-2 anomaly must
        # be capturable), evictions are counted
        assert [e["name"] for e in t.events()] == ["s3", "s4"]
        assert t.to_chrome_trace()["metadata"]["dropped_events"] == 3

    def test_disabled_tracer_records_nothing(self):
        t = Tracer(enabled=False)
        with span("x", tracer=t):
            pass
        assert t.events() == []


# ---------------------------------------------------------------------------
# wiring: serving + estimator telemetry land in the default registry
# ---------------------------------------------------------------------------


def _tiny_classifier(tmp_path):
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Flatten
    from analytics_zoo_tpu.pipeline.api.keras.topology import Sequential

    m = Sequential()
    m.add(Flatten(input_shape=(4, 4, 1)))
    m.add(Dense(5, activation="softmax"))
    m.build_params()
    path = str(tmp_path / "model.zoo")
    m.save(path)
    return path


@metrics_mark
class TestServingTelemetry:
    def test_step_populates_queue_latency_and_broker_gauge(
            self, tmp_path, fresh_registry):
        from analytics_zoo_tpu.serving import (
            ClusterServing,
            ClusterServingHelper,
            InMemoryBroker,
            InputQueue,
        )

        broker = InMemoryBroker()
        serving = ClusterServing(
            ClusterServingHelper(model_path=_tiny_classifier(tmp_path),
                                 batch_size=4, data_shape=(4, 4, 1),
                                 log_dir=str(tmp_path / "logs")),
            broker=broker)
        inq = InputQueue(broker=broker)
        for i in range(6):
            inq.enqueue_image(f"u{i}", np.zeros((4, 4, 1), np.float32))
        served = serving.step(block_ms=0)
        assert served == 4
        reg = fresh_registry
        # latency histogram populated by the non-empty step
        lat = reg.histogram("zoo_serving_step_latency_seconds", "")
        assert lat.summary()["count"] == 1 and lat.summary()["sum"] > 0
        assert reg.histogram("zoo_serving_batch_size", "").summary() != {}
        assert reg.counter("zoo_serving_records_total", "").get() == 4
        # queue depth observed AFTER the poll: 2 records remain
        assert reg.gauge("zoo_serving_queue_depth", "").get() == 2
        # broker memory_ratio published as a gauge (broker.py wiring)
        g = reg.gauge("zoo_serving_broker_memory_ratio", "").get()
        assert 0.0 <= g <= 1.0
        # inference layer: per-bucket compile count + predict latency
        text = prometheus_text(reg)
        assert "zoo_inference_compiles_total" in text
        assert "zoo_inference_predict_seconds_count" in text
        serving.summary.close()

    def test_prometheus_export_after_serving_is_valid(
            self, tmp_path, fresh_registry):
        from analytics_zoo_tpu.serving import (
            ClusterServing,
            ClusterServingHelper,
            InMemoryBroker,
            InputQueue,
        )

        broker = InMemoryBroker()
        serving = ClusterServing(
            ClusterServingHelper(model_path=_tiny_classifier(tmp_path),
                                 batch_size=2, data_shape=(4, 4, 1),
                                 log_dir=str(tmp_path / "logs")),
            broker=broker)
        InputQueue(broker=broker).enqueue_image(
            "one", np.zeros((4, 4, 1), np.float32))
        serving.step(block_ms=0)
        text = prometheus_text(fresh_registry)
        # every family has a TYPE line and histograms end at +Inf == count
        assert "# TYPE zoo_serving_step_latency_seconds histogram" in text
        inf_line = [l for l in text.splitlines()
                    if l.startswith("zoo_serving_step_latency_seconds_"
                                    "bucket") and 'le="+Inf"' in l][0]
        count_line = [l for l in text.splitlines()
                      if l.startswith(
                          "zoo_serving_step_latency_seconds_count")][0]
        assert inf_line.split()[-1] == count_line.split()[-1]
        # idle polls record NO spans: an idle loop must not flood the
        # bounded tracer with zero-information events
        t = Tracer(jax_bridge=False)
        prev = set_tracer(t)
        try:
            assert serving.step(block_ms=0) == 0
            assert t.events() == []
        finally:
            set_tracer(prev)
        serving.summary.close()


@metrics_mark
class TestEstimatorTelemetry:
    def test_fit_records_step_breakdown(self, fresh_registry, zoo_ctx):
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.topology import (
            Sequential,
        )

        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = rng.integers(0, 4, size=(64,)).astype(np.int32)
        m = Sequential()
        m.add(Dense(4, activation="softmax", input_shape=(8,)))
        m.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy")
        m.fit(x, y, batch_size=32, nb_epoch=1)
        reg = fresh_registry
        assert reg.counter("zoo_train_steps_total", "").get() == 2
        assert reg.counter("zoo_train_records_total", "").get() == 64
        for name in ("zoo_train_data_wait_seconds",
                     "zoo_train_step_dispatch_seconds",
                     "zoo_train_step_seconds"):
            assert reg.histogram(name, "").summary()["count"] == 2, name
        assert reg.gauge("zoo_train_throughput_records_per_sec",
                         "").get() > 0
        # span() instrumentation is on by default: the fit loop produced
        # zoo.train.step events in the default tracer
        from analytics_zoo_tpu.metrics import get_tracer

        assert any(e["name"] == "zoo.train.step_dispatch"
                   for e in get_tracer().events())


@metrics_mark
class TestPipelineTelemetry:
    def test_gpipe_records_bubble_metrics(self, fresh_registry):
        import jax.numpy as jnp

        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        zoo.init_zoo_context(seed=0, mesh_shape={"data": 2, "pipe": 4},
                             mesh_axes=("data", "pipe"))
        stages = jnp.ones((4, 6, 6)) * 0.5

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        x = jnp.ones((8, 6))
        try:
            out = gpipe(stage_fn, stages, x, n_microbatch=4)
            assert out.shape == (8, 6)
        except AttributeError:
            # this image's jax lacks jax.shard_map (pre-existing for all
            # pipeline schedules here); the schedule metrics under test
            # are recorded before the shard_map construction
            pass
        reg = fresh_registry
        g = reg.gauge("zoo_pipeline_bubble_fraction", "", ("schedule",))
        # GPipe bubble: (S-1)/(M+S-1) = 3/7
        assert g.labels(schedule="gpipe").get() == pytest.approx(3 / 7)
        per_mb = reg.gauge("zoo_pipeline_bubble_ticks_per_microbatch",
                           "", ("schedule",))
        assert per_mb.labels(schedule="gpipe").get() == \
            pytest.approx(3 / 4)

    def test_1f1b_records_bubble_metrics(self, fresh_registry):
        import jax
        import jax.numpy as jnp

        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.parallel.pipeline import gpipe_1f1b_grads

        zoo.init_zoo_context(seed=0, mesh_shape={"data": 2, "pipe": 4},
                             mesh_axes=("data", "pipe"))
        S, M = 4, 8
        stages = jnp.ones((S, 6, 6)) * 0.1

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        def loss_fn(o, t):
            return jnp.mean((o - t) ** 2)

        x = jnp.ones((16, 6))
        try:
            gpipe_1f1b_grads(stage_fn, loss_fn, stages, x, x,
                             n_microbatch=M)
        except AttributeError:
            pass  # pre-shim jax: metrics still recorded at trace time
        g = fresh_registry.gauge("zoo_pipeline_bubble_fraction", "",
                                 ("schedule",))
        # dual fwd/bwd schedule: T = M + 2S - 1 ticks, each stream
        # idles 2S - 1 of them -> 7/15 (NOT 6/15: the fwd->bwd offset
        # at the last stage costs one extra tick)
        assert g.labels(schedule="1f1b").get() == pytest.approx(7 / 15)


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------


class TestActorWorkerAuth:
    """ADVICE r05 medium: loopback default + shared-secret handshake
    before any unpickling."""

    def test_default_bind_is_loopback(self):
        from analytics_zoo_tpu.parallel.actor_worker import (
            start_worker_server,
        )

        srv = start_worker_server(0, block=False)
        try:
            assert srv.getsockname()[0] == "127.0.0.1"
        finally:
            srv.close()

    def test_nonloopback_bind_requires_secret_or_optin(self, monkeypatch):
        from analytics_zoo_tpu.parallel.actor_worker import (
            start_worker_server,
        )

        monkeypatch.delenv("ZOO_ACTOR_SECRET", raising=False)
        with pytest.raises(ValueError, match="secret"):
            start_worker_server(0, bind="0.0.0.0", block=False)
        srv = start_worker_server(0, bind="0.0.0.0", block=False,
                                  secret="tok")
        srv.close()
        srv = start_worker_server(0, bind="0.0.0.0", block=False,
                                  allow_unauthenticated=True)
        srv.close()

    def test_handshake_gates_unpickling(self):
        from analytics_zoo_tpu.parallel.actor_worker import (
            _HELLO_AUTH,
            SockConn,
            _client_proof,
            _server_proof,
            start_worker_server,
        )

        srv = start_worker_server(0, block=False, secret="s3cret")
        port = srv.getsockname()[1]
        try:
            # correct secret: passes auth, reaches the frame dispatcher
            # (a bad spawn kind comes back as init_error — proof the
            # server processed our pickle AFTER auth).  Mutual: the
            # server's counter-proof must verify too.
            c = SockConn(socket.create_connection(("127.0.0.1", port),
                                                  timeout=10))
            hello = c.recv_bytes(timeout=10, max_len=64)
            assert hello.startswith(_HELLO_AUTH)
            challenge = hello[len(_HELLO_AUTH):]
            nonce = os.urandom(32)
            c.send_bytes(nonce + _client_proof(b"s3cret", challenge,
                                               nonce))
            counter = c.recv_bytes(timeout=10, max_len=64)
            assert counter == _server_proof(b"s3cret", challenge, nonce)
            c.send(("not-spawn", None))
            kind, _ = c.recv()
            assert kind == "init_error"
            c.close()

            # wrong secret: connection closed before any unpickling
            c = SockConn(socket.create_connection(("127.0.0.1", port),
                                                  timeout=10))
            c.recv_bytes(timeout=10, max_len=64)
            c.send_bytes(b"\x00" * 32)
            c.send(("spawn", b"evil"))
            with pytest.raises((EOFError, OSError, TimeoutError)):
                for _ in range(10):  # server closes; recv must fail
                    c.poll(0.2)
                    c.recv()
            c.close()
        finally:
            srv.close()

    def test_secret_presence_mismatch_fails_fast(self, monkeypatch):
        """Hello frame announces the auth mode: a driver/worker secret
        mismatch raises immediately (either direction), no 30s hang."""
        from analytics_zoo_tpu.parallel.actor_worker import (
            connect_and_spawn,
            start_worker_server,
        )

        monkeypatch.delenv("ZOO_ACTOR_SECRET", raising=False)
        # worker authenticated, driver without a secret
        srv = start_worker_server(0, block=False, secret="s3cret")
        addr = "127.0.0.1:%d" % srv.getsockname()[1]
        try:
            with pytest.raises(RuntimeError, match="requires a shared"):
                connect_and_spawn(addr, b"payload")
        finally:
            srv.close()
        # worker open, driver configured with a secret: refuse downgrade
        srv = start_worker_server(0, block=False)
        addr = "127.0.0.1:%d" % srv.getsockname()[1]
        try:
            with pytest.raises(RuntimeError, match="unauthenticated"):
                connect_and_spawn(addr, b"payload", secret="s3cret")
        finally:
            srv.close()
        # WRONG secret value (both ends authenticated): the server's
        # silent close surfaces as an auth error, not a bare EOFError
        srv = start_worker_server(0, block=False, secret="right")
        addr = "127.0.0.1:%d" % srv.getsockname()[1]
        try:
            with pytest.raises(RuntimeError,
                               match="WRONG shared secret"):
                connect_and_spawn(addr, b"payload", secret="wrong")
        finally:
            srv.close()

    def test_options_secret_reaches_connect(self, monkeypatch):
        """The public actor API (`.options(secret=...)`) plumbs the
        shared secret down to connect_and_spawn for drivers that cannot
        set ZOO_ACTOR_SECRET."""
        import analytics_zoo_tpu.parallel.actor_worker as aw
        from analytics_zoo_tpu.parallel.actors import _RemoteClass

        seen = {}

        def fake_connect(addr, payload, secret=None):
            seen["addr"], seen["secret"] = addr, secret
            raise RuntimeError("stop-here")

        monkeypatch.setattr(aw, "connect_and_spawn", fake_connect)

        class Dummy:
            pass

        import analytics_zoo_tpu.parallel.actors as actors_mod

        ctx = actors_mod.ActorContext.current()
        monkeypatch.setattr(
            ctx, "_resolve_worker", lambda w: w, raising=False)
        rc = _RemoteClass(Dummy).options(worker="127.0.0.1:9040",
                                         secret="vault-token")
        with pytest.raises(RuntimeError, match="stop-here"):
            rc.remote()
        assert seen == {"addr": "127.0.0.1:9040",
                        "secret": "vault-token"}

    def test_spoofed_server_rejected_before_driver_unpickles(self):
        """Mutual auth: an endpoint that speaks the hello protocol but
        cannot produce the server counter-proof is refused BEFORE the
        driver deserializes anything it sends."""
        from analytics_zoo_tpu.parallel.actor_worker import (
            _HELLO_AUTH,
            _LEN,
            connect_and_spawn,
        )

        srv = socket.create_server(("127.0.0.1", 0))
        addr = "127.0.0.1:%d" % srv.getsockname()[1]

        def fake_worker():
            sock, _ = srv.accept()
            frame = _HELLO_AUTH + b"\x00" * 32
            sock.sendall(_LEN.pack(len(frame)) + frame)
            sock.recv(4096)  # client's nonce+proof (useless to us)
            bogus = b"\x11" * 32  # cannot forge _server_proof
            sock.sendall(_LEN.pack(len(bogus)) + bogus)
            sock.close()

        t = threading.Thread(target=fake_worker, daemon=True)
        t.start()
        try:
            with pytest.raises(RuntimeError, match="prove knowledge"):
                connect_and_spawn(addr, b"payload", secret="s3cret")
        finally:
            srv.close()

    def test_oversized_preauth_frame_rejected(self):
        from analytics_zoo_tpu.parallel.actor_worker import (
            SockConn,
            start_worker_server,
        )

        srv = start_worker_server(0, block=False, secret="s3cret")
        port = srv.getsockname()[1]
        try:
            c = SockConn(socket.create_connection(("127.0.0.1", port),
                                                  timeout=10))
            c.recv_bytes(timeout=10, max_len=64)
            c.send_bytes(b"\x00" * 4096)  # > pre-auth 64-byte limit
            with pytest.raises((EOFError, OSError, TimeoutError)):
                for _ in range(10):
                    c.poll(0.2)
                    c.recv()
            c.close()
        finally:
            srv.close()


class TestReshardZero1:
    """ADVICE r05 low (strategies.py:219): flat vectors matched by exact
    padded length; everything else replicated, never truncated."""

    def test_exact_match_and_replication(self):
        import jax

        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.parallel import reshard_zero1_opt_state

        # model axis soaks up the spare devices: leftover devices would
        # otherwise fold INTO the data axis (engine._infer_mesh_shape)
        zoo.init_zoo_context(seed=0, mesh_shape={"data": 4, "model": 2})
        params = {"w": np.arange(10.0, dtype=np.float32)}  # size 10
        padded_old = 16  # saved under n_old=8: 10 + 6 pad
        opt_state = {
            "mu": np.arange(padded_old, dtype=np.float32),
            "nu": np.ones(padded_old, np.float32),
            "count": np.zeros((), np.float32),
            # coincidental 1-D leaf LONGER than the flat layout: the old
            # `size >= param_size` match would truncate + force-shard it
            "odd": np.arange(17, dtype=np.float32),
            # coincidental 1-D leaf BETWEEN size and the padded length:
            # the shared-length preference must not let this unique
            # length shadow the mu/nu mirrors' agreed padded length
            "odd2": np.arange(12, dtype=np.float32),
            # ndim>=1 leaf whose dim 0 the new mesh cannot divide: the
            # old force-shard P(DATA_AXIS) made device_put fail
            "mat": np.ones((3, 3), np.float32),
        }
        for n_old in (8, None):  # explicit and inferred old layouts
            out = reshard_zero1_opt_state(opt_state, params, n_old=n_old)
            # matched vectors: pad stripped, re-padded for n_new=4 -> 12
            assert out["mu"].shape == (12,)
            np.testing.assert_array_equal(
                np.asarray(out["mu"])[:10], opt_state["mu"][:10])
            assert np.asarray(out["mu"])[10:].sum() == 0
            # non-matching leaves: untouched values, replicated layout
            np.testing.assert_array_equal(np.asarray(out["odd"]),
                                          opt_state["odd"])
            np.testing.assert_array_equal(np.asarray(out["odd2"]),
                                          opt_state["odd2"])
            np.testing.assert_array_equal(np.asarray(out["mat"]),
                                          opt_state["mat"])
            assert out["odd"].sharding.is_fully_replicated
            assert out["odd2"].sharding.is_fully_replicated
            assert out["mat"].sharding.is_fully_replicated
            assert not out["mu"].sharding.is_fully_replicated
            assert out["count"].shape == ()


# ---------------------------------------------------------------------------
# ISSUE 2 satellites: exposition name hygiene, disabled-mode exporters,
# tracer eviction counter
# ---------------------------------------------------------------------------


@metrics_mark
class TestPrometheusNameHygiene:
    """Satellite regression: registry names are unconstrained (dotted
    span-style names are natural), but the exposition must stay inside
    the Prometheus charset instead of emitting invalid series."""

    def test_dots_and_invalid_chars_sanitized(self):
        from analytics_zoo_tpu.metrics import sanitize_metric_name

        reg = MetricsRegistry()
        reg.counter("zoo.serving.step_total", "dotted").inc(2)
        reg.gauge("weird name-metric", "").set(1)
        h = reg.histogram("zoo.lat.seconds", "", buckets=(1.0,))
        h.observe(0.5)
        text = prometheus_text(reg)
        import re

        name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            name = line.split("{")[0].split(" ")[0]
            assert name_re.match(name), f"invalid exposition name {name!r}"
        assert "zoo_serving_step_total 2.0" in text
        assert "weird_name_metric 1.0" in text
        assert 'zoo_lat_seconds_bucket{le="1.0"} 1' in text
        # leading digit gets a prefix, valid names pass through untouched
        assert sanitize_metric_name("9lives") == "_9lives"
        assert sanitize_metric_name("zoo_ok_total") == "zoo_ok_total"

    def test_label_names_sanitized(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "", ("my.label",)).labels(
            **{"my.label": "v"}).inc()
        text = prometheus_text(reg)
        assert 'c_total{my_label="v"} 1.0' in text

    def test_label_name_collisions_get_deterministic_suffix(self):
        # "a.b" and "a_b" both sanitize to a_b: a duplicate label name
        # inside one sample is invalid exposition, so one key gets a
        # stable crc32 suffix
        reg = MetricsRegistry()
        reg.counter("c_total", "", ("a.b", "a_b")).labels(
            **{"a.b": "1", "a_b": "2"}).inc()
        text = prometheus_text(reg)
        line = [l for l in text.splitlines()
                if l.startswith("c_total{")][0]
        import re

        names = re.findall(r'([a-zA-Z0-9_]+)="', line)
        assert len(names) == len(set(names)) == 2
        assert "a_b" in names
        assert prometheus_text(reg) == text  # deterministic

    def test_sanitize_collisions_get_deterministic_suffix(self):
        # two DISTINCT registry names mapping onto one exposition name
        # must not emit duplicate TYPE blocks (a parser rejects the
        # whole body) — the later one gets a stable crc32 suffix
        reg = MetricsRegistry()
        reg.counter("zoo.lat_total", "").inc(1)
        reg.counter("zoo_lat_total", "").inc(2)
        text = prometheus_text(reg)
        type_lines = [l for l in text.splitlines()
                      if l.startswith("# TYPE")]
        names = [l.split()[2] for l in type_lines]
        assert len(names) == len(set(names)) == 2
        assert "zoo_lat_total" in names
        suffixed = next(n for n in names if n != "zoo_lat_total")
        assert suffixed.startswith("zoo_lat_total_x")
        # deterministic: a second render produces the same names
        assert prometheus_text(reg) == text


@metrics_mark
class TestDisabledExporters:
    """Satellite: every exporter against the ZOO_METRICS=0 no-op
    registry must produce empty-but-valid output and allocate no
    families/children per call."""

    def test_disabled_registry_hands_out_null_only(self):
        reg = MetricsRegistry(enabled=False)
        assert reg.counter("a_total", "") is NULL
        assert reg.gauge("g", "") is NULL
        assert reg.gauge("g", "").labels() is NULL
        assert reg.histogram("h_seconds", "") is NULL
        assert reg.counter("a_total", "", ("l",)).labels(l="x") is NULL
        assert reg.collect() == []  # nothing was ever allocated

    def test_prometheus_text_empty_but_valid(self):
        reg = MetricsRegistry(enabled=False)
        reg.counter("a_total", "").inc(5)
        assert prometheus_text(reg) == ""

    def test_jsonl_empty_but_valid(self, tmp_path):
        reg = MetricsRegistry(enabled=False)
        reg.histogram("h", "").observe(1.0)
        path = str(tmp_path / "m.jsonl")
        doc = JsonlExporter(path, reg).write(step=7)
        assert doc["samples"] == [] and doc["step"] == 7
        line = json.loads(open(path).read())
        assert line["samples"] == []

    def test_tensorboard_export_writes_nothing(self):
        class Writer:
            def __init__(self):
                self.calls = []

            def add_scalar(self, *a):
                self.calls.append(a)

        reg = MetricsRegistry(enabled=False)
        reg.gauge("g", "").set(3)
        w = Writer()
        assert TensorBoardExporter(w, reg).export(step=1) == 0
        assert w.calls == []

    def test_no_allocation_per_call(self):
        reg = MetricsRegistry(enabled=False)
        for _ in range(100):
            reg.counter("x_total", "").inc()
            reg.histogram("y_seconds", "").observe(0.1)
        assert reg.collect() == []  # still zero families
        # the snapshot side allocates nothing either
        from analytics_zoo_tpu.metrics import telemetry_snapshot

        assert telemetry_snapshot(reg)["samples"] == []


@metrics_mark
class TestTracerDropCounter:
    def test_ring_evictions_increment_registry_counter(self,
                                                       fresh_registry):
        t = Tracer(jax_bridge=False, max_events=2)
        for i in range(5):
            with span(f"s{i}", tracer=t):
                pass
        assert t.dropped == 3
        c = fresh_registry.counter(
            "zoo_trace_spans_dropped_total", "")
        assert c.get() == 3
        # and /varz carries the same number without needing /trace
        from analytics_zoo_tpu.metrics import MetricsServer

        srv = MetricsServer(port=0, host="127.0.0.1",
                            registry=fresh_registry, tracer=t).start()
        try:
            import urllib.request

            doc = json.loads(urllib.request.urlopen(
                srv.url + "/varz", timeout=10).read())
            assert doc["trace"]["dropped_spans"] == 3
            assert any(s["name"] == "zoo_trace_spans_dropped_total"
                       and s["value"] == 3 for s in doc["samples"])
        finally:
            srv.stop()


@metrics_mark
class TestHistogramDeltaSince:
    """Histogram.snapshot_state/delta_since — the rolling-window reader
    controllers use (feature/autotune.py) instead of lifetime blurs."""

    def _hist(self):
        from analytics_zoo_tpu.metrics import MetricsRegistry

        return MetricsRegistry().histogram(
            "h", "", buckets=(0.001, 0.01, 0.1, 1.0))

    def test_window_sees_only_recent_observations(self):
        h = self._hist()
        for _ in range(50):
            h.observe(0.0005)  # old regime: sub-ms
        base = h.snapshot_state()
        for _ in range(10):
            h.observe(0.5)  # new regime: half a second
        d = h.delta_since(base)
        assert d["count"] == 10
        assert d["p50"] > 0.1  # the window reflects the NEW regime...
        assert h.summary()["p50"] < 0.01  # ...while lifetime still blurs
        assert abs(d["sum"] - 5.0) < 1e-9
        assert abs(d["mean"] - 0.5) < 1e-9

    def test_empty_window(self):
        h = self._hist()
        h.observe(0.05)
        base = h.snapshot_state()
        d = h.delta_since(base)
        assert d == {"count": 0, "sum": 0.0, "mean": 0.0,
                     "p50": 0.0, "p95": 0.0, "p99": 0.0}

    def test_none_baseline_is_lifetime(self):
        h = self._hist()
        h.observe(0.05)
        assert h.delta_since(None) == h.summary()

    def test_partial_window_spanning_merged_buckets(self):
        h = self._hist()
        h.observe(0.0005)
        base = h.snapshot_state()
        # the window spans three different buckets + the +Inf tail
        for v in (0.005, 0.005, 0.05, 0.5, 2.0):
            h.observe(v)
        d = h.delta_since(base)
        assert d["count"] == 5
        assert 0.001 < d["p50"] <= 0.1
        assert d["p99"] >= 1.0  # the +Inf-tail observation is visible

    def test_mismatched_bucket_layout_raises(self):
        from analytics_zoo_tpu.metrics import MetricsRegistry

        h = self._hist()
        other = MetricsRegistry().histogram("h2", "", buckets=(0.1,))
        other.observe(0.05)
        with pytest.raises(ValueError, match="buckets"):
            h.delta_since(other.snapshot_state())

    def test_reset_baseline_degrades_to_full_summary(self):
        h = self._hist()
        h.observe(0.05)
        h.observe(0.05)
        ahead = (list(h.snapshot_state()[0]), 99.0, 99, 0.0)
        ahead[0][0] += 100  # a baseline AHEAD of the child (reset case)
        d = h.delta_since(tuple(ahead))
        assert d == h.summary()

    def test_null_metric_parity(self):
        from analytics_zoo_tpu.metrics import NULL

        assert NULL.snapshot_state() is None
        assert NULL.delta_since(None) == {}
