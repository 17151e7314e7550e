"""zoofleet: exactly-once work claiming, continuous batching, and the
SLO-aware autoscaling fleet (serving/broker.py claim protocol,
serving/server.py fleet mode, serving/fleet.py, serving/scaler.py)."""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.serving import (
    ClusterServing, ClusterServingHelper, FileBroker, InMemoryBroker,
    InputQueue, OutputQueue, ServingTimeout,
)
from analytics_zoo_tpu.serving.fleet import (
    FleetController, _SyntheticModel, varz_doc,
)
from analytics_zoo_tpu.serving.scaler import FleetSignals, SloScaler

STREAM = "image_stream"


@pytest.fixture(params=["memory", "file", "redis"])
def broker(request, tmp_path):
    if request.param == "memory":
        return InMemoryBroker()
    if request.param == "file":
        return FileBroker(str(tmp_path / "spool"))
    # Redis leg (ISSUE 20 satellite): the claim/lease protocol against a
    # REAL redis — opt-in via ZOO_TEST_REDIS=host[:port] so CI without a
    # server skips instead of hanging on a connect timeout.
    spec = os.environ.get("ZOO_TEST_REDIS")
    if not spec:
        pytest.skip("set ZOO_TEST_REDIS=host[:port] to run the "
                    "RedisBroker protocol leg")
    host, _, port = spec.partition(":")
    from analytics_zoo_tpu.serving import RedisBroker

    try:
        b = RedisBroker(host=host or "localhost",
                        port=int(port) if port else 6379)
        b.xlen(STREAM)  # fail fast on an unreachable server
    except Exception as e:
        pytest.skip(f"redis at {spec!r} unusable: {e}")
    # isolate this test's keys: the shared server may hold state from
    # previous runs
    for key in list(b.keys("")):
        b.delete(key)
    b.xtrim(STREAM, 0)
    return b


# ---------------------------------------------------------------------------
# Broker claim/extend/release protocol
# ---------------------------------------------------------------------------


def test_claim_is_exclusive_and_preserves_order(broker):
    for i in range(6):
        broker.xadd(STREAM, {"i": str(i)})
    a = broker.claim(STREAM, "A", 4, lease_ms=5000)
    b = broker.claim(STREAM, "B", 10, lease_ms=5000)
    assert [f["i"] for _, f in a] == ["0", "1", "2", "3"]
    assert [f["i"] for _, f in b] == ["4", "5"]  # disjoint, no overlap
    assert broker.claim(STREAM, "C", 10, lease_ms=5000) == []
    assert broker.xlen(STREAM) == 6  # claimed records stay in the stream
    assert broker.unclaimed(STREAM) == 0


def test_lease_expiry_enables_takeover(broker):
    for i in range(3):
        broker.xadd(STREAM, {"i": str(i)})
    broker.claim(STREAM, "dead", 3, lease_ms=200)
    assert broker.claim(STREAM, "B", 3, lease_ms=200) == []
    time.sleep(0.25)
    got = broker.claim(STREAM, "B", 3, lease_ms=5000)
    assert [f["i"] for _, f in got] == ["0", "1", "2"]
    assert broker.pop_takeovers("B") == 3  # counted once...
    assert broker.pop_takeovers("B") == 0  # ...and reset on read


def test_extend_prolongs_lease(broker):
    broker.xadd(STREAM, {"i": "0"})
    [(rid, _)] = broker.claim(STREAM, "A", 1, lease_ms=300)
    time.sleep(0.15)
    broker.extend(STREAM, "A", [rid], lease_ms=5000)
    time.sleep(0.3)  # past the ORIGINAL expiry
    assert broker.claim(STREAM, "B", 1, lease_ms=300) == []
    assert broker.unclaimed(STREAM) == 0


def test_release_done_acks_and_release_requeues(broker):
    for i in range(4):
        broker.xadd(STREAM, {"i": str(i)})
    recs = broker.claim(STREAM, "A", 4, lease_ms=5000)
    ids = [r[0] for r in recs]
    broker.release(STREAM, "A", ids[:2], done=True)
    assert broker.xlen(STREAM) == 2  # served records left the stream
    broker.release(STREAM, "A", ids[2:], done=False)
    assert broker.unclaimed(STREAM) == 2  # requeued, immediately claimable
    again = broker.claim(STREAM, "B", 4, lease_ms=5000)
    assert [f["i"] for _, f in again] == ["2", "3"]
    assert broker.pop_takeovers("B") == 0  # requeue is not a takeover


def test_release_skips_foreign_claims(broker):
    broker.xadd(STREAM, {"i": "0"})
    [(rid, _)] = broker.claim(STREAM, "A", 1, lease_ms=5000)
    broker.release(STREAM, "B", [rid], done=True)  # not B's to ack
    assert broker.xlen(STREAM) == 1
    broker.extend(STREAM, "B", [rid], lease_ms=50)  # nor B's to extend
    time.sleep(0.1)
    assert broker.claim(STREAM, "C", 1, lease_ms=300) == []


def test_inmemory_blocking_xread_wakes_on_add():
    """Satellite pin: a blocking xread is Condition-woken by xadd within
    milliseconds — no poll/busy-wait loop (an idle replica burns no
    CPU waiting out block_ms)."""
    b = InMemoryBroker()
    out = {}

    def waiter():
        t0 = time.monotonic()
        out["recs"] = b.xread(STREAM, 4, block_ms=5000)
        out["dt"] = time.monotonic() - t0

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.15)
    b.xadd(STREAM, {"i": "0"})
    t.join(timeout=5)
    assert out["recs"], "woke empty"
    assert 0.1 < out["dt"] < 0.6, out["dt"]  # woke on notify, not timeout


def test_inmemory_blocking_claim_wakes_on_add_and_expiry():
    b = InMemoryBroker()
    out = {}

    def waiter(key):
        t0 = time.monotonic()
        out[key] = b.claim(STREAM, "W", 1, lease_ms=1000, block_ms=5000)
        out[key + "_dt"] = time.monotonic() - t0

    t = threading.Thread(target=waiter, args=("add",))
    t.start()
    time.sleep(0.15)
    b.xadd(STREAM, {"i": "0"})
    t.join(timeout=5)
    assert out["add"] and 0.1 < out["add_dt"] < 0.6, out
    b.release(STREAM, "W", [out["add"][0][0]], done=True)
    # expiry wake: a dead owner's lease ends mid-wait — the blocked
    # claimer self-wakes at the expiry instant, no notify involved
    b.xadd(STREAM, {"i": "1"})
    b.claim(STREAM, "dead", 1, lease_ms=300)
    t2 = threading.Thread(target=waiter, args=("exp",))
    t2.start()
    t2.join(timeout=5)
    assert out["exp"] and 0.2 < out["exp_dt"] < 0.8, out
    assert b.pop_takeovers("W") == 1


# ---------------------------------------------------------------------------
# Client polling (satellite: timeout + bounded backoff)
# ---------------------------------------------------------------------------


def test_client_poll_returns_late_result():
    broker = InMemoryBroker()
    outq = OutputQueue(broker=broker)

    def later():
        time.sleep(0.2)
        broker.hset("result:u1", {"value": "[[1, 0.9]]"})

    threading.Thread(target=later).start()
    res = outq.poll("u1", timeout=5.0)
    assert res == [[1, 0.9]]


def test_client_poll_timeout_is_typed_and_backoff_bounded():
    broker = InMemoryBroker()
    calls = {"n": 0}
    orig = broker.hgetall

    def counting(key):
        calls["n"] += 1
        return orig(key)

    broker.hgetall = counting
    outq = OutputQueue(broker=broker)
    t0 = time.monotonic()
    with pytest.raises(ServingTimeout) as ei:
        outq.poll("lost", timeout=0.6, initial_delay=0.005, max_delay=0.05)
    dt = time.monotonic() - t0
    assert 0.5 < dt < 2.0, dt
    assert ei.value.uri == "lost" and ei.value.timeout == 0.6
    assert isinstance(ei.value, TimeoutError)  # typed, catchable broadly
    # exponential backoff bounds the broker round-trips: a 5ms spin
    # loop would make ~120 calls in 0.6s; backoff to 50ms makes ~< 20
    assert calls["n"] < 30, calls["n"]


# ---------------------------------------------------------------------------
# Fleet-mode serving: continuous batching + exactly-once across replicas
# ---------------------------------------------------------------------------


class _CountingModel:
    """Sleep model that records each predict's batch size."""

    def __init__(self, sleep_per_record_s=0.0):
        self.sleep_s = sleep_per_record_s
        self.batches = []
        self._lock = threading.Lock()

    def predict(self, arr):
        with self._lock:
            self.batches.append(int(arr.shape[0]))
        if self.sleep_s:
            time.sleep(self.sleep_s * arr.shape[0])
        out = np.zeros((arr.shape[0], 5), np.float32)
        out[:, 0] = 1.0
        return out


def _fleet_server(broker, owner, model, tmp_path, batch_size=8,
                  budget_ms=25.0, lease_ms=3000, serve_log=None):
    return ClusterServing(
        ClusterServingHelper(model_path=None, batch_size=batch_size,
                             batch_budget_ms=budget_ms, lease_ms=lease_ms,
                             log_dir=str(tmp_path / ("logs-" + owner))),
        model=model, broker=broker, owner=owner, serve_log=serve_log)


@pytest.mark.parametrize("n_records, through_controller", [
    (24, False), (300, True)],
    ids=["two-servers", "fleet-pinned-at-two"])
def test_two_replicas_serve_exactly_once(tmp_path, n_records,
                                         through_controller):
    """Two replicas over ONE broker, as two bare servers and as a
    ``FleetController`` held at two draining a saturated backlog: the
    claim protocol is their only coordination."""
    broker = InMemoryBroker()
    log = str(tmp_path / "served.log")
    inq = InputQueue(broker=broker)
    for i in range(n_records):
        inq.enqueue(f"u{i}", np.zeros((3,), np.float32))
    if through_controller:
        running = [FleetController(
            ClusterServingHelper(
                model_path=None, batch_size=8, batch_budget_ms=5.0,
                lease_ms=5_000, log_dir=str(tmp_path / "logs")),
            broker, model_factory=lambda: _SyntheticModel(2.0),
            scaler=SloScaler(min_replicas=2, max_replicas=2),
            interval=0.5, serve_log=log)]
    else:
        running = [
            _fleet_server(broker, owner, _CountingModel(0.002), tmp_path,
                          serve_log=log) for owner in ("r1", "r2")]
    for r in running:
        r.start()
    outq = OutputQueue(broker=broker)
    got = {}
    deadline = time.time() + 60
    while len(got) < n_records and time.time() < deadline:
        got.update(outq.dequeue())
        time.sleep(0.01)
    for r in running:
        r.stop()
    assert len(got) == n_records
    assert broker.xlen(STREAM) == 0  # all acked via release(done=True)
    # the serve audit log is the exactly-once ledger: every uri exactly
    # once across BOTH replicas, and both replicas did real work
    lines = [ln.split() for ln in open(log).read().splitlines()]
    uris = sorted(u for _, u in lines)
    assert uris == sorted(f"u{i}" for i in range(n_records))
    # the claim protocol shared the load
    assert len({o for o, _ in lines}) == 2


def test_lone_request_served_within_budget(tmp_path):
    """Continuous batching's latency bound: one request against a
    batch_size-8 bucket is flushed at the budget, not held for
    co-batchable traffic that never arrives."""
    broker = InMemoryBroker()
    model = _CountingModel()
    srv = _fleet_server(broker, "solo", model, tmp_path, batch_size=8,
                        budget_ms=150.0)
    srv.start()
    try:
        inq = InputQueue(broker=broker)
        t0 = time.perf_counter()
        inq.enqueue("lone", np.zeros((3,), np.float32))
        res = OutputQueue(broker=broker).poll("lone", timeout=10.0)
        dt = time.perf_counter() - t0
    finally:
        srv.stop()
    assert res is not None
    # budget 150ms + claim/predict/write overhead; far under any
    # "wait for a full bucket" regime (which would be the 10s timeout)
    assert dt < 1.5, dt
    assert model.batches == [1]


def test_trickle_coalesces_into_padded_bucket(tmp_path):
    """A trickle of same-shape requests inside one budget window lands
    in ONE padded predict, not 6 singleton dispatches."""
    broker = InMemoryBroker()
    model = _CountingModel()
    srv = _fleet_server(broker, "solo", model, tmp_path, batch_size=8,
                        budget_ms=400.0)
    srv.start()
    try:
        inq = InputQueue(broker=broker)
        for i in range(6):
            inq.enqueue(f"t{i}", np.zeros((3,), np.float32))
            time.sleep(0.02)
        outq = OutputQueue(broker=broker)
        got = {}
        deadline = time.time() + 15
        while len(got) < 6 and time.time() < deadline:
            got.update(outq.dequeue())
            time.sleep(0.01)
    finally:
        srv.stop()
    assert len(got) == 6
    assert sum(model.batches) == 6
    assert len(model.batches) <= 2, model.batches  # coalesced
    assert max(model.batches) >= 3


def test_keepalive_extends_lease_through_slow_predict(tmp_path):
    """A predict longer than the lease (the first-compile shape) must
    NOT forfeit its records: the keepalive extends in-flight leases, so
    an idle second replica never takes them over."""
    broker = InMemoryBroker()
    log = str(tmp_path / "served.log")
    slow = _CountingModel(1.2)  # one record -> 1.2s predict >> 400ms lease
    fast = _CountingModel()
    s1 = _fleet_server(broker, "slow", slow, tmp_path, budget_ms=5.0,
                       lease_ms=400, serve_log=log)
    s2 = _fleet_server(broker, "idle", fast, tmp_path, budget_ms=5.0,
                       lease_ms=400, serve_log=log)
    s1.start()
    try:
        InputQueue(broker=broker).enqueue(
            "x", np.zeros((3,), np.float32))
        deadline = time.time() + 10
        while broker.unclaimed(STREAM) and time.time() < deadline:
            time.sleep(0.01)  # s1 holds the claim before s2 exists
        s2.start()
        res = OutputQueue(broker=broker).poll("x", timeout=15.0)
        time.sleep(1.0)  # a takeover double-serve would land here
    finally:
        s1.stop()
        s2.stop()
    assert res is not None
    lines = open(log).read().splitlines()
    assert lines == ["slow x"], lines  # exactly once, by the slow owner
    assert fast.batches == []  # never taken over


def test_kill9_replica_mid_batch_survivors_serve_exactly_once(tmp_path):
    """THE fleet fault-tolerance acceptance: kill -9 a replica that has
    claimed records mid-batch; after lease expiry the survivor serves
    every enqueued record exactly once (serve-log ledger)."""
    spool = str(tmp_path / "spool")
    log = str(tmp_path / "served.log")
    broker = FileBroker(spool)
    inq = InputQueue(broker=broker)
    for i in range(20):
        inq.enqueue(f"u{i}", np.zeros((3,), np.float32))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ZOO_SERVING_LOG_DIR=str(tmp_path))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def spawn(owner, sleep_ms):
        return subprocess.Popen(
            [sys.executable, "-m", "analytics_zoo_tpu.serving.fleet",
             "--replica", "--broker", "dir:" + spool, "--owner", owner,
             "--batch-size", "4", "--budget-ms", "10",
             "--lease-ms", "1500", "--synthetic-sleep-ms", str(sleep_ms),
             "--serve-log", log],
            env=env, cwd=repo)

    # A's 2s/record predict means its first batch takes ~8s: it will be
    # SIGKILLed long before completing anything, holding live claims
    a = spawn("A", 2000)
    sdir = os.path.join(spool, "stream-" + STREAM)
    deadline = time.time() + 60
    while time.time() < deadline:
        if os.path.isdir(sdir) and any(
                n.startswith(".c-") for n in os.listdir(sdir)):
            break
        time.sleep(0.05)
    else:
        a.kill()
        pytest.fail("replica A never claimed")
    os.kill(a.pid, signal.SIGKILL)
    a.wait()
    assert not os.path.exists(log) or not open(log).read(), \
        "A must die mid-batch, before serving anything"

    b = spawn("B", 0)
    try:
        outq = OutputQueue(broker=broker)
        got = {}
        deadline = time.time() + 90
        while len(got) < 20 and time.time() < deadline:
            got.update(outq.dequeue())
            time.sleep(0.05)
    finally:
        b.terminate()
        b.wait(timeout=20)
    assert len(got) == 20, f"survivor served {len(got)}/20"
    lines = [ln.split() for ln in open(log).read().splitlines()]
    uris = sorted(u for _, u in lines)
    assert uris == sorted(f"u{i}" for i in range(20))  # exactly once
    assert {o for o, _ in lines} == {"B"}  # all by the survivor
    assert broker.xlen(STREAM) == 0  # nothing leaked


# ---------------------------------------------------------------------------
# SLO scaler policy (pure unit tests on fabricated windows)
# ---------------------------------------------------------------------------


def _sig(p99_ms=0.0, count=10, rate=100.0, queue=0, mem=0.0):
    return FleetSignals(predict_p99_s=p99_ms / 1e3, window_count=count,
                        service_rate=rate, queue_depth=queue,
                        memory_ratio=mem)


def test_scaler_scales_up_only_on_sustained_violation():
    s = SloScaler(slo_p99_ms=100.0, min_replicas=1, max_replicas=4,
                  up_windows=2, down_windows=3)
    bad = _sig(p99_ms=300.0)
    assert s.decide(1, bad) == (1, "violation_streak")  # not yet
    target, reason = s.decide(1, bad)
    assert target == 3 and reason == "slo_violation"  # ceil(1 * 300/100)
    # a single good window resets the streak
    s2 = SloScaler(slo_p99_ms=100.0, up_windows=2)
    s2.decide(1, bad)
    s2.decide(1, _sig(p99_ms=80.0))
    assert s2.decide(1, bad) == (1, "violation_streak")


def test_scaler_queue_delay_counts_toward_violation():
    s = SloScaler(slo_p99_ms=100.0, up_windows=1, max_replicas=4)
    # predict itself is fast, but 50 queued / 100 rec/s = 500ms wait
    target, reason = s.decide(1, _sig(p99_ms=10.0, queue=50, rate=100.0))
    assert target > 1 and reason == "slo_violation"


def test_scaler_stalled_backlog_and_memory_pressure():
    s = SloScaler(slo_p99_ms=100.0, up_windows=1, max_replicas=4)
    assert s.decide(2, _sig(count=0, rate=0.0, queue=10)) == \
        (3, "stalled_backlog")  # unbounded wait estimate: step up
    s2 = SloScaler(slo_p99_ms=100.0, up_windows=1, max_replicas=4,
                   memory_high=0.5)
    assert s2.decide(1, _sig(p99_ms=10.0, mem=0.6)) == \
        (4, "broker_pressure")  # records about to be trimmed: jump


def test_scaler_scales_down_on_sustained_slack_respecting_min():
    s = SloScaler(slo_p99_ms=100.0, min_replicas=1, max_replicas=4,
                  up_windows=1, down_windows=3)
    idle = _sig(p99_ms=5.0, count=0, rate=0.0, queue=0)
    assert s.decide(3, idle) == (3, "slack_streak")
    assert s.decide(3, idle) == (3, "slack_streak")
    assert s.decide(3, idle) == (2, "sustained_slack")
    # never below min
    s.decide(1, idle)
    s.decide(1, idle)
    assert s.decide(1, idle) == (1, "slack_streak")
    # the comfort band (neither violated nor slack) resets the streak
    s3 = SloScaler(slo_p99_ms=100.0, down_windows=2, slack_ratio=0.5)
    s3.decide(2, idle)
    assert s3.decide(2, _sig(p99_ms=80.0)) == (2, "")
    assert s3.decide(2, idle) == (2, "slack_streak")


def test_scaler_validates_bounds():
    with pytest.raises(ValueError):
        SloScaler(slo_p99_ms=0)
    with pytest.raises(ValueError):
        SloScaler(min_replicas=3, max_replicas=2)


# ---------------------------------------------------------------------------
# FleetController integration: autoscale up + down, telemetry trail
# ---------------------------------------------------------------------------


def test_fleet_autoscales_up_and_down_with_full_telemetry(tmp_path):
    from analytics_zoo_tpu.metrics import get_flight_recorder, snapshot

    broker = InMemoryBroker()
    helper = ClusterServingHelper(
        model_path=None, batch_size=8, batch_budget_ms=10, lease_ms=3000,
        log_dir=str(tmp_path))
    ctrl = FleetController(
        helper, broker, model_factory=lambda: _SyntheticModel(5.0),
        scaler=SloScaler(slo_p99_ms=300.0, min_replicas=1, max_replicas=3,
                         up_windows=2, down_windows=4),
        interval=0.3)
    ctrl.start()
    try:
        inq = InputQueue(broker=broker)
        outq = OutputQueue(broker=broker)
        for i in range(600):  # ~3 replica-seconds of service in one burst
            inq.enqueue(f"u{i}", np.zeros((3,), np.float32))
        got, max_reps = {}, 1
        deadline = time.time() + 60
        while len(got) < 600 and time.time() < deadline:
            got.update(outq.dequeue())
            max_reps = max(max_reps, ctrl.replica_count())
            time.sleep(0.02)
        assert len(got) == 600
        assert max_reps >= 2, "never scaled up under overload"
        deadline = time.time() + 20
        while ctrl.replica_count() > 1 and time.time() < deadline:
            time.sleep(0.1)
        assert ctrl.replica_count() == 1, "never scaled back down"
        decisions = ctrl.decision_log()
    finally:
        ctrl.stop()
    acts = [d["action"] for d in decisions]
    assert "up" in acts and "down" in acts
    up = next(d for d in decisions if d["action"] == "up")
    assert up["reason"] in ("slo_violation", "stalled_backlog",
                            "broker_pressure")
    assert up["est_p99_ms"] is None or up["est_p99_ms"] > 300.0
    # decision trail parity: /varz panel, flight events, metric family
    doc = varz_doc()
    assert any(c["current"]["slo_p99_ms"] == 300.0
               for c in doc["controllers"])
    assert [d["action"] for d in doc["decisions"][-len(acts):]] == acts
    kinds = {e.get("kind") for e in get_flight_recorder().events()}
    assert "fleet_scale" in kinds
    names = {s["name"] for s in snapshot()["samples"]}
    for n in ("zoo_fleet_replicas", "zoo_fleet_replicas_target",
              "zoo_fleet_decisions_total", "zoo_fleet_est_p99_seconds",
              "zoo_fleet_unclaimed_backlog",
              "zoo_fleet_batch_flushes_total"):
        assert n in names, n


def test_fleet_supervision_replaces_dead_replica(tmp_path):
    broker = InMemoryBroker()
    helper = ClusterServingHelper(
        model_path=None, batch_size=4, batch_budget_ms=5, lease_ms=1000,
        log_dir=str(tmp_path))
    ctrl = FleetController(
        helper, broker, model_factory=lambda: _SyntheticModel(0.0),
        scaler=SloScaler(slo_p99_ms=1000.0, min_replicas=2,
                         max_replicas=2),
        interval=0.2)
    ctrl.start()
    try:
        assert ctrl.replica_count() == 2
        # simulate a replica death: stop its server thread directly
        with ctrl._lock:
            victim = ctrl._replicas[0]
        victim.server.stop()
        deadline = time.time() + 15
        while time.time() < deadline:
            with ctrl._lock:
                alive = [r for r in ctrl._replicas if r.alive()]
            if len(alive) == 2 and victim not in alive:
                break
            time.sleep(0.05)
        else:
            pytest.fail("controller never replaced the dead replica")
        assert any(d["action"] == "replace" for d in ctrl.decision_log())
    finally:
        ctrl.stop()


def test_metrics_dump_renders_fleet_panel():
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    try:
        import metrics_dump
    finally:
        sys.path.pop(0)
    doc = {"fleet": {
        "controllers": [{"current": {
            "replicas": 2, "target": 3, "max_replicas": 4,
            "slo_p99_ms": 500.0, "mode": "thread",
            "window": {"predict_p99_ms": 12.0, "service_rate": 180.0,
                       "queue_depth": 40, "memory_ratio": 0.01}},
            "decisions": []}],
        "decisions": [{"ts": 1.0, "action": "up", "old": 1, "new": 3,
                       "reason": "slo_violation", "est_p99_ms": 750.0,
                       "queue_depth": 82}],
    }}
    out = []
    metrics_dump.render_fleet(doc, out=out)
    text = "\n".join(out)
    assert "replicas=2/3" in text and "slo_p99=500.0ms" in text
    assert "slo_violation" in text and "1 -> 3" in text
    # --prefix filtering skips the panel
    out2 = []
    metrics_dump.render_fleet(doc, prefix="zoo_serving", out=out2)
    assert out2 == []


# ---------------------------------------------------------------------------
# Review-hardening regressions
# ---------------------------------------------------------------------------


def test_reader_failure_requeues_claims_instead_of_wedging(tmp_path):
    """A broker hiccup AFTER claiming (here: pop_takeovers raising
    mid-admission) must not wedge the claimed records: they are dropped
    from the keepalive's in-flight set and requeued for immediate
    re-claim — not lease-extended forever while invisible to every
    replica.  The 60s lease makes the requeue path the ONLY way these
    records can be re-served inside the test deadline."""

    class HiccupBroker(InMemoryBroker):
        def __init__(self):
            super().__init__()
            self.hiccups = 2

        def pop_takeovers(self, owner):
            if self.hiccups > 0:
                self.hiccups -= 1
                raise ConnectionError("transient broker hiccup")
            return super().pop_takeovers(owner)

    broker = HiccupBroker()
    inq = InputQueue(broker=broker)
    for i in range(8):
        inq.enqueue(f"u{i}", np.zeros((3,), np.float32))
    model = _CountingModel()
    srv = _fleet_server(broker, "r1", model, tmp_path, lease_ms=60_000)
    srv.start()
    try:
        outq = OutputQueue(broker=broker)
        got = {}
        deadline = time.time() + 15
        while len(got) < 8 and time.time() < deadline:
            got.update(outq.dequeue())
            time.sleep(0.01)
    finally:
        srv.stop()
    assert sorted(got) == sorted(f"u{i}" for i in range(8))
    assert broker.hiccups == 0  # the failure path actually ran


def test_scaler_window_falls_back_to_backlog_drain_rate(tmp_path):
    """mode='process' replicas record into their OWN registries, so the
    controller sees no predict samples.  A draining backlog must then
    read as a finite drain-rate sojourn estimate — not service_rate=0
    => est=inf 'stalled_backlog' scaling a healthy fleet to max."""
    broker = InMemoryBroker()
    for i in range(100):
        broker.xadd(STREAM, {"i": str(i)})
    ctrl = FleetController(
        ClusterServingHelper(model_path=None, batch_size=4,
                             log_dir=str(tmp_path)),
        broker, model_factory=_CountingModel, interval=60.0)
    try:
        ctrl._gather_window()  # baseline window
        time.sleep(0.05)
        # other processes' replicas drain 60 records
        drained = broker.claim(STREAM, "elsewhere", 60, lease_ms=5000)
        broker.release(STREAM, "elsewhere", [r[0] for r in drained],
                       done=True)
        sig = ctrl._gather_window()
    finally:
        ctrl.stop()
    assert sig.queue_depth == 40
    assert sig.service_rate > 0, "drain-rate fallback did not engage"
    assert ctrl.scaler.estimate_p99_s(sig) != float("inf")


# ---------------------------------------------------------------------------
# Config knobs
# ---------------------------------------------------------------------------


def test_zooconfig_fleet_knobs_validated_eagerly(monkeypatch):
    from analytics_zoo_tpu.common.engine import ZooConfig

    cfg = ZooConfig()
    assert cfg.serving_batch_budget_ms == 25.0
    assert cfg.slo_p99_ms == 500.0
    assert (cfg.fleet_min_replicas, cfg.fleet_max_replicas) == (1, 4)
    assert cfg.fleet_interval == 1.0 and cfg.fleet_lease_ms == 10_000
    monkeypatch.setenv("ZOO_SERVING_BATCH_BUDGET_MS", "7.5")
    monkeypatch.setenv("ZOO_FLEET_MAX_REPLICAS", "8")
    cfg2 = ZooConfig()
    assert cfg2.serving_batch_budget_ms == 7.5
    assert cfg2.fleet_max_replicas == 8
    for var, bad in [("ZOO_SERVING_BATCH_BUDGET_MS", "-1"),
                     ("ZOO_SLO_P99_MS", "nope"),
                     ("ZOO_FLEET_MIN_REPLICAS", "0"),
                     ("ZOO_FLEET_LEASE_MS", "50"),
                     ("ZOO_FLEET_INTERVAL", "0")]:
        monkeypatch.setenv(var, bad)
        with pytest.raises(ValueError, match=var):
            ZooConfig()
        monkeypatch.delenv(var)
    # explicit argument beats env, and min > max is rejected
    with pytest.raises(ValueError, match="MAX_REPLICAS"):
        ZooConfig(fleet_min_replicas=5, fleet_max_replicas=2)


def test_helper_fleet_knobs_env_and_override(monkeypatch, tmp_path):
    monkeypatch.setenv("ZOO_SERVING_BATCH_BUDGET_MS", "12.5")
    monkeypatch.setenv("ZOO_FLEET_LEASE_MS", "2500")
    h = ClusterServingHelper(model_path=None, log_dir=str(tmp_path))
    assert h.batch_budget_ms == 12.5 and h.lease_ms == 2500
    h2 = ClusterServingHelper(model_path=None, batch_budget_ms=3.0,
                              lease_ms=700, log_dir=str(tmp_path))
    assert h2.batch_budget_ms == 3.0 and h2.lease_ms == 700
    monkeypatch.setenv("ZOO_FLEET_LEASE_MS", "bogus")
    with pytest.raises(ValueError, match="ZOO_FLEET_LEASE_MS"):
        ClusterServingHelper(model_path=None, log_dir=str(tmp_path))
    # documented precedence: an explicit override wins WITHOUT parsing
    # the (bad) env var at all
    h3 = ClusterServingHelper(model_path=None, lease_ms=700,
                              log_dir=str(tmp_path))
    assert h3.lease_ms == 700
