"""Predictive serving plane (ISSUE 20): the serving roofline + the
bucket-stamped report join, the choose_serving verdict contract with
its logged prediction->outcome pairs, the oracle-seeded scaler prior,
admission accept/shed hysteresis with the typed client reject, the
two-model router, ZOO_SERVING_MODELS parsing, the ZooConfig knobs, and
the verdict priming a fleet end to end."""

import json
import time

import numpy as np
import pytest

from analytics_zoo_tpu.analysis.costmodel import (
    load_serving_rows,
    predict_serving_seconds,
    resolve_peaks,
)
from analytics_zoo_tpu.analysis.oracle import ConfigOracle
from analytics_zoo_tpu.common.engine import ZooConfig
from analytics_zoo_tpu.serving import (
    InMemoryBroker,
    InputQueue,
    OutputQueue,
    ServingRejected,
    model_stream,
)
from analytics_zoo_tpu.serving.admission import (
    ADMISSION_KEY_PREFIX,
    AdmissionController,
)
from analytics_zoo_tpu.serving.modelspec import (
    ModelSpec,
    format_model_specs,
    parse_model_specs,
)
from analytics_zoo_tpu.serving.scaler import FleetSignals, SloScaler


@pytest.fixture(autouse=True)
def _clean_serving_env(monkeypatch):
    """The knobs under test resolve from the env — stay hermetic."""
    for var in ("ZOO_ADMISSION", "ZOO_SERVING_MODELS",
                "ZOO_HLO_REPORT_DIR", "ZOO_ORACLE_PEAKS"):
        monkeypatch.delenv(var, raising=False)


def _cpu_peaks():
    return resolve_peaks("cpu")


def _bucket_feats(bucket, service_ms, peaks=None):
    """Features whose analytic CPU predict time is bucket x service_ms
    (compute-bound: flops sized against the peak table, zero bytes)."""
    peaks = peaks or _cpu_peaks()
    return {"matmul_flops": bucket * service_ms / 1e3 * peaks.flops,
            "bytes_accessed": 0, "collective_bytes": 0, "op_count": 10}


# ---------------------------------------------------------------------------
# the serving roofline
# ---------------------------------------------------------------------------

def test_predict_serving_seconds_overhead_floor_and_monotone():
    """An empty program costs exactly the per-call dispatch overhead
    (serving is k=1 — nothing amortizes it), and more work never
    predicts a FASTER dispatch."""
    peaks = _cpu_peaks()
    floor = predict_serving_seconds({}, peaks=peaks)
    assert floor == pytest.approx(peaks.dispatch_overhead_s)
    small = predict_serving_seconds(_bucket_feats(8, 1.0), peaks=peaks)
    big = predict_serving_seconds(_bucket_feats(16, 1.0), peaks=peaks)
    assert floor < small < big
    # memory term: the roofline takes max(compute, memory) + overhead
    membound = predict_serving_seconds(
        {"matmul_flops": 0, "bytes_accessed": peaks.hbm_bytes_per_s,
         "collective_bytes": 0, "op_count": 1}, peaks=peaks)
    assert membound == pytest.approx(1.0 + peaks.dispatch_overhead_s)


def test_load_serving_rows_bucket_join(tmp_path):
    """Only inference_b* reports load, keyed + sorted by bucket; the
    bucket comes from the stamped meta when present, the label suffix
    otherwise; later files win per label; non-serving labels are not
    serving rows."""
    def write(name, doc):
        with open(tmp_path / name, "w") as f:
            json.dump(doc, f)

    write("hlo-a-1-1.json", {
        "schema": "zoo-hlo-report/2", "label": "inference_b16",
        "bucket": 16, "features": {"matmul_flops": 160}})
    write("hlo-b-1-2.json", {  # no stamped bucket: parsed from label
        "schema": "zoo-hlo-report/2", "label": "inference_b8",
        "features": {"matmul_flops": 1}})
    write("hlo-b-1-3.json", {  # same label, later file: wins
        "schema": "zoo-hlo-report/2", "label": "inference_b8",
        "features": {"matmul_flops": 80}})
    write("hlo-c-1-4.json", {  # training row: not a serving row
        "schema": "zoo-hlo-report/2", "label": "step",
        "features": {"matmul_flops": 7}})

    rows = load_serving_rows(str(tmp_path))
    assert [r["bucket"] for r in rows] == [8, 16]
    assert rows[0]["features"]["matmul_flops"] == 80.0
    assert rows[1]["features"]["matmul_flops"] == 160.0


# ---------------------------------------------------------------------------
# choose_serving
# ---------------------------------------------------------------------------

def test_choose_serving_verdict_contract_and_logging():
    """Per-bucket feasibility against the SLO service slice, replica
    math from the best bucket's derated capacity, the batch budget as
    the leftover slice, and a logged prediction per bucket that
    record_outcome closes with a rel_error."""
    oracle = ConfigOracle(peaks=_cpu_peaks())
    feats = {8: _bucket_feats(8, 4.0), 16: _bucket_feats(16, 4.0)}
    verdict = oracle.choose_serving(
        feats, slo_p99_ms=100.0, offered_rate=300.0, model="m")
    # b8 predicts 32.5ms <= 50ms slice; b16 predicts 64.5ms > 50ms
    assert verdict["pad_buckets"] == [8]
    pred8 = verdict["predicted"]["8"]["predict_seconds"]
    assert pred8 == pytest.approx(0.0325)
    assert not verdict["predicted"]["16"]["feasible"]
    # capacity = 8/0.0325 * 0.6 ~ 147.7 rps -> ceil(300/147.7) = 3
    assert verdict["replicas"] == 3
    assert verdict["batch_budget_ms"] == pytest.approx(
        (0.05 - 0.0325) * 1e3)
    assert verdict["config"] == "serving:m"

    oracle.record_outcome("serving:m:b8", 1.0 / pred8,
                          consumer="serving")
    closed = [r for r in oracle.prediction_log()
              if r["config"] == "serving:m:b8"
              and r.get("rel_error") is not None]
    assert closed and closed[-1]["rel_error"] == pytest.approx(0.0, abs=1e-6)


def test_choose_serving_smallest_bucket_never_drops():
    """An SLO no bucket fits still yields a non-empty pad set (the
    smallest bucket) — serving degrades, it does not refuse."""
    oracle = ConfigOracle(peaks=_cpu_peaks())
    verdict = oracle.choose_serving(
        {8: _bucket_feats(8, 4.0)}, slo_p99_ms=1.0, offered_rate=1.0,
        model="tight")
    assert verdict["pad_buckets"] == [8]
    assert verdict["replicas"] >= 1


# ---------------------------------------------------------------------------
# the oracle-seeded scaler prior
# ---------------------------------------------------------------------------

def test_scaler_prior_seeds_then_reactive_takes_over():
    """A fresh scaler with a prior jumps straight to the oracle target
    on the first (empty) window and never re-applies it — the reactive
    policy owns every later decision."""
    s = SloScaler(slo_p99_ms=400.0, min_replicas=1, max_replicas=4,
                  up_windows=2, prior_target=3)
    assert s.initial_target() == 3
    target, reason = s.decide(1, FleetSignals())
    assert (target, reason) == (3, "oracle_prior")
    # the prior is consumed: an idle window now HOLDS (no re-prime)
    target, reason = s.decide(3, FleetSignals())
    assert target == 3 and reason != "oracle_prior"
    # without a prior the same cold start sits at min_replicas
    cold = SloScaler(slo_p99_ms=400.0, min_replicas=1, max_replicas=4)
    assert cold.initial_target() == 1
    assert cold.decide(1, FleetSignals())[0] == 1


def test_scaler_prior_clamped_to_replica_bounds():
    s = SloScaler(min_replicas=2, max_replicas=4, prior_target=99)
    assert s.initial_target() == 4
    s = SloScaler(min_replicas=2, max_replicas=4, prior_target=1)
    assert s.initial_target() == 2


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def _drain(broker, stream, n):
    ids = [r[0] for r in broker.claim(stream, "t", n, 60_000)]
    broker.release(stream, "t", ids, done=True)


def test_admission_shed_hysteresis_and_typed_reject():
    """Backlog beyond the limit sheds with a drain-sized retry-after;
    the door holds shut (draining) until the backlog falls below the
    resume floor; admit() raises the typed reject; stop() clears the
    published verdict so the stream reads unguarded again."""
    broker = InMemoryBroker()
    stream = model_stream("m")
    ac = AdmissionController(broker, stream=stream, model="m",
                             backlog_limit=4, interval=999.0)
    try:
        assert ac.evaluate()["state"] == "accept"
        ac.admit("ok")  # accept path does not raise

        for i in range(6):
            broker.xadd(stream, {"uri": f"u{i}"})
        verdict = ac.evaluate()
        assert verdict["state"] == "shed" and verdict["reason"] == "backlog"
        assert float(verdict["retry_after_ms"]) >= ac.min_retry_ms
        # published for cross-process clients
        hashed = broker.hgetall(ADMISSION_KEY_PREFIX + stream)
        assert hashed.get("state") == "shed"
        with pytest.raises(ServingRejected) as ei:
            ac.admit("rejected-uri")
        assert ei.value.uri == "rejected-uri"
        assert ei.value.reason == "backlog"
        assert ei.value.retry_after_s > 0

        # hysteresis: 3 outstanding is UNDER the limit but above the
        # resume floor (4 * 0.5 = 2) -> still shut, reason "draining"
        _drain(broker, stream, 3)
        verdict = ac.evaluate()
        assert verdict["state"] == "shed" and verdict["reason"] == "draining"

        _drain(broker, stream, 3)
        assert ac.evaluate()["state"] == "accept"
        ac.admit("ok-again")

        transitions = [(d["state"], d["reason"])
                       for d in ac.decision_log()]
        assert ("shed", "backlog") in transitions
        assert ("accept", "") in transitions
    finally:
        ac.stop()
    assert broker.hgetall(ADMISSION_KEY_PREFIX + stream) == {}


def test_admission_counts_total_outstanding_not_just_unclaimed():
    """The backlog signal is stream xlen — claimed-but-unserved work a
    replica holds still counts (it is sojourn time the client pays),
    so a full claim queue cannot hide an overload from the door."""
    broker = InMemoryBroker()
    stream = model_stream("m")
    ac = AdmissionController(broker, stream=stream, model="m",
                             backlog_limit=4, interval=999.0)
    try:
        for i in range(6):
            broker.xadd(stream, {"uri": f"u{i}"})
        broker.claim(stream, "replica", 6, 60_000)  # all claimed
        assert broker.unclaimed(stream) == 0
        verdict = ac.evaluate()
        assert verdict["state"] == "shed" and verdict["reason"] == "backlog"
    finally:
        ac.stop()


def test_admission_slo_burn_trigger():
    """A firing burn alert among the watched names sheds even with an
    empty stream — the door closes on the early-warning signal."""
    class _Engine:
        def firing(self):
            return [{"slo": "predict_p99", "firing": True}]

    broker = InMemoryBroker()
    ac = AdmissionController(broker, stream=model_stream("m"), model="m",
                             slo_engine=_Engine(), interval=999.0)
    try:
        verdict = ac.evaluate()
        assert verdict["state"] == "shed"
        assert verdict["reason"] == "slo_burn:predict_p99"
    finally:
        ac.stop()


def test_client_enqueue_reads_published_verdict():
    """The cross-process path: InputQueue.enqueue raises the typed
    reject from the published hash BEFORE the record enters the
    stream; an absent hash means every enqueue is accepted."""
    broker = InMemoryBroker()
    stream = model_stream("gated")
    q = InputQueue(broker=broker, model="gated")
    rec = np.zeros((4,), np.float32)
    q.enqueue("open", rec)
    assert broker.xlen(stream) == 1

    broker.hset(ADMISSION_KEY_PREFIX + stream, {
        "state": "shed", "retry_after_ms": "250.0", "reason": "backlog"})
    with pytest.raises(ServingRejected) as ei:
        q.enqueue("shut", rec)
    assert ei.value.retry_after_s == pytest.approx(0.25)
    assert broker.xlen(stream) == 1  # the record never entered

    broker.delete(ADMISSION_KEY_PREFIX + stream)
    q.enqueue("open-again", rec)
    assert broker.xlen(stream) == 2


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_router_two_models_routed_and_decided():
    """Two specs (given as the raw ZOO_SERVING_MODELS string) get
    their own streams, verdicts, and start/stop decisions; records
    enqueued per model come back per model."""
    from analytics_zoo_tpu.serving.fleet import _SyntheticModel
    from analytics_zoo_tpu.serving.router import ModelRouter

    broker = InMemoryBroker()
    oracle = ConfigOracle(peaks=_cpu_peaks())
    router = ModelRouter(
        broker, "fast=300@60,slow=800",
        model_factory=lambda spec: _SyntheticModel(1.0),
        oracle=oracle,
        features={"fast": {8: _bucket_feats(8, 1.0)},
                  "slow": {8: _bucket_feats(8, 1.0)}},
        max_replicas=2, interval=0.2)
    router.start()
    try:
        assert sorted(router.models()) == ["fast", "slow"]
        for name in ("fast", "slow"):
            v = router.verdict(name)
            assert v["model"] == name and v["replicas"] >= 1
        inq = {n: InputQueue(broker=broker, model=n)
               for n in ("fast", "slow")}
        rec = np.zeros((4,), np.float32)
        want = set()
        for i in range(4):
            for n in ("fast", "slow"):
                uri = f"{n}:{i}"
                inq[n].enqueue(uri, rec)
                want.add(uri)
        outq = OutputQueue(broker=broker)
        got = set()
        deadline = time.time() + 60
        while want - got and time.time() < deadline:
            got.update(outq.dequeue())
            time.sleep(0.02)
        assert want <= got
    finally:
        router.stop()
    actions = [(d["model"], d["action"]) for d in router.decision_log()]
    for name in ("fast", "slow"):
        assert (name, "start") in actions
        assert (name, "stop") in actions


# ---------------------------------------------------------------------------
# spec parsing + the ZooConfig knobs
# ---------------------------------------------------------------------------

def test_model_spec_parse_and_format_round_trip():
    specs = parse_model_specs("resnet=250@120, bert=500")
    assert specs == [ModelSpec("resnet", 250.0, 120.0),
                     ModelSpec("bert", 500.0, 0.0)]
    assert parse_model_specs("") == []
    assert parse_model_specs(
        format_model_specs(specs)) == specs


def test_model_spec_errors_name_the_source():
    for bad in ("resnet", "resnet=", "resnet=abc", "a=0",
                "a=100@-5", "a=100,a=200", "a b=100"):
        with pytest.raises(ValueError, match="ZOO_SERVING_MODELS"):
            parse_model_specs(bad)


def test_zooconfig_serving_knobs_validate_eagerly(monkeypatch):
    """Bad env values fail at ZooConfig construction, naming the
    variable — not at the first routed request."""
    monkeypatch.setenv("ZOO_ADMISSION", "bogus")
    with pytest.raises(ValueError, match="ZOO_ADMISSION"):
        ZooConfig()
    monkeypatch.delenv("ZOO_ADMISSION")

    monkeypatch.setenv("ZOO_SERVING_MODELS", "resnet=nope")
    with pytest.raises(ValueError, match="ZOO_SERVING_MODELS"):
        ZooConfig()
    monkeypatch.delenv("ZOO_SERVING_MODELS")

    monkeypatch.setenv("ZOO_ADMISSION", "1")
    monkeypatch.setenv("ZOO_SERVING_MODELS", "resnet=250@120")
    cfg = ZooConfig()
    assert cfg.admission is True
    assert cfg.serving_models == "resnet=250@120"
    assert ZooConfig(admission=False).admission is False


# ---------------------------------------------------------------------------
# verdict -> scaler prior -> fleet, end to end
# ---------------------------------------------------------------------------

def test_oracle_verdict_primes_the_fleet_before_any_traffic(tmp_path):
    """``choose_serving`` predicts the replica target for an offered 80
    records/s from the per-bucket serving cost model (20 ms a record: one
    replica saturates near 50), logging a prediction for every bucket;
    the target seeds the scaler, and the fleet STARTS there: its first
    decision is the prior's, with no record enqueued yet.  The primed
    fleet then answers a burst."""
    from analytics_zoo_tpu.serving import ClusterServingHelper
    from analytics_zoo_tpu.serving.fleet import (
        FleetController,
        _SyntheticModel,
    )

    service_ms, buckets = 20.0, (8, 16)
    oracle = ConfigOracle(peaks=_cpu_peaks())
    verdict = oracle.choose_serving(
        {b: _bucket_feats(b, service_ms) for b in buckets},
        slo_p99_ms=400.0, offered_rate=80.0, model="step")
    assert {f"serving:step:b{b}" for b in buckets} \
        <= {row["config"] for row in oracle.prediction_log()}
    assert 1 < verdict["replicas"] <= 3, verdict

    broker = InMemoryBroker()
    ctrl = FleetController(
        ClusterServingHelper(
            model_path=None, batch_size=8, batch_budget_ms=5.0,
            lease_ms=5_000, log_dir=str(tmp_path)),
        broker, model_factory=lambda: _SyntheticModel(service_ms),
        scaler=SloScaler(slo_p99_ms=400.0, min_replicas=1, max_replicas=3,
                         up_windows=2, down_windows=10_000,
                         prior_target=verdict["replicas"]),
        interval=0.25)
    ctrl.start()
    try:
        first = ctrl.decision_log()[0]
        assert (first["reason"], first["new"]) \
            == ("oracle_prior", verdict["replicas"])
        assert ctrl.replica_count() == verdict["replicas"]
        inq, outq = InputQueue(broker=broker), OutputQueue(broker=broker)
        for i in range(40):
            inq.enqueue(f"q{i}", np.zeros((8,), np.float32))
        served, deadline = {}, time.time() + 60
        while len(served) < 40 and time.time() < deadline:
            served.update(outq.dequeue())
            time.sleep(0.01)
    finally:
        ctrl.stop()
    assert sorted(served) == sorted(f"q{i}" for i in range(40))
