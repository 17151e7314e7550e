"""zooelastic: the elastic training runtime (ISSUE 16) — lease-based
membership (elastic/membership.py), deterministic chaos
(elastic/chaos.py), the training supervisor (elastic/supervisor.py),
and THE acceptance run: a 4-worker cohort losing one worker to
``kill -9`` and another to SIGTERM mid-``fit()`` finishes unattended
with a trajectory bit-exact against the uninterrupted run."""

import json
import os
import pickle
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.elastic import (
    ChaosEvent, ChaosSchedule, ElasticSession, GenerationChange,
    MembershipLedger, TrainSupervisor, equal_shares, rebalance_shares,
)
from analytics_zoo_tpu.elastic import supervisor as supervisor_mod
from analytics_zoo_tpu.elastic.membership import fget
from analytics_zoo_tpu.metrics import StragglerBoard
from analytics_zoo_tpu.serving import FileBroker, InMemoryBroker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(params=["memory", "file", "redis"])
def broker(request, tmp_path):
    if request.param == "memory":
        return InMemoryBroker()
    if request.param == "file":
        return FileBroker(str(tmp_path / "spool"))
    spec = os.environ.get("ZOO_TEST_REDIS")
    if not spec:
        pytest.skip("set ZOO_TEST_REDIS=host:port to run redis "
                    "membership tests")
    from analytics_zoo_tpu.serving.broker import connect_broker

    return connect_broker(spec)


# ---------------------------------------------------------------------------
# Membership ledger (lease-based liveness + the generation counter)
# ---------------------------------------------------------------------------


def test_join_scan_generation_lifecycle(broker):
    led = MembershipLedger(broker, prefix="t-elastic", lease_ms=400)
    assert led.members() == []
    h0 = led.join("w0")
    doc, changed = led.scan()
    assert changed and doc["generation"] == 1 and doc["members"] == ["w0"]
    # stable membership: scan does NOT bump
    doc2, changed = led.scan()
    assert not changed and doc2["generation"] == 1

    h1 = led.join("w1")
    doc, changed = led.scan()
    assert changed and doc["generation"] == 2
    assert doc["members"] == ["w0", "w1"] and doc["world"] == 2

    # graceful leave drops the member on the NEXT scan (no lease wait)
    h1.leave()
    doc, changed = led.scan()
    assert changed and doc["generation"] == 3 and doc["members"] == ["w0"]

    # kill -9 shape: keepalive stops, nothing released -> the member
    # survives exactly until the lease expires
    h0._stop.set()
    assert led.members() == ["w0"]
    time.sleep(0.6)
    doc, changed = led.scan()
    assert changed and doc["generation"] == 4 and doc["world"] == 0


def test_keepalive_outlives_many_lease_periods(broker):
    led = MembershipLedger(broker, prefix="t-keep", lease_ms=150)
    h = led.join("w0")
    time.sleep(1.0)  # ~7 lease periods
    assert led.members() == ["w0"]
    h.leave()


def test_respawn_waits_out_dead_incarnations_lease(broker):
    led = MembershipLedger(broker, prefix="t-slot", lease_ms=400)
    h = led.join("w0")
    h._stop.set()  # dead incarnation: lease still ticking
    t0 = time.monotonic()
    h2 = led.join("w0")  # blocks until the broker expires the claim
    waited = time.monotonic() - t0
    assert waited < 2.0  # well under the join timeout
    assert led.members() == ["w0"]
    h2.leave()


def test_join_timeout_when_slot_never_frees(broker):
    led = MembershipLedger(broker, prefix="t-timeout", lease_ms=300)
    h = led.join("w0")  # keepalive KEEPS extending
    led2 = MembershipLedger(broker, prefix="t-timeout", lease_ms=300)
    with pytest.raises(TimeoutError):
        led2.join("w0", timeout_ms=700)
    h.leave()


def test_concurrent_joins_all_land(broker):
    """Regression pin: per-worker roster hashes.  A SHARED roster hash
    is a read-modify-write race on FileBroker (hset reads the file and
    rewrites it), so simultaneous joins silently dropped each other and
    the supervisor formed a cohort of 1 out of 4."""
    import concurrent.futures as cf

    led = MembershipLedger(broker, prefix="t-race", lease_ms=2000)
    with cf.ThreadPoolExecutor(4) as ex:
        handles = list(ex.map(
            lambda i: led.join(f"w{i}"), range(4)))
    assert led.members() == ["w0", "w1", "w2", "w3"]
    doc, _ = led.scan()
    assert doc["world"] == 4
    for h in handles:
        h.leave()


def test_generation_change_carries_doc():
    doc = {"generation": 5, "world": 3, "members": ["w0", "w1", "w2"]}
    e = GenerationChange(doc)
    assert e.doc == doc and "5" in str(e) and "world 3" in str(e)


def test_fget_tolerates_bytes():
    assert fget({b"k": b"v"}, "k") == "v"
    assert fget({"k": "v"}, "k") == "v"
    assert fget({}, "k", "d") == "d"
    assert fget(None, "k", "d") == "d"


# ---------------------------------------------------------------------------
# ElasticSession: the step barrier's read side
# ---------------------------------------------------------------------------


def test_session_sees_generation_bump_and_heartbeats():
    b = InMemoryBroker()
    led = MembershipLedger(b, prefix="t-sess", lease_ms=2000)
    h = led.join("w0")
    led.scan()  # -> generation 1
    s = ElasticSession(b, prefix="t-sess", generation=1, worker_id="w0",
                       start_step=10, min_poll_s=0.0)
    assert s.poll() is None  # generation unchanged
    assert s.step() == 11  # one dispatch counted on top of start_step
    hb = b.hgetall(led.hb_key("w0"))
    assert fget(hb, "step") == "11" and fget(hb, "role") == "chief"

    led.join("w1")
    doc, changed = led.scan()  # -> generation 2
    assert changed
    got = s.poll()
    assert got is not None and got["generation"] == 2
    h.leave()


def test_session_consumes_stall_exactly_once():
    b = InMemoryBroker()
    led = MembershipLedger(b, prefix="t-stall", lease_ms=2000)
    s = ElasticSession(b, prefix="t-stall", worker_id="w0",
                       min_poll_s=0.0)
    b.hset(led.ctl_key("w0"), {"stall_s": "0.2"})
    t0 = time.monotonic()
    s.poll()
    assert time.monotonic() - t0 >= 0.2  # slept the injected stall
    assert b.hgetall(led.ctl_key("w0")) == {}  # consumed
    hb = b.hgetall(led.hb_key("w0"))
    assert float(fget(hb, "step_s")) >= 0.2  # visible to the board
    t0 = time.monotonic()
    s.poll()
    assert time.monotonic() - t0 < 0.15  # one-shot, not sticky


def test_session_rate_limits_broker_reads():
    b = InMemoryBroker()
    s = ElasticSession(b, prefix="t-rate", worker_id="w0",
                       min_poll_s=60.0)
    s.poll()  # first tick publishes
    led = MembershipLedger(b, prefix="t-rate")
    b.delete(led.hb_key("w0"))
    for _ in range(50):
        assert s.poll() is None
    assert b.hgetall(led.hb_key("w0")) == {}  # no broker traffic since
    assert s.step() == 51


# ---------------------------------------------------------------------------
# Chaos schedules
# ---------------------------------------------------------------------------


def test_chaos_parse_and_due():
    sch = ChaosSchedule.parse("kill@12:w1, term@20:w2, stall@16:w3:1.5")
    assert [(e.action, e.at_step, e.target) for e in sch.events] == [
        ("kill", 12, "w1"), ("stall", 16, "w3"), ("term", 20, "w2")]
    assert sch.events[1].arg == 1.5
    assert [e.target for e in sch.due(16)] == ["w1", "w3"]
    for e in sch.due(16):
        e.fired = True
    assert sch.due(16) == [] and not sch.done()
    sch.due(99)[0].fired = True
    assert sch.done()


def test_chaos_from_seed_deterministic_and_bounded():
    a = ChaosSchedule.from_seed(7, ["w0", "w1", "w2", "w3"], 100,
                                n_events=3)
    b = ChaosSchedule.from_seed(7, ["w0", "w1", "w2", "w3"], 100,
                                n_events=3)
    assert a.to_doc() == b.to_doc()  # reproducible from the seed
    targets = [e.target for e in a.events]
    assert len(set(targets)) == len(targets)  # distinct targets
    for e in a.events:
        assert 25 <= e.at_step <= 75  # middle half of the run


def test_chaos_rejects_unknown_action():
    with pytest.raises(ValueError):
        ChaosEvent(at_step=1, action="nuke", target="w0")
    with pytest.raises(ValueError):
        ChaosSchedule.parse("kill@12")


# ---------------------------------------------------------------------------
# Share arithmetic + straggler board (the rebalance signal path)
# ---------------------------------------------------------------------------


def test_equal_shares_largest_remainder():
    assert equal_shares(32, ["w0", "w1", "w2", "w3"]) == {
        "w0": 8, "w1": 8, "w2": 8, "w3": 8}
    s = equal_shares(32, ["w0", "w1", "w2"])
    assert sum(s.values()) == 32 and sorted(s.values()) == [10, 11, 11]
    assert equal_shares(5, []) == {}


def test_rebalance_preserves_global_batch_exactly():
    shares = equal_shares(32, ["w0", "w1", "w2", "w3"])
    new = rebalance_shares(shares, {"w2": 3.0})
    assert sum(new.values()) == 32  # THE invariant: global batch
    assert new["w2"] < shares["w2"]  # slow worker shrank
    assert all(new[w] >= shares[w] for w in ("w0", "w1", "w3"))
    assert min(new.values()) >= 1


def test_rebalance_min_share_floor_and_degenerate_inputs():
    new = rebalance_shares({"w0": 2, "w1": 2}, {"w1": 100.0})
    assert new["w1"] >= 1 and sum(new.values()) == 4
    assert rebalance_shares({}, {}) == {}
    # total too small to give everyone min_share: unchanged
    tiny = {"w0": 1, "w1": 1}
    assert rebalance_shares(tiny, {"w1": 9.0}, min_share=2) == tiny


def test_straggler_board_factors():
    b = StragglerBoard(window=16, min_steps=3)
    for _ in range(6):
        for w in ("w0", "w1", "w2"):
            b.observe(w, 0.1)
        b.observe("w3", 0.3)
    f = b.factors()
    assert abs(f["w0"] - 1.0) < 1e-6
    assert abs(f["w3"] - 3.0) < 1e-6
    assert b.slowdown("w3") == pytest.approx(3.0)
    b.forget("w3")
    assert "w3" not in b.factors()


def test_straggler_board_warmup_suppression():
    b = StragglerBoard(window=16, min_steps=5)
    assert b.observe("w0", 5.0) == 1.0  # thin history: no verdict
    assert b.factors() == {}


# ---------------------------------------------------------------------------
# Supervisor units (no subprocesses)
# ---------------------------------------------------------------------------


def test_supervisor_rejects_live_broker_and_missing_ckpt_dir(tmp_path):
    with pytest.raises(ValueError, match="broker spec"):
        TrainSupervisor(InMemoryBroker(), {"ckpt_dir": str(tmp_path)})
    with pytest.raises(ValueError, match="ckpt_dir"):
        TrainSupervisor("dir:" + str(tmp_path), {})


def test_supervisor_from_config_and_env_tier(tmp_path, monkeypatch):
    from analytics_zoo_tpu.common.engine import ZooConfig

    monkeypatch.setenv("ZOO_ELASTIC", "yes")
    monkeypatch.setenv("ZOO_ELASTIC_LEASE_MS", "1200")
    monkeypatch.setenv("ZOO_ELASTIC_MIN_WORKERS", "2")
    monkeypatch.setenv("ZOO_ELASTIC_GRACE_MS", "700")
    cfg = ZooConfig()
    assert (cfg.elastic, cfg.elastic_lease_ms, cfg.elastic_min_workers,
            cfg.elastic_grace_ms) == (True, 1200, 2, 700)
    sup = TrainSupervisor.from_config(
        cfg, "dir:" + str(tmp_path / "sp"),
        {"ckpt_dir": str(tmp_path / "ck")})
    assert (sup.lease_ms, sup.min_workers, sup.grace_ms) == (
        1200, 2, 700)


def test_zoo_config_rejects_bad_elastic_knobs(monkeypatch):
    from analytics_zoo_tpu.common.engine import ZooConfig

    monkeypatch.setenv("ZOO_ELASTIC", "sideways")
    with pytest.raises(ValueError, match="ZOO_ELASTIC"):
        ZooConfig()
    monkeypatch.delenv("ZOO_ELASTIC")
    monkeypatch.setenv("ZOO_ELASTIC_LEASE_MS", "50")  # below minimum
    with pytest.raises(ValueError, match="ZOO_ELASTIC_LEASE_MS"):
        ZooConfig()
    monkeypatch.setenv("ZOO_ELASTIC_LEASE_MS", "3000")
    monkeypatch.setenv("ZOO_ELASTIC_MIN_WORKERS", "0")
    with pytest.raises(ValueError, match="ZOO_ELASTIC_MIN_WORKERS"):
        ZooConfig()


def test_varz_and_render_elastic(tmp_path):
    sup = TrainSupervisor("dir:" + str(tmp_path / "sp"),
                          {"ckpt_dir": str(tmp_path / "ck")}, workers=4)
    sup._record_decision("rejoin", "leave", generation=3, world=3,
                         worker="w1")
    doc = supervisor_mod.varz_doc()
    assert any(s["current"]["target_workers"] == 4
               for s in doc["supervisors"])
    assert any(d["action"] == "rejoin" for d in doc["decisions"])

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from metrics_dump import render_elastic
    finally:
        sys.path.pop(0)
    out = []
    render_elastic({"elastic": doc}, out=out)
    text = "\n".join(out)
    assert "elastic: generation=" in text and "rejoin" in text
    out2 = []
    render_elastic({"elastic": doc}, prefix="zoo_prefetch", out=out2)
    assert out2 == []  # --prefix filters the panel out


# ---------------------------------------------------------------------------
# SIGTERM flight-dump vs async checkpoint writer (the ISSUE 16 race pin)
# ---------------------------------------------------------------------------


_SIGTERM_RACE_SCRIPT = r"""
import os, pickle, signal, sys, time
import numpy as np
from analytics_zoo_tpu.metrics.flight import get_flight_recorder
from analytics_zoo_tpu.pipeline.estimator import estimator as est_mod

flight = get_flight_recorder().install()

real_dump = pickle.dump
def slow_dump(obj, f, *a, **k):
    time.sleep(1.0)  # wide-open race window: writer mid-serialization
    return real_dump(obj, f, *a, **k)
pickle.dump = slow_dump

ck = est_mod._Checkpointer(sys.argv[1])
ck.save("race", {"params": np.zeros(8, np.float32), "global_step": 1,
                 "epoch": 1})
os.kill(os.getpid(), signal.SIGTERM)  # dump while the write is in flight
time.sleep(30)  # never reached
"""


def test_sigterm_dump_flushes_async_checkpoint_writer(tmp_path):
    """A SIGTERM flight dump must contain the in-flight snapshot's final
    ``ckpt`` complete event — the pre-dump hook joins the writer thread
    (bounded by ZOO_ELASTIC_GRACE_MS) before the ring is snapshotted.
    Before the fix the dump ended at phase=start and the snapshot died
    half-written with the process."""
    flight_dir = str(tmp_path / "flight")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ZOO_FLIGHT_DIR=flight_dir, ZOO_ELASTIC_GRACE_MS="10000")
    p = subprocess.run(
        [sys.executable, "-c", _SIGTERM_RACE_SCRIPT,
         str(tmp_path / "ck")],
        env=env, cwd=REPO, timeout=120, capture_output=True, text=True)
    assert p.returncode != 0  # died to the SIGTERM, not the sleep
    dumps = [f for f in os.listdir(flight_dir) if f.endswith(".json")]
    assert dumps, p.stderr
    with open(os.path.join(flight_dir, dumps[0])) as f:
        doc = json.load(f)
    phases = [e.get("phase") for e in doc["events"]
              if e.get("kind") == "ckpt"]
    assert "complete" in phases, phases  # flushed BEFORE the snapshot
    # and the durable artifact is whole: LATEST names a loadable pickle
    with open(os.path.join(str(tmp_path / "ck"), "LATEST")) as f:
        name = f.read().strip()
    with open(os.path.join(str(tmp_path / "ck"), name), "rb") as f:
        payload = pickle.load(f)
    assert payload["global_step"] == 1


# ---------------------------------------------------------------------------
# THE acceptance run (ISSUE 16): 4 workers, kill -9 + SIGTERM mid-run,
# unattended completion, trajectory bit-exact vs the uninterrupted run
# ---------------------------------------------------------------------------


def _uninterrupted_params(spec, mesh):
    """The oracle trajectory: same model/data/plan, no faults, straight
    through in-process on a {data: mesh} mesh."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    zoo.init_zoo_context(seed=spec["seed"], mesh_shape={"data": mesh})
    m = Sequential()
    m.add(Dense(spec["hidden"], activation="relu",
                input_shape=(spec["in_dim"],)))
    m.add(Dense(spec["classes"], activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    rng = np.random.default_rng(spec["seed"])
    x = rng.standard_normal(
        (spec["n"], spec["in_dim"])).astype(np.float32)
    y = rng.integers(0, spec["classes"],
                     size=(spec["n"],)).astype(np.int32)
    m.fit(x, y, batch_size=spec["batch_size"],
          nb_epoch=spec["nb_epoch"], plan=spec["plan"])
    return m, [h["loss"] for h in m._estimator.history]


def _latest_payload(ckpt_dir):
    with open(os.path.join(ckpt_dir, "LATEST")) as f:
        name = f.read().strip()
    with open(os.path.join(ckpt_dir, name), "rb") as f:
        return pickle.load(f)


def test_chaos_acceptance_kill9_and_sigterm_unattended(tmp_path):
    """4-worker TrainSupervisor over a dir: broker; chaos kills one
    worker with SIGKILL and another with SIGTERM mid-run.  The cohort
    must finish the full nb_epoch target with ZERO human intervention;
    every fault shows up in the decision log as
    chaos -> leave-rejoin -> respawn -> join-rejoin; the oracle re-picks
    EXACTLY once per generation change; and the final parameters are
    bit-exact against the uninterrupted single-leg run (resume from
    LATEST + resharding preserved the trajectory across every world
    size the run passed through)."""
    ck = str(tmp_path / "ckpt")
    spec = dict(ckpt_dir=ck, nb_epoch=6, plan="fsdp", k=1,
                throttle_s=0.08)
    sup = TrainSupervisor(
        "dir:" + str(tmp_path / "spool"), spec, workers=4,
        lease_ms=800, min_workers=1, interval=0.1,
        chaos=ChaosSchedule.parse("kill@12:w1,term@24:w2"),
        worker_env={"ZOO_FLIGHT_DIR": str(tmp_path / "flight")})

    def logged(action, reason):
        return sum(1 for d in sup.decision_log()
                   if d["action"] == action and d["reason"] == reason)

    # A respawn takes seconds to import and join, the 24 steps after the
    # SIGTERM about two.  From the SIGTERM until the second respawn has
    # joined, the chief finds a chaos stall of 2 s armed at each step
    # barrier (its control hash, consumed as it fires), so that the round
    # cannot end before the join it is checked for.
    finished = threading.Event()

    def stall_the_chief():
        broker = sup.ledger.broker
        while not finished.is_set() and logged("rejoin", "join") < 3:
            if logged("chaos", "term"):
                ctl = sup.ledger.ctl_key(sup.current()["chief"])
                if not fget(broker.hgetall(ctl), "stall_s"):
                    broker.hset(ctl, {"stall_s": "2.0"})
            time.sleep(0.02)

    staller = threading.Thread(target=stall_the_chief, daemon=True)
    staller.start()
    try:
        res = sup.run(timeout_s=420)
    finally:
        finished.set()
        staller.join()

    # unattended completion: full target reached, result posted
    assert res is not None and res["done"] == 1, sup.decision_log()
    steps_per_epoch = sup.spec["n"] // sup.spec["batch_size"]
    assert res["final_step"] == steps_per_epoch * sup.spec["nb_epoch"]

    log = sup.decision_log()
    by_action = {}
    for d in log:
        by_action.setdefault(d["action"], []).append(d)
    # both faults fired, at their scripted steps or the tick after
    chaos = {d["reason"]: d for d in by_action["chaos"]}
    assert set(chaos) == {"kill", "term"}
    for d in chaos.values():
        assert d["fired_step"] - d["at_step"] <= 3
    # each fault produced a leave-rejoin; each respawn a join-rejoin
    rejoins = by_action["rejoin"]
    assert sum(1 for d in rejoins if d["reason"] == "leave") >= 2
    assert sum(1 for d in rejoins if d["reason"] == "join") >= 3
    assert len(by_action["respawn"]) >= 2
    # every step is accounted for: any step past LATEST at a kill is
    # REPLAYED, not dropped — the decision log carries the replay count
    kills = [d for d in rejoins if d["reason"] == "leave"]
    assert all(d["steps_lost"] >= 0 for d in kills)

    # exactly ONE oracle re-pick per generation change that produced an
    # assignment, logged as a prediction (outcome fed on completion)
    repicks = sup.repick_log()
    assert len(repicks) == len(rejoins)
    assert [r["generation"] for r in repicks] == \
        [d["generation"] for d in rejoins]  # one per generation, in order
    assert all(r["pick"]["plan"] for r in repicks)
    done = by_action["done"][0]
    assert done["steps_per_sec"] > 0  # the outcome that closed the loop

    # trajectory: bit-exact against the uninterrupted run
    import jax

    m, full_losses = _uninterrupted_params(sup.spec, mesh=4)
    final = _latest_payload(ck)
    assert final["global_step"] == res["final_step"]
    chaos_final = [np.asarray(a) for a in
                   jax.tree_util.tree_leaves(final["params"])]
    clean_final = [np.asarray(a) for a in
                   jax.tree_util.tree_leaves(m.params)]
    assert len(chaos_final) == len(clean_final)
    for a, b in zip(chaos_final, clean_final):
        np.testing.assert_array_equal(a, b)  # BIT-exact
    # full per-epoch losses of the final leg line up with the clean run
    # (the leg's FIRST history entry may cover a partially-replayed
    # epoch — resumed mid-epoch its average spans fewer batches)
    for h in res["history"][1:]:
        np.testing.assert_allclose(
            h["loss"], full_losses[h["epoch"] - 1], rtol=1e-6)


def test_chaos_supervisor_over_redis_broker(tmp_path):
    """The chaos/supervision path over a REAL RedisBroker (the
    cross-host deployment shape): the membership ledger, assignment
    docs, chaos kill, respawn and the posted result all travel through
    redis instead of a shared filesystem.  The membership tests above
    already parametrize over redis via the ``broker`` fixture; this
    covers the full supervisor loop.  A unique prefix isolates the run
    on a shared server."""
    spec = os.environ.get("ZOO_TEST_REDIS")
    if not spec:
        pytest.skip("set ZOO_TEST_REDIS=host:port to run redis "
                    "supervisor/chaos tests")
    prefix = f"t-chaos-{os.getpid()}-{int(time.time())}"
    sup = TrainSupervisor(
        spec, dict(ckpt_dir=str(tmp_path / "ckpt"), nb_epoch=3,
                   plan="dp", k=1, throttle_s=0.08),
        workers=3, prefix=prefix, lease_ms=800, min_workers=1,
        interval=0.1, chaos=ChaosSchedule.parse("kill@10:w1"))
    res = sup.run(timeout_s=420)

    assert res is not None and res["done"] == 1, sup.decision_log()
    steps_per_epoch = sup.spec["n"] // sup.spec["batch_size"]
    assert res["final_step"] == steps_per_epoch * sup.spec["nb_epoch"]
    by_action = {}
    for d in sup.decision_log():
        by_action.setdefault(d["action"], []).append(d)
    assert by_action["chaos"][0]["reason"] == "kill"
    assert len(by_action["respawn"]) >= 1
    assert any(d["reason"] == "leave" for d in by_action["rejoin"])
    assert any(d["reason"] == "join" for d in by_action["rejoin"])
