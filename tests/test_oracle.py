"""Predictive compile plane (analysis/costmodel.py + analysis/oracle.py):
roofline shape (monotone in work, concave K-amortization), peak-table
resolution + the ZOO_ORACLE_PEAKS override contract, residual
fit/predict round-trip with the analytic fallback below the sample
floor, the zoo-hlo-report/2 + tune-log readers and their training-row
join, choose_plan budget cases, the autotuner's oracle-prior
convergence in <= 8 tuning dispatches, the ZOO_TUNE_LOG_DIR JSONL
persistence + rotation satellite, and both consumers of the prior
through a real ``fit()``."""

import json
import os

import pytest

from analytics_zoo_tpu.analysis.costmodel import (
    PLATFORM_PEAKS,
    ResidualModel,
    load_report_rows,
    load_tune_log_rows,
    normalize_features,
    plan_collective_bytes,
    predict_chip_bytes,
    predict_step_seconds,
    predict_steps_per_sec,
    resolve_peaks,
    training_rows,
)
from analytics_zoo_tpu.analysis.hlo import HloReport, remember_report
from analytics_zoo_tpu.analysis.oracle import ConfigOracle, oracle_enabled
from analytics_zoo_tpu.feature.autotune import (
    AutotuneController,
    _append_tune_log,
)


@pytest.fixture(autouse=True)
def _clean_oracle_env(monkeypatch):
    """Peaks/dirs resolve from the env — keep each test hermetic."""
    for var in ("ZOO_ORACLE_PEAKS", "ZOO_HLO_REPORT_DIR",
                "ZOO_TUNE_LOG_DIR", "ZOO_TUNE_LOG_MAX_BYTES",
                "ZOO_ORACLE"):
        monkeypatch.delenv(var, raising=False)


def _feats(flops=1e9, bytes_accessed=4e8, collective_bytes=0,
           op_count=100):
    return {"matmul_flops": flops, "bytes_accessed": bytes_accessed,
            "collective_bytes": collective_bytes, "op_count": op_count}


# ---------------------------------------------------------------------------
# roofline shape
# ---------------------------------------------------------------------------

def test_roofline_monotone_in_work():
    """More flops / more bytes / more collective traffic must never
    predict a FASTER step — the roofline is monotone in every work
    term."""
    peaks = PLATFORM_PEAKS["cpu"]
    base = predict_step_seconds(_feats(), peaks=peaks)
    for grown in (_feats(flops=4e9),
                  _feats(bytes_accessed=4e9),
                  _feats(collective_bytes=1e9)):
        assert predict_step_seconds(grown, peaks=peaks) >= base


def test_roofline_k_amortization_concave():
    """step_seconds(K) falls monotonically with diminishing returns
    (only the dispatch-overhead term divides by K) and plateaus at the
    compute/memory bound."""
    peaks = PLATFORM_PEAKS["cpu"]
    ks = (1, 2, 4, 8, 16)
    s = [predict_step_seconds(_feats(), k=k, peaks=peaks) for k in ks]
    gains = [a - b for a, b in zip(s, s[1:])]
    assert all(g > 0 for g in gains)            # monotone improvement
    assert all(a > b for a, b in zip(gains, gains[1:]))  # concave
    floor = predict_step_seconds(_feats(), k=10**9, peaks=peaks)
    bound = max(1e9 / peaks.flops, 4e8 / peaks.hbm_bytes_per_s)
    assert floor == pytest.approx(bound, rel=1e-6)  # plateau = roofline


def test_roofline_inverse():
    sps = predict_steps_per_sec(_feats(), k=4)
    assert sps == pytest.approx(
        1.0 / predict_step_seconds(_feats(), k=4), rel=1e-9)


def test_normalize_features_aliases():
    """All three emitted shapes (HloReport.features, zoo_hlo_* scrape,
    bench hlo block) normalize to one canonical vector; missing keys
    become 0 so a v1 report with nulls still yields a usable vector."""
    canon = normalize_features({"zoo_hlo_flops": 7, "zoo_hlo_ops": 3})
    assert canon["matmul_flops"] == 7.0
    assert canon["op_count"] == 3.0
    assert canon["bytes_accessed"] == 0.0


# ---------------------------------------------------------------------------
# peak resolution + env override
# ---------------------------------------------------------------------------

def test_resolve_peaks_device_kind():
    assert resolve_peaks("tpu", "TPU v4").source == "tpu-v4"
    # the device_kind one v5e chip reports
    assert resolve_peaks(None, "TPU v5 lite").source == "tpu-v5e"
    assert resolve_peaks("cpu", None).source == "cpu-default"
    # the no-argument call asks jax.devices()[0]: the CPU here
    assert resolve_peaks().source == "cpu-default"
    # a TPU without a row is an error, never another chip's row
    with pytest.raises(ValueError, match="tpu-v99"):
        resolve_peaks("tpu", "tpu-v99")


def test_peaks_env_override(monkeypatch):
    monkeypatch.setenv("ZOO_ORACLE_PEAKS", json.dumps(
        {"hbm_bytes": 123456.0}))
    peaks = resolve_peaks("cpu")
    assert peaks.hbm_bytes == 123456.0
    assert peaks.source == "env"
    # untouched fields keep the platform row
    assert peaks.flops == PLATFORM_PEAKS["cpu"].flops


def test_peaks_env_override_rejects_unknown_field(monkeypatch):
    monkeypatch.setenv("ZOO_ORACLE_PEAKS", json.dumps({"hbm_byte": 1}))
    with pytest.raises(ValueError, match="hbm_byte"):
        resolve_peaks("cpu")


def test_peaks_env_override_rejects_non_object(monkeypatch):
    monkeypatch.setenv("ZOO_ORACLE_PEAKS", "[1, 2]")
    with pytest.raises(ValueError):
        resolve_peaks("cpu")
    monkeypatch.setenv("ZOO_ORACLE_PEAKS", "{not json")
    with pytest.raises(ValueError):
        resolve_peaks("cpu")


def test_oracle_enabled_default_on(monkeypatch):
    assert oracle_enabled()
    monkeypatch.setenv("ZOO_ORACLE", "0")
    assert not oracle_enabled()


# ---------------------------------------------------------------------------
# residual model: fit/predict round-trip + analytic fallback
# ---------------------------------------------------------------------------

def _synthetic_rows(peaks, factor=1.7):
    rows = []
    for k in (1, 2, 4, 8, 16):
        for scale in (1.0, 2.0):
            f = _feats(flops=1e9 * scale, bytes_accessed=4e8 * scale)
            rows.append({
                "features": f, "k": k,
                "measured_steps_per_sec":
                    factor * predict_steps_per_sec(f, k=k, peaks=peaks)})
    return rows


def test_residual_fit_round_trip():
    """Measurements a constant 1.7x off the analytic roofline: the
    fitted residual must reproduce them — on every training row the
    corrected prediction lands within 5% of the measurement."""
    peaks = PLATFORM_PEAKS["cpu"]
    rows = _synthetic_rows(peaks)
    model = ResidualModel(peaks=peaks).fit(rows)
    assert model.ready
    assert model.n_samples == len(rows)
    for row in rows:
        pred = model.predict_steps_per_sec(row["features"], k=row["k"])
        assert pred == pytest.approx(
            row["measured_steps_per_sec"], rel=0.05)


def test_residual_zero_sample_analytic_fallback():
    """Below MIN_FIT_SAMPLES the model stays analytic: ready is False
    and predictions equal the pure roofline bit-for-bit, so callers
    never branch on readiness."""
    peaks = PLATFORM_PEAKS["cpu"]
    rows = _synthetic_rows(peaks)[:3]
    model = ResidualModel(peaks=peaks).fit(rows)
    assert not model.ready
    assert model.n_samples == 3
    f = _feats()
    assert model.predict_steps_per_sec(f, k=4) == \
        predict_steps_per_sec(f, k=4, peaks=peaks)
    # unfit model (no fit() call at all) behaves identically
    assert ResidualModel(peaks=peaks).predict_steps_per_sec(f, k=4) == \
        predict_steps_per_sec(f, k=4, peaks=peaks)


def test_residual_drops_unmeasured_rows():
    peaks = PLATFORM_PEAKS["cpu"]
    rows = _synthetic_rows(peaks)
    rows += [{"features": _feats(), "k": 1,
              "measured_steps_per_sec": 0}] * 5
    model = ResidualModel(peaks=peaks).fit(rows)
    assert model.n_samples == len(rows) - 5


# ---------------------------------------------------------------------------
# report/tune-log readers + the training join
# ---------------------------------------------------------------------------

def _write_report_doc(report_dir, doc, name="hlo-t-1-1.json"):
    os.makedirs(report_dir, exist_ok=True)
    with open(os.path.join(report_dir, name), "w") as f:
        json.dump(doc, f)


def test_report_reader_v2_and_v1(tmp_path):
    """The v2 writer round-trips through the reader; a v1 report (no
    compile/config context) still loads with the new fields None."""
    rpt = HloReport(label="step", matmul_flops=123, bytes_accessed=456,
                    op_count=7, compile_seconds=0.41, plan="fsdp",
                    mesh_shape={"data": 8}, steps_per_dispatch=16,
                    dtype_histogram={"f32": 5})
    _write_report_doc(str(tmp_path), rpt.to_doc(), "hlo-step-1-1.json")
    _write_report_doc(str(tmp_path), {
        "schema": "zoo-hlo-report/1", "label": "old",
        "features": {"matmul_flops": 9},
    }, "hlo-old-1-2.json")
    _write_report_doc(str(tmp_path), {"schema": "other"}, "hlo-x-1-3.json")
    (tmp_path / "hlo-broken-1-4.json").write_text("{not json")

    rows = {r["label"]: r for r in load_report_rows(str(tmp_path))}
    assert set(rows) == {"step", "old"}
    v2 = rows["step"]
    assert v2["features"]["matmul_flops"] == 123.0
    assert v2["k"] == 16
    assert v2["plan"] == "fsdp"
    assert v2["mesh_shape"] == {"data": 8}
    assert v2["compile_seconds"] == 0.41
    assert v2["dtype_histogram"] == {"f32": 5}
    v1 = rows["old"]
    assert v1["features"]["matmul_flops"] == 9.0
    assert v1["k"] is None and v1["plan"] is None
    assert v1["compile_seconds"] is None


def test_tune_log_persistence_and_rotation(tmp_path, monkeypatch):
    """ZOO_TUNE_LOG_DIR persists decisions as JSONL; past the byte cap
    the file rotates to .1 (one predecessor kept) instead of growing
    unboundedly; the reader turns settle records' cost curves into
    per-K measurement rows."""
    monkeypatch.setenv("ZOO_TUNE_LOG_DIR", str(tmp_path))
    settle = {"type": "settle", "label": "step", "k": 16,
              "k_cost_per_step_s": {"1": 0.01, "16": 0.002}}
    _append_tune_log(settle)
    path = tmp_path / f"tune-{os.getpid()}.jsonl"
    assert path.exists()

    rows = load_tune_log_rows(str(tmp_path))
    assert {(r["k"], r["measured_steps_per_sec"]) for r in rows} == \
        {(1, 100.0), (16, 500.0)}
    assert all(r["label"] == "step" for r in rows)

    monkeypatch.setenv("ZOO_TUNE_LOG_MAX_BYTES", "150")
    for _ in range(10):
        _append_tune_log(settle)
    assert (tmp_path / (path.name + ".1")).exists()
    assert path.stat().st_size <= 150 + len(json.dumps(settle)) + 1


def test_training_rows_join(tmp_path, monkeypatch):
    """Tune-log rows (measurement, no features) join with the latest
    report row of the same compile label; unjoinable labels drop
    silently and the empty-history result is []."""
    report_dir, tune_dir = tmp_path / "rpt", tmp_path / "tune"
    rpt = HloReport(label="step", matmul_flops=123, bytes_accessed=456)
    _write_report_doc(str(report_dir), rpt.to_doc())
    monkeypatch.setenv("ZOO_TUNE_LOG_DIR", str(tune_dir))
    _append_tune_log({"type": "settle", "label": "step", "k": 4,
                      "k_cost_per_step_s": {"4": 0.004}})
    _append_tune_log({"type": "settle", "label": "orphan", "k": 2,
                      "k_cost_per_step_s": {"2": 0.02}})

    rows = training_rows(report_dir=str(report_dir),
                         tune_log_dir=str(tune_dir))
    assert len(rows) == 1
    assert rows[0]["k"] == 4
    assert rows[0]["features"]["matmul_flops"] == 123.0
    assert rows[0]["measured_steps_per_sec"] == pytest.approx(250.0)
    assert training_rows(report_dir=str(tmp_path / "none"),
                         tune_log_dir=str(tmp_path / "none")) == []


# ---------------------------------------------------------------------------
# ConfigOracle: predict_k, choose_plan, the prediction->outcome log
# ---------------------------------------------------------------------------

def test_predict_k_overhead_bound_prefers_large_k():
    """Tiny program: dispatch overhead dominates, so the largest K wins
    by a margin — and EVERY candidate's prediction is logged so the
    settled K always has a pair to score."""
    oracle = ConfigOracle(peaks=PLATFORM_PEAKS["cpu"])
    tiny = _feats(flops=1e3, bytes_accessed=1e3)
    k_hat = oracle.predict_k(tiny, (1, 2, 4, 8, 16))
    assert k_hat == 16
    log = {p["config"]: p for p in oracle.prediction_log()}
    assert set(log) == {f"k={k}" for k in (1, 2, 4, 8, 16)}
    assert log["k=16"]["chosen"]
    assert not log["k=1"]["chosen"]


def test_predict_k_compute_bound_prefers_small_k():
    """Compute-bound program: K cannot help, all candidates tie within
    the margin, and the tie goes to the smallest K (finer checkpoint
    cadence for free)."""
    oracle = ConfigOracle(peaks=PLATFORM_PEAKS["cpu"])
    big = _feats(flops=1e12, bytes_accessed=1e10)
    assert oracle.predict_k(big, (1, 2, 4, 8, 16)) == 1


def test_record_outcome_closes_pair():
    oracle = ConfigOracle(peaks=PLATFORM_PEAKS["cpu"])
    oracle.predict_k(_feats(flops=1e3, bytes_accessed=1e3),
                     (1, 2, 4, 8, 16))
    predicted = {p["config"]: p["predicted_steps_per_sec"]
                 for p in oracle.prediction_log()}["k=16"]
    pair = oracle.record_outcome("k=16", predicted * 1.25,
                                 consumer="autotune_k")
    assert pair is not None
    assert pair["rel_error"] == pytest.approx(0.2, abs=1e-3)
    # an outcome with no recorded prediction logs but returns None
    assert oracle.record_outcome("k=99", 1.0) is None
    doc = oracle.to_doc()
    assert doc["fit_samples"] == 0 and not doc["residual_ready"]


def test_choose_plan_budget_cases():
    """Tight budget -> the only feasible plan (fsdp); generous budget
    -> the least-collective plan (dp); infeasible-everywhere -> the
    most memory-frugal candidate with feasible=False recorded."""
    oracle = ConfigOracle(peaks=PLATFORM_PEAKS["cpu"])
    p, o, n = 800_000, 1_600_000, 8
    assert predict_chip_bytes(p, o, "dp", n) == p + o
    assert predict_chip_bytes(p, o, "zero1", n) == p + o // n
    assert predict_chip_bytes(p, o, "fsdp", n) == (p + o) // n

    name, doc = oracle.choose_plan(p, o, n, hbm_budget=400_000)
    assert name == "fsdp" and doc["feasible"]
    name, doc = oracle.choose_plan(p, o, n, hbm_budget=10 * (p + o))
    assert name == "dp" and doc["feasible"]
    name, doc = oracle.choose_plan(p, o, n, hbm_budget=1_000)
    assert name == "fsdp" and not doc["feasible"]
    by_plan = {c["plan"]: c for c in doc["candidates"]}
    assert not by_plan["dp"]["fits_budget"]
    # sharding only adds collectives: dp moves the least per step
    assert plan_collective_bytes(p, "dp", n) < \
        plan_collective_bytes(p, "fsdp", n)


# ---------------------------------------------------------------------------
# the autotuner consuming the prior: <= 8 tuning dispatches to settle
# ---------------------------------------------------------------------------

def test_controller_prior_converges_in_few_dispatches():
    """Overhead-dominated synthetic cost curve: with the oracle prior
    the controller jumps to the predicted K=16 and settles after
    validating only the +-1 ladder neighbors — the acceptance budget is
    <= 8 TUNING dispatches (stale in-flight chunks from before a K
    switch are pipeline latency and excluded by design)."""
    label = "oracle-prior-unit"
    remember_report(HloReport(label=label, matmul_flops=1e3,
                              bytes_accessed=1e3, op_count=10))
    oracle = ConfigOracle(peaks=PLATFORM_PEAKS["cpu"])
    ctrl = AutotuneController(oracle=oracle,
                              k_candidates=(1, 2, 4, 8, 16))
    ctrl.set_feature_label(label)
    # per-dispatch cost model: 1e-4 s/step + 5e-4 s dispatch overhead
    for _ in range(64):
        if ctrl.k_settled:
            break
        k = ctrl.current()["k"]
        ctrl.observe_dispatch(k, k * 1e-4 + 5e-4)
    assert ctrl.k_settled
    snap = ctrl.current()
    assert snap["k"] == 16
    assert snap["k_settle_dispatch"] <= 8
    # the first dispatch (queued at K=1 before the prior flipped the
    # knob) is stale: observed, but not a tuning dispatch
    assert snap["dispatches_observed"] == snap["tuning_dispatches"] + 1
    reasons = [d["reason"] for d in ctrl.decision_log()]
    assert "oracle_prior" in reasons
    assert "probe_up" not in reasons  # validation pass, not a climb
    # settle closed a prediction->outcome pair on the chosen config
    pairs = {p["config"]: p for p in oracle.prediction_log()}
    assert pairs["k=16"]["measured_steps_per_sec"] is not None
    assert pairs["k=16"]["rel_error"] is not None


def test_controller_blind_without_oracle():
    """No oracle attached: the blind hill-climb still probes up from
    K=1 — the prior is an accelerator, not a dependency."""
    ctrl = AutotuneController(k_candidates=(1, 2, 4), k_samples=2,
                              k_warm_skip=1)
    for _ in range(64):
        if ctrl.k_settled:
            break
        k = ctrl.current()["k"]
        ctrl.observe_dispatch(k, k * 1e-4 + 5e-4)
    assert ctrl.k_settled
    assert ctrl.current()["k"] == 4
    assert "probe_up" in [d["reason"] for d in ctrl.decision_log()]


# ---------------------------------------------------------------------------
# both consumers of the prior through a real fit()
# ---------------------------------------------------------------------------

#: per-chip budget (bytes) for the plan="auto" leg: between fsdp's
#: measured ~115 kB and zero1's ~384 kB of parameters and optimizer state
#: for the 32 -> 256 -> 256 -> 10 net on 8 devices, so exactly one of the
#: swept plans fits
PLAN_HBM_BUDGET = 200_000


def _dense_net(feat, *widths):
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    m = Sequential()
    m.add(Dense(widths[0], activation="relu", input_shape=(feat,)))
    for width in widths[1:-1]:
        m.add(Dense(width, activation="relu"))
    m.add(Dense(widths[-1], activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    return m


def _classes_of_a_random_projection(n, feat, classes, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, feat)).astype(np.float32)
    y = np.argmax(x @ rng.normal(size=(feat, classes)),
                  axis=1).astype(np.int32)
    return x, y


def test_prior_settles_k_in_budget_and_auto_plan_agrees_with_the_sweep(
        monkeypatch):
    """The prior-guided controller settles K within the 8-tuning-dispatch
    budget on a dispatch-bound net with the loss trajectory bitwise
    K = 1's.  ``plan="auto"`` under a budget that one plan fits picks
    that plan, as measuring every plan's placed state does, and the
    predicted bytes a chip are within 5% of the measured."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.engine import ZooConfig
    from analytics_zoo_tpu.parallel.plan import per_chip_bytes

    x, y = _classes_of_a_random_projection(192 * 16, 32, 10, seed=5)

    def losses_of(autotune=None, **cfg):
        zoo.init_zoo_context(ZooConfig(seed=11, **cfg))
        m = _dense_net(32, 64, 10)
        m.fit(x, y, batch_size=16, nb_epoch=2, autotune=autotune)
        return [h["loss"] for h in m._estimator.history]

    k1_losses = losses_of(steps_per_dispatch=1)
    ctrl = AutotuneController(oracle=ConfigOracle.from_env())
    try:
        tuned_losses = losses_of(autotune=ctrl)
    finally:
        ctrl.stop()
    cur = ctrl.current()
    assert cur["k_settled"], cur
    # tuning observations only: chunks queued before a K switch keep
    # their old size (pipeline latency, not search)
    assert cur["k_settle_dispatch"] <= 8, cur
    assert tuned_losses == k1_losses

    x, y = _classes_of_a_random_projection(512, 32, 10, seed=7)

    def fit_under(plan):
        zoo.init_zoo_context(seed=11, mesh_shape={"data": 8},
                             platform="cpu")
        m = _dense_net(32, 256, 256, 10)
        m.fit(x, y, batch_size=64, nb_epoch=1, plan=plan)
        return m._estimator

    monkeypatch.setenv("ZOO_ORACLE_PEAKS",
                       json.dumps({"hbm_bytes": PLAN_HBM_BUDGET}))
    record = fit_under("auto")._plan_record
    monkeypatch.delenv("ZOO_ORACLE_PEAKS")
    auto = record["auto"]
    measured = {}
    for plan in ("dp", "fsdp", "zero1"):
        est = fit_under(plan)
        measured[plan] = per_chip_bytes(
            (est.model.params, est._opt_state))
        predicted = predict_chip_bytes(
            auto["param_bytes"], auto["opt_bytes"], plan, auto["n_shards"])
        assert abs(predicted - measured[plan]) / measured[plan] < 0.05, \
            (plan, predicted, measured[plan])
    # the sweep measured sharding only, so agreement is on the base plan;
    # the remat suffix is chosen against the activation estimate
    assert [plan for plan, chip in measured.items()
            if chip <= PLAN_HBM_BUDGET] == [record["name"].split("+")[0]]
