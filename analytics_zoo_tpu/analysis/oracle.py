"""ConfigOracle — the predictive compile plane's decision surface.

:mod:`analytics_zoo_tpu.analysis.costmodel` predicts; this module
DECIDES and is wired in as the prior for the two consumers that used
to search blind:

- the autotuner's K hill-climb (feature/autotune.py) calls
  :meth:`ConfigOracle.predict_k` after the first compiled dispatch and
  jumps straight to the predicted ``steps_per_dispatch``, demoting the
  ladder sweep to a ±1-neighbor validation pass — ≤8 dispatches to
  settle (``tests/test_oracle.py``) where the blind climb took about 53
  on a one-core CPU host in PR 8, trajectory still
  bitwise-equal because per-inner-step RNG folds on the global step
  index regardless of the K schedule;
- ``estimator.fit(plan="auto")`` calls :meth:`ConfigOracle.choose_plan`
  to pick among dp/zero1/fsdp/tp from predicted per-chip bytes vs the
  HBM budget, preferring the least-collective-traffic plan that fits.

Every prediction→outcome pair is logged three ways (the autotune
convention): the ``zoo_oracle_*`` metric family, an ``oracle`` flight
event, and a bounded predicted-vs-measured table served at ``/varz``
(rendered by ``tools/metrics_dump.py``) — closing the data loop the
residual model trains on.  Opt-out: ``ZOO_ORACLE=0`` restores the
blind sweep everywhere.
"""

from __future__ import annotations

import collections
import math
import os
import threading
import time
import weakref
from typing import Iterable, Mapping, Sequence

from analytics_zoo_tpu.analysis.costmodel import (
    DTYPE_PEAK_FACTORS,
    REMAT_FLOPS_FACTORS,
    PeakTable,
    ResidualModel,
    choose_kernel,
    normalize_features,
    plan_collective_bytes,
    plan_exposed_fraction,
    predict_chip_bytes,
    predict_serving_seconds,
    predict_step_seconds,
    predict_steps_per_sec,
    resolve_peaks,
    training_rows,
)
from analytics_zoo_tpu.metrics import (
    OracleMetrics,
    get_flight_recorder,
)

__all__ = ["ConfigOracle", "oracle_enabled", "varz_doc",
           "KERNEL_STEP_FACTORS", "SERVING_SLO_FRACTION",
           "SERVING_UTILIZATION"]

#: plans the oracle can choose among for ``plan="auto"``, ordered from
#: least to most sharded so infeasible-everywhere ties break toward the
#: established layout (fsdp before the equivalent-memory zero3) —
#: tensor parallelism needs a model-specific rule table and pipeline a
#: staged model, so they participate in ranking only when the caller
#: passes them explicitly
DEFAULT_PLAN_CANDIDATES = ("dp", "zero1", "zero2", "fsdp", "zero3")

#: a prediction within this margin of the best is "as good" — ties go
#: to the smaller K (finer checkpoint cadence), mirroring the
#: autotuner's own k_margin settle rule
PREDICT_MARGIN = 0.05

#: Step-time factor the KERNEL dimension applies to a candidate's
#: compute term in :meth:`ConfigOracle.choose_plan`.  On TPU the fused
#: Pallas kernels cut the optimizer/loss HBM round trips ~2.5-3x
#: (costmodel.kernel_bytes: fused_adam 24n vs 60n, fused_softmax_xent
#: 4BV vs 12BV) but those scopes are a slice of the whole step, so the
#: ranking coefficient is a modest 0.9 — it exists to ORDER "+kernels"
#: above its plain twin on TPU, like the plan_collective_bytes
#: coefficients, not to predict seconds.  On non-TPU peaks the factor
#: is exactly 1.0: the kernels fall back to the same XLA program, so
#: the tie breaks toward the plain candidate (candidate order) — the
#: oracle DECLINING pallas on the CPU tier.
KERNEL_STEP_FACTORS = {None: 1.0, "kernels": 0.9}

#: Share of the p99 SLO :meth:`ConfigOracle.choose_serving` budgets for
#: SERVICE time (the padded dispatch itself); the remainder is queueing
#: headroom — Little's-law delay under the target utilization plus the
#: batcher's fill wait.  A bucket whose predicted dispatch exceeds this
#: slice of the SLO cannot meet the tail even on an idle replica, so it
#: is excluded from the pad-bucket set.
SERVING_SLO_FRACTION = 0.5

#: Per-replica utilization the replica math plans to: predicted
#: capacity is derated by this factor so the fleet absorbs arrival
#: burstiness without the queue estimate blowing through the SLO
#: headroom (the classic M/M/1 knee — above ~0.7 the queue term
#: dominates).
SERVING_UTILIZATION = 0.6


def oracle_enabled() -> bool:
    """``ZOO_ORACLE`` gate (default ON — the oracle only reorders
    searches, it never changes results; ``0``/``false``/``off``
    restores the blind sweep)."""
    return os.environ.get("ZOO_ORACLE", "1").strip().lower() not in (
        "0", "false", "off")


# ---------------------------------------------------------------------------
# Live-oracle registry: /varz (metrics/http.py) includes the
# predicted-vs-measured tables of whatever oracles exist, via
# sys.modules only — metrics-only processes never import this module.
# ---------------------------------------------------------------------------

_active_lock = threading.Lock()
_active: "weakref.WeakSet[ConfigOracle]" = (  # guarded-by: _active_lock
    weakref.WeakSet())


def varz_doc() -> dict:
    """The ``oracle`` section of ``/varz``: every live oracle's peak
    table, residual-fit size, and merged time-ordered
    prediction→outcome log."""
    with _active_lock:
        oracles = list(_active)
    docs = [o.to_doc() for o in oracles]
    predictions = sorted(
        (p for doc in docs for p in doc["predictions"]),
        key=lambda p: p["ts"])
    return {"oracles": docs, "predictions": predictions}


class ConfigOracle:
    """Ranks candidate (K, sharding plan) configs from the analytic
    roofline, corrected by the fitted residual once enough outcome
    history exists.

    One oracle serves one process; build with :meth:`from_env` to get
    platform-resolved peaks and a residual fitted from whatever
    ``ZOO_HLO_REPORT_DIR`` / ``ZOO_TUNE_LOG_DIR`` history has
    accumulated.  All prediction state is lock-guarded — the autotuner
    consults it from the estimator loop while /varz snapshots it from
    the HTTP thread."""

    def __init__(self, peaks: PeakTable | None = None,
                 residual: ResidualModel | None = None,
                 registry=None, log_capacity: int = 256):
        self.peaks = peaks if peaks is not None else resolve_peaks()
        self.residual = residual if residual is not None else \
            ResidualModel(peaks=self.peaks)
        self.metrics = OracleMetrics(registry=registry)
        self._lock = threading.Lock()
        # config key -> the latest prediction record for it (outcome
        # fields filled in when record_outcome closes the pair)
        self._pairs: "collections.OrderedDict[str, dict]" = (  # guarded-by: _lock
            collections.OrderedDict())
        self._log_capacity = int(log_capacity)
        self.metrics.fit_samples.set(self.residual.n_samples)
        with _active_lock:
            _active.add(self)

    # ------------------------------------------------------------------
    # construction from the env tier
    # ------------------------------------------------------------------
    @classmethod
    def from_env(cls, registry=None) -> "ConfigOracle":
        """Peaks of ``jax.devices()[0]`` (``ZOO_ORACLE_PEAKS`` override
        last) + a residual model fitted from the accumulated
        report/tune-log history — analytic-only when nothing has
        accumulated yet."""
        oracle = cls(registry=registry)
        oracle.refit()
        return oracle

    def refit(self, rows: Iterable[Mapping] | None = None) -> int:
        """(Re)fit the residual from ``rows``, or from the env-dir
        history (``ZOO_HLO_REPORT_DIR`` joined with ``ZOO_TUNE_LOG_DIR``)
        when not given.  Returns the fitted sample count — 0 means the
        oracle stays analytic."""
        rows = list(rows) if rows is not None else training_rows()
        self.residual.fit(rows)
        self.metrics.fit_samples.set(self.residual.n_samples)
        return self.residual.n_samples

    # ------------------------------------------------------------------
    # prediction surface
    # ------------------------------------------------------------------
    def predict_steps_per_sec(self, features: Mapping, k: int = 1) -> float:
        """Fitted prediction when the residual is ready, pure analytic
        roofline otherwise — callers never branch on readiness."""
        return self.residual.predict_steps_per_sec(features, k=k)

    def predict_k(self, features: Mapping,
                  k_candidates: Sequence[int]) -> int:
        """The ``steps_per_dispatch`` the autotuner should START at:
        smallest candidate whose predicted steps/sec is within
        :data:`PREDICT_MARGIN` of the best (the autotuner's own settle
        tie-break).  Predictions for EVERY candidate are logged, so
        whatever K the ±1 validation pass settles on has a recorded
        prediction to score against."""
        preds = {int(k): self.predict_steps_per_sec(features, k=k)
                 for k in k_candidates}
        best = max(preds.values())
        k_hat = min(k for k, sps in preds.items()
                    if sps >= best * (1.0 - PREDICT_MARGIN))
        now = time.time()
        with self._lock:
            for k, sps in sorted(preds.items()):
                self._remember_locked({
                    "ts": now, "consumer": "autotune_k",
                    "config": f"k={k}", "predicted_steps_per_sec": sps,
                    "chosen": k == k_hat,
                    "measured_steps_per_sec": None, "rel_error": None})
        self.metrics.predictions.labels(consumer="autotune_k").inc()
        self.metrics.predicted_sps.labels(
            config=f"k={k_hat}").set(preds[k_hat])
        get_flight_recorder().record(
            "oracle", consumer="autotune_k", config=f"k={k_hat}",
            predicted_steps_per_sec=round(preds[k_hat], 3),
            fit_samples=self.residual.n_samples)
        return k_hat

    def choose_plan(self, param_bytes: int, opt_bytes: int,
                    n_shards: int, hbm_budget: int | None = None,
                    features: Mapping | None = None,
                    plans: Sequence[str] = DEFAULT_PLAN_CANDIDATES,
                    batch_bytes: int = 0,
                    activation_bytes: int = 0,
                    remat_options: Sequence[str | None] = (None,),
                    dtype_options: Sequence[str | None] = (None,),
                    kernel_options: Sequence[str | None] = (None,),
                    ) -> tuple[str, dict]:
        """The sharding plan ``plan="auto"`` resolves to: among the
        (plan × remat) candidates whose predicted per-chip bytes fit
        the HBM budget, the one whose predicted step time (roofline ×
        the remat recompute factor, plus the *exposed* slice of the
        plan's per-step collective traffic over the link ceiling —
        ``+overlap`` candidates hide the rest behind compute, serial
        plans expose all of it) is lowest — i.e. the
        least-sharded, least-rematted feasible config, since sharding
        only adds collectives and remat only adds FLOPs.  Ties keep
        candidate order.  Returns ``(plan_name, doc)``; the doc records
        every candidate's predicted bytes/traffic/feasibility plus
        ``chosen_remat`` (``None`` unless a remat policy was needed to
        fit).  ``remat_options`` defaults to no-remat-only, so existing
        callers sweep exactly the old space; ``fit(plan="auto")``
        passes ``(None, "full")`` and an activation estimate to sweep
        the full memory plan.  Infeasible-everywhere falls back to the
        most memory-frugal candidate (training may still OOM, but that
        config is the only one with a chance).

        ``dtype_options`` adds the PRECISION dimension (dtype-dependent
        ceilings, DTYPE_PEAK_FACTORS): a ``"bf16"`` candidate's compute
        term shrinks by the dtype's matmul-rate factor and its
        fsdp/zero3 gather traffic by the element-size ratio (the
        f32-accumulation contract keeps gradient collectives f32), so
        the oracle can trade precision for speed under an SLO or HBM
        budget.  Defaults to f32-only — existing callers sweep exactly
        the old space; the estimator passes ``(None, "bf16")`` when
        ``ZOO_DTYPE_POLICY=auto``.

        ``kernel_options`` adds the KERNEL dimension
        (:data:`KERNEL_STEP_FACTORS`): a ``"kernels"`` candidate's
        compute term scales by the fused-kernel factor ON TPU PEAKS
        ONLY — on any other platform the factor is 1.0 and the tie
        breaks toward the plain candidate (candidate order), so the
        CPU tier declines pallas by construction.  Defaults to
        no-kernels-only; the estimator passes ``(None, "kernels")``
        under ``ZOO_USE_PALLAS=1``."""
        budget = int(hbm_budget) if hbm_budget else int(self.peaks.hbm_bytes)
        feats = features or {}
        base_s = 1.0 / self.predict_steps_per_sec(feats, k=1)
        on_tpu = self.peaks.source.lower().startswith("tpu")
        candidates = []
        for dtype in dtype_options:
            dfact = DTYPE_PEAK_FACTORS[dtype if dtype else "f32"]
            for remat in remat_options:
                for plan in plans:
                    chip = predict_chip_bytes(
                        param_bytes, opt_bytes, plan, n_shards,
                        batch_bytes=batch_bytes,
                        activation_bytes=activation_bytes, remat=remat,
                        dtype=dtype)
                    coll = plan_collective_bytes(
                        param_bytes, plan, n_shards, dtype=dtype)
                    coll_s = coll / max(self.peaks.link_bytes_per_s, 1.0)
                    # Overlap-aware roofline: a "+overlap" candidate
                    # hides (1 - exposed) of its collective time behind
                    # compute, so only the exposed slice is additive.
                    # Serial plans have exposed == 1.0, which reduces to
                    # the old purely additive formula bit-for-bit — the
                    # default candidate sweep (and fit(plan="auto")
                    # agreement with it) is unchanged.
                    exposed = plan_exposed_fraction(plan)
                    for kern in kernel_options:
                        kfact = (KERNEL_STEP_FACTORS[kern]
                                 if on_tpu else 1.0)
                        compute_s = (base_s * REMAT_FLOPS_FACTORS[remat]
                                     / dfact["flops"] * kfact)
                        step_s = (max(compute_s,
                                      coll_s * (1.0 - exposed))
                                  + coll_s * exposed)
                        config = f"plan={plan}" if remat is None \
                            else f"plan={plan}+remat_{remat}"
                        if dtype:
                            config += f"+{dtype}"
                        if kern:
                            config += "+kernels"
                        candidates.append({
                            "plan": plan, "remat": remat,
                            "dtype": dtype, "kernels": kern,
                            "config": config,
                            "predicted_chip_bytes": chip,
                            "predicted_collective_bytes_per_step": coll,
                            "predicted_steps_per_sec":
                                round(1.0 / step_s, 3),
                            "fits_budget": chip <= budget})
        feasible = [c for c in candidates if c["fits_budget"]]
        pool = feasible or sorted(
            candidates, key=lambda c: c["predicted_chip_bytes"])[:1]
        chosen = max(pool, key=lambda c: c["predicted_steps_per_sec"])
        doc = {"chosen": chosen["plan"], "chosen_remat": chosen["remat"],
               "chosen_dtype": chosen["dtype"],
               "chosen_kernels": chosen["kernels"],
               "chosen_config": chosen["config"],
               "hbm_budget_bytes": budget,
               "n_shards": int(n_shards), "param_bytes": int(param_bytes),
               "opt_bytes": int(opt_bytes),
               "activation_bytes": int(activation_bytes),
               "candidates": candidates,
               "feasible": bool(feasible)}
        now = time.time()
        with self._lock:
            for c in candidates:
                self._remember_locked({
                    "ts": now, "consumer": "plan_auto",
                    "config": c["config"],
                    "predicted_steps_per_sec":
                        c["predicted_steps_per_sec"],
                    "chosen": c is chosen,
                    "measured_steps_per_sec": None, "rel_error": None})
        self.metrics.predictions.labels(consumer="plan_auto").inc()
        self.metrics.predicted_sps.labels(
            config=chosen["config"]).set(
                chosen["predicted_steps_per_sec"])
        get_flight_recorder().record(
            "oracle", consumer="plan_auto", config=chosen["config"],
            chip_bytes=chosen["predicted_chip_bytes"],
            hbm_budget=budget, feasible=bool(feasible))
        return chosen["plan"], doc

    def choose_kernels(self, kernel_sizes: Mapping[str, Mapping],
                       platform: str | None = None) -> dict:
        """Per-kernel kernel-vs-XLA verdicts for the kernel plane.

        ``kernel_sizes`` maps kernel name → the size kwargs its byte
        model needs (:func:`~analytics_zoo_tpu.analysis.costmodel
        .kernel_bytes`), e.g. ``{"fused_adam": {"n": 4096}}``.
        ``platform`` defaults to the peak table's source, so an oracle
        built from CPU peaks declines every kernel (Pallas lowers via
        Mosaic) and one built from TPU peaks picks by the analytic byte
        model.  Every verdict is a logged prediction under
        ``config="kernel=<name>"`` — the bench's measured per-variant
        steps/sec closes the pair via :meth:`record_outcome`."""
        platform = platform or self.peaks.source
        verdicts = {}
        now = time.time()
        for name, sizes in kernel_sizes.items():
            v = choose_kernel(name, platform=platform, peaks=self.peaks,
                              **sizes)
            verdicts[name] = v
            sps = 1.0 / max(v["predicted_s"][
                "kernel" if v["choice"] == name else "xla"], 1e-12)
            with self._lock:
                self._remember_locked({
                    "ts": now, "consumer": "kernel_plane",
                    "config": f"kernel={name}",
                    "predicted_steps_per_sec": round(sps, 3),
                    "chosen": v["choice"] == name,
                    "measured_steps_per_sec": None, "rel_error": None})
            self.metrics.predictions.labels(
                consumer="kernel_plane").inc()
            self.metrics.predicted_sps.labels(
                config=f"kernel={name}").set(round(sps, 3))
            get_flight_recorder().record(
                "oracle", consumer="kernel_plane",
                config=f"kernel={name}", choice=v["choice"],
                predicted_kernel_bytes=v["predicted_bytes"]["kernel"],
                predicted_xla_bytes=v["predicted_bytes"]["xla"])
        return verdicts

    def choose_serving(self, model_features, slo_p99_ms: float,
                       offered_rate: float, model: str = "default",
                       max_replicas: int = 8,
                       kernel_sizes: Mapping[str, Mapping] | None = None,
                       ) -> dict:
        """The serving config a model should be PRIMED with before its
        first request — the TpuGraphs cost-model plane applied to
        inference (ISSUE 20).

        ``model_features`` is the per-bucket feature source: either the
        row list :func:`~analytics_zoo_tpu.analysis.costmodel
        .load_serving_rows` returns (one ``inference_b<bucket>`` report
        row per pad bucket, produced by ``InferenceModel.warmup`` under
        ``ZOO_HLO_REPORT_DIR``) or a plain ``{bucket: features}``
        mapping.  Per bucket the serving roofline
        (:func:`predict_serving_seconds`, corrected by the fitted
        residual once it is ready) predicts one dispatch's wall
        seconds; from those predictions the oracle derives

        - **pad_buckets** — buckets whose predicted dispatch fits the
          service slice of the SLO (:data:`SERVING_SLO_FRACTION`); the
          smallest bucket always qualifies so the set is never empty;
        - **replicas** — ``ceil(offered_rate / capacity)`` where
          capacity is the best bucket's ``bucket/seconds`` derated by
          :data:`SERVING_UTILIZATION`, clamped to ``[1, max_replicas]``
          — the :class:`~analytics_zoo_tpu.serving.scaler.SloScaler`
          prior target, so the fleet starts AT the predicted size
          instead of discovering it through a violation;
        - **batch_budget_ms** — the ``ZOO_SERVING_BATCH_BUDGET_MS``
          slice left after the best bucket's service time, i.e. how
          long the batcher may wait filling a bucket without eating the
          tail headroom;
        - **quantize** — ``"int8"`` exactly when the predict program is
          memory-bound (weight-stationary int8 quarters HBM traffic —
          ``quantize_params_for_plan`` applies it plan-aware); a
          dispatch- or compute-bound program keeps f32;
        - **kernels** — per-kernel verdicts via :meth:`choose_kernels`
          when ``kernel_sizes`` is given (CPU peaks decline by
          construction).

        Every per-bucket prediction is a logged pair under
        ``config="serving:<model>:b<bucket>"`` (dispatches/sec); the
        bench's measured per-bucket latency closes them via
        :meth:`record_outcome`.  Returns the config doc the router
        primes a fleet from."""
        slo_s = float(slo_p99_ms) / 1e3
        if slo_s <= 0:
            raise ValueError(f"slo_p99_ms must be > 0, got {slo_p99_ms}")
        rows: dict[int, Mapping] = {}
        dtype_hists: dict[int, Mapping | None] = {}
        if isinstance(model_features, Mapping):
            for bucket, feats in model_features.items():
                rows[int(bucket)] = feats or {}
                dtype_hists[int(bucket)] = None
        else:
            for row in model_features or ():
                bucket = int(row.get("bucket") or 0)
                if bucket <= 0:
                    continue
                rows[bucket] = row.get("features") or {}
                dtype_hists[bucket] = row.get("dtype_histogram")
        predicted: dict[str, dict] = {}
        feasible: list[int] = []
        for bucket in sorted(rows):
            feats = rows[bucket]
            pred_s = predict_serving_seconds(
                feats, batch=bucket, peaks=self.peaks,
                dtype_histogram=dtype_hists.get(bucket))
            if self.residual.ready:
                # the residual is fitted on step seconds from the SAME
                # feature vector; apply its correction as a ratio so
                # the serving-specific terms (per-call overhead, batch
                # scaling) survive
                analytic_s = predict_step_seconds(
                    feats, k=1, peaks=self.peaks)
                fitted_s = 1.0 / max(
                    self.residual.predict_steps_per_sec(feats, k=1),
                    1e-12)
                pred_s *= fitted_s / max(analytic_s, 1e-12)
            fits = pred_s <= slo_s * SERVING_SLO_FRACTION
            if fits:
                feasible.append(bucket)
            predicted[str(bucket)] = {
                "bucket": bucket,
                "predict_seconds": pred_s,
                "capacity_rps":
                    bucket / max(pred_s, 1e-12) * SERVING_UTILIZATION,
                "feasible": fits,
            }
        if not feasible and rows:
            # nothing fits the service slice: serve at the smallest
            # bucket anyway (the only config with a chance), mirroring
            # choose_plan's infeasible-everywhere fallback
            feasible = [min(rows)]
        best = max(feasible) if feasible else 0
        if best:
            best_doc = predicted[str(best)]
            replicas = max(1, min(int(max_replicas), math.ceil(
                max(float(offered_rate), 0.0)
                / max(best_doc["capacity_rps"], 1e-12))))
            budget_ms = min(
                max((slo_s * SERVING_SLO_FRACTION
                     - best_doc["predict_seconds"]) * 1e3, 1.0),
                float(slo_p99_ms) * SERVING_SLO_FRACTION)
            f = normalize_features(rows[best])
            mem_s = f["bytes_accessed"] / max(
                self.peaks.hbm_bytes_per_s, 1.0)
            comp_s = f["matmul_flops"] / max(self.peaks.flops, 1.0)
            quantize = "int8" if mem_s > comp_s else None
        else:
            # zero feature rows (no warmup has run): conservative prior
            replicas, budget_ms, quantize = 1, slo_p99_ms / 4.0, None
        kernels = (self.choose_kernels(kernel_sizes)
                   if kernel_sizes else {})
        config = f"serving:{model}"
        doc = {
            "model": str(model), "config": config,
            "replicas": int(replicas),
            "pad_buckets": sorted(feasible),
            "batch_budget_ms": round(float(budget_ms), 3),
            "quantize": quantize, "kernels": kernels,
            "predicted": predicted,
            "slo_p99_ms": float(slo_p99_ms),
            "offered_rate": float(offered_rate),
            "fit_samples": self.residual.n_samples,
        }
        now = time.time()
        with self._lock:
            for key, p in sorted(predicted.items(),
                                 key=lambda kv: kv[1]["bucket"]):
                self._remember_locked({
                    "ts": now, "consumer": "serving",
                    "config": f"{config}:b{p['bucket']}",
                    "predicted_steps_per_sec":
                        round(1.0 / max(p["predict_seconds"], 1e-12), 3),
                    "chosen": p["bucket"] == best,
                    "measured_steps_per_sec": None, "rel_error": None})
        self.metrics.predictions.labels(consumer="serving").inc()
        for p in predicted.values():
            self.metrics.serving_predicted_seconds.labels(
                model=str(model), bucket=str(p["bucket"])).set(
                    p["predict_seconds"])
        self.metrics.serving_predicted_replicas.labels(
            model=str(model)).set(replicas)
        self.metrics.serving_predicted_budget_ms.labels(
            model=str(model)).set(doc["batch_budget_ms"])
        if best:
            self.metrics.predicted_sps.labels(config=config).set(
                round(1.0 / max(
                    predicted[str(best)]["predict_seconds"], 1e-12), 3))
        get_flight_recorder().record(
            "oracle", consumer="serving", config=config,
            replicas=int(replicas), pad_buckets=sorted(feasible),
            batch_budget_ms=doc["batch_budget_ms"],
            quantize=quantize,
            slo_p99_ms=float(slo_p99_ms),
            offered_rate=float(offered_rate),
            fit_samples=self.residual.n_samples)
        return doc

    def repick(self, param_bytes: int, opt_bytes: int, n_shards: int,
               k_candidates: Sequence[int] = (1, 2, 4, 8),
               features: Mapping | None = None,
               hbm_budget: int | None = None,
               batch_bytes: int = 0, activation_bytes: int = 0,
               remat_options: Sequence[str | None] = (None, "full"),
               dtype_options: Sequence[str | None] = (None,),
               ) -> dict:
        """ONE full (plan, K, remat) re-pick for a NEW topology — the
        elastic supervisor's generation-change hook (ISSUE 16).

        A generation change (worker died / rejoined) changes
        ``n_shards``; instead of re-tuning blind, the supervisor asks
        for exactly one :meth:`choose_plan` sweep (plan x remat against
        the HBM budget at the new shard count) plus one
        :meth:`predict_k` (the fused-dispatch prior), so every rejoin
        decision is a logged prediction the round's measured steps/sec
        later scores via :meth:`record_outcome`.  Returns ``{"plan",
        "k", "remat", "config", "doc"}``; ``config`` is the key to
        report the outcome against."""
        feats = features or {}
        plan, doc = self.choose_plan(
            param_bytes, opt_bytes, n_shards, hbm_budget=hbm_budget,
            features=feats, batch_bytes=batch_bytes,
            activation_bytes=activation_bytes,
            remat_options=remat_options, dtype_options=dtype_options)
        k = self.predict_k(feats, k_candidates)
        return {"plan": plan, "k": int(k), "remat": doc["chosen_remat"],
                "dtype": doc["chosen_dtype"],
                "config": doc["chosen_config"], "doc": doc}

    # ------------------------------------------------------------------
    # the outcome half of the data loop
    # ------------------------------------------------------------------
    def record_outcome(self, config: str, measured_steps_per_sec: float,
                       consumer: str = "") -> dict | None:
        """Close a prediction→outcome pair: the consumer reports what
        the config actually measured (the autotuner at K settle, the
        bench per plan leg).  Returns the closed pair (or None when no
        prediction was recorded for ``config`` — outcome still logged,
        error unknowable)."""
        measured = float(measured_steps_per_sec)
        with self._lock:
            pair = self._pairs.get(config)
            if pair is not None:
                pair["measured_steps_per_sec"] = measured
                predicted = pair["predicted_steps_per_sec"]
                pair["rel_error"] = round(
                    abs(predicted - measured) / max(measured, 1e-12), 4)
                pair = dict(pair)
        self.metrics.measured_sps.labels(config=config).set(measured)
        if pair is not None:
            self.metrics.rel_error.labels(config=config).set(
                pair["rel_error"])
        get_flight_recorder().record(
            "oracle", consumer=consumer or "outcome", config=config,
            measured_steps_per_sec=round(measured, 3),
            rel_error=pair["rel_error"] if pair else None)
        return pair

    def _remember_locked(self, record: dict) -> None:
        """Insert/refresh one prediction record under the bounded
        per-config table; called with the lock held."""
        # zoolint: disable=guarded-by -- _locked suffix: callers hold _lock across this call
        self._pairs[record["config"]] = record
        self._pairs.move_to_end(record["config"])
        while len(self._pairs) > self._log_capacity:
            # zoolint: disable=guarded-by -- _locked suffix: callers hold _lock across this call
            self._pairs.popitem(last=False)

    # ------------------------------------------------------------------
    # introspection (/varz, metrics_dump, benches)
    # ------------------------------------------------------------------
    def prediction_log(self) -> list[dict]:
        with self._lock:
            return [dict(p) for p in self._pairs.values()]

    def to_doc(self) -> dict:
        return {
            "peaks": self.peaks.to_doc(),
            "fit_samples": self.residual.n_samples,
            "residual_ready": self.residual.ready,
            "predictions": self.prediction_log(),
        }
