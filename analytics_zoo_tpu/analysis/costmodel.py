"""Analytic + fitted cost model for the predictive compile plane.

The compile choke point already extracts a static feature vector per
compiled program (``analysis/hlo.py``: matmul FLOPs, bytes touched,
collective bytes, fused-dispatch count) — TpuGraphs (arXiv:2308.13490)
shows exactly these features rank configs well, and tf.data
(arXiv:2101.12127) shows an analytic prior refined online beats blind
search.  This module is both halves:

- :func:`predict_step_seconds` — a **roofline** over the feature
  vector: per-step time = max(flops/peak_flops, bytes/peak_bw) +
  collective_bytes/link_bw + dispatch_overhead/K.  The K term is the
  fused-dispatch amortization the autotuner otherwise discovers by
  measurement (about 53 dispatches on a one-core CPU host in PR 8; no
  chip number); the ceilings come
  from a small per-platform :class:`PeakTable` with a CPU-calibrated
  default, any field overridable via ``ZOO_ORACLE_PEAKS`` (a JSON
  object, e.g. ``{"dispatch_overhead_s": 4e-4}``).
- :func:`predict_chip_bytes` / :func:`plan_collective_bytes` — per-chip
  memory and per-step interconnect traffic per sharding plan
  (dp/zero1/fsdp/tp memory factors; ring-collective byte counts), the
  inputs of ``plan="auto"``.
- :class:`ResidualModel` — a least-squares fit IN LOG SPACE of
  measured/predicted against the log-features (stdlib only — the
  normal equations are solved by Gaussian elimination, no
  sklearn/numpy.linalg).  Trained from accumulated
  ``ZOO_HLO_REPORT_DIR`` reports (:func:`load_report_rows`, schema v1
  accepted with nulls) joined with the autotuner's persisted decision
  history (:func:`load_tune_log_rows`, ``ZOO_TUNE_LOG_DIR``).  Below
  :data:`MIN_FIT_SAMPLES` joined samples the model reports
  ``ready == False`` and callers fall back to the analytic prediction
  alone — the zero-data path is first-class, not an error.

Consumed by :mod:`analytics_zoo_tpu.analysis.oracle` (the
``ConfigOracle`` that primes the autotuner and resolves
``plan="auto"``); documented in docs/performance.md ("Predictive
compile plane").
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Iterable, Mapping, Sequence

__all__ = [
    "PeakTable", "resolve_peaks", "PLATFORM_PEAKS", "MIN_FIT_SAMPLES",
    "normalize_features", "predict_step_seconds", "predict_steps_per_sec",
    "plan_exposed_fraction", "EXPOSED_FRACTIONS",
    "predict_chip_bytes", "plan_collective_bytes", "PLAN_MEMORY_FACTORS",
    "REMAT_ACTIVATION_FACTORS", "REMAT_FLOPS_FACTORS",
    "DTYPE_PEAK_FACTORS", "plan_dtype", "dtype_peaks",
    "histogram_compute_dtype",
    "KERNEL_BYTE_MODELS", "kernel_bytes", "choose_kernel",
    "ResidualModel", "load_report_rows",
    "load_tune_log_rows", "training_rows",
    "predict_serving_seconds", "serving_bucket_label",
    "load_serving_rows", "SERVING_LABEL_PREFIX",
]

#: below this many joined (features, K, measured steps/sec) samples the
#: residual model refuses to fit and the analytic roofline stands alone
MIN_FIT_SAMPLES = 8


@dataclasses.dataclass(frozen=True)
class PeakTable:
    """Hardware ceilings the roofline divides by.

    ``flops``/``hbm_bytes_per_s``/``link_bytes_per_s`` are per-chip
    peaks; ``dispatch_overhead_s`` is the fixed host cost of one jitted
    dispatch (the quantity ``steps_per_dispatch`` K amortizes);
    ``hbm_bytes`` is the per-chip memory budget ``plan="auto"`` fits
    against.  ``source`` names the table entry (or "env" after a
    ``ZOO_ORACLE_PEAKS`` override) so artifacts record which
    calibration produced a prediction.
    """

    flops: float
    hbm_bytes_per_s: float
    link_bytes_per_s: float
    dispatch_overhead_s: float
    hbm_bytes: float
    source: str = "cpu-default"

    def to_doc(self) -> dict:
        return dataclasses.asdict(self)


#: Per-platform ceilings.  The CPU row is CALIBRATED, not theoretical:
#: dispatch_overhead_s comes from a per-step cost curve measured on a
#: one-core CPU host in PR 8 (cost(K) = compute + overhead/K over
#: K∈{1..16} gave overhead ≈ 5e-4 s there; no chip number), and the
#: flops/bandwidth rows are order-of-magnitude host numbers — for the
#: dispatch-bound programs the CPU backend exists to exercise, the
#: overhead term dominates and ranking is insensitive to them.  TPU
#: rows use published per-chip peaks (the benchmark keeps its own table,
#: ``benchmark/peaks.json``).
PLATFORM_PEAKS: dict[str, PeakTable] = {
    "cpu": PeakTable(
        flops=5.0e10, hbm_bytes_per_s=2.0e10, link_bytes_per_s=1.0e10,
        dispatch_overhead_s=5.0e-4, hbm_bytes=float(4 << 30),
        source="cpu-default"),
    "tpu-v4": PeakTable(
        flops=2.75e14, hbm_bytes_per_s=1.2e12, link_bytes_per_s=2.4e11,
        dispatch_overhead_s=1.0e-4, hbm_bytes=float(32 << 30),
        source="tpu-v4"),
    "tpu-v5e": PeakTable(
        flops=1.97e14, hbm_bytes_per_s=8.1e11, link_bytes_per_s=1.6e11,
        dispatch_overhead_s=1.0e-4, hbm_bytes=float(16 << 30),
        source="tpu-v5e"),
    "tpu-v3": PeakTable(
        flops=1.23e14, hbm_bytes_per_s=9.0e11, link_bytes_per_s=1.4e11,
        dispatch_overhead_s=1.0e-4, hbm_bytes=float(16 << 30),
        source="tpu-v3"),
    "tpu-v2": PeakTable(
        flops=4.5e13, hbm_bytes_per_s=7.0e11, link_bytes_per_s=1.0e11,
        dispatch_overhead_s=1.0e-4, hbm_bytes=float(8 << 30),
        source="tpu-v2"),
}


#: What a chip reports as ``device_kind`` where that is not its row's
#: name: one v5e chip says "TPU v5 lite".
_DEVICE_KIND_ROWS = {"tpu-v5-lite": "tpu-v5e"}


def resolve_peaks(platform: str | None = None,
                  device_kind: str | None = None) -> PeakTable:
    """The ceilings for this process: the table row of the device kind
    (or, without one, of the platform string) — "TPU v4" maps to the
    v4 row, "TPU v5 lite" to the v5e row.  Called with neither, it asks
    ``jax.devices()[0]``.  A TPU that has no row raises: a prediction
    against another chip's ceilings is worse than none.  Anything that
    is not a TPU gets the CPU-calibrated row.  ``ZOO_ORACLE_PEAKS``
    (JSON object) overrides individual fields last; unknown keys in the
    override are rejected loudly — a typo'd ceiling must not silently
    leave the default in place."""
    if platform is None and device_kind is None:
        import jax

        dev = jax.devices()[0]
        platform, device_kind = dev.platform, dev.device_kind
    kind = (device_kind or platform).lower().replace(" ", "-")
    kind = _DEVICE_KIND_ROWS.get(kind, kind)
    table = PLATFORM_PEAKS["cpu"]
    if kind.startswith("tpu"):
        rows = [peaks for key, peaks in PLATFORM_PEAKS.items()
                if key != "cpu" and key in kind]
        if not rows:
            raise ValueError(
                f"no peak table row for TPU device kind "
                f"{device_kind or platform!r}; known rows: "
                f"{sorted(k for k in PLATFORM_PEAKS if k != 'cpu')}")
        table = rows[0]
    raw = os.environ.get("ZOO_ORACLE_PEAKS")
    if not raw:
        return table
    try:
        override = json.loads(raw)
    except json.JSONDecodeError as e:
        raise ValueError(
            f"ZOO_ORACLE_PEAKS must be a JSON object of PeakTable "
            f"fields: {e}") from e
    if not isinstance(override, dict):
        raise ValueError(
            f"ZOO_ORACLE_PEAKS must be a JSON object, got "
            f"{type(override).__name__}")
    fields = {f.name for f in dataclasses.fields(PeakTable)}
    unknown = set(override) - fields
    if unknown:
        raise ValueError(
            f"ZOO_ORACLE_PEAKS: unknown field(s) {sorted(unknown)}; "
            f"valid: {sorted(fields - {'source'})}")
    merged = {**table.to_doc(), **{
        k: (float(v) if k != "source" else str(v))
        for k, v in override.items()}}
    merged["source"] = str(override.get("source", "env"))
    return PeakTable(**merged)


# ---------------------------------------------------------------------------
# Roofline prediction.
# ---------------------------------------------------------------------------

_FEATURE_ALIASES = {
    "matmul_flops": ("matmul_flops", "flops", "zoo_hlo_flops"),
    "bytes_accessed": ("bytes_accessed", "zoo_hlo_bytes_accessed"),
    "collective_bytes": ("collective_bytes", "zoo_hlo_collective_bytes"),
    "collective_count": ("collective_count", "zoo_hlo_collectives"),
    "fused_dispatch_count": ("fused_dispatch_count",
                             "zoo_hlo_fused_dispatches"),
    "op_count": ("op_count", "zoo_hlo_ops"),
    "async_collective_count": ("async_collective_count",
                               "zoo_hlo_async_collectives"),
    "overlapped_collective_bytes": ("overlapped_collective_bytes",
                                    "zoo_hlo_overlapped_collective_bytes"),
}


def normalize_features(features: Mapping) -> dict:
    """Canonical feature dict from any of the shapes the repo emits:
    :meth:`HloReport.features`, a ``zoo_hlo_*``-prefixed metrics
    scrape, or a BENCH_*.json ``hlo`` block.  Missing keys become 0 —
    a v1 report with nulls still yields a usable vector."""
    out = {}
    for canon, names in _FEATURE_ALIASES.items():
        val = 0
        for name in names:
            got = features.get(name)
            if got is not None:
                val = got
                break
        out[canon] = float(val)
    return out


#: fraction of a plan's collective seconds that stays EXPOSED (serial
#: with compute) per overlap mode.  Serial plans expose everything —
#: the pre-overlap additive roofline exactly.  Bucketed "+overlap"
#: plans hide all but the tail: the last gradient bucket's
#: reduce-scatter has no backward segment left to hide behind, and the
#: first prefetch gather precedes any compute.  The 0.25 was set against
#: serial and bucketed legs timed on a one-core CPU host in PR 14; no
#: chip number.
EXPOSED_FRACTIONS = {"serial": 1.0, "overlap": 0.25}


#: Per-dtype ceiling factors relative to the f32 row of a
#: :class:`PeakTable` — the precision plane's roofline terms:
#: ``flops`` multiplies the matmul ceiling (TPU MXUs run bf16 at ~2× the
#: f32 rate and int8 at ~2× bf16; the CPU backend shows no such win, but
#: the RANKING the oracle needs is the TPU one — the CPU-tier benches
#: assert bytes/feature deltas, not throughput), ``bytes`` is the
#: element-size ratio (what a compute-copy collective or activation
#: weighs against its f32 twin).
DTYPE_PEAK_FACTORS = {
    None: {"flops": 1.0, "bytes": 1.0},
    "f32": {"flops": 1.0, "bytes": 1.0},
    "bf16": {"flops": 2.0, "bytes": 0.5},
    "f16": {"flops": 2.0, "bytes": 0.5},
    "int8": {"flops": 4.0, "bytes": 0.25},
}


def _dtype_factors(dtype: str | None) -> dict:
    try:
        return DTYPE_PEAK_FACTORS[dtype]
    except KeyError:
        raise ValueError(
            f"unknown compute dtype {dtype!r}; valid: "
            f"{', '.join(str(k) for k in DTYPE_PEAK_FACTORS)}") from None


def plan_dtype(plan: str | None) -> str | None:
    """Compute-dtype segment of a plan/config name (``"fsdp+bf16"`` →
    ``"bf16"``; :func:`~analytics_zoo_tpu.parallel.plan.with_dtype`
    naming), ``None`` when the name declares no precision variant."""
    if plan is None:
        return None
    for seg in str(plan).split("+")[1:]:
        if seg in ("bf16", "f16", "int8"):
            return seg
    return None


def dtype_peaks(peaks: PeakTable, dtype: str | None) -> PeakTable:
    """A :class:`PeakTable` with the matmul ceiling scaled for a compute
    dtype (:data:`DTYPE_PEAK_FACTORS` — bf16 doubles the f32 rate, int8
    doubles it again); ``None``/``"f32"`` return ``peaks`` unchanged."""
    f = _dtype_factors(dtype)["flops"]
    if f == 1.0:
        return peaks
    return dataclasses.replace(peaks, flops=peaks.flops * f,
                               source=f"{peaks.source}+{dtype}")


def histogram_compute_dtype(dtype_histogram: Mapping | None) -> str | None:
    """Dominant floating compute dtype of a zoo-hlo-report/2
    ``dtype_histogram`` — the MEASURED confirmation that a dtype policy
    actually lowered (a bf16_mixed program's histogram shifts from f32-
    to bf16-majority), and the dtype the roofline ceilings should use
    when predicting from that program's features."""
    if not dtype_histogram:
        return None
    floats = {k: int(v) for k, v in dtype_histogram.items()
              if k in ("f32", "bf16", "f16") and v}
    if not floats:
        return None
    return max(floats, key=lambda k: (floats[k], k))


def plan_exposed_fraction(plan: str | None) -> float:
    """Exposed-collective fraction for a plan NAME: ``+overlap`` plans
    (bucketed grad scatter / gather prefetch) hide all but the tail
    bucket; every other plan serializes its collectives after the
    backward (fraction 1.0 — the old additive model)."""
    if plan is None:
        return EXPOSED_FRACTIONS["serial"]
    # segment match, not suffix: with_remat() composes names like
    # "fsdp+overlap+remat_full"
    return (EXPOSED_FRACTIONS["overlap"]
            if "overlap" in str(plan).split("+")
            else EXPOSED_FRACTIONS["serial"])


def predict_step_seconds(features: Mapping, k: int = 1,
                         peaks: PeakTable | None = None,
                         plan: str | None = None,
                         exposed_fraction: float | None = None,
                         dtype: str | None = None,
                         dtype_histogram: Mapping | None = None) -> float:
    """Overlap-aware roofline per-STEP wall seconds at
    ``steps_per_dispatch=k``:
    ``max(compute, memory, overlappable_collectives)
    + exposed_collectives + dispatch_overhead/k``.

    The max() is the classic roofline extended with the collective
    seconds a latency-hiding schedule can run CONCURRENTLY with
    compute; only the exposed remainder serializes after it.  The
    exposed fraction comes from (highest priority first) the
    ``exposed_fraction`` argument, the ``overlapped_collective_bytes``
    feature when the HLO actually contains async start/done pairs, or
    the plan name (:func:`plan_exposed_fraction` — serial plans expose
    1.0, which reproduces the pre-overlap additive model EXACTLY).  The
    overhead term is what K amortizes.

    The matmul ceiling is DTYPE-DEPENDENT (:func:`dtype_peaks`): the
    compute dtype comes from the ``dtype`` argument, else the program's
    measured ``dtype_histogram`` (zoo-hlo-report/2,
    :func:`histogram_compute_dtype`), else the plan name's precision
    segment (``"fsdp+bf16"``).  The byte features are NOT rescaled —
    they were extracted from the lowered program, which already counts
    its tensors at their true widths."""
    peaks = peaks if peaks is not None else resolve_peaks()
    if dtype is None:
        dtype = histogram_compute_dtype(dtype_histogram) \
            or plan_dtype(plan)
    peaks = dtype_peaks(peaks, dtype)
    f = normalize_features(features)
    compute_s = f["matmul_flops"] / max(peaks.flops, 1.0)
    memory_s = f["bytes_accessed"] / max(peaks.hbm_bytes_per_s, 1.0)
    collective_s = f["collective_bytes"] / max(peaks.link_bytes_per_s, 1.0)
    if exposed_fraction is None:
        overlapped = f["overlapped_collective_bytes"]
        if overlapped > 0 and f["collective_bytes"] > 0:
            exposed_fraction = 1.0 - overlapped / f["collective_bytes"]
        else:
            exposed_fraction = plan_exposed_fraction(plan)
    exposed_fraction = min(max(float(exposed_fraction), 0.0), 1.0)
    overlappable_s = collective_s * (1.0 - exposed_fraction)
    exposed_s = collective_s * exposed_fraction
    overhead_s = peaks.dispatch_overhead_s / max(int(k), 1)
    return max(compute_s, memory_s, overlappable_s) + exposed_s \
        + overhead_s


def predict_steps_per_sec(features: Mapping, k: int = 1,
                          peaks: PeakTable | None = None,
                          plan: str | None = None,
                          exposed_fraction: float | None = None,
                          dtype: str | None = None,
                          dtype_histogram: Mapping | None = None) -> float:
    """Inverse of :func:`predict_step_seconds`."""
    return 1.0 / max(
        predict_step_seconds(features, k=k, peaks=peaks, plan=plan,
                             exposed_fraction=exposed_fraction,
                             dtype=dtype,
                             dtype_histogram=dtype_histogram), 1e-12)


# ---------------------------------------------------------------------------
# Serving (predict-step) roofline — ISSUE 20, the TpuGraphs framing
# applied to inference: the per-bucket predict programs the
# InferenceModel compiles through timed_compile carry the same
# zoo_hlo_* feature vector as train steps, so the same roofline
# predicts their wall seconds BEFORE the first request.
# ---------------------------------------------------------------------------

#: compile-label prefix of the bucketed predict programs
#: (pipeline/inference/inference_model.py ``_get_compiled``)
SERVING_LABEL_PREFIX = "inference_b"


def serving_bucket_label(bucket: int) -> str:
    """The compile label ``InferenceModel`` stamps on the pad-bucket's
    predict program — the join key between a bucket's hlo report row
    and its measured predict seconds."""
    return f"{SERVING_LABEL_PREFIX}{int(bucket)}"


def predict_serving_seconds(features: Mapping, batch: int = 1,
                            peaks: PeakTable | None = None,
                            dtype: str | None = None,
                            dtype_histogram: Mapping | None = None,
                            ) -> float:
    """Roofline wall seconds for ONE dispatch of a bucketed predict
    program.

    ``features`` is the zoo_hlo_* vector of the PAD-BUCKET program
    (already sized for the padded batch); ``batch`` only matters when
    the features were extracted at a different bucket size — the
    compute/memory byte terms scale linearly with the batch dimension
    (activations dominate a forward pass), while the dispatch overhead
    is per-call and does not.  Serving dispatches are k=1 by
    construction (each request batch is one executable call — there is
    no multi-step fusion to amortize the overhead across), which is why
    the overhead term matters MORE here than in training: at small
    buckets it is the floor the pad-bucket set must respect."""
    peaks = peaks if peaks is not None else resolve_peaks()
    if dtype is None:
        dtype = histogram_compute_dtype(dtype_histogram)
    peaks = dtype_peaks(peaks, dtype)
    f = normalize_features(features)
    scale = max(float(batch), 1.0) / max(
        float(f.get("feature_batch") or batch or 1), 1.0)
    compute_s = scale * f["matmul_flops"] / max(peaks.flops, 1.0)
    memory_s = scale * f["bytes_accessed"] \
        / max(peaks.hbm_bytes_per_s, 1.0)
    collective_s = f["collective_bytes"] \
        / max(peaks.link_bytes_per_s, 1.0)
    return max(compute_s, memory_s) + collective_s \
        + peaks.dispatch_overhead_s


def load_serving_rows(report_dir: str) -> list[dict]:
    """The predict-labelled slice of :func:`load_report_rows`, keyed by
    pad bucket: one row per ``inference_b<bucket>`` report (latest file
    per label wins), with ``bucket`` parsed from the label or the
    stamped meta.  The serving oracle's feature source — empty until an
    :class:`InferenceModel` has compiled (or warmed) its buckets under
    ``ZOO_HLO_REPORT_DIR``."""
    by_label: dict[str, dict] = {}
    for row in load_report_rows(report_dir):
        label = str(row.get("label") or "")
        if not label.startswith(SERVING_LABEL_PREFIX):
            continue
        bucket = row.get("bucket")
        if bucket is None:
            suffix = label[len(SERVING_LABEL_PREFIX):]
            if not suffix.isdigit():
                continue
            bucket = int(suffix)
        row = dict(row)
        row["bucket"] = int(bucket)
        by_label[label] = row  # sorted read order: later files win
    return sorted(by_label.values(), key=lambda r: r["bucket"])


# ---------------------------------------------------------------------------
# Per-plan memory + interconnect models (the plan="auto" inputs).
# ---------------------------------------------------------------------------

#: (param_factor, opt_factor) of per-chip resident bytes as a fraction
#: of the global tree, for an n-way shard: dp replicates both, zero1
#: shards optimizer state only, zero2 adds the gradient reduce-scatter
#: (grads are transient in JAX, so PERSISTENT state matches zero1),
#: zero3/fsdp shard both, pipeline splits the stage-stacked tree over
#: the pipe axis, tp shards params + opt over the model axis
#: (rule-table dependent; 1/n is the intended steady state).  Matches
#: the bytes of the placed arrays on 8 devices (fsdp and zero3 ≈ 0.125x:
#: ``tests/test_memory_plan.py``, ``tests/test_oracle.py``).
PLAN_MEMORY_FACTORS = {
    "dp": (1.0, 1.0),
    "zero1": (1.0, None),   # None -> 1/n
    "zero2": (1.0, None),
    "fsdp": (None, None),
    "zero3": (None, None),
    "pipeline": (None, None),
    "tp": (None, None),
}

#: fraction of the ACTIVATION estimate still resident under a remat
#: policy: full recomputes everything (only layer boundaries survive),
#: dots keeps contraction outputs, attn keeps only the tagged
#: attention context.
REMAT_ACTIVATION_FACTORS = {
    None: 1.0,
    "full": 0.15,
    "dots": 0.5,
    "attn": 0.35,
}

#: compute-time multiplier a remat policy costs (the recompute half of
#: the memory/FLOPs tradeoff): full remat replays the forward inside
#: the backward (~4/3 of baseline training FLOPs), partial policies
#: replay proportionally less.
REMAT_FLOPS_FACTORS = {
    None: 1.0,
    "full": 4.0 / 3.0,
    "dots": 1.15,
    "attn": 1.25,
}


def _plan_key(plan: str) -> str:
    """Normalize a plan name for table lookup: a ``+remat_*`` /
    ``+overlap`` suffix (``with_remat`` / ``overlap=`` naming) strips
    off, and every ``pipeline_<schedule>`` plan shares the ``pipeline``
    row."""
    base = str(plan).split("+", 1)[0]
    return "pipeline" if base.startswith("pipeline") else base


def predict_chip_bytes(param_bytes: int, opt_bytes: int, plan: str,
                       n_shards: int, batch_bytes: int = 0,
                       activation_bytes: int = 0,
                       remat: str | None = None,
                       dtype: str | None = None) -> int:
    """Predicted per-chip resident bytes under ``plan`` on an
    ``n_shards``-way mesh axis: the persistent param+opt footprint the
    sharding plan controls, plus the per-chip batch slice and — when an
    ``activation_bytes`` estimate is given — the activation residue the
    ``remat`` policy leaves live (:data:`REMAT_ACTIVATION_FACTORS`).

    ``dtype`` (or the plan name's precision segment) scales the
    ACTIVATION term only: under the precision plane's accumulation
    contract the stored params and optimizer state are f32 masters
    whatever the compute dtype, so their footprint is dtype-independent
    — the activations (and the transient compute copies they imply) are
    what bf16 halves."""
    if dtype is None:
        dtype = plan_dtype(plan)
    try:
        pf, of = PLAN_MEMORY_FACTORS[_plan_key(plan)]
    except KeyError:
        raise ValueError(
            f"unknown plan {plan!r}; valid: "
            f"{', '.join(sorted(PLAN_MEMORY_FACTORS))}") from None
    try:
        af = REMAT_ACTIVATION_FACTORS[remat]
    except KeyError:
        raise ValueError(
            f"unknown remat policy {remat!r}; valid: "
            f"{', '.join(str(k) for k in REMAT_ACTIVATION_FACTORS)}"
        ) from None
    n = max(int(n_shards), 1)
    pf = pf if pf is not None else 1.0 / n
    of = of if of is not None else 1.0 / n
    af *= _dtype_factors(dtype)["bytes"]
    return int(param_bytes * pf + opt_bytes * of
               + batch_bytes / n + activation_bytes * af)


#: the portion of a plan's collective coefficient that moves COMPUTE
#: copies (param all-gathers, forward+backward) rather than gradients —
#: under the f32-accumulation contract only this portion shrinks with
#: the compute dtype; gradient reduce-scatters / all-reduces stay f32.
_GATHER_COEFF = {"fsdp": 2.0, "zero3": 2.0}


def plan_collective_bytes(param_bytes: int, plan: str,
                          n_shards: int,
                          dtype: str | None = None) -> int:
    """Per-STEP interconnect bytes a plan moves for ``param_bytes`` of
    weights on an ``n_shards``-way axis (ring-collective accounting,
    2·P·(n-1)/n per all-reduce equivalent):

    - dp: one gradient all-reduce (2P);
    - zero1: reduce-scatter grads into the moment shards + all-gather
      the updates back (2P, plus the sharded update's gather skew —
      charged 2.5P so dp ranks strictly first at equal memory);
    - zero2: zero1's traffic plus the pinned gradient scatter's
      re-layout (2.6P, so zero1 ranks first at equal memory);
    - fsdp: all-gather params on use (forward AND backward) +
      reduce-scatter grads (3P);
    - zero3: fsdp's traffic with the explicit gradient-shard pin
      (3.1P, so fsdp ranks first at equal memory);
    - pipeline: stage-boundary ppermute traffic, activation-sized and
      model dependent — charged like dp's 2P as a neutral default;
    - tp: activation collectives, model/rule dependent — charged like
      dp's 2P as a neutral default.

    These coefficients exist to RANK plans (fewest collectives first at
    equal feasibility), not to predict absolute seconds; the residual
    model absorbs the constants once outcomes accumulate.

    ``dtype`` (or the plan name's precision segment) applies the
    accumulation contract: the param-GATHER portion of fsdp/zero3
    traffic (:data:`_GATHER_COEFF` — the all-gathers move compute
    copies) scales by the dtype's element-size ratio, while the
    gradient reduce-scatter / all-reduce portion stays f32 — so
    ``fsdp+bf16`` predicts 2/3 of fsdp's bytes, the measurable
    collective-bytes reduction the precision bench pins."""
    if dtype is None:
        dtype = plan_dtype(plan)
    n = max(int(n_shards), 1)
    if n <= 1:
        return 0
    ring = param_bytes * (n - 1) / n
    coeff = {"dp": 2.0, "zero1": 2.5, "zero2": 2.6, "fsdp": 3.0,
             "zero3": 3.1, "pipeline": 2.0, "tp": 2.0}
    key = _plan_key(plan)
    try:
        total = coeff[key]
    except KeyError:
        raise ValueError(
            f"unknown plan {plan!r}; valid: "
            f"{', '.join(sorted(coeff))}") from None
    gather = _GATHER_COEFF.get(key, 0.0)
    bytes_factor = _dtype_factors(dtype)["bytes"]
    return int((total - gather + gather * bytes_factor) * ring)


# ---------------------------------------------------------------------------
# Kernel plane: per-kernel analytic HBM byte terms.  Pallas kernels win
# by collapsing round trips, so the quantity that ranks kernel vs XLA is
# bytes touched, not FLOPs — and it is exactly the quantity the choke
# point MEASURES after lowering (HloReport.custom_kernel_bytes sums
# custom-call operand+result bytes).  Each "kernel" term below is that
# operand+result sum, which is why the bench can assert
# |measured - predicted| / predicted <= 0.05 rather than hand-waving.
# ---------------------------------------------------------------------------

#: Word counts behind the formulas (f32 = 4 bytes unless noted):
#:
#: - ``fused_adam``: one custom call moves g/mu/nu in and upd/mu'/nu'
#:   out (6 f32 arrays of padded size n) plus a (6,) SMEM scalar vector
#:   -> 24n + 24.  The unfused optax chain re-materializes mu, nu,
#:   mu_hat, nu_hat, the quotient and the lr scale as separate
#:   elementwise passes: 15 f32 words/element -> 60n.
#: - ``fused_softmax_xent``: logits + (B,1) int32 labels in, (B,1)
#:   loss + (B,1) lse out -> 4BV + 12B.  The XLA path writes the (B,V)
#:   log-prob tensor and reads it back for the gather: 3 passes over
#:   the big tensor -> 12BV (+ the same small per-row terms, dropped).
#: - ``int8_matmul``: weight-stationary — x (f32) + int8 weights +
#:   per-channel scales in, f32 out -> 4MK + KN + 4N + 4MN.  The
#:   dequantize-first path additionally writes AND reads the f32
#:   weight tensor -> 4MK + KN + 8KN + 4MN.
#: - ``flash``: q/k/v/o only -> 16·B·H·L·D; the dense path also writes
#:   and reads the (L,L) score matrix per head -> + 8·B·H·L².
KERNEL_BYTE_MODELS = ("fused_adam", "fused_softmax_xent", "int8_matmul",
                      "flash")


def kernel_bytes(kernel: str, **sizes) -> dict:
    """Analytic HBM bytes for one invocation of ``kernel`` vs its
    unfused XLA twin: ``{"kernel": bytes, "xla": bytes}``.

    Size kwargs per kernel: ``fused_adam(n)`` — padded element count;
    ``fused_softmax_xent(batch, vocab)``; ``int8_matmul(m, k, n)``;
    ``flash(batch, heads, seq, head_dim)``.  The "kernel" term is the
    custom call's operand+result byte sum — the same number
    ``HloReport.custom_kernel_bytes`` measures after TPU lowering."""
    if kernel == "fused_adam":
        n = float(sizes["n"])
        return {"kernel": 24.0 * n + 24.0, "xla": 60.0 * n}
    if kernel == "fused_softmax_xent":
        b, v = float(sizes["batch"]), float(sizes["vocab"])
        return {"kernel": 4.0 * b * v + 12.0 * b,
                "xla": 12.0 * b * v + 12.0 * b}
    if kernel == "int8_matmul":
        m, k, n = float(sizes["m"]), float(sizes["k"]), float(sizes["n"])
        io = 4.0 * m * k + k * n + 4.0 * m * n
        return {"kernel": io + 4.0 * n, "xla": io + 8.0 * k * n}
    if kernel == "flash":
        b, h = float(sizes["batch"]), float(sizes["heads"])
        l, d = float(sizes["seq"]), float(sizes["head_dim"])
        qkvo = 16.0 * b * h * l * d
        return {"kernel": qkvo, "xla": qkvo + 8.0 * b * h * l * l}
    raise ValueError(
        f"unknown kernel {kernel!r}; valid: "
        f"{', '.join(KERNEL_BYTE_MODELS)}")


def choose_kernel(kernel: str, platform: str | None = None,
                  peaks: PeakTable | None = None, **sizes) -> dict:
    """Kernel-vs-XLA verdict for one scope on one platform.

    Platform gates first: Pallas lowers through Mosaic, so any
    non-TPU platform picks ``"xla"`` regardless of the byte model —
    this is the oracle DECLINING the kernel on the CPU tier, not a
    failure.  On TPU the pick is the smaller analytic byte term, with
    per-variant seconds at the platform's HBM ceiling recorded so the
    verdict doc ranks like the roofline does."""
    predicted = kernel_bytes(kernel, **sizes)
    if peaks is None:
        peaks = resolve_peaks(platform)
    bw = float(peaks.hbm_bytes_per_s)
    doc = {
        "kernel": kernel,
        "platform": platform or "cpu",
        "sizes": {k: int(v) for k, v in sizes.items()},
        "predicted_bytes": {k: int(v) for k, v in predicted.items()},
        "predicted_s": {k: v / bw for k, v in predicted.items()},
        "peaks_source": peaks.source,
    }
    on_tpu = str(platform or "cpu").lower().startswith("tpu")
    if not on_tpu:
        doc["choice"] = "xla"
        doc["reason"] = ("pallas kernels lower via Mosaic (TPU only); "
                         "the jnp fallback on this platform is the "
                         "same XLA program")
    elif predicted["kernel"] < predicted["xla"]:
        doc["choice"] = kernel
        saved = predicted["xla"] - predicted["kernel"]
        doc["reason"] = (f"kernel saves {int(saved)} HBM bytes/step "
                         f"({predicted['kernel'] / predicted['xla']:.2f}x "
                         f"of the unfused traffic)")
    else:
        doc["choice"] = "xla"
        doc["reason"] = ("analytic byte model predicts no HBM win at "
                         "these sizes")
    return doc


# ---------------------------------------------------------------------------
# The fitted residual: least squares over log-space features, stdlib
# only.  target = log(measured_sps) - log(analytic_sps); prediction
# multiplies the analytic roofline by exp(w·x).
# ---------------------------------------------------------------------------


def _residual_vector(features: Mapping, k: int) -> list[float]:
    f = normalize_features(features)
    return [
        1.0,
        math.log1p(f["matmul_flops"]),
        math.log1p(f["bytes_accessed"]),
        math.log1p(f["collective_bytes"]),
        math.log(max(int(k), 1)),
        math.log1p(f["op_count"]),
    ]


def _solve_ridge(rows: Sequence[Sequence[float]],
                 targets: Sequence[float],
                 lam: float = 1e-3) -> list[float]:
    """(AᵀA + λI) w = Aᵀb by Gaussian elimination with partial
    pivoting — six unknowns, so O(d³) in pure Python is microseconds.
    The ridge term keeps the system nonsingular when every sample
    shares a feature value (one model swept over K alone)."""
    d = len(rows[0])
    ata = [[lam if i == j else 0.0 for j in range(d)] for i in range(d)]
    atb = [0.0] * d
    for row, t in zip(rows, targets):
        for i in range(d):
            atb[i] += row[i] * t
            for j in range(d):
                ata[i][j] += row[i] * row[j]
    # augmented elimination
    for col in range(d):
        pivot = max(range(col, d), key=lambda r: abs(ata[r][col]))
        if abs(ata[pivot][col]) < 1e-12:
            continue
        ata[col], ata[pivot] = ata[pivot], ata[col]
        atb[col], atb[pivot] = atb[pivot], atb[col]
        inv = 1.0 / ata[col][col]
        for r in range(d):
            if r == col:
                continue
            factor = ata[r][col] * inv
            if factor == 0.0:
                continue
            for c in range(col, d):
                ata[r][c] -= factor * ata[col][c]
            atb[r] -= factor * atb[col]
    return [atb[i] / ata[i][i] if abs(ata[i][i]) > 1e-12 else 0.0
            for i in range(d)]


class ResidualModel:
    """Multiplicative correction to the analytic roofline, fitted from
    accumulated (features, K, measured steps/sec) rows.

    ``ready`` stays False below ``min_samples`` rows (or before any
    :meth:`fit`): callers must then use the analytic prediction alone —
    :meth:`predict_steps_per_sec` does exactly that, so the zero-data
    path needs no branching at call sites."""

    def __init__(self, peaks: PeakTable | None = None,
                 min_samples: int = MIN_FIT_SAMPLES):
        self.peaks = peaks if peaks is not None else resolve_peaks()
        self.min_samples = int(min_samples)
        self.weights: list[float] | None = None
        self.n_samples = 0

    @property
    def ready(self) -> bool:
        return self.weights is not None

    def fit(self, rows: Iterable[Mapping]) -> "ResidualModel":
        """``rows``: dicts with ``features`` (any alias shape), ``k``
        and ``measured_steps_per_sec``.  Rows without a positive
        measurement are dropped; below ``min_samples`` survivors the
        model stays analytic (``ready`` False)."""
        xs, ts = [], []
        for row in rows:
            sps = row.get("measured_steps_per_sec") or 0
            if sps <= 0:
                continue
            feats = row.get("features") or {}
            k = int(row.get("k") or 1)
            analytic = predict_steps_per_sec(feats, k=k, peaks=self.peaks)
            xs.append(_residual_vector(feats, k))
            ts.append(math.log(sps) - math.log(analytic))
        self.n_samples = len(xs)
        if self.n_samples < self.min_samples:
            self.weights = None
            return self
        self.weights = _solve_ridge(xs, ts)
        return self

    def predict_steps_per_sec(self, features: Mapping, k: int = 1) -> float:
        analytic = predict_steps_per_sec(features, k=k, peaks=self.peaks)
        if self.weights is None:
            return analytic
        x = _residual_vector(features, k)
        log_corr = sum(w * xi for w, xi in zip(self.weights, x))
        # clamp the correction: an extrapolated fit must dent the
        # analytic prediction, not replace it with nonsense
        log_corr = max(-3.0, min(3.0, log_corr))
        return analytic * math.exp(log_corr)


# ---------------------------------------------------------------------------
# Training-row loaders: the data loop's read side.
# ---------------------------------------------------------------------------


def load_report_rows(report_dir: str) -> list[dict]:
    """``ZOO_HLO_REPORT_DIR`` reports as feature rows.  Accepts schema
    ``zoo-hlo-report/1`` (no plan/mesh/K/compile-seconds — those fields
    come back None) alongside v2; unparseable files are skipped, never
    raised."""
    rows = []
    try:
        names = sorted(os.listdir(report_dir))
    except OSError:
        return rows
    for name in names:
        if not (name.startswith("hlo-") and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(report_dir, name)) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if not str(doc.get("schema", "")).startswith("zoo-hlo-report/"):
            continue
        rows.append({
            "label": doc.get("label"),
            "features": normalize_features(doc.get("features") or {}),
            "k": doc.get("steps_per_dispatch"),
            "plan": doc.get("plan"),
            "mesh_shape": doc.get("mesh_shape"),
            "compile_seconds": doc.get("compile_seconds"),
            "dtype_histogram": doc.get("dtype_histogram"),
            "dtype_policy": doc.get("dtype_policy"),
            "bucket": doc.get("bucket"),
            "ts": doc.get("ts"),
        })
    return rows


def load_tune_log_rows(tune_log_dir: str) -> list[dict]:
    """Measured per-K rows from the autotuner's persisted decision
    history (``ZOO_TUNE_LOG_DIR`` JSONL, feature/autotune.py): each
    ``settle`` record carries the full measured cost curve
    ``k_cost_per_step_s`` under the program's compile label — joined
    with a report row's features by that label, each (K, cost) pair
    becomes a training sample."""
    rows = []
    try:
        names = sorted(os.listdir(tune_log_dir))
    except OSError:
        return rows
    for name in names:
        if ".jsonl" not in name:
            continue
        try:
            with open(os.path.join(tune_log_dir, name)) as f:
                lines = f.readlines()
        except OSError:
            continue
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("type") != "settle":
                continue
            for k, cost in (rec.get("k_cost_per_step_s") or {}).items():
                if not cost or float(cost) <= 0:
                    continue
                rows.append({
                    "label": rec.get("label"),
                    "k": int(k),
                    "measured_steps_per_sec": 1.0 / float(cost),
                })
    return rows


def training_rows(report_dir: str | None = None,
                  tune_log_dir: str | None = None) -> list[dict]:
    """The residual model's joined training set: tune-log rows
    (measurement, no features) join with the latest report row of the
    same compile label (features, no measurement).  Unjoinable rows
    drop silently — with nothing accumulated yet the result is [] and
    the caller's fit stays analytic."""
    report_dir = report_dir or os.environ.get("ZOO_HLO_REPORT_DIR")
    tune_log_dir = tune_log_dir or os.environ.get("ZOO_TUNE_LOG_DIR")
    rows = []
    reports = load_report_rows(report_dir) if report_dir else []
    by_label: dict[str, dict] = {}
    for rpt in reports:  # later files win: freshest features per label
        if rpt.get("label"):
            by_label[rpt["label"]] = rpt
    for rec in (load_tune_log_rows(tune_log_dir) if tune_log_dir else []):
        rpt = by_label.get(rec.get("label"))
        if rpt is None:
            continue
        rows.append({**rec, "features": rpt["features"],
                     "plan": rpt.get("plan")})
    return rows
