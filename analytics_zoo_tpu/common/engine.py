"""Engine & context runtime — the TPU-native equivalent of the reference's
``NNContext`` layer (reference zoo/.../common/NNContext.scala:133-149 creates a
SparkContext + BigDL ``Engine.init``; pyzoo/zoo/common/nncontext.py:104-124 is
the Python twin).

Instead of a SparkContext over a cluster, the runtime here owns a
``jax.sharding.Mesh`` over the TPU slice.  Mesh axes are first-class: ``data``
(DP — the reference's only strategy), plus ``model`` (TP), ``seq`` (SP/CP) and
``expert`` (EP) axes the reference never had (SURVEY.md §2.4).  Everything that
trains or predicts asks this module for the current mesh; tests force an
8-device CPU mesh via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the analogue of the reference's local[4] Spark testing trick, SURVEY.md §4).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import threading
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger("analytics_zoo_tpu")

# Canonical mesh-axis names, ordered outermost-first.  DCN-crossing axes
# (multi-slice data parallelism) must come first so that XLA lays collectives
# on ICI for the inner axes.
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"
ALL_AXES = (DATA_AXIS, MODEL_AXIS, SEQ_AXIS, EXPERT_AXIS, PIPE_AXIS)


def _parse_bytes(raw, name: str) -> int:
    """Byte-count knob parser: plain int, or a K/M/G (binary) suffix —
    ``"512M"`` reads as 512 MiB.  Errors name the knob."""
    s = str(raw).strip()
    mult = 1
    if s and s[-1].upper() in "KMG":
        mult = {"K": 1 << 10, "M": 1 << 20, "G": 1 << 30}[s[-1].upper()]
        s = s[:-1]
    try:
        val = int(float(s) * mult)
    except (ValueError, OverflowError):  # 'inf' overflows int(), not
        #                                  float() — same bad-knob error
        raise ValueError(
            f"{name} must be a byte count (integer, optionally with a "
            f"K/M/G suffix), got {raw!r}") from None
    if val < 1:
        raise ValueError(f"{name} must be >= 1 byte, got {raw!r}")
    return val


@dataclasses.dataclass
class ZooConfig:
    """Typed engine configuration — the reference's three-tier conf system
    (packaged conf file merged into SparkConf + JVM sysprops + env vars,
    NNContext.scala:188-237) collapsed into one dataclass with a documented
    env tier.

    Precedence: explicit ``init_zoo_context`` arguments / conf dict >
    environment variables > dataclass defaults.

    Environment tier (the reference's sysprop/env knobs):
      ZOO_COMPUTE_DTYPE        "bf16" | "f32" | "f16" (platform default:
                               bf16 on TPU, f32 elsewhere)
      ZOO_FAILURE_RETRY_TIMES  retry-from-checkpoint budget (reference
                               ``bigdl.failure.retryTimes``, default 5)
      ZOO_PROFILE_DIR          when set, the Estimator captures ONE
                               jax.profiler trace of ``profile_steps``
                               train steps per fit() into this directory
      ZOO_PROFILE_STEPS        steps per captured trace (default 5)
      ZOO_INFEED_DEPTH         host->device feeder queue depth (default 2)
      ZOO_PREFETCH_WORKERS     > 0: the estimator fit loop wraps the train
                               set in the parallel host data plane
                               (FeatureSet.prefetch — feature/prefetch.py)
                               with this many pool workers; 0 (default)
                               keeps the serial path.  Delivery is ordered,
                               so the batch stream is byte-identical
                               either way.
      ZOO_PREFETCH_DEPTH       bounded prefetch queue depth when the
                               data plane is on (default 4)
      ZOO_STEPS_PER_DISPATCH   K > 1: the estimator fuses K train steps
                               into ONE jitted dispatch (jax.lax.scan
                               over a K-stacked super-batch) — amortizes
                               the Python→device round-trip when the
                               harness is dispatch-bound.  Loss
                               trajectory is bit-identical to K=1;
                               checkpoints/validation/TB move to K-step
                               boundaries (docs/performance.md).
                               Default 1 (off).
      ZOO_COMPILE_CACHE        persistent XLA compilation cache dir
                               (common/compile_cache.py): a second
                               process start / warmup() of the same
                               program skips XLA — cold-vs-warm shows in
                               zoo_compile_* metrics
      ZOO_SHARD_OPTIMIZER      "1": ZeRO-1 — shard optimizer state over
                               the data axis (1/n memory + update compute
                               per chip; params stay replicated).  Legacy
                               spelling of ZOO_SHARDING_PLAN=zero1.
      ZOO_SHARDING_PLAN        named sharding plan for training
                               (parallel/plan.py; docs/parallelism.md):
                               "dp" (replicate — default), "zero1"
                               (optimizer state sharded over data),
                               "zero2" (zero1 + gradients
                               reduce-scattered to per-chip shards),
                               "zero3"/"fsdp" (params + optimizer
                               state sharded over data; gather-on-use
                               / reduce-scatter — ~1/n param+opt bytes
                               per chip at a bit-identical loss
                               trajectory; zero3 also shards the
                               gradient tree in-graph).  Any of
                               zero1/zero2/zero3/fsdp also accepts a
                               "+overlap" suffix (e.g.
                               "zero2+overlap"): gradient collectives
                               are bucketed behind backward compute
                               and fsdp gathers double-buffered —
                               same bitwise trajectory, less exposed
                               collective time (docs/performance.md
                               "Latency hiding").  fit(
                               plan="auto") asks the oracle to sweep
                               these × remat policies against the HBM
                               budget.  Tensor-parallel and pipeline
                               plans carry a rule table, so they are
                               passed as objects (fit(plan=
                               tensor_parallel(rules))), not named
                               here.
      ZOO_DTYPE_POLICY         precision plane (parallel/plan.py
                               dtype_rules; docs/parallelism.md
                               "Precision plane"): "f32" (no-op
                               default), "bf16_mixed" (bf16 compute
                               params + f32 masters / f32 grad and
                               collective accumulation — the canned
                               mixed_precision() plan overlay),
                               "int8_serving" (weights marked for the
                               plan-aware weight-only int8 serving
                               path), "auto" (plan="auto" sweeps dtype
                               alongside sharding × remat against the
                               HBM budget), or an explicit
                               "pattern=role,..." rule string (roles
                               f32/bf16/f16/int8/keep).  Validated
                               EAGERLY at context init naming this
                               var.  A plan passed with its own
                               dtype_rules wins over this env tier.
      ZOO_USE_PALLAS           "1": kernel plane (parallel/plan.py
                               kernel_rules; docs/performance.md
                               "Kernel plane") — overlay the default
                               kernel table (attention=flash,
                               optimizer.adam=fused_adam,
                               loss.softmax_xent=fused_softmax_xent,
                               serving.int8_matmul=int8_matmul) on the
                               resolved plan, adding the "+kernels"
                               name suffix.  A plan passed with its own
                               kernel_rules wins over this env tier.
                               Unset: no ops/pallas kernel module is
                               even imported and the trajectory is
                               bit-identical (flash attention keeps its
                               pre-existing eligibility routing either
                               way).  Validated as a boolean eagerly.
      ZOO_DTYPE_RESUME         "cast": resuming a checkpoint whose
                               recorded dtype policy differs from the
                               current plan's casts deliberately
                               (with a warning) instead of failing
                               loudly
      ZOO_OVERLAP_BUCKET_BYTES target gradient-bucket size (bytes) for
                               "+overlap" plans — each bucket's
                               reduce-scatter/all-reduce is issued as
                               its backward segment completes
                               (parallel/plan.py
                               default_bucket_bytes; default 4 MiB).
                               Grouping is part of the plan cache key,
                               so changing it recompiles but never
                               changes the trajectory.
      ZOO_ASYNC_CHECKPOINT     "0" forces checkpoint saves back onto
                               the train thread (gather + serialize +
                               atomic rename inline).  Default on:
                               saves snapshot on-device, then gather/
                               serialize/rename on a daemon writer
                               thread — fit stalls only for the
                               snapshot (zoo_ckpt_stall_seconds vs
                               zoo_ckpt_write_seconds), a kill mid-
                               write leaves the previous complete
                               checkpoint loadable.
      ZOO_DCN_AXIS             mesh axis that crosses the data-center
                               network when parallel.plan.build_mesh
                               assembles a hybrid ICI x DCN mesh from a
                               bare slice count (default "data"; a name
                               not in the ICI axes, e.g. "dcn", is
                               prepended as a new outermost axis)
      ZOO_METRICS_PORT         serve /metrics /varz /trace /healthz
                               /flightz over HTTP from the serving loop /
                               estimator fit (metrics/http.py; bind
                               address via ZOO_METRICS_HOST)
      ZOO_FLIGHT_DIR           arm the crash flight recorder's dump
                               (metrics/flight.py; ZOO_FLIGHT=0 disables,
                               ZOO_FLIGHT_EVENTS caps the ring)
      ZOO_HLO_LINT             "0" disables the HLO graph lint + cost
                               extraction riding every timed_compile
                               (analysis/hlo.py; default on — zoo_hlo_*
                               metrics, flight hlo_lint events)
      ZOO_HLO_REPORT_DIR       when set, every compile additionally
                               writes a zoo-hlo-report/2 JSON file with
                               the analytic features + findings plus
                               compile wall-seconds, plan, mesh shape,
                               K and a dtype histogram — one row is a
                               self-contained cost-model training
                               example (docs/static-analysis.md)
      ZOO_ORACLE               "0" disables the predictive compile
                               plane (analysis/oracle.py; default on):
                               the autotuner's K search falls back to
                               the blind hill-climb (plan="auto" still
                               predicts — it is an explicit request)
      ZOO_ORACLE_PEAKS         JSON object overriding PeakTable fields
                               (flops, hbm_bytes_per_s,
                               link_bytes_per_s, dispatch_overhead_s,
                               hbm_bytes) over the per-platform
                               defaults — calibrate the roofline, or
                               pin the HBM budget plan="auto" fits
                               against (docs/performance.md)
      ZOO_TUNE_LOG_DIR         when set, the autotuner persists its
                               decision log there as JSONL (decision +
                               settle records; the settle rows carry
                               the measured per-K cost curve the
                               oracle's residual model trains on);
                               size-capped by ZOO_TUNE_LOG_MAX_BYTES
                               (default 4M) with one rotated
                               predecessor
      ZOO_SAN                  "1": install the runtime concurrency
                               sanitizer at package import — wraps the
                               package's locks (lockdep cycle detection
                               with both stacks), validates guarded-by
                               annotations on attribute writes, flags
                               blocking calls under a held lock
                               (analysis/sanitizer.py; zoo_san_* metrics
                               + san_finding flight events).  Unset:
                               nothing is patched, zero overhead.
      ZOO_SAN_STRICT           "1": the pytest session fails if
                               sanitizer findings are left un-drained
                               at session end (tests/conftest.py)
      ZOO_AUTOTUNE             "1": closed-loop autotuning
                               (feature/autotune.py) — a controller
                               thread resizes the prefetch worker pool,
                               queue depth and shard read-ahead online
                               from the zoo_data_prefetch_* telemetry
                               (consumer-wait p50 → 0 under the RAM
                               budget) and hill-climbs
                               steps_per_dispatch over {1,2,4,8,16}
                               from measured per-dispatch time.  Loss
                               trajectory stays bit-identical; every
                               decision lands in zoo_autotune_*
                               metrics, the flight ring, and /varz.
                               Unset: zero new threads, zero overhead.
      ZOO_AUTOTUNE_RAM_BUDGET  host-RAM budget (bytes; K/M/G suffixes
                               accepted, e.g. "512M") for the prefetch
                               window the autotuner may grow into
                               (default 2G)
      ZOO_AUTOTUNE_INTERVAL    controller tick seconds (default 0.25)
      ZOO_AUTOTUNE_MAX_WORKERS cap on the autotuned worker pool
                               (default min(8, 4 x cpu count) — prefetch
                               workers scale GIL-releasing IO/decode,
                               so cores only floor the cap)
      ZOO_SERVING_BATCH_BUDGET_MS
                               continuous-batching latency budget (ms)
                               for claim-mode (fleet) serving: a PARTIAL
                               shape bucket waits at most this long for
                               co-batchable arrivals before predict — a
                               lone request is served within the budget,
                               a trickle coalesces into one padded
                               predict.  0 flushes every claim batch
                               immediately.  Default 25.
      ZOO_SLO_P99_MS           the serving fleet's p99 latency SLO (ms,
                               default 500): the autoscaler scales up
                               when its estimated tail sojourn (predict
                               p99 + backlog/service-rate) sustainedly
                               exceeds this, down on sustained slack
                               (serving/scaler.py)
      ZOO_FLEET_MIN_REPLICAS   autoscaler floor (default 1)
      ZOO_FLEET_MAX_REPLICAS   autoscaler ceiling (default 4)
      ZOO_FLEET_INTERVAL       scaler window/tick seconds (default 1.0)
      ZOO_FLEET_LEASE_MS       work-claim lease (ms, default 10000): a
                               replica silent this long forfeits its
                               claimed-but-unserved records to the
                               surviving replicas (exactly-once via
                               lease expiry; serving/broker.py)
      ZOO_ELASTIC              enable the elastic training runtime
                               (default off): fit() joins the broker-
                               backed membership ledger and yields at
                               step barriers on generation changes
                               (elastic/; docs/elastic-training.md)
      ZOO_ELASTIC_LEASE_MS     membership lease (ms, default 3000): a
                               training worker whose keepalive is
                               silent this long is declared dead and
                               the generation counter increments —
                               shorter detects faults faster, longer
                               tolerates GC/compile pauses
      ZOO_ELASTIC_MIN_WORKERS  cohort floor (default 1): the supervisor
                               holds training (no chief assignment)
                               while fewer members are live
      ZOO_ELASTIC_GRACE_MS     shutdown grace (ms, default 5000): bound
                               on the SIGTERM-path flush of the async
                               checkpoint writer before the flight
                               dump, and on a worker's SIGTERM->SIGKILL
                               escalation
      ZOO_SCRAPE_TARGETS       static scrape list for the zoowatch
                               federation tier (metrics/scrape.py):
                               comma/space-separated host:port, URL, or
                               name=url entries; a VarzScraper built
                               without explicit targets adopts them
      ZOO_SCRAPE_INTERVAL      scrape cadence seconds (default 1.0,
                               floor 0.05)
      ZOO_SCRAPE_STALE_AFTER   a target silent this many seconds is
                               stale: its health verdict flips and the
                               aggregator labels its samples
                               ``stale="true"`` (default 10.0)
      ZOO_SLO_OBJECTIVE        default SLO objective for the burn-rate
                               engine (metrics/slo.py): fraction of
                               good events promised, in (0, 1)
                               (default 0.99)
      ZOO_SLO_SHORT_WINDOW     burn-rate fast window seconds (default
                               30): both windows must burn above the
                               threshold for an alert to fire
      ZOO_SLO_LONG_WINDOW      burn-rate slow window seconds (default
                               300); must exceed the short window
      ZOO_SLO_BURN_THRESHOLD   burn-rate multiple that fires an alert
                               (default 1.0 = burning budget exactly
                               at the objective's sustainable rate)

    ``ZOO_PREFETCH_WORKERS`` / ``ZOO_PREFETCH_DEPTH`` /
    ``ZOO_STEPS_PER_DISPATCH`` are validated EAGERLY here: a
    non-integer or out-of-range value fails at context init with an
    error naming the env var, never from deep inside the pipeline.
    """

    app_name: str = "analytics-zoo-tpu"
    seed: int = 0
    mesh_shape: Mapping[str, int] | None = None
    mesh_axes: Sequence[str] = (DATA_AXIS, MODEL_AXIS)
    platform: str | None = None
    compute_dtype: object = None
    # None = "not explicitly set": resolved env > default in __post_init__,
    # so an explicit value always beats the environment (the documented
    # precedence) even when it equals the default.
    failure_retry_times: int | None = None
    profile_dir: str | None = None
    profile_steps: int | None = None
    infeed_depth: int | None = None
    # Parallel host data plane (feature/prefetch.py): workers > 0 makes
    # the estimator prefetch the train set; env ZOO_PREFETCH_WORKERS /
    # ZOO_PREFETCH_DEPTH.
    prefetch_workers: int | None = None
    prefetch_depth: int | None = None
    # Fused multi-step dispatch: K > 1 runs K train steps inside one
    # jitted lax.scan per host round-trip (bit-identical trajectory;
    # K-boundary callbacks).  Env: ZOO_STEPS_PER_DISPATCH.
    steps_per_dispatch: int | None = None
    # Persistent XLA compile cache dir (common/compile_cache.py).
    # Env: ZOO_COMPILE_CACHE.
    compile_cache: str | None = None
    # ZeRO-1: shard optimizer state (Adam moments) over the data axis via
    # GSPMD sharding constraints — 1/n optimizer memory and update compute
    # per chip; parameters stay replicated.  Env: ZOO_SHARD_OPTIMIZER=1.
    # (Legacy spelling of sharding_plan="zero1".)
    shard_optimizer: bool | None = None
    # Unified partitioner (parallel/plan.py): named sharding plan for
    # every fit ("dp" | "zero1" | "zero2" | "zero3" | "fsdp"); None = dp
    # (or zero1 when the legacy shard_optimizer flag is set).
    # Env: ZOO_SHARDING_PLAN.
    sharding_plan: str | None = None
    # Precision plane (parallel/plan.py dtype_rules): named dtype policy
    # ("f32" | "bf16_mixed" | "int8_serving" | "auto") or an explicit
    # "pattern=role,..." rule string overlaid on the resolved plan.
    # Env: ZOO_DTYPE_POLICY.
    dtype_policy: str | None = None
    # Kernel plane (parallel/plan.py kernel_rules): overlay the default
    # pallas kernel table on the resolved plan.  Env: ZOO_USE_PALLAS=1.
    use_pallas: bool | None = None
    # Hybrid ICI x DCN meshes (plan.build_mesh): which axis crosses the
    # DCN when given a bare slice count.  Env: ZOO_DCN_AXIS.
    dcn_axis: str | None = None
    # Closed-loop autotuning (feature/autotune.py): resize the prefetch
    # plane online and hill-climb steps_per_dispatch from telemetry.
    # Env: ZOO_AUTOTUNE=1 plus the budget knobs below.
    autotune: bool | None = None
    autotune_ram_budget: int | None = None
    autotune_interval: float | None = None
    autotune_max_workers: int | None = None
    # Serving fleet (serving/fleet.py): continuous-batching budget, p99
    # SLO target, and autoscaler bounds.  Env: ZOO_SERVING_BATCH_BUDGET_MS,
    # ZOO_SLO_P99_MS, ZOO_FLEET_MIN/MAX_REPLICAS, ZOO_FLEET_INTERVAL,
    # ZOO_FLEET_LEASE_MS.
    serving_batch_budget_ms: float | None = None
    slo_p99_ms: float | None = None
    fleet_min_replicas: int | None = None
    fleet_max_replicas: int | None = None
    fleet_interval: float | None = None
    fleet_lease_ms: int | None = None
    # Predictive serving plane (serving/router.py, serving/admission.py):
    # front-door admission control and the multi-tenant model roster
    # ("name=slo_p99_ms[@offered_rate],..." — one oracle-primed fleet
    # per entry).  Env: ZOO_ADMISSION=1, ZOO_SERVING_MODELS.
    admission: bool | None = None
    serving_models: str | None = None
    # Elastic training runtime (elastic/): membership lease, cohort
    # floor, and shutdown grace.  Env: ZOO_ELASTIC,
    # ZOO_ELASTIC_LEASE_MS, ZOO_ELASTIC_MIN_WORKERS,
    # ZOO_ELASTIC_GRACE_MS.
    elastic: bool | None = None
    elastic_lease_ms: int | None = None
    elastic_min_workers: int | None = None
    elastic_grace_ms: int | None = None
    # Zoowatch federation tier (metrics/scrape.py, metrics/slo.py):
    # static scrape targets, cadence, staleness threshold, and the
    # burn-rate engine's default objective/windows.  Env:
    # ZOO_SCRAPE_TARGETS, ZOO_SCRAPE_INTERVAL, ZOO_SCRAPE_STALE_AFTER,
    # ZOO_SLO_OBJECTIVE, ZOO_SLO_SHORT/LONG_WINDOW,
    # ZOO_SLO_BURN_THRESHOLD.
    scrape_targets: str | None = None
    scrape_interval: float | None = None
    scrape_stale_after: float | None = None
    slo_objective: float | None = None
    slo_short_window: float | None = None
    slo_long_window: float | None = None
    slo_burn_threshold: float | None = None

    def __post_init__(self):
        env = os.environ

        def resolve(value, env_key, default, cast=int):
            if value is not None:
                return value
            if env_key in env:
                return cast(env[env_key])
            return default

        def resolve_int(value, env_key, default, minimum):
            """Eager-validated integer knob: a bad value fails HERE with
            an error naming its source (env var or field), not from
            deep inside the pipeline/estimator it configures."""
            if value is not None:
                src, raw = "ZooConfig " + env_key[4:].lower(), value
            elif env_key in env:
                src, raw = env_key, env[env_key]
            else:
                return default
            try:
                out = int(str(raw))
            except (TypeError, ValueError):
                raise ValueError(
                    f"{src} must be an integer >= {minimum}, "
                    f"got {raw!r}") from None
            if out < minimum:
                raise ValueError(
                    f"{src} must be >= {minimum}, got {out}")
            return out

        self.failure_retry_times = resolve(
            self.failure_retry_times, "ZOO_FAILURE_RETRY_TIMES", 5)
        self.profile_steps = resolve(
            self.profile_steps, "ZOO_PROFILE_STEPS", 5)
        self.infeed_depth = resolve(
            self.infeed_depth, "ZOO_INFEED_DEPTH", 2)
        # 0 = prefetch off (the documented default); depth/K floor at 1
        self.prefetch_workers = resolve_int(
            self.prefetch_workers, "ZOO_PREFETCH_WORKERS", 0, minimum=0)
        self.prefetch_depth = resolve_int(
            self.prefetch_depth, "ZOO_PREFETCH_DEPTH", 4, minimum=1)
        self.steps_per_dispatch = resolve_int(
            self.steps_per_dispatch, "ZOO_STEPS_PER_DISPATCH", 1,
            minimum=1)
        self.shard_optimizer = bool(resolve(
            self.shard_optimizer, "ZOO_SHARD_OPTIMIZER", False))
        self.sharding_plan = resolve(
            self.sharding_plan, "ZOO_SHARDING_PLAN", None, cast=str)
        if self.sharding_plan is not None:
            # eager validation (the resolve_int contract): a typo'd plan
            # name fails at context init naming the knob, not from the
            # first fit()
            from analytics_zoo_tpu.parallel.plan import (
                DTYPE_ROLES,
                PLAN_NAMES,
            )

            valid = tuple(PLAN_NAMES) + ("auto",)
            name = str(self.sharding_plan).strip().lower()
            # kernel plane: "+kernels" is appended last by with_kernels,
            # so it strips first — mirroring resolve_plan's parse order
            if name.endswith("+kernels"):
                name = name[:-len("+kernels")]
            # precision plane: any plan also accepts a trailing dtype-
            # role suffix ("zero1+overlap+bf16") — strip it before the
            # name check, mirroring resolve_plan's parse order
            for role in DTYPE_ROLES:
                if name.endswith("+" + role):
                    name = name[:-len("+" + role)]
                    break
            base = name[:-len("+overlap")] \
                if name.endswith("+overlap") else name
            overlappable = ("zero1", "zero2", "zero3", "fsdp")
            ok = name in valid or (name.endswith("+overlap")
                                   and base in overlappable)
            if not ok:
                raise ValueError(
                    f"ZOO_SHARDING_PLAN must be one of "
                    f"{', '.join(valid)} (zero1/zero2/zero3/fsdp also "
                    f"accept a '+overlap' suffix, and any plan a "
                    f"trailing dtype-role suffix like '+bf16'); "
                    f"got {self.sharding_plan!r}")
        self.dtype_policy = resolve(
            self.dtype_policy, "ZOO_DTYPE_POLICY", None, cast=str)
        if self.dtype_policy is not None:
            # eager validation (the resolve_int contract): a typo'd
            # policy fails at context init naming the knob, not from
            # the first fit()'s plan resolution
            from analytics_zoo_tpu.parallel.plan import resolve_dtype_rules

            policy = str(self.dtype_policy).strip().lower()
            if policy != "auto":
                try:
                    resolve_dtype_rules(self.dtype_policy)
                except ValueError as e:
                    raise ValueError(
                        f"ZOO_DTYPE_POLICY: {e}") from None
        self.dcn_axis = resolve(
            self.dcn_axis, "ZOO_DCN_AXIS", None, cast=str)
        if self.dcn_axis is not None and not str(self.dcn_axis).strip():
            raise ValueError("ZOO_DCN_AXIS must be a mesh axis name")
        def bool_parser(var):
            def parse(raw):
                s = str(raw).strip().lower()
                if s in ("1", "true", "yes", "on"):
                    return True
                if s in ("", "0", "false", "no", "off"):
                    return False
                # 'false'-alikes must never silently ENABLE a feature;
                # anything unrecognized fails loudly naming the var
                raise ValueError(
                    f"{var} must be a boolean "
                    f"(1/0/true/false/yes/no/on/off), got {raw!r}")
            return parse

        parse_bool = bool_parser("ZOO_AUTOTUNE")
        self.use_pallas = bool(resolve(
            self.use_pallas, "ZOO_USE_PALLAS", False,
            cast=bool_parser("ZOO_USE_PALLAS")))
        self.autotune = bool(resolve(
            self.autotune, "ZOO_AUTOTUNE", False, cast=parse_bool))
        if self.autotune_ram_budget is None:
            raw = env.get("ZOO_AUTOTUNE_RAM_BUDGET")
            if raw:
                self.autotune_ram_budget = _parse_bytes(
                    raw, "ZOO_AUTOTUNE_RAM_BUDGET")
        elif self.autotune_ram_budget < 1:
            raise ValueError(
                f"ZooConfig autotune_ram_budget must be >= 1 byte, "
                f"got {self.autotune_ram_budget}")
        self.autotune_interval = resolve(
            self.autotune_interval, "ZOO_AUTOTUNE_INTERVAL", 0.25,
            cast=float)
        if self.autotune_interval <= 0:
            raise ValueError(
                f"ZOO_AUTOTUNE_INTERVAL must be > 0, "
                f"got {self.autotune_interval}")
        self.autotune_max_workers = resolve_int(
            self.autotune_max_workers, "ZOO_AUTOTUNE_MAX_WORKERS", None,
            minimum=1)

        def resolve_float(value, env_key, default, minimum):
            """Eager-validated float knob — same contract as
            resolve_int: fails here naming the env var or field."""
            if value is not None:
                src, raw = "ZooConfig " + env_key[4:].lower(), value
            elif env_key in env:
                src, raw = env_key, env[env_key]
            else:
                return default
            try:
                out = float(str(raw))
            except (TypeError, ValueError):
                raise ValueError(
                    f"{src} must be a number >= {minimum}, "
                    f"got {raw!r}") from None
            if out < minimum:
                raise ValueError(
                    f"{src} must be >= {minimum}, got {out}")
            return out

        # Serving-fleet tier: budgets/SLO validated eagerly so a bad
        # knob fails at context init, not from inside a serving replica
        self.serving_batch_budget_ms = resolve_float(
            self.serving_batch_budget_ms, "ZOO_SERVING_BATCH_BUDGET_MS",
            25.0, minimum=0.0)
        self.slo_p99_ms = resolve_float(
            self.slo_p99_ms, "ZOO_SLO_P99_MS", 500.0, minimum=1.0)
        self.fleet_min_replicas = resolve_int(
            self.fleet_min_replicas, "ZOO_FLEET_MIN_REPLICAS", 1,
            minimum=1)
        self.fleet_max_replicas = resolve_int(
            self.fleet_max_replicas, "ZOO_FLEET_MAX_REPLICAS", 4,
            minimum=1)
        if self.fleet_max_replicas < self.fleet_min_replicas:
            raise ValueError(
                f"ZOO_FLEET_MAX_REPLICAS ({self.fleet_max_replicas}) must "
                f"be >= ZOO_FLEET_MIN_REPLICAS "
                f"({self.fleet_min_replicas})")
        self.fleet_interval = resolve_float(
            self.fleet_interval, "ZOO_FLEET_INTERVAL", 1.0, minimum=0.01)
        self.fleet_lease_ms = resolve_int(
            self.fleet_lease_ms, "ZOO_FLEET_LEASE_MS", 10_000,
            minimum=100)
        self.admission = bool(resolve(
            self.admission, "ZOO_ADMISSION", False,
            cast=bool_parser("ZOO_ADMISSION")))
        self.serving_models = resolve(
            self.serving_models, "ZOO_SERVING_MODELS", None, cast=str)
        if self.serving_models is not None:
            # eager validation (the resolve_int contract): a malformed
            # model roster fails at context init naming the env var,
            # not from the router's first tenant build.  Lazy import —
            # serving.modelspec is pure stdlib, but keep engine's
            # import graph serving-free (the parallel.plan precedent).
            from analytics_zoo_tpu.serving.modelspec import (
                parse_model_specs,
            )

            parse_model_specs(self.serving_models,
                              source="ZOO_SERVING_MODELS")

        # Elastic-training tier (elastic/): validated eagerly so a bad
        # knob fails at context init, never from inside a training
        # worker mid-rejoin (the PR 7/8 contract).
        def parse_elastic_bool(raw):
            s = str(raw).strip().lower()
            if s in ("1", "true", "yes", "on"):
                return True
            if s in ("", "0", "false", "no", "off"):
                return False
            raise ValueError(
                f"ZOO_ELASTIC must be a boolean "
                f"(1/0/true/false/yes/no/on/off), got {raw!r}")

        self.elastic = bool(resolve(
            self.elastic, "ZOO_ELASTIC", False, cast=parse_elastic_bool))
        self.elastic_lease_ms = resolve_int(
            self.elastic_lease_ms, "ZOO_ELASTIC_LEASE_MS", 3_000,
            minimum=100)
        self.elastic_min_workers = resolve_int(
            self.elastic_min_workers, "ZOO_ELASTIC_MIN_WORKERS", 1,
            minimum=1)
        self.elastic_grace_ms = resolve_int(
            self.elastic_grace_ms, "ZOO_ELASTIC_GRACE_MS", 5_000,
            minimum=0)

        # Zoowatch federation tier (metrics/scrape.py, metrics/slo.py):
        # same eager-validation contract — a typo'd objective fails at
        # context init, never from the first burn-rate evaluation.
        self.scrape_targets = resolve(
            self.scrape_targets, "ZOO_SCRAPE_TARGETS", None, cast=str)
        self.scrape_interval = resolve_float(
            self.scrape_interval, "ZOO_SCRAPE_INTERVAL", 1.0,
            minimum=0.05)
        self.scrape_stale_after = resolve_float(
            self.scrape_stale_after, "ZOO_SCRAPE_STALE_AFTER", 10.0,
            minimum=0.05)
        self.slo_objective = resolve_float(
            self.slo_objective, "ZOO_SLO_OBJECTIVE", 0.99, minimum=0.0)
        if not 0.0 < self.slo_objective < 1.0:
            raise ValueError(
                f"ZOO_SLO_OBJECTIVE must be in (0, 1) — the fraction "
                f"of good events promised — got {self.slo_objective}")
        self.slo_short_window = resolve_float(
            self.slo_short_window, "ZOO_SLO_SHORT_WINDOW", 30.0,
            minimum=0.1)
        self.slo_long_window = resolve_float(
            self.slo_long_window, "ZOO_SLO_LONG_WINDOW", 300.0,
            minimum=0.1)
        if self.slo_long_window <= self.slo_short_window:
            raise ValueError(
                f"ZOO_SLO_LONG_WINDOW ({self.slo_long_window}) must be "
                f"> ZOO_SLO_SHORT_WINDOW ({self.slo_short_window}) — "
                f"multi-window burn-rate alerting needs a slow window "
                f"to confirm the fast one")
        self.slo_burn_threshold = resolve_float(
            self.slo_burn_threshold, "ZOO_SLO_BURN_THRESHOLD", 1.0,
            minimum=0.0)
        if self.slo_burn_threshold <= 0:
            raise ValueError(
                f"ZOO_SLO_BURN_THRESHOLD must be > 0, "
                f"got {self.slo_burn_threshold}")
        if self.profile_dir is None:
            self.profile_dir = env.get("ZOO_PROFILE_DIR") or None
        if self.compile_cache is None:
            self.compile_cache = env.get("ZOO_COMPILE_CACHE") or None


@dataclasses.dataclass
class ZooContext:
    """Runtime context: the device mesh plus engine-level knobs.

    The reference's ``NNContext.initNNContext`` returns a SparkContext after
    tuning executor env (KMP_AFFINITY / OMP_NUM_THREADS,
    NNContext.scala:209-237).  The TPU equivalent owns the mesh and global
    numerics policy instead.
    """

    mesh: Mesh
    platform: str
    seed: int = 0
    # Forward/backward math dtype (params-in-compute); None = full f32.
    # Master params, optimizer state and loss stay f32 — the standard TPU
    # mixed-precision recipe that keeps the MXU at bf16 rate.
    compute_dtype: object = None
    config: "ZooConfig" = dataclasses.field(default_factory=lambda: ZooConfig())
    _step_rng: jax.Array | None = None

    @property
    def num_devices(self) -> int:
        return self.mesh.size

    @property
    def data_parallel_size(self) -> int:
        return self.mesh.shape.get(DATA_AXIS, 1)

    def axis_size(self, axis: str) -> int:
        return self.mesh.shape.get(axis, 1)

    def sharding(self, *spec) -> NamedSharding:
        """NamedSharding on this context's mesh for a PartitionSpec."""
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self, ndim: int,
                       axes: Sequence[str] = (DATA_AXIS,)) -> NamedSharding:
        """Shard the leading (batch) dim over ``axes`` (default the data
        axis — a hybrid-mesh plan may pass ``("dcn", "data")``),
        replicate the rest.  Scalars (ndim 0) are replicated."""
        if ndim == 0:
            return self.replicated()
        lead = axes[0] if len(axes) == 1 else tuple(axes)
        return NamedSharding(self.mesh, P(lead, *([None] * (ndim - 1))))

    def shard_batch(self, tree, axes: Sequence[str] = (DATA_AXIS,)):
        """Device-put a host batch pytree sharded over the data axis.

        This is the per-chip host infeed replacing the reference's
        RDD-partition → task iterator feed (FeatureSet.scala:240-289).

        Single-process: a plain sharded ``device_put`` of the global batch.
        Multi-process (``jax.distributed``): each host holds only ITS slice
        of the global batch (``process_local_batch_slice``) and the global
        array is assembled with ``jax.make_array_from_process_local_data`` —
        the per-partition locality the reference gets from RDD partitioning
        (FeatureSet.scala:240-289); host 0's data never crosses hosts.
        """
        # batch_sharding(0) is replicated, so scalars (n_valid, seeds —
        # same value on every process) and batch arrays go through the
        # same call.
        return self._put_tree(
            tree, lambda ndim: self.batch_sharding(ndim, axes))

    def shard_batch_stacked(self, tree,
                            axes: Sequence[str] = (DATA_AXIS,)):
        """Device-put a K-STACKED super-batch (leading axis = inner step
        index, axis 1 = batch) for the fused multi-step dispatch
        (``ZOO_STEPS_PER_DISPATCH``, Estimator scan-K path).

        Axis 1 is sharded over the data axis — each chip holds the SAME
        rows of every inner batch it would hold under K=1, so the fused
        ``lax.scan`` sees per-step shards identical to K single
        dispatches.  Rank-<2 leaves (stacked per-step scalars like
        ``n_valid`` → shape [K]) are replicated.
        """
        def sharding_of(ndim: int) -> NamedSharding:
            if ndim < 2:
                return self.replicated()
            lead = axes[0] if len(axes) == 1 else tuple(axes)
            return NamedSharding(
                self.mesh, P(None, lead, *([None] * (ndim - 2))))

        return self._put_tree(tree, sharding_of)

    def _put_tree(self, tree, sharding_of):
        """Shared device-put scaffolding for the batch shard paths:
        single-process does a sharded ``device_put`` per leaf;
        multi-process assembles the global array from this host's rows
        via ``jax.make_array_from_process_local_data``.  ``sharding_of``
        maps leaf ndim -> NamedSharding."""
        if jax.process_count() > 1:
            def put(x):
                x = np.asarray(x)
                return jax.make_array_from_process_local_data(
                    sharding_of(np.ndim(x)), x)
            return jax.tree_util.tree_map(put, tree)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(
                np.asarray(x), sharding_of(np.ndim(x))),
            tree,
        )

    def next_rng(self) -> jax.Array:
        if self._step_rng is None:
            self._step_rng = jax.random.PRNGKey(self.seed)
        self._step_rng, out = jax.random.split(self._step_rng)
        return out


def cast_floats(tree, dtype):
    """Cast floating-point leaves of a pytree to ``dtype`` (None = no-op).

    The mixed-precision primitive: integer leaves (labels, token ids) pass
    through untouched.
    """
    if dtype is None:
        return tree
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.result_type(a), jnp.floating) else a,
        tree,
    )


def _resolve_compute_dtype(spec, platform: str):
    """Resolve the compute dtype policy.

    Precedence: explicit arg/conf > ZOO_COMPUTE_DTYPE env > platform default
    (bfloat16 on TPU — the MXU's native rate; f32 elsewhere so CPU-mesh tests
    stay bit-accurate vs oracles).
    """
    if spec is None:
        spec = os.environ.get("ZOO_COMPUTE_DTYPE")
    if spec is None:
        return jnp.bfloat16 if platform == "tpu" else None
    if spec in (jnp.bfloat16, jnp.float16, jnp.float32):
        return None if spec == jnp.float32 else spec
    s = str(spec).lower()
    if s in ("float32", "f32", "fp32", "none", ""):
        return None
    if s in ("bfloat16", "bf16"):
        return jnp.bfloat16
    if s in ("float16", "f16", "fp16"):
        return jnp.float16
    raise ValueError(f"unknown compute_dtype {spec!r}")


_LOCK = threading.Lock()
_CONTEXT: ZooContext | None = None  # guarded-by: _LOCK


def _infer_mesh_shape(
    devices: Sequence, axes: Sequence[str], shape: Mapping[str, int] | None
) -> dict[str, int]:
    n = len(devices)
    if shape is None:
        # Default: pure data parallelism — the reference's only inter-node
        # strategy (SURVEY.md §2.4) and the right default for dense training.
        return {a: (n if a == DATA_AXIS else 1) for a in axes}
    out = dict(shape)
    unknown = [a for a in axes if a not in out]
    given = math.prod(out.values())
    if n % given != 0:
        raise ValueError(
            f"mesh shape {out} does not divide device count {n}"
        )
    rest = n // given
    for a in unknown:
        out[a] = 1
    # Fold leftover devices into the data axis.
    if rest != 1:
        out[DATA_AXIS] = out.get(DATA_AXIS, 1) * rest
    return {a: out[a] for a in axes}


def init_zoo_context(
    conf: Mapping[str, object] | str | None = None,
    *,
    mesh_shape: Mapping[str, int] | None = None,
    mesh_axes: Sequence[str] | None = None,
    seed: int | None = None,
    platform: str | None = None,
    compute_dtype=None,
    dcn_shape: Mapping[str, int] | None = None,
    slice_groups=None,
    allow_idle: bool = False,
) -> ZooContext:
    """Initialise (or re-initialise) the global runtime context.

    Mirrors ``init_nncontext`` (reference pyzoo/zoo/common/nncontext.py:104):
    the reference builds a SparkContext with a tuned conf; here we discover
    devices, build a Mesh, and fix numerics policy.

    Args:
      conf: optional dict (or app-name string, accepted for API fidelity with
        ``init_nncontext("app name")``) of engine options: ``seed``,
        ``mesh_shape``, ``platform``.
      mesh_shape: e.g. ``{"data": 8}`` or ``{"data": 4, "model": 2}``; missing
        axes get size 1 and leftover devices fold into ``data`` — also
        when ``data`` is given: ``{"data": 4}`` on eight devices is an
        eight-device mesh (ROADMAP D10).
      mesh_axes: axis names, outermost first.
      platform: force a jax platform ("cpu", "tpu"); tests use cpu meshes.
      dcn_shape: multi-slice extents, e.g. ``{"data": 2}`` for
        data-parallelism across 2 TPU slices — the mesh is then built by
        :func:`analytics_zoo_tpu.parallel.hybrid_mesh` with ``mesh_shape``
        as the per-slice (ICI) extents, and every ``fit``/``predict``
        through this context trains multi-slice.
      slice_groups: explicit per-slice device groups for ``dcn_shape``
        (CI emulation / exotic topologies; default: ``device.slice_index``).
      allow_idle: let the hybrid mesh leave surplus per-slice devices idle
        (otherwise a per-slice shape smaller than the slice is an error).
    """
    global _CONTEXT
    if isinstance(conf, ZooConfig):
        cfg = dataclasses.replace(conf)  # never mutate the caller's config
    else:
        if isinstance(conf, str):
            conf = {"app_name": conf}
        conf = dict(conf or {})
        known = {f.name for f in dataclasses.fields(ZooConfig)}
        cfg = ZooConfig(**{k: v for k, v in conf.items() if k in known})
        unknown = set(conf) - known
        if unknown:
            raise ValueError(
                f"unknown conf keys {sorted(unknown)}; "
                f"valid: {sorted(known)}")
    # Keyword args use None as the "not given" sentinel, so an explicitly
    # passed kwarg ALWAYS wins over the conf/config value (no ambiguity
    # when the explicit value happens to equal a default).
    if seed is not None:
        cfg.seed = int(seed)
    if mesh_shape is not None:
        cfg.mesh_shape = mesh_shape
    if mesh_axes is not None:
        cfg.mesh_axes = tuple(mesh_axes)
    if platform is not None:
        cfg.platform = platform
    if compute_dtype is not None:
        cfg.compute_dtype = compute_dtype

    # metrics/ stays off this module's import path (it imports nothing of
    # common/, and the reverse holds at import time)
    from analytics_zoo_tpu.metrics import get_registry, span

    # the first jax.devices() of a process starts the backend: on a TPU
    # that is the seconds it takes to reach the chip
    with span("zoo.context.init", observe=get_registry().gauge(
            "zoo_context_init_seconds",
            "the last init_zoo_context: backend start (the first call of "
            "a process reaches the chip there) and mesh").set):
        devices = jax.devices(cfg.platform) if cfg.platform else jax.devices()
        axes = tuple(cfg.mesh_axes)
        if slice_groups is not None and not dcn_shape:
            raise ValueError("slice_groups requires dcn_shape")
        if dcn_shape:
            # multi-slice: DCN-crossing axis outermost, per-slice ICI extents
            # from mesh_shape (see parallel.multihost.hybrid_mesh).  The FULL
            # axes tuple is kept — unlisted axes get size 1 exactly like the
            # plain path, so PartitionSpecs naming them keep working.
            from analytics_zoo_tpu.parallel.multihost import hybrid_mesh

            ici = dict(cfg.mesh_shape or {})
            if not ici:
                raise ValueError("dcn_shape requires an explicit mesh_shape "
                                 "(the per-slice ICI extents)")
            mesh = hybrid_mesh(ici, dict(dcn_shape), axes=axes,
                               devices=devices, slice_groups=slice_groups,
                               allow_idle=allow_idle)
            devices = list(mesh.devices.ravel())
        else:
            shape = _infer_mesh_shape(devices, axes, cfg.mesh_shape)
            n_used = math.prod(shape.values())
            dev_array = np.asarray(devices[:n_used]).reshape(
                [shape[a] for a in axes])
            mesh = Mesh(dev_array, axes)
        ctx = ZooContext(
            mesh=mesh, platform=devices[0].platform, seed=cfg.seed,
            compute_dtype=_resolve_compute_dtype(
                cfg.compute_dtype, devices[0].platform),
            config=cfg,
        )
    with _LOCK:
        _CONTEXT = ctx
    logger.info(
        "init_zoo_context: %d %s device(s), mesh %s",
        len(devices), ctx.platform, dict(mesh.shape),
    )
    return ctx


def get_zoo_context() -> ZooContext:
    """Current context, creating a default (all-devices DP mesh) on demand.

    Matches the reference's lazy ``getOrCreateSparkContext``
    (pyzoo/zoo/common/nncontext.py:127-135).
    """
    global _CONTEXT
    with _LOCK:
        if _CONTEXT is None:
            pass  # created below outside the lock (init takes the lock)
        else:
            return _CONTEXT
    return init_zoo_context()


def num_devices() -> int:
    return get_zoo_context().num_devices
