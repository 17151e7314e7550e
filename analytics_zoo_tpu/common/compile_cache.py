"""Persistent compile plane — pay XLA compilation once, not per process.

Every process start (and every new batch/bucket shape) pays a full XLA
compile before the first useful step; on a big model that is minutes of
dead time before the first step.  This module is the shared cure, three
pieces:

1. :func:`maybe_enable_persistent_cache` — turn on JAX's on-disk
   compilation cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the
   cache was placed from outside: JAX reads that variable itself, that
   directory IS the cache and this module points JAX nowhere else.
   Otherwise the directory is an explicit path or
   ``ZOO_COMPILE_CACHE=<dir>``.  A second process compiling the SAME
   program (same HLO, same shapes/shardings/flags) deserializes the
   executable instead of re-running XLA — the moral equivalent of
   OpenVINO's saved IR.
2. :func:`timed_compile` — the one choke point every AOT
   ``.lower().compile()`` in the repo goes through: it times the compile
   into ``zoo_compile_seconds{label=...}`` and classifies it as a
   persistent-cache hit or miss (``zoo_compile_cache_hits_total`` /
   ``zoo_compile_cache_misses_total``), so cold-vs-warm shows up in
   ``/varz`` instead of being folded invisibly into the first step.
3. AOT warmup callers — ``Estimator.warmup(batch)`` and
   ``InferenceModel.warmup(...)`` lower+compile their steps through this
   module BEFORE the first real batch/request, so user-visible latency
   starts at step one, not compile one.

Hit/miss classification is observational: a compile that completes
without adding an entry under the enabled cache directory was served
from it (every compile is persisted — ``min_compile_time_secs`` is
pinned to 0).  With no cache dir enabled every compile counts as a miss.
"""

from __future__ import annotations

import logging
import os
import threading
import time

logger = logging.getLogger("analytics_zoo_tpu")

_LOCK = threading.Lock()
_ENABLED_DIR: str | None = None  # guarded-by: _LOCK

# Histogram bounds shaped for compile times: sub-second CPU toys through
# multi-minute TPU programs.
COMPILE_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                   60.0, 120.0, 300.0)


def cache_dir() -> str | None:
    """The enabled persistent-cache directory, or None."""
    return _ENABLED_DIR


def _persist_everything() -> None:
    import jax

    # The default min-compile-time/min-entry-size heuristics would skip
    # exactly the small-but-frequent programs a dispatch-bound job
    # recompiles most, and the hit/miss classifier in timed_compile
    # counts on every compile landing in the directory.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def maybe_enable_persistent_cache(path: str | None = None) -> str | None:
    """Enable JAX's persistent compilation cache; idempotent.

    Resolution: ``JAX_COMPILATION_CACHE_DIR`` env (the cache was placed
    from outside; ``path`` and ``ZOO_COMPILE_CACHE`` are then ignored and
    JAX's own directory setting is left alone) > explicit ``path`` >
    ``ZOO_COMPILE_CACHE`` env.  Returns the enabled directory, or None
    when none is set (no-op — the in-memory jit cache still applies).
    Safe to call from every train / predict entry point: the first call
    wins and later calls with the same (or no) path are no-ops; a later
    call with a DIFFERENT explicit path re-points the cache and logs the
    switch.
    """
    global _ENABLED_DIR
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if external is not None:
        external = os.path.abspath(external)
        with _LOCK:
            if _ENABLED_DIR != external:
                _persist_everything()
                logger.info("persistent compile cache at %s "
                            "(JAX_COMPILATION_CACHE_DIR)", external)
                _ENABLED_DIR = external
        return external
    if path is None and _ENABLED_DIR is not None:
        # no-arg call after an explicit enable: the first call won —
        # do NOT let the env re-point a deliberately chosen directory
        return _ENABLED_DIR
    resolved = path or os.environ.get("ZOO_COMPILE_CACHE") or None
    if resolved is None:
        return _ENABLED_DIR
    resolved = os.path.abspath(resolved)
    with _LOCK:
        if _ENABLED_DIR == resolved:
            return _ENABLED_DIR
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        os.makedirs(resolved, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", resolved)
        _persist_everything()
        # The cache singleton initializes LAZILY on the first compile —
        # if any jit ran before this call (context init, PRNG helpers),
        # it memoized "no cache dir" and would silently ignore the
        # directory we just configured.  Reset so the next compile
        # re-initializes against it.
        compilation_cache.reset_cache()
        if _ENABLED_DIR is not None:
            logger.info("compile cache re-pointed %s -> %s",
                        _ENABLED_DIR, resolved)
        else:
            logger.info("persistent compile cache enabled at %s", resolved)
        _ENABLED_DIR = resolved
    return _ENABLED_DIR


def disable_persistent_cache() -> None:
    """Turn the persistent cache back off (tests; symmetric teardown for
    :func:`maybe_enable_persistent_cache`).  A cache placed through
    ``JAX_COMPILATION_CACHE_DIR`` is JAX's to keep: only this module's
    record of it is dropped."""
    global _ENABLED_DIR
    with _LOCK:
        if _ENABLED_DIR is None:
            return
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            import jax
            from jax.experimental.compilation_cache import (
                compilation_cache,
            )

            jax.config.update("jax_compilation_cache_dir", None)
            compilation_cache.reset_cache()
        _ENABLED_DIR = None


def _cache_entries() -> int | None:
    """Number of executable entries in the enabled cache dir (None when
    disabled).  Only ``*-cache`` payload files count — the ``*-atime``
    companions are touched on reads and would misclassify hits."""
    if _ENABLED_DIR is None:
        return None
    try:
        return sum(1 for f in os.listdir(_ENABLED_DIR)
                   if f.endswith("-cache"))
    except OSError:
        return None


def _metrics(label: str):
    from analytics_zoo_tpu.metrics import get_registry

    reg = get_registry()
    return (
        reg.histogram("zoo_compile_seconds",
                      "wall time of AOT lower().compile() calls",
                      ("label",), buckets=COMPILE_BUCKETS)
        .labels(label=label),
        reg.counter("zoo_compile_cache_hits_total",
                    "AOT compiles served from the persistent cache",
                    ("label",)).labels(label=label),
        reg.counter("zoo_compile_cache_misses_total",
                    "AOT compiles that ran XLA (no persistent-cache "
                    "entry)", ("label",)).labels(label=label),
    )


def timed_compile(lowered, label: str, meta: dict | None = None):
    """``lowered.compile()`` with the compile plane's telemetry.

    Records ``zoo_compile_seconds{label=}`` and increments the
    hit/miss counter pair; returns the compiled executable.  ``lowered``
    is whatever ``jax.jit(f).lower(*args)`` returned.

    The HLO graph lint (``analytics_zoo_tpu.analysis.hlo``) rides this
    choke point: the lowered module text is inspected BEFORE the
    compile — f64 ops / host callbacks / unexpected all-gathers /
    oversized baked constants become logged findings, and the analytic
    cost features (matmul FLOPs, bytes, collective count/bytes,
    fused-dispatch count) land in ``zoo_hlo_*{label=}`` metrics, the
    flight recorder and the optional ``ZOO_HLO_REPORT_DIR`` JSON
    report.  Linting before compiling means a crash during XLA
    compilation still leaves "what was being compiled" in the flight
    ring; the JSON report alone is written AFTER the compile so the
    ``zoo-hlo-report/2`` row carries the measured compile
    wall-seconds.  ``meta`` is the compile context the lowered text
    cannot show (``plan`` / ``mesh_shape`` / ``steps_per_dispatch``),
    stamped into the report for the cost model's training join.
    Disable with ``ZOO_HLO_LINT=0``; lint errors never propagate into
    the compile.
    """
    from analytics_zoo_tpu.analysis.hlo import (
        maybe_lint_lowered,
        maybe_write_report,
    )

    rpt = maybe_lint_lowered(lowered, label, meta=meta,
                             defer_report=True)
    hist, hits, misses = _metrics(label)
    before = _cache_entries()
    t0 = time.perf_counter()
    exe = lowered.compile()
    dt = time.perf_counter() - t0
    hist.observe(dt)
    maybe_write_report(rpt, compile_seconds=dt)
    after = _cache_entries()
    # A true hit deserializes an EXISTING entry, so the dir must be
    # non-empty and unchanged.  (Residual blind spot: a cache dir whose
    # writes fail mid-stream — e.g. volume filled up after some entries
    # landed — still classifies later full compiles as hits; jax logs
    # the write failures.)
    hit = before is not None and after == before and (after or 0) > 0
    if hit:
        hits.inc()
    else:
        misses.inc()
    logger.debug("compile[%s]: %.3fs (%s)", label, dt,
                 "cache hit" if hit else "miss")
    return exe
