"""The Pallas kernel plane — hand-tuned kernels behind the compile
choke point.

Every kernel in this package follows one contract (docs/performance.md
"Kernel plane"):

* a pure-jnp/XLA **fallback** that is the numerical oracle — CPU runs
  it automatically, ``ZOO_KERNEL_INTERPRET=1`` forces the Pallas path
  in interpret mode for kernel-path CI coverage, and
  ``ZOO_KERNEL_FORCE_PALLAS=1`` routes to the real kernels for
  lowering-only checks (trace + ``lower(platforms=("tpu",))``, no chip);
* eager compiles route through :func:`kernel_step` so they lower via
  ``compile_step``/``timed_compile`` under a ``kernel_<name>`` label —
  persistent cache, ``zoo_compile_seconds`` and the HLO feature pipe
  see every kernel;
* selection is the plan's business, not the call site's: the
  ``kernel_rules`` table on :class:`ShardingPlan` (fifth rule table)
  maps scopes to kernel names, and consumers ask
  ``resolve_kernel(scope)`` — ``"xla"`` means the fallback, always.

This ``__init__`` must stay import-light: it is pulled in by
``ops/attention.py`` on every call and the negative pin asserts that
without ``ZOO_USE_PALLAS`` no kernel MODULE below it is imported.
"""

from __future__ import annotations

import sys

# kernel name -> module path, for the invocation-count aggregator; only
# modules ALREADY imported are consulted (the negative pin's contract)
_KERNEL_MODULES = {
    "flash_attention": "analytics_zoo_tpu.ops.pallas.flash_attention",
    "fused_adam": "analytics_zoo_tpu.ops.pallas.fused_adam",
    "fused_softmax_xent":
        "analytics_zoo_tpu.ops.pallas.fused_softmax_xent",
    "int8_matmul": "analytics_zoo_tpu.ops.pallas.int8_matmul",
    "grouped_matmul": "analytics_zoo_tpu.ops.pallas.grouped_matmul",
    # the walk's kernels are ``kda_scan.py``'s; the caller counts
    "kda_scan": "analytics_zoo_tpu.ops.linear_attention",
}

_PLANNED_STEPS: dict = {}


def kernel_step(name: str, fn):
    """Compile ``fn`` through the choke point under the
    ``kernel_<name>`` label and cache the :class:`PlannedStep`.

    This is how EAGER kernel invocations (bench legs, serving helpers)
    get the same treatment as a train step: persistent-cache
    hit/miss counters, ``zoo_compile_seconds{label="kernel_<name>"}``,
    the HLO lint/feature pipe and flight records.  Calls from inside a
    trace must NOT come here — they inline into the enclosing step's
    program and are already covered by its label."""
    key = (name, fn)
    step = _PLANNED_STEPS.get(key)
    if step is None:
        from analytics_zoo_tpu.parallel.plan import compile_step

        step = compile_step(fn, label=f"kernel_{name}")
        _PLANNED_STEPS[key] = step
    return step


def kernel_invocation_counts() -> dict:
    """Per-kernel ``{"pallas": n, "fallback": n}`` routing counters,
    aggregated over the kernel modules that are actually imported —
    an unimported kernel contributes nothing (so the ZOO_USE_PALLAS
    negative pin can assert absence here too)."""
    out = {}
    for name, modpath in _KERNEL_MODULES.items():
        mod = sys.modules.get(modpath)
        counts = getattr(mod, "invocation_counts", None)
        if counts:
            out[name] = dict(counts)
    return out


def record_kernel_bytes(label: str, measured_bytes: int,
                        predicted_bytes: int | None = None) -> dict:
    """Publish the ``zoo_kernel_*bytes*`` gauges for one kernel label —
    closing the bytes loop the way ``record_mem_gauges`` does for chip
    memory: measured HLO bytes-accessed (hlo.py's custom_call
    attribution) vs the cost model's analytic prediction."""
    from analytics_zoo_tpu.metrics import get_registry

    reg = get_registry()
    lab = ("label",)
    reg.gauge("zoo_kernel_measured_bytes",
              "measured HLO bytes-accessed for a kernel label",
              lab).labels(label=label).set(int(measured_bytes))
    doc = {"measured_bytes": int(measured_bytes)}
    if predicted_bytes is not None:
        reg.gauge("zoo_kernel_predicted_bytes",
                  "cost-model predicted bytes for a kernel label",
                  lab).labels(label=label).set(int(predicted_bytes))
        doc["predicted_bytes"] = int(predicted_bytes)
        if predicted_bytes > 0:
            rel = abs(measured_bytes - predicted_bytes) / predicted_bytes
            reg.gauge("zoo_kernel_bytes_rel_error",
                      "|measured - predicted| / predicted bytes for a "
                      "kernel label", lab).labels(label=label).set(rel)
            doc["rel_error"] = rel
    return doc
