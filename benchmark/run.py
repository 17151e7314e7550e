"""One run of one cell of ``BENCHMARK.json``:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell.  Set-up (imports, data and weights from the seed,
compilation or the load from the compile cache, the first steps) is timed
as ``setup_s``; then the window runs for ``--seconds``; then the device's
peak is read, the program's state is freed and the plain reference decides
``correct``.  The last line of standard output is the result.  Without a
TPU, or with fewer chips than the cell asks for, the run prints no result
and exits with code 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the script's own directory off the path (a module here must not shadow
# one of the standard library), the checkout's root on it
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

def device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int | None:
    """The peak on the fullest chip.  The TPU's allocator counts live
    arrays (``peak_bytes_in_use``) apart from the scratch it reserves for
    a loaded program's temporaries (``peak_bytes_reserved``), which it
    holds for as long as the program stays loaded: the chip holds both."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def registry_sums() -> dict:
    """The program's registry as {(name, label): (sum or value, count)}."""
    from analytics_zoo_tpu.metrics import snapshot

    out = {}
    for s in snapshot()["samples"]:
        label = (s.get("labels") or {}).get("label", "")
        if s["kind"] == "histogram":
            out[(s["name"], label)] = (float(s["sum"]), int(s["count"]))
        else:
            out[(s["name"], label)] = (float(s["value"]), 1)
    return out


class CompileCounter:
    """Counts XLA compilations through JAX's own monitoring events (a
    load from the persistent cache counts: it stalls a step as well)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0
        self.active = False

    def install(self) -> "CompileCounter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event: str, _duration: float, **_kw) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1


def read_layers(run: dict, result: dict, device_out: dict, job,
                trace_dir: str) -> dict:
    """The traced run's half: the capture into ``run``, the device's busy
    seconds into ``device_out``, the breakdown and what the step's phases
    left without a scope into ``result``; returns the per-layer metrics."""
    from benchmark import xplane
    from benchmark.manifest import load_module

    manifest, window = run["manifest"], run["window"]
    layer_metrics = os.path.join(manifest.home, "layer_metrics")
    run["trace_dir"] = trace_dir
    run["capture"] = capture = xplane.load(xplane.find_xplane(trace_dir))
    run["step_modules"] = {
        plane: [m for m in modules if m.name.startswith(job.STEP_PROGRAM)]
        for plane, modules in capture.modules.items()}
    if capture.device_ops:
        busy = [xplane.busy_seconds(ops)
                for ops in capture.device_ops.values()]
        device_out["busy_s"] = sum(busy) / len(busy)
        device_out["window_s"] = window["elapsed_s"]
        plane = next(iter(capture.device_ops))
        # a gap between programs by the host's span open then, where the
        # two clocks can be paired
        spans = load_module(os.path.join(layer_metrics, "_spans.py"))
        shifted, by_span = spans.on_device_clock(run), None
        if shifted is not None:
            def by_span(gaps):
                return spans.idle_by_span(gaps, shifted[plane])
        result["breakdown"] = {
            "device_ops": xplane.top(
                xplane.op_seconds(capture.device_ops[plane])),
            "idle_gaps": xplane.top(xplane.idle_by_place(
                capture.device_ops[plane],
                run["step_modules"].get(plane, []), job.steps_per_call,
                window["elapsed_s"], by_span))}
    metrics = {}
    for metric in manifest.per_layer(run["cell"]["name"]):
        value = manifest.reader(metric["name"])(run)
        if value is not None:
            metrics[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}
    phases = load_module(os.path.join(layer_metrics, "_phases.py"))
    found = phases.of_run(run)
    if found is not None:
        # the breakdown's two lists are the contract's; the operations
        # that carry no scope stand beside them, seconds of the window
        result["no_scope_ops"] = xplane.top(
            {name: ns / 1e9 for name, ns in found["unnamed"].items()})
    return metrics


def run_cell(manifest, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True,
             config_overrides: dict | None = None,
             traffic_overrides: dict | None = None,
             scratch: str | None = None, t_start: float | None = None):
    """Drive one cell and return (result, numbers table).  The keyword
    arguments are for the tests, which run toy sizes on the CPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = manifest.cell(workload)
    configuration = manifest.configuration(cell["config"], config_overrides)
    traffic = manifest.traffic(cell["traffic"], traffic_overrides)
    limits = manifest.limits(workload)

    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.common.compile_cache import (
        maybe_enable_persistent_cache,
    )

    ctx = init_zoo_context("benchmark " + workload, seed=seed & 0x7FFFFFFF)
    device = device_facts()
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell["chips"]):
        sys.stderr.write(f"benchmark: {workload} needs {cell['chips']} TPU "
                         f"chip(s), JAX found {device}\n")
        raise SystemExit(2)
    if require_tpu:
        manifest.peaks(device["kind"])   # an unknown kind fails before work
        maybe_enable_persistent_cache(os.path.join(manifest.root,
                                                   ".jax_cache"))
    if require_tpu and ctx.data_parallel_size != cell["chips"]:
        raise SystemExit(f"benchmark: mesh {dict(ctx.mesh.shape)} for a "
                         f"cell of {cell['chips']} chip(s)")

    compiles = CompileCounter().install()
    job = manifest.job(traffic["job"]).Job(configuration, traffic, seed,
                                           device["platform"])
    job.setup()
    before = registry_sums()

    trace_dir = None
    if trace:
        trace_dir = os.path.join(scratch or os.path.join(
            manifest.root, ".bench_scratch"), "trace-" + workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        # the device's planes alone: see benchmark/xplane.py
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles.active = True
    setup_s = time.perf_counter() - t_start
    try:
        window = job.window(seconds)
    finally:
        compiles.active = False
        if trace:
            jax.profiler.stop_trace()
    after = registry_sums()
    peak = memory_peak_bytes()
    job.free()

    numbers = job.numbers()
    from benchmark import compare

    correct, table = compare.verdict(numbers, limits)

    run = {
        "manifest": manifest, "cell": cell, "sizes": configuration.sizes,
        "configuration": configuration, "traffic": traffic,
        "window": window, "setup_s": setup_s, "device": device,
        "registry_before": before, "registry_after": after,
        "compiles_in_window": compiles.count, "capture": None,
        "step_modules": {},     # device plane -> its executed step programs
    }
    device_out = {**device, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"]}
    if trace:
        try:
            metrics = read_layers(run, result, device_out, job, trace_dir)
        finally:
            # the capture's directory outlives the readers: one added as a
            # file alone can reach it (``run["trace_dir"]``)
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"setup_s": setup_s, **window["end_to_end"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in manifest.end_to_end(workload)}
    result["metrics"] = metrics
    result["device"] = device_out
    result["compared"] = {k: [row["value"], row["limit"]]
                          for k, row in table.items()}
    return result, table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.manifest import Manifest

    result, table = run_cell(Manifest(ROOT), args.workload, args.seed,
                             args.seconds, bool(args.trace),
                             t_start=T_START)
    sys.stdout.flush()
    for name, row in table.items():
        sys.stderr.write(f"compared {name}: {row['value']:.6g} "
                         f"(limit {row['limit']:.6g}) at {row['at']}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
