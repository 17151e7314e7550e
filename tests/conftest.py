"""Test harness: force an 8-device CPU mesh so all psum/pjit/sharding code
paths run without TPUs — the analogue of the reference's local[4] Spark
testing strategy (SURVEY.md §4: pyzoo/test/zoo/pipeline/utils/test_utils.py
sets sparkConf local[4])."""

import faulthandler
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    )

# XLA's CPU client sizes its thread pool to the schedulable CPUs, and an
# in-process collective holds one pool thread per device until all eight
# have arrived.  On a host with eight cores or fewer, anything else on the
# pool (the feeder's transfers, the next step) leaves the last participant
# without a thread: the all-reduce wedges and the process aborts 40 s later
# (rendezvous.cc "Termination timeout") — under xdist load, in any long
# fit of a tiny model.  Children inherit the variable.
os.environ.setdefault("PJRT_NPROC", "32")

import jax  # noqa: E402

# The variable above is read when jax is first imported; the config knob
# also holds where something imported jax before this file ran.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# zoosan pytest plugin: under ZOO_SAN=1 the runtime sanitizer installs
# BEFORE any test imports the package, so every lock the package creates
# is wrapped and the whole quick tier doubles as a sanitizer workload.
# Findings are passive (tests assert on the ones they plant); whatever
# is left at session end is reported in the terminal summary, and
# ZOO_SAN_STRICT=1 turns leftovers into a failing exit status.
# ---------------------------------------------------------------------------

if os.environ.get("ZOO_SAN") == "1":
    from analytics_zoo_tpu.analysis import sanitizer as _zoosan

    _zoosan.install()

# ---------------------------------------------------------------------------
# Quick tier (VERDICT r03 weak #10): `pytest -m quick` runs a <2-minute
# subset covering the end-to-end slice (compile/fit/evaluate/predict on the
# CPU mesh) plus every fast subsystem — the per-commit gate.  The whole
# suite (examples retraining, transformer stacks, pipelines, the benchmark's
# toy runs) stays the pre-merge gate.  Files are tier-marked here centrally
# so new tests in these files inherit the marker.
# ---------------------------------------------------------------------------

QUICK_FILES = {
    "test_config.py", "test_tfrecord.py", "test_safe_pickle.py",
    "test_tensorboard.py", "test_dataset.py", "test_minimum_slice.py",
    "test_onnx.py", "test_image_ops.py", "test_inference.py",
    "test_serving.py", "test_keras2.py", "test_caffe.py",
    "test_layer_oracle_enforcement.py", "test_api_docs.py",
    "test_textset.py", "test_image3d.py", "test_transfer_learning.py",
    "test_layer_serialization.py", "test_metrics.py",
    "test_prefetch.py",  # host data plane + overlap of sleep-bound work
    "test_dispatch.py",  # fused scan-K dispatch + its count of dispatches
    "test_autotune.py",  # closed-loop autotune, resizing byte-identical
    "test_compile_cache.py",  # persistent compile plane
    "test_partitioner.py",  # unified partitioner, plans at two sizes
    "test_partition_rules.py",  # rule matching + path rendering
    "test_zoolint.py",  # static analysis + package-clean CI gate
    "test_zoosan.py",  # whole-program pass + runtime sanitizer
    "test_telemetry.py",  # ~9s incl. two actor spawns
    "test_fleet.py",  # serving fleet: claim protocol, autoscaler, kill -9
    "test_overlap.py",  # latency-hiding plane + bucketed-vs-two-phase
    "test_elastic.py",  # elastic runtime: membership, chaos, supervisor
    "test_zoowatch.py",  # federation plane: scrape/SLO + two e2e runs
    # test_actors.py left OUT since the spawn switch: interpreter
    # startup per actor makes it one of the slower files
}


# ---------------------------------------------------------------------------
# Order of the files.  Under `-p xdist --dist loadfile` a worker takes whole
# files from one queue, and the run ends when the last file handed out
# ends.  xdist sorts that queue by each file's number of tests, so a file of
# few long tests (a toy benchmark run a test) went out last and ran on alone
# while the other workers sat idle.  The queue follows the collection order
# here instead, and the costly files lead it, so that the short ones fill in
# at the end: first every file under ``bench_harness/`` (the benchmark's toy
# runs, 30-100 s a test, a new configuration's file among them), then the
# other files of 100 worker-seconds or more, longest first.  The order is a
# constant: every worker collects the same.  Tests keep their order within
# a file.
# ---------------------------------------------------------------------------

COSTLY_FILES = (
    "test_examples.py",
    "test_tpu_compile.py",
    "test_flash_attention.py",
    "test_latent_moe_decoder.py",
    "test_imagenet_zoo.py",
    "test_actors.py",
    "test_models.py",
    "test_looped_decoder.py",
    "test_tfpark.py",
    "test_moe_layer.py",
    "test_linear_attention.py",
    "test_ssd.py",
    "test_parallel.py",
    "test_pipeline_parallel.py",
)


def _cost_rank(item) -> int:
    path = str(item.fspath).replace(os.sep, "/")
    if "/tests/bench_harness/" in path:
        return 0
    name = os.path.basename(path)
    if name in COSTLY_FILES:
        return 1 + COSTLY_FILES.index(name)
    return 1 + len(COSTLY_FILES)


# ---------------------------------------------------------------------------
# A time limit on every test, its fixtures' setup and teardown included.
# ``faulthandler`` arms a timer on a thread of its own that needs no Python:
# a test stuck in native code (an XLA rendezvous on the 8-device mesh, say),
# where no signal handler would ever run, still has its stacks dumped and
# its process ended.  Under xdist the worker goes down, the test is
# reported failed and a new worker takes the rest of the queue.  300 s is
# more than twice the slowest test's 72-132 s under six workers (the whole
# compile of the kanana step for a v5e).
# ---------------------------------------------------------------------------

TEST_TIME_LIMIT_S = 300


def time_limit(seconds: float):
    """A ``pytest_runtest_protocol`` hook wrapper that ends the process,
    stacks dumped, once one test has run ``seconds``."""
    stderr = []

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_protocol(item, nextitem):
        if not stderr:
            # outside a test's phases fd 2 is the terminal, not the
            # capture's file, which the ended process would take with it
            stderr.append(os.fdopen(os.dup(2), "w"))
        faulthandler.dump_traceback_later(seconds, exit=True,
                                          file=stderr[0])
        try:
            yield
        finally:
            faulthandler.cancel_dump_traceback_later()

    return pytest_runtest_protocol


pytest_runtest_protocol = time_limit(TEST_TIME_LIMIT_S)


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """``--dist loadfile`` as xdist has it, but for a worker that goes
    down: xdist puts the file's unfinished tests back in the queue with the
    one that was running, which then takes each new worker down in turn
    and keeps the rest of its file from running.  Here it stays reported
    failed and is not run again."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class OnceScheduling(LoadFileScheduling):
        def remove_node(self, node):
            for unit in self.assigned_work.get(node, {}).values():
                running = next((test for test, done in unit.items()
                                if not done), None)
                if running is not None:
                    unit[running] = True
                    super().remove_node(node)
                    return running
            return super().remove_node(node)

    return OnceScheduling(config, log)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: fast per-commit tier (<2 min; see conftest)")
    config.addinivalue_line(
        "markers", "metrics: observability-subsystem telemetry tests "
        "(analytics_zoo_tpu.metrics; tier-1 — not marked slow)")
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in QUICK_FILES:
            item.add_marker(pytest.mark.quick)
    items.sort(key=_cost_rank)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    try:
        from analytics_zoo_tpu.analysis import sanitizer
    except Exception:
        return
    if not sanitizer.installed():
        return
    leftovers = sanitizer.findings()
    terminalreporter.section("zoosan (ZOO_SAN=1)")
    terminalreporter.line(
        f"runtime sanitizer active; {len(leftovers)} finding(s) left "
        "un-drained at session end"
        + (" — set ZOO_SAN_STRICT=1 to fail on these" if leftovers
           else ""))
    for f in leftovers[:25]:
        terminalreporter.line(
            f"  {f.path}:{f.line} [{f.rule}] {f.message[:100]}")


def pytest_sessionfinish(session, exitstatus):
    if os.environ.get("ZOO_SAN_STRICT") != "1":
        return
    try:
        from analytics_zoo_tpu.analysis import sanitizer
    except Exception:
        return
    if sanitizer.installed() and sanitizer.findings() \
            and session.exitstatus == 0:
        session.exitstatus = 1


@pytest.fixture()
def zoo_ctx():
    from analytics_zoo_tpu import init_zoo_context

    return init_zoo_context(seed=42)


@pytest.fixture()
def rng():
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _kernel_counts_as_a_fresh_process_has_them(request):
    """The benchmark's routing tests (``tests/bench_harness``) read the
    kernels' trace-time routing counters after one toy run and expect what
    a process that ran nothing else would hold; under xdist a worker has
    run other files first (an interpret-mode flash test leaves ``pallas``
    above 0 and ``fallback`` at 0, and ``routing_fault`` then names another
    kernel than the test expects).  Zero the counters of the kernel modules
    that are imported before each such test; tests elsewhere compare a
    count with its value before, and are left alone."""
    if "bench_harness" in str(request.node.fspath):
        import sys

        for name in ("analytics_zoo_tpu.ops.pallas.flash_attention",
                     "analytics_zoo_tpu.ops.pallas.grouped_matmul",
                     "analytics_zoo_tpu.ops.linear_attention"):
            counts = getattr(sys.modules.get(name), "invocation_counts",
                             None)
            if counts is not None:
                counts.update(dict.fromkeys(counts, 0))
    yield
