"""Estimator — the distributed training core.

TPU-native re-design of the reference's training stack:

- ``Estimator.train/evaluate`` facade (reference
  zoo/.../pipeline/estimator/Estimator.scala:65-183),
- ``InternalDistriOptimizer.train`` — the distributed driver
  (Topology.scala:1076-1259).

The reference's per-iteration machinery is two Spark jobs: (1) each task
forward/backwards its partition slice on core-local model replicas; (2)
gradient slices are shuffled to owner tasks, updated, and broadcast back
through the block manager (docs/docs/wp-bigdl.md:148-164).  Here the whole
iteration is ONE jit-compiled SPMD program: the global batch arrives sharded
over the mesh ``data`` axis, XLA partitions the forward/backward per chip,
inserts a reduce-scatter/all-gather (the ``psum``) over ICI for the gradient,
and fuses the optimizer update — donated buffers, so weights update in place
in HBM.

Also re-implemented with exact-state semantics instead of best-effort:

- triggers for validation/checkpoint (ZooTrigger),
- gradient clipping (constant / L2-norm, Topology.scala clipping setters),
- checkpoint + resume including the *data iterator* position,
- the retry-from-checkpoint failure loop (Topology.scala:1171-1253,
  ``bigdl.failure.retryTimes`` default 5).
"""

from __future__ import annotations

import contextvars
import dataclasses
import logging
import os
import pickle
from analytics_zoo_tpu.common.safe_pickle import (
    safe_load,
)
import queue
import threading
import time
import weakref
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from analytics_zoo_tpu.common.engine import (
    ZooContext,
    cast_floats,
    get_zoo_context,
)
from analytics_zoo_tpu.common.triggers import (
    EveryEpoch,
    MaxEpoch,
    TrainingState,
    ZooTrigger,
)
from analytics_zoo_tpu.feature.dataset import FeatureSet
from analytics_zoo_tpu.metrics import (
    StepMetrics,
    StragglerDetector,
    get_flight_recorder,
    get_health,
    get_registry,
    maybe_start_from_env,
    record_device_memory,
    register_predump_hook,
    span,
)

logger = logging.getLogger("analytics_zoo_tpu")

_SENTINEL = object()  # feeder-exhausted marker


def _process_shard() -> tuple[int, int] | None:
    """(process_index, process_count) under multi-host jax, else None.

    Handed to ``FeatureSet.batches`` so each host materializes only its rows
    of every global batch (per-partition locality, the role of the
    reference's RDD partitioning — FeatureSet.scala:240-289); see
    ``parallel.multihost.process_local_batch_slice``.
    """
    if jax.process_count() > 1:
        return (jax.process_index(), jax.process_count())
    return None


from analytics_zoo_tpu.ops.moe import collect_aux_cost as _collect_aux_cost
from analytics_zoo_tpu.pipeline.api.keras.engine import training_targets


def _publish_state_gauges(state) -> None:
    """What layers report a step through the layer-state channel, of the
    epoch's last step, as gauges: a looped decoder's per-pass numbers
    (``zoo_loop_exit_mass{label=<pass>}``, ``zoo_loop_pass_loss{label=
    <pass>}``) and a routed decoder's per-layer counts
    (``zoo_moe_held_assignments{label=<routed layer>}``,
    ``zoo_moe_load_max_over_mean{label=<routed layer>}``,
    ``zoo_moe_walk_windows{label=<routed layer>}``) with its
    ``zoo_moe_dropped_assignments``, and a KDA layer's numbers
    (``zoo_kda_chunk_log_decay_min{label=<KDA layer>}``,
    ``zoo_kda_sub_block_log_decay_min{label=<KDA layer>}``,
    ``zoo_kda_state_rms{label=<KDA layer>}``) and an SSD layer's
    (``zoo_ssd_chunk_log_decay_min{label=<SSD layer>}``,
    ``zoo_ssd_dt_max{label=<SSD layer>}``,
    ``zoo_ssd_state_rms{label=<SSD layer>}``).  Called after the epoch's
    closing sync and at no other time: the state is then computed, so the
    fetch waits for nothing."""
    if not isinstance(state, dict):
        return
    for key, family, text in (
            ("loop_exit_mass", "zoo_loop_exit_mass",
             "mean exit probability of a looped decoder's pass over the "
             "last step's tokens"),
            ("loop_pass_loss", "zoo_loop_pass_loss",
             "mean cross-entropy of a looped decoder's pass over the last "
             "step's tokens"),
            ("moe_held_assignments", "zoo_moe_held_assignments",
             "assignments of the last step's tokens that fell on the "
             "experts a routed layer holds here, and were multiplied"),
            ("moe_load_max_over_mean", "zoo_moe_load_max_over_mean",
             "rows of the fullest expert held over the mean of the held "
             "experts, a routed layer, in the last step"),
            ("moe_walk_windows", "zoo_moe_walk_windows",
             "windows of R sorted rows that a routed layer's walk of its "
             "held rows ran in the last step: 1 in an ordinary step, 0 "
             "with nothing held, 2 or more past R"),
            ("kda_chunk_log_decay_min", "zoo_kda_chunk_log_decay_min",
             "most negative log-decay that any key channel of a KDA "
             "layer summed to over any chunk of the last step: what the "
             "chunked form's exponentials have to survive"),
            ("kda_sub_block_log_decay_min",
             "zoo_kda_sub_block_log_decay_min",
             "most negative log-decay that any key channel of a KDA "
             "layer summed to from the first row of any sub-block to its "
             "last in the last step: under -80 (ops.linear_attention."
             "CLAMP) the chunked form is no longer exact"),
            ("kda_state_rms", "zoo_kda_state_rms",
             "root mean square of a KDA layer's recurrent states after "
             "the last token of the last step's sequences"),
            ("ssd_chunk_log_decay_min", "zoo_ssd_chunk_log_decay_min",
             "most negative sum of dt A that any head of an SSD (Mamba-2) "
             "layer reached over any chunk of the last step: how far a "
             "head forgets inside one chunk"),
            ("ssd_dt_max", "zoo_ssd_dt_max",
             "largest step dt of any head and token of an SSD layer in "
             "the last step"),
            ("ssd_state_rms", "zoo_ssd_state_rms",
             "root mean square of an SSD layer's states after the last "
             "token of the last step's sequences")):
        if key in state:
            gauge = get_registry().gauge(family, text, ("label",))
            for t, value in enumerate(np.asarray(state[key]), start=1):
                gauge.labels(label=str(t)).set(float(value))
    if "moe_dropped_assignments" in state:
        get_registry().gauge(
            "zoo_moe_dropped_assignments",
            "assignments on held experts that the last step did not "
            "multiply, over the routed layers: the routed layer has no "
            "capacity, so 0").set(float(state["moe_dropped_assignments"]))
    for child in state.values():
        _publish_state_gauges(child)


def _normalize_grad_clip(grad_clip):
    """Canonical grad-clip spec shared by every train-step builder:
    ``None | ("l2norm", max) | ("const", lo, hi)``; a bare scalar is
    accepted as a max-norm.  Tag AND arity are validated here so a bad
    spec fails at build time, not from inside a jit trace."""
    if grad_clip is None:
        return None
    if not isinstance(grad_clip, (tuple, list)):
        return ("l2norm", float(grad_clip))
    t = tuple(grad_clip)
    if len(t) == 2 and t[0] == "l2norm":
        return ("l2norm", float(t[1]))
    if len(t) == 3 and t[0] == "const":
        return ("const", float(t[1]), float(t[2]))
    raise ValueError(f"unknown grad clip {grad_clip!r}")


def _clip_grads(grads, grad_clip):
    grad_clip = _normalize_grad_clip(grad_clip)
    if grad_clip is None:
        return grads
    if grad_clip[0] == "const":
        _, lo, hi = grad_clip
        return jax.tree_util.tree_map(lambda g: jnp.clip(g, lo, hi), grads)
    _, max_norm = grad_clip
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def _chunk_batches(batch_iter, k: int):
    """Group a batch stream into K-sized chunks for the fused dispatch.

    Yields ``("scan", [b_0..b_{k-1}])`` for every full chunk and
    ``("single", b)`` per leftover batch — the partial tail of an epoch
    (or of a mid-epoch resume window) degrades to the K=1 step, so the
    optimizer sees exactly the same batch sequence as an unfused run.
    """
    chunk = []
    for b in batch_iter:
        chunk.append(b)
        if len(chunk) == k:
            yield ("scan", chunk)
            chunk = []
    for b in chunk:
        yield ("single", b)


def _chunk_batches_dynamic(batch_iter, k_fn):
    """Dynamic-K chunker for the autotune plane (feature/autotune.py):
    the target K is re-read from ``k_fn()`` at every CHUNK boundary, so
    the controller's hill-climb takes effect within one dispatch of a
    decision while any in-flight chunk keeps the size it started with.

    The batch SEQUENCE is untouched — only the grouping changes — and
    per-inner-step RNG folds on the global step index, so the loss
    trajectory is bit-identical for every K schedule this can emit
    (the same contract :func:`_chunk_batches` rides).  K=1 chunks are
    emitted as ``("single", b)`` so they dispatch the plain (non-scan)
    program, exactly like the static K=1 path; a leftover tail degrades
    to singles like the static chunker.
    """
    chunk = []
    k = max(1, int(k_fn()))
    for b in batch_iter:
        if k <= 1:
            yield ("single", b)
            k = max(1, int(k_fn()))
            continue
        chunk.append(b)
        if len(chunk) == k:
            yield ("scan", chunk)
            chunk = []
            k = max(1, int(k_fn()))
    for b in chunk:
        yield ("single", b)


class _DeviceFeeder:
    """Double-buffered host→device infeed.

    A background thread assembles the next host batch and dispatches its
    (async) ``device_put`` while the devices run the current step — the
    host/device overlap SURVEY.md §7 names hard-part #1.  Plays the role of
    the reference's per-partition RDD iterators keeping executors fed
    (FeatureSet.scala:240-289), minus the Spark scheduling gap between
    iterations.

    Under ``ZOO_STEPS_PER_DISPATCH > 1`` the estimator hands it the
    ``_chunk_batches`` stream and a shard_fn that STACKS each full chunk
    into a [K, batch, ...] super-batch (``ZooContext.shard_batch_stacked``)
    — the queue then double-buffers super-batches, composing unchanged
    with ``ZOO_INFEED_DEPTH`` and the PR-4 prefetch plane upstream.
    """

    _END = object()

    def __init__(self, batches, shard_fn, depth: int = 2,
                 heartbeat=None, on_exit=None, metrics=None, context=None):
        """``metrics``: the fit's :class:`StepMetrics`, whose two
        ``feed_*`` histograms the thread observes.  ``context``: the
        :class:`contextvars.Context` the thread runs under, by default a
        copy of the caller's, so that the thread's ``zoo.feed.*`` spans
        carry the caller's ``fit`` and open span as parent."""
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err: BaseException | None = None
        on_batch = metrics.feed_host_batch.observe if metrics else None
        on_shard = metrics.feed_shard.observe if metrics else None

        def run():
            try:
                it = iter(batches)
                while True:
                    # a batch stream that dies mid-gather closes this
                    # span on its way out, the exception's type in args
                    with span("zoo.feed.batch", observe=on_batch):
                        b = next(it, self._END)
                    if b is self._END:
                        break
                    if heartbeat is not None:
                        heartbeat()  # /healthz: the feeder is alive
                    with span("zoo.feed.shard", observe=on_shard):
                        item = shard_fn(b)
                    with span("zoo.feed.blocked"):
                        while not self._stop.is_set():
                            try:
                                self._q.put(item, timeout=0.1)
                                break
                            except queue.Full:
                                # keep beating while blocked on a full
                                # queue: waiting for the consumer (e.g.
                                # through a multi-minute first-step
                                # compile) is not being wedged
                                if heartbeat is not None:
                                    heartbeat()
                                continue
                    if self._stop.is_set():
                        return
            except BaseException as e:  # re-raised on the consumer side
                self._err = e
            finally:
                # on_exit runs ON THIS THREAD, sequenced after every
                # beat above — the estimator uses it to unregister the
                # infeed health component, so a feeder that finished
                # early (small epoch fully buffered) cannot read as
                # stale during a slow step, and no beat can resurrect
                # the component after its unregister
                if on_exit is not None:
                    try:
                        on_exit()
                    except Exception:
                        pass
                while not self._stop.is_set():
                    try:
                        self._q.put(self._END, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        if context is None:
            context = contextvars.copy_context()
        self._thread = threading.Thread(
            target=context.run, args=(run,), daemon=True,
            name="zoo-infeed")
        self._thread.start()

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._END:
                if self._err is not None:
                    raise self._err
                return
            yield item

    def stop(self):
        self._stop.set()


def _gather_for_save(tree):
    """Multi-host: replicate plan-sharded device leaves SPMD — every
    process participates — so the single writer's host conversion can
    read the full value (``np.asarray`` on a non-fully-addressable
    ``jax.Array`` raises).  Fully-addressable leaves (every single-host
    array, replicated multi-host state) pass through untouched, so the
    pre-partitioner save path is byte-for-byte unchanged."""
    from jax.sharding import NamedSharding, PartitionSpec

    def fix(leaf):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable \
                and isinstance(leaf.sharding, NamedSharding):
            repl = NamedSharding(leaf.sharding.mesh, PartitionSpec())
            # zoolint: disable=raw-jit -- SPMD replicate-identity (one trivial all-gather per leaf shape, deduped by jit's own cache); not a model program the compile plane should meter
            return jax.jit(lambda a: a, out_shardings=repl)(leaf)
        return leaf

    return jax.tree_util.tree_map(fix, tree)


def _async_checkpoint_enabled() -> bool:
    """``ZOO_ASYNC_CHECKPOINT`` env gate, default ON.  ``0`` forces the
    serialization+rename back onto the caller's thread (the pre-overlap
    behavior) — the conservative fallback."""
    raw = os.environ.get("ZOO_ASYNC_CHECKPOINT")
    if raw is None:
        return True
    s = str(raw).strip().lower()
    if s in ("", "1", "true", "yes", "on"):
        return True
    if s in ("0", "false", "no", "off"):
        return False
    raise ValueError(
        f"ZOO_ASYNC_CHECKPOINT must be a boolean "
        f"(1/0/true/false/yes/no/on/off), got {raw!r}")


# ---------------------------------------------------------------------------
# Shutdown-ordering fix (ISSUE 16): the SIGTERM flight-dump handler
# (metrics/flight.py, PR 2) and the async checkpoint writer thread
# (PR 14) used to race at process death — the dump could be written
# while the daemon writer was mid-pickle, so the postmortem's final
# ``ckpt`` event said "start" with no complete/error, and the writer
# died silently with the process.  Every live _Checkpointer registers
# here; the flight recorder runs the flush (bounded by
# ZOO_ELASTIC_GRACE_MS) BEFORE snapshotting the ring, so a SIGTERM dump
# records the snapshot as flushed-or-failed, never as a mystery.
# ---------------------------------------------------------------------------

_live_ckpt_lock = threading.Lock()
# keyed by id(): _Checkpointer is a dataclass (eq, no hash), so a
# WeakSet cannot hold it
_live_checkpointers: "weakref.WeakValueDictionary" = (  # guarded-by: _live_ckpt_lock
    weakref.WeakValueDictionary())


def _dump_flush_grace_s() -> float:
    """Lenient runtime read of ZOO_ELASTIC_GRACE_MS (the eager
    validation lives in ZooConfig; this path runs inside a dying
    process and must never raise)."""
    try:
        return max(0.0, int(os.environ.get("ZOO_ELASTIC_GRACE_MS",
                                           "5000")) / 1e3)
    except (TypeError, ValueError):
        return 5.0


def _flush_checkpointers_for_dump() -> None:
    with _live_ckpt_lock:
        cks = list(_live_checkpointers.values())
    for c in cks:
        c._flush_for_dump()


@dataclasses.dataclass
class _Checkpointer:
    """Snapshot (params, opt_state, model state, step/epoch, iterator pos).

    Role of BigDL's ``model.<iter>`` + ``optimMethod.<iter>`` snapshots
    (Topology.scala:245-255), plus data-iterator state the reference never
    checkpointed (its RDD iterators restart from scratch on resume).

    Saves are ASYNC (the orbax-style plan of SURVEY.md §5): the caller's
    thread only dispatches device-side copies of the live buffers (so the
    next step's donation can't touch them), while D2H transfer, pickling
    and the atomic rename happen on a background thread.  At most one save
    is in flight; a newer save (and ``latest``/``list``) waits for it.

    Latency-hiding plane (ISSUE 15): the caller-visible stall is recorded
    per save into ``zoo_ckpt_stall_seconds``; the writer thread runs as
    the ``checkpoint_writer`` health component and records ``ckpt``
    flight events (start/complete/error); each completed snapshot
    atomically updates a ``LATEST`` pointer file AFTER the snapshot's own
    atomic rename, so a kill -9 at any point leaves the pointer naming
    the previous COMPLETE snapshot.  ``ZOO_ASYNC_CHECKPOINT=0`` runs the
    write inline (synchronous fallback) — the stall histogram then
    measures the full gather+serialize+rename.
    """

    path: str
    over_write: bool = True
    keep: int = 3

    LATEST = "LATEST"

    def __post_init__(self):
        self._pending: threading.Thread | None = None
        self._pending_err: BaseException | None = None
        reg = get_registry()
        self._stall_hist = reg.histogram(
            "zoo_ckpt_stall_seconds",
            "train-thread stall per checkpoint save: join of the "
            "previous in-flight write + device-side snapshot dispatch "
            "(the whole gather+serialize+rename when "
            "ZOO_ASYNC_CHECKPOINT=0)")
        self._write_hist = reg.histogram(
            "zoo_ckpt_write_seconds",
            "background D2H gather + serialization + atomic-rename time "
            "per snapshot")
        self._writes = reg.counter(
            "zoo_ckpt_writes_total", "completed checkpoint snapshots")
        with _live_ckpt_lock:
            _live_checkpointers[id(self)] = self
        register_predump_hook(_flush_checkpointers_for_dump)

    def _flush_for_dump(self):
        """Bounded join of the in-flight async write so a flight dump
        (SIGTERM/exit/crash) contains this snapshot's final ``ckpt``
        complete/error event.  Never raises, never unbounded: a wedged
        writer only delays the dump by the grace window."""
        t = self._pending
        if t is not None and t.is_alive():
            t.join(timeout=_dump_flush_grace_s())

    def _wait(self):
        if self._pending is not None:
            self._pending.join()
            self._pending = None
            if self._pending_err is not None:
                err, self._pending_err = self._pending_err, None
                raise err

    FORMAT_VERSION = 1

    def save(self, tag: str, payload: dict) -> str:
        fname = os.path.join(self.path, f"ckpt-{tag}.pkl")
        # Multi-host: exactly one writer.  Every process calls save(),
        # but only process 0 touches the shared checkpoint dir —
        # concurrent writers racing os.replace on shared storage would
        # interleave half-written snapshots.  Plan-sharded leaves
        # (fsdp/zero1) are replicated SPMD FIRST — all processes
        # participate in that collective, THEN non-writers return —
        # so the writer's host gather sees every shard.
        t0 = time.perf_counter()
        shard = _process_shard()
        if shard is not None:
            payload = _gather_for_save(payload)
            if shard[0] != 0:
                return fname
        self._wait()
        os.makedirs(self.path, exist_ok=True)
        # Device-side copies: cheap dispatches; the live arrays stay free
        # to be donated by the next train step.
        snap = jax.tree_util.tree_map(
            lambda a: jnp.copy(a) if isinstance(a, jax.Array) else a,
            payload)

        def write():
            health = get_health()
            flight = get_flight_recorder()
            t_w = time.perf_counter()
            try:
                health.heartbeat("checkpoint_writer")
                flight.record("ckpt", phase="start", tag=str(tag),
                              file=os.path.basename(fname))
                # device arrays → host in ONE batched device_get (was:
                # np.asarray per leaf — a serial D2H sync each); python
                # scalars/strings (step counters, the plan's spec
                # record) stay as-is
                leaves, treedef = jax.tree_util.tree_flatten(snap)
                dev = [i for i, a in enumerate(leaves)
                       if isinstance(a, jax.Array)]
                for i, v in zip(dev,
                                jax.device_get([leaves[i] for i in dev])):
                    leaves[i] = v
                host = jax.tree_util.tree_unflatten(treedef, [
                    a if isinstance(a, (str, bytes, bool, int, float,
                                        np.ndarray)) else np.asarray(a)
                    for a in leaves])
                host["__ckpt_meta__"] = {
                    "format_version": self.FORMAT_VERSION,
                    "saved_unix": time.time(),
                    "jax_version": jax.__version__,
                }
                tmp = fname + ".tmp"
                with open(tmp, "wb") as f:
                    pickle.dump(host, f)
                    # fsync BEFORE the rename: os.replace alone makes
                    # the name durable without the data — after a power
                    # loss the pointer could name a truncated snapshot
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, fname)
                # crash-safe "last complete" pointer: updated only AFTER
                # the snapshot's own atomic rename
                self._write_latest(os.path.basename(fname))
                self._gc()
                dt = time.perf_counter() - t_w
                self._writes.inc()
                self._write_hist.observe(dt)
                health.set_status("checkpoint_writer", True)
                flight.record("ckpt", phase="complete", tag=str(tag),
                              seconds=round(dt, 6))
            except BaseException as e:  # surfaced on the next save/_wait
                health.set_status("checkpoint_writer", False)
                flight.record("ckpt", phase="error", tag=str(tag),
                              error=repr(e))
                self._pending_err = e

        if _async_checkpoint_enabled():
            self._pending = threading.Thread(target=write, daemon=True,
                                             name="zoo-ckpt")
            self._pending.start()
            # the caller-visible stall: previous-write join + snapshot
            # dispatch; the serialization overlaps the next train steps
            self._stall_hist.observe(time.perf_counter() - t0)
        else:
            write()
            self._stall_hist.observe(time.perf_counter() - t0)
            if self._pending_err is not None:
                err, self._pending_err = self._pending_err, None
                raise err
        return fname

    def _write_latest(self, basename: str):
        ptr = os.path.join(self.path, self.LATEST)
        tmp = ptr + ".tmp"
        with open(tmp, "w") as f:
            f.write(basename)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, ptr)
        # fsync the DIRECTORY so both renames (snapshot + pointer) are
        # durable, not just the file contents
        try:
            dfd = os.open(self.path, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:  # e.g. fs without directory fsync support
            pass

    def _gc(self):
        # raw listing: _gc runs ON the writer thread, so it must not _wait
        files = self._list_files()
        for f in files[:-self.keep]:
            try:
                os.remove(f)
            except OSError:
                pass

    def _list_files(self) -> list[str]:
        if not os.path.isdir(self.path):
            return []
        files = [os.path.join(self.path, f) for f in os.listdir(self.path)
                 if f.startswith("ckpt-") and f.endswith(".pkl")]
        return sorted(files, key=os.path.getmtime)

    def list(self) -> list[str]:
        self._wait()  # a half-written snapshot must not be resumed from
        return self._list_files()

    def latest(self) -> dict | None:
        """Reference ``getLatestFile`` (Topology.scala:1511-1528).

        Multi-host: the checkpoint dir must be SHARED storage (the
        reference's HDFS contract).  Process 0 is the only writer
        (:meth:`save`), so before reading, process 0 joins its in-flight
        writer and THEN all processes barrier — guaranteeing every host
        resumes from the same completed snapshot instead of racing the
        os.replace."""
        if _process_shard() is not None:
            self._wait()  # no-op on processes that never write
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("zoo-ckpt-latest")
        files = self.list()
        # prefer the crash-safe LATEST pointer (always names the newest
        # COMPLETE snapshot — a kill -9 mid-write never advanced it);
        # fall back to mtime order for pre-pointer checkpoint dirs
        fname = None
        try:
            with open(os.path.join(self.path, self.LATEST)) as f:
                name = f.read().strip()
            cand = os.path.join(self.path, name)
            if name and os.path.exists(cand):
                fname = cand
        except OSError:
            fname = None
        if fname is None:
            if not files:
                return None
            fname = files[-1]
        elif files and files[-1] != fname:
            # an out-of-band snapshot (dropped in by a restore workflow,
            # never written through save()) can be newer than the pointer
            # target; any file under its final ckpt-*.pkl name is complete
            # (fsync-before-rename), so trusting the newer one is safe
            try:
                if os.path.getmtime(files[-1]) > os.path.getmtime(fname):
                    fname = files[-1]
            except OSError:
                pass
        with open(fname, "rb") as f:
            payload = safe_load(f)
        # schema check: refuse snapshots from a NEWER format (their layout
        # is unknown); pre-versioning (r03) snapshots carry no meta and
        # load as version 0
        meta = payload.pop("__ckpt_meta__", {"format_version": 0})
        if meta.get("format_version", 0) > self.FORMAT_VERSION:
            raise ValueError(
                f"checkpoint {fname} has format_version "
                f"{meta['format_version']} > supported "
                f"{self.FORMAT_VERSION}; upgrade the framework to resume "
                "from it")
        return payload


class Estimator:
    """Train/evaluate a KerasNet-like model on a device mesh.

    Reference: Estimator.scala:65-183 (facade) driving
    InternalDistriOptimizer (Topology.scala:1076-1259).
    """

    def __init__(self, model, optimizer=None, loss=None, metrics=None,
                 model_dir: str | None = None, grad_clip=None,
                 tensorboard=None, checkpoint=None,
                 ctx: ZooContext | None = None, plan=None):
        self.model = model
        # Unified partitioner (parallel/plan.py): a ShardingPlan or a
        # canned-plan name; None defers to ZOO_SHARDING_PLAN / the
        # legacy ZOO_SHARD_OPTIMIZER flag, then plain data parallelism.
        # train(plan=) overrides per fit.
        self.plan = plan
        self.optimizer = optimizer
        self.loss = loss
        self.metrics = list(metrics or [])
        self.grad_clip = grad_clip
        self.ctx = ctx or get_zoo_context()
        self._ckpt = None
        ckpt_path = None
        if checkpoint is not None:
            ckpt_path, over_write = checkpoint
            self._ckpt = _Checkpointer(ckpt_path, over_write)
        elif model_dir:
            self._ckpt = _Checkpointer(model_dir)
        self._writers = None
        if tensorboard is not None:
            log_dir, app_name = tensorboard
            from analytics_zoo_tpu.tensorboard import (
                TrainSummary,
                ValidationSummary,
            )
            self._writers = (
                TrainSummary(log_dir, app_name),
                ValidationSummary(log_dir, app_name),
            )
        # training state
        self.global_step = 0
        self.epoch = 1
        # compiled-step cache, keyed (device_transform, steps_per_dispatch)
        # — fit() and measure_pure_step() share it, so alternating probes
        # and training legs never thrash each other's jit cache
        self._train_step_fns: dict[tuple, Any] = {}
        self._eval_step_fn = None
        self._loss_buffer: list[tuple[int, Any]] = []
        self._opt_state = None  # persists across fit() calls
        self._profiled = False  # one jax.profiler capture per estimator
        # plan="auto" resolution cache: the oracle's choice is stable
        # for one estimator (same model/optimizer/mesh), so it is made
        # once; _auto_plan_record keeps the per-candidate prediction doc
        self._auto_plan = None
        self._auto_plan_record = None
        self.history: list[dict] = []
        # measure_pure_step probe bookkeeping: per-signature first-call
        # warmup time (compile included), so repeated probes report
        # steady state and the compile cost separately
        self._pure_step_warm: dict[tuple, float] = {}
        self.last_probe_warmup_seconds: float | None = None

    # ------------------------------------------------------------------
    # sharding plan (parallel/plan.py — ZOO_SHARDING_PLAN; the old
    # ZOO_SHARD_OPTIMIZER ZeRO-1 path is now the zero1() plan)
    # ------------------------------------------------------------------
    def _resolved_plan(self, override=None, params=None):
        """The effective ShardingPlan: explicit train(plan=) override >
        estimator plan > ZOO_SHARDING_PLAN > legacy ZOO_SHARD_OPTIMIZER
        (zero1) > data_parallel.

        ``"auto"`` (any of those tiers) is resolved HERE, not by
        ``resolve_plan``: the config oracle (analysis/oracle.py) picks
        among the canned plans from predicted per-chip param+opt bytes
        vs the peak table's HBM budget — see :meth:`_choose_auto_plan`.
        The choice is cached per estimator.

        The config tier's dtype policy (``ZOO_DTYPE_POLICY`` /
        ``ZooConfig.dtype_policy``) is overlaid on the result — the
        precision plane rides whatever sharding plan was picked, unless
        the plan already carries explicit ``dtype_rules`` (explicit
        beats environment, the documented precedence)."""
        from analytics_zoo_tpu.parallel.plan import resolve_plan

        requested = override if override is not None else self.plan
        if requested is None:
            requested = getattr(self.ctx.config, "sharding_plan", None)
        if isinstance(requested, str) \
                and requested.strip().lower() == "auto":
            if self._auto_plan is None:
                if params is None:
                    params, _ = self.model.build_params()
                self._auto_plan = self._choose_auto_plan(params)
            return self._apply_kernel_policy(
                self._apply_dtype_policy(self._auto_plan))
        return self._apply_kernel_policy(self._apply_dtype_policy(
            resolve_plan(
                override if override is not None else self.plan,
                self.ctx.config)))

    def _apply_dtype_policy(self, plan):
        """Overlay ``ZooConfig.dtype_policy`` (env ZOO_DTYPE_POLICY)
        onto a resolved plan.  No-ops when no policy is configured,
        when the plan already carries dtype_rules (explicit > env), or
        for policy "auto" — that one is resolved by the oracle's dtype
        sweep inside :meth:`_choose_auto_plan` (it needs the candidate
        predictions, not a blanket overlay)."""
        policy = getattr(self.ctx.config, "dtype_policy", None)
        if not policy or plan.dtype_rules:
            return plan
        if str(policy).strip().lower() == "auto":
            return plan
        from analytics_zoo_tpu.parallel.plan import with_dtype_policy

        return with_dtype_policy(plan, policy)

    def _apply_kernel_policy(self, plan):
        """Overlay the default kernel table (env ZOO_USE_PALLAS /
        ``ZooConfig.use_pallas``) onto a resolved plan — the kernel
        plane's env tier, same precedence contract as
        :meth:`_apply_dtype_policy`: no-op when the knob is off or the
        plan already carries kernel_rules (explicit > env)."""
        if not getattr(self.ctx.config, "use_pallas", False) \
                or plan.kernel_rules:
            return plan
        from analytics_zoo_tpu.parallel.plan import with_kernels

        return with_kernels(plan)

    def _choose_auto_plan(self, params):
        """Ask the config oracle to pick the memory plan: predicted
        per-chip bytes per (plan × remat) candidate (params measured
        from the built tree, optimizer state sized via
        ``jax.eval_shape`` — no allocation; activations estimated as
        one param-tree copy, the usual MLP-ish order of magnitude)
        against the HBM budget, preferring the least-collective-traffic
        least-rematted config that fits.  The full per-candidate
        prediction doc lands in ``_auto_plan_record`` (and the plan
        record / bench artifacts)."""
        from analytics_zoo_tpu.analysis.oracle import ConfigOracle
        from analytics_zoo_tpu.parallel.plan import (
            resolve_plan,
            with_dtype,
            with_remat,
        )

        def tree_bytes(tree):
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                shape = getattr(leaf, "shape", None)
                dtype = getattr(leaf, "dtype", None)
                if shape is None or dtype is None:
                    continue
                total += int(np.prod(shape)) * np.dtype(dtype).itemsize
            return total

        param_bytes = tree_bytes(params)
        opt_bytes = tree_bytes(jax.eval_shape(self.optimizer.init, params))
        oracle = ConfigOracle.from_env()
        # ZOO_DTYPE_POLICY=auto widens the sweep to sharding × remat ×
        # dtype: bf16 candidates get the doubled flops ceiling, the
        # halved activation footprint and the shrunken fsdp gather
        # bytes (analysis/costmodel.py DTYPE_PEAK_FACTORS); f32 stays
        # the tie-break default.
        policy = getattr(self.ctx.config, "dtype_policy", None)
        dtype_options = ((None, "bf16")
                         if policy
                         and str(policy).strip().lower() == "auto"
                         else (None,))
        # ZOO_USE_PALLAS=1 widens the sweep with the kernel dimension:
        # "+kernels" candidates get the fused-kernel compute factor on
        # TPU peaks and tie-break AGAINST kernels everywhere else, so
        # the CPU tier's auto plan declines pallas while recording the
        # declined candidate in the prediction log.
        kernel_options = ((None, "kernels")
                          if getattr(self.ctx.config, "use_pallas", False)
                          else (None,))
        name, doc = oracle.choose_plan(
            param_bytes, opt_bytes, self.ctx.data_parallel_size,
            activation_bytes=param_bytes,
            remat_options=(None, "full"),
            dtype_options=dtype_options,
            kernel_options=kernel_options)
        self._auto_plan_record = doc
        logger.info(
            "plan=auto resolved to %r (remat=%s dtype=%s kernels=%s; "
            "per-chip %s bytes vs %s budget, %s-way)", name,
            doc["chosen_remat"], doc.get("chosen_dtype"),
            doc.get("chosen_kernels"),
            next(c["predicted_chip_bytes"] for c in doc["candidates"]
                 if c["config"] == doc["chosen_config"]),
            doc["hbm_budget_bytes"], doc["n_shards"])
        plan = resolve_plan(name)
        if doc["chosen_remat"]:
            plan = with_remat(plan, doc["chosen_remat"])
        if doc.get("chosen_dtype"):
            plan = with_dtype(plan, doc["chosen_dtype"])
        if doc.get("chosen_kernels"):
            from analytics_zoo_tpu.parallel.plan import with_kernels

            plan = with_kernels(plan)
        return plan

    def _place_opt_state(self, opt_state, plan=None):
        """Optimizer-state placement through the partitioner — the one
        resharding path (a checkpoint's global logical arrays land in
        the CURRENT plan/mesh layout by this device_put, whatever shape
        they were saved under)."""
        plan = plan if plan is not None else self._resolved_plan()
        return plan.place_opt_state(opt_state, self.ctx.mesh)

    def _place_params(self, params, plan=None):
        plan = plan if plan is not None else self._resolved_plan()
        return plan.place_params(params, self.ctx.mesh)

    def _publish_mem_gauges(self, plan, params, opt_state):
        """zoo_mem_* per plan label: measured per-chip param+opt bytes
        of the state just placed, against the cost model's
        ``predict_chip_bytes`` for this plan/mesh."""
        from analytics_zoo_tpu.analysis.costmodel import predict_chip_bytes
        from analytics_zoo_tpu.parallel.plan import (
            per_chip_bytes,
            record_dtype_gauges,
            record_kernel_gauges,
            record_mem_gauges,
        )

        try:
            global_bytes = [
                sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                    for l in jax.tree_util.tree_leaves(t)
                    if hasattr(l, "shape"))
                for t in (params, opt_state)]
            predicted = predict_chip_bytes(
                global_bytes[0], global_bytes[1], plan.name,
                self.ctx.data_parallel_size)
            measured = per_chip_bytes((params, opt_state))
            tag = "" if plan.name == "dp" else f"_{plan.name}"
            record_mem_gauges(f"train_step{tag}",
                              predicted_bytes=predicted,
                              measured_bytes=measured)
            if plan.dtype_rules:
                # Precision plane: per-role leaf counts and the
                # compute-vs-master byte ratio (zoo_dtype_* family)
                record_dtype_gauges(f"train_step{tag}", plan, params)
            if plan.kernel_rules:
                # Kernel plane: per-scope kernel selections and the
                # pallas/fallback routing counters (zoo_kernel_* family)
                record_kernel_gauges(f"train_step{tag}", plan)
        except Exception as e:  # telemetry must never fail a fit
            logger.debug("zoo_mem gauges skipped: %s", e)

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------
    def _train_step_for(self, device_transform=None,
                        steps_per_dispatch: int = 1, plan=None):
        """The (cached) compiled train step for this transform/K/plan
        triple.

        Returning the SAME function object across calls is what makes
        the compiled-step cache effective: a fresh closure per call
        would retrace and recompile an identical program.  Bounded:
        callers that build a fresh transform closure per fit() would
        otherwise pin one compiled program per call forever — oldest
        entries are evicted past 8 (in-flight fns stay alive through the
        caller's local reference)."""
        plan = plan if plan is not None else self._resolved_plan()
        key = (device_transform, int(steps_per_dispatch),
               plan.cache_key())
        fn = self._train_step_fns.get(key)
        if fn is None:
            fn = self._build_train_step(device_transform,
                                        steps_per_dispatch=key[1],
                                        plan=plan)
            while len(self._train_step_fns) >= 8:
                old = next(iter(self._train_step_fns))
                self._train_step_fns.pop(old)
                if old[1] == 1:
                    # the probe's warmth bookkeeping rode on this entry:
                    # a future measure_pure_step re-pays compile, so it
                    # must re-report warmup instead of claiming 0.0
                    self._pure_step_warm = {
                        s: v for s, v in self._pure_step_warm.items()
                        if s[0] is not old[0]}
            self._train_step_fns[key] = fn
        return fn

    def _build_train_step(self, device_transform=None,
                          steps_per_dispatch: int = 1, plan=None):
        """Build the compiled train step — through ``compile_step``,
        the unified partitioner's choke point (parallel/plan.py), so
        every plan's program shares the persistent compile cache, AOT
        warmup, ``zoo_compile_seconds`` and the HLO lint/feature pipe.

        ``steps_per_dispatch=1``: the classic single-step program.
        ``steps_per_dispatch=K>1``: the FUSED program — one donated-carry
        dispatch whose body is ``jax.lax.scan`` over K inner steps of the
        SAME per-step math (shared ``one_step`` closure), consuming a
        [K, batch, ...] super-batch.  Each inner step folds the RNG on
        the GLOBAL step index (``step0 + i``), so the loss trajectory is
        bit-identical to K single dispatches; only the Python→device
        round-trip count changes (1 instead of K).

        The plan's sharding enters twice: inputs are device_put into the
        plan layout by the caller, and the updated params/opt state are
        re-constrained in-graph so donation reuses the sharded buffers
        (an fsdp plan's weights must come back sharded, not
        'helpfully' replicated by XLA).  The math is placement-invariant
        — every plan trains bit-identically.
        """
        from analytics_zoo_tpu.parallel.plan import compile_step

        plan = plan if plan is not None else self._resolved_plan()
        mesh = self.ctx.mesh
        model, loss_fn = self.model, self.loss
        opt, grad_clip = self.optimizer, self.grad_clip
        # Kernel plane: a plan routing optimizer.adam to the fused
        # pallas kernel swaps the transform here — fused_adam's inner
        # chain is built from the SAME optax.adam arguments, so init()
        # state structure, checkpoints and the fallback trajectory are
        # identical; only the TPU lowering changes.  "xla" (or no rule)
        # leaves the original optimizer untouched.
        if plan.kernel_rules \
                and getattr(opt, "name", None) == "adam" \
                and hasattr(opt, "hyperparams") \
                and plan.kernel_for("optimizer.adam") == "fused_adam":
            from analytics_zoo_tpu.ops.pallas.fused_adam import fused_adam

            opt = fused_adam(**opt.hyperparams)
        compute_dtype = self.ctx.compute_dtype
        # Transfer learning (KerasNet.freeze/freeze_up_to): frozen layers'
        # grads AND optimizer updates are masked to zero — updates too, so
        # decoupled weight decay (adamw) cannot drift frozen weights.
        frozen = frozenset(getattr(model, "_frozen", ()) or ())

        def _mask_frozen(tree):
            return {
                k: (jax.tree_util.tree_map(jnp.zeros_like, v)
                    if k in frozen else v)
                for k, v in tree.items()
            }

        def one_step(params, opt_state, state, rng, batch):
            if device_transform is not None:
                # On-device preprocessing (uint8 decode/normalize/augment):
                # fuses into the step, so the host link ships compact dtypes.
                batch = device_transform(batch)

            def loss_of(p):
                # fsdp gather prefetch (plan.prefetch): explicit
                # double-buffered all-gathers, bucket k+1's gather
                # barrier-chained behind bucket k so it issues while k
                # computes; the vjp transposes each gather into the
                # matching bucketed reduce-scatter.  No-op (returns p
                # untouched) for plans without prefetch.
                p = plan.prefetch_params(p, mesh)
                # Params-in-compute mixed precision: master params stay f32
                # (the differentiation variable); the cast is inside the
                # graph so its vjp returns f32 grads.  Loss math is f32.
                # A plan with dtype_rules (the precision plane —
                # mixed_precision()) takes precedence over the context-
                # wide compute dtype: per-leaf roles, same in-graph cast.
                if plan.dtype_rules:
                    pc = plan.cast_params_for_compute(p)
                    xc = cast_floats(batch["x"],
                                     plan.compute_cast_dtype()
                                     or compute_dtype)
                else:
                    pc = cast_floats(p, compute_dtype)
                    xc = cast_floats(batch["x"], compute_dtype)
                # A loss that a layer takes itself (InModelLoss: a looped
                # decoder's exit-gate loss) gets the targets where its
                # logits are made and comes back under a `*_cost` leaf of
                # the state; `preds` is then dead code in this program.
                in_model = getattr(loss_fn, "in_model", False)
                if in_model and batch.get("w") is not None:
                    raise ValueError(f"loss {loss_fn.name!r} is taken "
                                     "inside the model: no sample weights")
                with training_targets(batch.get("y") if in_model else None):
                    preds, new_state = model.forward(
                        pc, xc, state=state, training=True, rng=rng
                    )
                if in_model:
                    l = jnp.zeros((), jnp.float32)
                else:
                    preds = cast_floats(preds, jnp.float32)
                    l = loss_fn.mean(batch.get("y"), preds, batch.get("w"))
                # Costs reported through the layer-state channel (MoE load
                # balancing: each stack stores its pre-weighted
                # contribution under `moe_aux_cost`; an in-model loss under
                # `loop_exit_cost`) join the training loss; eval loss
                # stays the task loss alone.
                l = l + _collect_aux_cost(new_state)
                return l, new_state

            (l, new_state), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params)
            if compute_dtype is not None or plan.dtype_rules:
                # Keep state dtypes stable across steps (donation and the
                # next trace both require it).
                new_state = jax.tree_util.tree_map(
                    lambda new, old: new.astype(old.dtype), new_state, state
                )
            # With the batch sharded over the `data` axis and params
            # replicated, XLA partitions this program SPMD and inserts the
            # gradient all-reduce (reduce-scatter + all-gather over ICI) —
            # the role of BigDL's AllReduceParameter (Topology.scala:1119).
            if frozen:
                grads = _mask_frozen(grads)
            grads = _clip_grads(grads, grad_clip)
            # ZeRO-2/3: grad_rules pin each gradient to per-chip shards,
            # so XLA lowers the gradient sum as a reduce-scatter and the
            # optimizer update below runs on 1/n of every leaf; plans
            # without grad_rules (dp/zero1/fsdp) leave this to GSPMD.
            grads = plan.constrain_grads(grads, mesh)
            updates, opt_state = opt.update(grads, opt_state, params)
            # Plan layout, in-graph: pinning the optimizer state (zero1/
            # fsdp) makes XLA partition the moment updates — and
            # reduce-scatter the grads feeding them — instead of
            # computing the full update redundantly on every chip;
            # pinning the params (fsdp/tp) keeps the weights stored
            # sharded (gather-on-use) so donation reuses the 1/n
            # buffers.  data_parallel constrains nothing (no-ops).
            opt_state = plan.constrain_opt(opt_state, mesh)
            if frozen:
                updates = _mask_frozen(updates)
            params = optax.apply_updates(params, updates)
            params = plan.constrain_params(params, mesh)
            return params, opt_state, new_state, l

        # per-plan compile labels (dp keeps the historical bare names):
        # zoo_compile_seconds / zoo_hlo_* tell an fsdp program from a dp
        # one at a glance
        tag = "" if plan.name == "dp" else f"_{plan.name}"
        if steps_per_dispatch <= 1:
            def train_step(params, opt_state, state, seed, step, batch):
                # RNG derived in-graph: no per-step host-side key
                # splitting.
                rng = jax.random.fold_in(jax.random.PRNGKey(seed), step)
                return one_step(params, opt_state, state, rng, batch)

            return compile_step(train_step, plan, mesh,
                                donate_argnums=(0, 1, 2),
                                label=f"train_step{tag}",
                                meta={"mesh_shape": dict(mesh.shape),
                                      "steps_per_dispatch": 1})

        k = int(steps_per_dispatch)

        def train_step_scan(params, opt_state, state, seed, step0,
                            stacked):
            key = jax.random.PRNGKey(seed)

            def body(carry, xs):
                p, o, s = carry
                batch_i, i = xs
                # GLOBAL step index: inner step i of this dispatch is
                # global step step0 + i, so the per-step RNG (dropout,
                # augmentation) matches the K=1 run exactly.
                rng = jax.random.fold_in(key, step0 + i)
                p, o, s, l = one_step(p, o, s, rng, batch_i)
                return (p, o, s), l

            (params, opt_state, state), losses = jax.lax.scan(
                body, (params, opt_state, state),
                (stacked, jnp.arange(k, dtype=jnp.int32)))
            return params, opt_state, state, losses

        return compile_step(train_step_scan, plan, mesh,
                            donate_argnums=(0, 1, 2),
                            label=f"train_step_scan{k}{tag}",
                            meta={"mesh_shape": dict(mesh.shape),
                                  "steps_per_dispatch": k})

    def _build_eval_step(self, device_transform=None):
        from analytics_zoo_tpu.parallel.plan import compile_step

        model, loss_fn, metrics = self.model, self.loss, self.metrics
        compute_dtype = self.ctx.compute_dtype
        plan = self._resolved_plan()

        def eval_step(params, state, batch):
            if device_transform is not None:
                batch = device_transform(batch)
            # State stays f32: BN running stats must not be rounded to bf16
            # (the layers upcast internally where needed).  The precision
            # plane casts per dtype role, same as the train step — eval
            # must see the dtypes it trained with.
            if plan.dtype_rules:
                pc = plan.cast_params_for_compute(params)
                xc = cast_floats(batch["x"],
                                 plan.compute_cast_dtype() or compute_dtype)
            else:
                pc = cast_floats(params, compute_dtype)
                xc = cast_floats(batch["x"], compute_dtype)
            preds, _ = model.forward(
                pc, xc, state=state, training=False)
            preds = cast_floats(preds, jnp.float32)
            n_valid = batch.get("n_valid")
            mask = None
            if n_valid is not None:
                b = preds.shape[0] if not isinstance(preds, list) \
                    else preds[0].shape[0]
                mask = (jnp.arange(b) < n_valid).astype(jnp.float32)
            stats = []
            if loss_fn is not None and "y" in batch:
                per = loss_fn(batch["y"], preds)
                if mask is not None:
                    stats.append((jnp.sum(per * mask), jnp.sum(mask)))
                else:
                    stats.append((jnp.sum(per),
                                  jnp.asarray(per.shape[0], jnp.float32)))
            for m in metrics:
                stats.append(m.batch_stats(batch["y"], preds, mask=mask))
            return stats

        # through the choke point too: eval programs get the same
        # compile metering / persistent cache / HLO features as train
        return compile_step(eval_step, plan,
                            self.ctx.mesh, label="eval_step")

    # ------------------------------------------------------------------
    # train (InternalDistriOptimizer.train, Topology.scala:1076-1259)
    # ------------------------------------------------------------------
    @span("zoo.fit", fit=True)
    def train(self, train_set: FeatureSet, batch_size: int = 32,
              nb_epoch: int | None = None,
              end_trigger: ZooTrigger | None = None,
              checkpoint_trigger: ZooTrigger | None = None,
              validation_set: FeatureSet | None = None,
              validation_trigger: ZooTrigger | None = None,
              seed: int | None = None,
              autotune=None, plan=None, elastic=None):
        """``plan``: a :class:`~analytics_zoo_tpu.parallel.plan.
        ShardingPlan` (or canned-plan name — "dp"/"zero1"/"zero2"/
        "fsdp"/"zero3") laying out params, optimizer state, grads and
        the batch for this fit; ``None`` defers to the estimator's
        plan, then ``ZOO_SHARDING_PLAN`` / the legacy
        ``ZOO_SHARD_OPTIMIZER``, then data parallelism.  A plan changes
        where bytes live (fsdp/zero3: ~1/n param+opt bytes per chip;
        zero2 reduce-scatters grads at zero1's resident state) and
        which collectives XLA inserts, never the math: fsdp/zero3 train
        BIT-identically to dp; zero1/zero2's differently-grouped
        gradient reduction matches to float tolerance (ulp-level:
        ``tests/test_partitioner.py`` holds rtol 1e-5).  ``"auto"`` asks
        the config oracle to sweep the (plan × remat) space against the
        HBM budget.  See docs/parallelism.md.

        ``autotune``: ``True`` (or ``ZOO_AUTOTUNE=1`` via the config
        tier, which ``None`` defers to) turns on the closed-loop tuner
        (feature/autotune.py): the train set is wrapped in the prefetch
        plane (starting from the configured knobs, or worst-case
        workers=1/depth=1 when prefetch is off) and a controller thread
        resizes it online while ``steps_per_dispatch`` hill-climbs at
        dispatch boundaries — loss trajectory bit-identical throughout.
        Pass an :class:`~analytics_zoo_tpu.feature.autotune.
        AutotuneController` instance to share/tune one across fits;
        ``False`` forces it off regardless of the env.

        ``elastic``: an :class:`~analytics_zoo_tpu.elastic.membership.
        ElasticSession` — the fit becomes one elastic training LEG: at
        every dispatch boundary the session's membership generation is
        polled, and on a change the loop snapshots through the async
        checkpointer (iterator position included), flushes, and raises
        :class:`~analytics_zoo_tpu.elastic.membership.
        GenerationChange` carrying the new (generation, world, members)
        doc — the caller (the elastic worker round loop) rejoins at the
        new world size and resumes from LATEST through the
        partitioner's bit-exact resharding.  ``None`` (default) trains
        exactly as before.  See docs/elastic-training.md."""
        with span("zoo.fit.enter"):
            ctx = self.ctx
            dp = ctx.data_parallel_size
            if batch_size % dp != 0:
                # The TFDataset contract (tf_dataset.py:136-143): global batch
                # must divide evenly across model replicas.
                raise ValueError(
                    f"batch_size ({batch_size}) must be a multiple of the "
                    f"data-parallel size ({dp})"
                )
            if end_trigger is None:
                # Keras semantics: each fit() call trains nb_epoch MORE epochs
                # (relative to the in-process counter).  Checkpoint resume in a
                # fresh process still continues to the absolute target, matching
                # the reference's getFinishedEpoch continuation
                # (Topology.scala:373-386).
                end_trigger = MaxEpoch(
                    self.epoch - 1 + (nb_epoch if nb_epoch is not None else 10))
            if checkpoint_trigger is None and self._ckpt is not None:
                checkpoint_trigger = EveryEpoch()
            if validation_set is not None and validation_trigger is None:
                validation_trigger = EveryEpoch()
            seed = ctx.seed if seed is None else seed
            # Closed-loop autotuning (ZOO_AUTOTUNE / autotune=True): resolve
            # the controller BEFORE the prefetch wrap so the pipeline starts
            # at (and is resized from) the controller's state.  autotune
            # unset/off ⇒ controller is None and every path below is the
            # static-knob code, no new threads (the disabled-mode contract).
            controller, own_controller, attached_set = None, False, None
            auto = autotune if autotune is not None else ctx.config.autotune
            if auto:
                from analytics_zoo_tpu.feature.autotune import (
                    AutotuneController,
                )
                if isinstance(auto, AutotuneController):
                    controller = auto
                else:
                    controller = AutotuneController.from_config(ctx.config)
                    own_controller = True
            if ctx.config.prefetch_workers or controller is not None:
                # Parallel host data plane (ZOO_PREFETCH_WORKERS): shard
                # loading, host transforms and batch assembly move onto pool
                # threads with ordered delivery, composing with the
                # double-buffered device infeed below — the feeder consumes
                # the prefetched stream instead of the serial generator, and
                # the stream itself is byte-identical (resume included).
                # Under autotune with prefetch off, start from the worst
                # case (workers=1, depth=1) and let the controller grow it —
                # but only when the set HAS host work to hide
                # (worth_prefetching); a resident no-transform array set
                # would pay queue handoffs for nothing, and an explicit
                # ZOO_PREFETCH_WORKERS always wins over that heuristic.
                from analytics_zoo_tpu.feature.prefetch import (
                    PrefetchFeatureSet,
                    worth_prefetching,
                )
                if isinstance(train_set, PrefetchFeatureSet):
                    if controller is not None \
                            and train_set._controller is None:
                        # attach for THIS fit only — detached in the finally
                        # below, so a later train(autotune=False) on the same
                        # FeatureSet cannot resurrect this fit's controller
                        train_set._controller = controller
                        attached_set = train_set
                elif ctx.config.prefetch_workers or \
                        worth_prefetching(train_set):
                    train_set = PrefetchFeatureSet(
                        train_set,
                        depth=(ctx.config.prefetch_depth
                               if ctx.config.prefetch_workers else 1),
                        workers=ctx.config.prefetch_workers or 1,
                        controller=controller)

            # Unified partitioner: resolve the plan ONCE per fit; placement,
            # in-graph constraints, the batch sharding and the checkpoint's
            # spec record all derive from it.  Params are built FIRST: a
            # plan="auto" resolution needs their byte sizes to predict each
            # candidate's per-chip footprint.
            with span("zoo.fit.enter.build"):
                params, state = self.model.build_params()
                plan = self._resolved_plan(plan, params=params)
                # Keras continuation semantics: a second fit() on the same estimator
                # keeps optimizer moments and the LR-schedule step count (they live
                # in opt_state), not just the weights.
                opt_state = (self._opt_state if self._opt_state is not None
                             else self.optimizer.init(params))
            repl = ctx.replicated()
            with span("zoo.fit.enter.place"):
                state = jax.device_put(state, repl)
                params = self._place_params(params, plan)
                opt_state = self._place_opt_state(opt_state, plan)
            # Close the MEMORY loop (zoo_mem_* family): measured per-chip
            # param+opt bytes under this plan vs predict_chip_bytes, the
            # way zoo_oracle rel_error closes steps/sec predictions.
            with span("zoo.fit.enter.mem_gauges"):
                self._publish_mem_gauges(plan, params, opt_state)
            # Checkpoint spec record: the plan's clamped spec trees ride
            # every snapshot, so a resume (any mesh size, any process) can
            # see what layout the state was trained under and reshard
            # through the partitioner — not a strategy-specific heuristic.
            with span("zoo.fit.enter.spec_record"):
                from analytics_zoo_tpu.parallel.plan import serialize_specs
                # report_unused: the once-per-fit audit point — a typo'd rule
                # that matched zero params surfaces as ONE warning here
                param_specs, _ = plan.param_specs(params, ctx.mesh,
                                                  report_unused=True)
                self._plan_record = {
                    "name": plan.name,
                    "mesh": dict(ctx.mesh.shape),
                    "param_specs": serialize_specs(param_specs),
                    "opt_specs": serialize_specs(
                        plan.opt_specs(opt_state, ctx.mesh)),
                    # precision contract ("" = no dtype rules): a resume under a
                    # DIFFERENT policy fails loudly below instead of silently
                    # mixing master widths
                    "dtype_policy": plan.dtype_policy_str(),
                }
                if self._auto_plan_record is not None:
                    # plan="auto": keep the oracle's per-candidate predictions
                    # next to the layout the fit actually ran under
                    self._plan_record["auto"] = self._auto_plan_record
            dev_tf = getattr(train_set, "device_transform", None)
            # Fused multi-step dispatch (ZOO_STEPS_PER_DISPATCH): K>1 runs K
            # inner steps per jitted dispatch; the K=1 step is always built
            # too — it serves partial tail chunks.  (K >= 1 is enforced by
            # ZooConfig.__post_init__ — no silent clamping here.)
            k = int(ctx.config.steps_per_dispatch or 1)
            with span("zoo.fit.enter.step_lookup"):
                step_fn = self._train_step_for(dev_tf, 1, plan)
                fused_fn = self._train_step_for(dev_tf, k, plan) \
                    if k > 1 else None
            if controller is not None:
                # name the K=1 program for the controller's oracle prior:
                # its compile (first dispatch) caches the HLO features the
                # predicted-K jump reads
                tag = "" if plan.name == "dp" else f"_{plan.name}"
                controller.set_feature_label(f"train_step{tag}")
            # Persistent compile plane (ZOO_COMPILE_CACHE): enable before the
            # first trace so this fit's compiles populate / hit the cache.
            from analytics_zoo_tpu.common.compile_cache import (
                maybe_enable_persistent_cache,
            )
            maybe_enable_persistent_cache(ctx.config.compile_cache)

            start_epoch, start_batch = self.epoch, 0
            # resume from checkpoint if present (Topology.scala:1220-1242)
            with span("zoo.fit.enter.resume"):
                resumed = self._ckpt.latest() if self._ckpt else None
                if resumed is not None:
                    # Elastic resume through the partitioner: the checkpoint
                    # stores GLOBAL logical arrays, so resharding onto THIS
                    # mesh/plan (saved {data:8}, resuming {data:4}; saved fsdp,
                    # resuming dp; ...) is exactly the plan's placement
                    # device_put — no layout surgery.
                    saved_plan = resumed.get("plan")
                    saved_policy = (saved_plan or {}).get("dtype_policy")
                    if saved_policy is not None \
                            and saved_policy != plan.dtype_policy_str():
                        # Precision contract guard: f32 masters saved under one
                        # policy must not be silently re-interpreted under
                        # another (pre-precision-plane checkpoints carry no
                        # policy key and skip the check).  ZOO_DTYPE_RESUME=cast
                        # opts into a DELIBERATE cast-on-resume.
                        if os.environ.get("ZOO_DTYPE_RESUME", "").strip().lower() \
                                in ("cast", "force"):
                            logger.warning(
                                "resuming checkpoint trained under dtype policy "
                                "%r into plan %r with policy %r "
                                "(ZOO_DTYPE_RESUME): casting on resume",
                                saved_policy, plan.name, plan.dtype_policy_str())
                        else:
                            raise ValueError(
                                f"checkpoint was trained under dtype policy "
                                f"{saved_policy!r} but this fit's plan "
                                f"{plan.name!r} declares "
                                f"{plan.dtype_policy_str()!r}; resume with a "
                                f"matching plan (mixed_precision(), "
                                f"ZOO_DTYPE_POLICY) or set ZOO_DTYPE_RESUME=cast "
                                f"to cast deliberately")
                    if saved_plan and (saved_plan.get("name") != plan.name
                                       or saved_plan.get("mesh")
                                       != dict(ctx.mesh.shape)):
                        logger.info(
                            "resharding checkpoint (saved plan=%s mesh=%s) into "
                            "plan=%s mesh=%s through the partitioner",
                            saved_plan.get("name"), saved_plan.get("mesh"),
                            plan.name, dict(ctx.mesh.shape))
                    params = self._place_params(resumed["params"], plan)
                    opt_state = jax.tree_util.tree_unflatten(
                        jax.tree_util.tree_structure(opt_state),
                        [jnp.asarray(x) for x in resumed["opt_flat"]],
                    )
                    opt_state = self._place_opt_state(opt_state, plan)
                    state = jax.device_put(resumed["state"], repl)
                    self.global_step = int(resumed["global_step"])
                    start_epoch = int(resumed["epoch"])
                    start_batch = int(resumed["next_batch"])
                    seed = int(resumed["seed"])
                    logger.info("resumed from checkpoint @ step %d (epoch %d.%d)",
                                self.global_step, start_epoch, start_batch)

            # ZooConfig env tier: ZOO_FAILURE_RETRY_TIMES (reference
            # ``bigdl.failure.retryTimes`` sysprop, Topology.scala:1172)
            retry_times = self.ctx.config.failure_retry_times
        try:
            params, opt_state, state = self._train_with_retries(
                params, opt_state, state, step_fn, fused_fn, k, dev_tf,
                plan, controller, train_set, batch_size, seed,
                start_epoch, start_batch, end_trigger, checkpoint_trigger,
                validation_set, validation_trigger, retry_times, repl,
                elastic)
        finally:
            if attached_set is not None:
                # undo the fit-scoped attachment on the CALLER's set
                attached_set._controller = None
            if own_controller:
                # the controller thread dies with this fit; a caller-
                # provided controller keeps running (shared across fits)
                controller.stop()

        with span("zoo.fit.exit"):
            self.model.params = params
            self.model.state = state
            self._opt_state = opt_state
            if self._ckpt is not None:
                # Flush the in-flight async save before returning: the process
                # may exit right after fit(), and a NEW estimator on the same
                # dir must see the final snapshot (not a half-written .tmp).
                # Also surfaces any deferred write error.
                self._ckpt._wait()
        return self

    def _train_with_retries(self, params, opt_state, state, step_fn,
                            fused_fn, k, dev_tf, plan, controller,
                            train_set, batch_size, seed, start_epoch,
                            start_batch, end_trigger, checkpoint_trigger,
                            validation_set, validation_trigger,
                            retry_times, repl, elastic=None):
        # GenerationChange is control flow, not a failure: it must reach
        # the elastic worker's round loop, never the retry path below.
        from analytics_zoo_tpu.elastic.membership import GenerationChange

        retries = 0
        while True:
            try:
                params, opt_state, state = self._train_loop(
                    params, opt_state, state, step_fn, fused_fn, k,
                    dev_tf, plan, controller,
                    train_set, batch_size, seed, start_epoch, start_batch,
                    end_trigger, checkpoint_trigger,
                    validation_set, validation_trigger, elastic,
                )
                break
            except (KeyboardInterrupt, ValueError, TypeError,
                    GenerationChange):
                raise
            except Exception as e:
                # retry-from-checkpoint loop (Topology.scala:1171-1253)
                # — recorded in the flight ring BEFORE the retry, so a
                # postmortem shows every attempt's failure, not just the
                # one that finally escaped
                get_flight_recorder().record_exception(e, where="train")
                retries += 1
                if self._ckpt is None or retries > retry_times:
                    raise
                # Drop device scalars produced by the failed attempt: their
                # conversion would re-raise the device error, and their steps
                # will be replayed from the checkpoint anyway.
                self._loss_buffer = []
                logger.exception(
                    "training failed; retry %d/%d from latest checkpoint",
                    retries, retry_times,
                )
                resumed = self._ckpt.latest()
                if resumed is None:
                    raise
                params = self._place_params(resumed["params"], plan)
                # same plan placement as the initial/resume sites:
                # restoring replicated here would retrigger the OOM the
                # zero1/fsdp layout exists to prevent, mid-retry
                opt_state = self._place_opt_state(
                    jax.tree_util.tree_unflatten(
                        jax.tree_util.tree_structure(opt_state),
                        [jnp.asarray(x) for x in resumed["opt_flat"]],
                    ), plan)
                state = jax.device_put(resumed["state"], repl)
                self.global_step = int(resumed["global_step"])
                start_epoch = int(resumed["epoch"])
                start_batch = int(resumed["next_batch"])
        return params, opt_state, state

    # zoolint: hot-path
    def _train_loop(self, params, opt_state, state, step_fn, fused_fn,
                    steps_per_dispatch, dev_tf, plan, controller,
                    train_set, batch_size, seed, start_epoch, start_batch,
                    end_trigger, checkpoint_trigger, validation_set,
                    validation_trigger, elastic=None):
        ctx = self.ctx
        cfg = ctx.config
        k = steps_per_dispatch
        tstate = TrainingState(epoch=start_epoch,
                               iteration=self.global_step)
        epoch = start_epoch
        # zoolint: disable=host-sync -- host int boxing once per fit, not a device fetch
        seed_arr = np.asarray(seed & 0x7FFFFFFF, np.int32)
        # Profiler knob (ZOO_PROFILE_DIR / ZooConfig.profile_dir): one
        # jax.profiler trace of profile_steps warm steps per fit() — armed
        # ONCE per fit (not per epoch) so it fires even when epochs have
        # fewer steps than the warmup offset.
        prof_dir = cfg.profile_dir
        prof_at = self.global_step + 3 if (
            prof_dir and not self._profiled) else None
        # Observability (metrics/): children resolved once here, so the
        # per-step cost is a handful of observe/inc calls — and on a
        # disabled registry those are the shared no-op singleton.
        step_metrics = StepMetrics()
        # Distributed telemetry plane (ISSUE 2): scrape endpoints opt in
        # via ZOO_METRICS_PORT; the flight recorder arms its crash dump
        # (ZOO_FLIGHT_DIR); the loop and the infeed feeder heartbeat
        # /healthz; steps beyond k x rolling-p50 are flagged stragglers.
        maybe_start_from_env()
        flight = get_flight_recorder().install()
        straggler = StragglerDetector()
        health = get_health()
        # The loop only beats once per COMPLETED step, and the first
        # step includes the XLA compile (routinely minutes on a big
        # model) — the silence budget must cover that, or /healthz
        # would 503 a healthy process through every warmup.
        health.register("train_loop", stale_after=600.0)
        while not end_trigger(tstate):
            with span("zoo.train.epoch", args={"epoch": epoch}):
                epoch_t0 = time.perf_counter()
                n_records = 0
                # the feeder thread inherits the epoch's span as parent (and
                # the call's fit id), not the feeder_start span it outlives
                feed_ctx = contextvars.copy_context()
                with span("zoo.train.feeder_start"):
                    batch_iter = train_set.batches(
                        batch_size, shuffle=True, seed=seed, epoch=epoch,
                        drop_last=True, start_batch=start_batch,
                        process_shard=_process_shard(),
                    )
                    loss_dev = None
                    bi = start_batch
                    # 60s budget: the feeder beats per batch AND while blocked
                    # on a full queue, so only a truly stalled input pipeline
                    # (the tf.data failure mode) exceeds it.  The feeder THREAD
                    # unregisters the component when it exits (on_exit), so the
                    # main thread never races a late beat.
                    health.register("infeed", stale_after=60.0)
                    # batch placement comes from the PLAN (its batch_axes — the
                    # data axis for every canned plan; ("dcn", "data") under a
                    # hybrid-mesh plan), not a hard-wired DATA_AXIS
                    baxes = plan.batch_axes
                    shard_single = partial(ctx.shard_batch, axes=baxes)
                    chunked = k > 1 or controller is not None
                    if chunked:
                        # Fused dispatch: the feeder consumes the CHUNKED stream.
                        # Full chunks are stacked into a [K, batch, ...]
                        # super-batch ON THE FEEDER THREAD (host work overlapping
                        # device compute, like every other shard_fn cost) and
                        # sharded with axis 1 on the data axis, so each inner
                        # scan step sees the same per-chip shards as K=1.
                        def shard_item(item, _stack=partial(
                                ctx.shard_batch_stacked, axes=baxes),
                                       _single=shard_single):
                            kind, payload = item
                            if kind == "scan":
                                stacked = jax.tree_util.tree_map(
                                    lambda *xs: np.stack(xs), *payload)
                                return ("scan", _stack(stacked), len(payload))
                            return ("single", _single(payload), 1)

                        # Autotune: chunk sizes follow the controller's K
                        # hill-climb, re-read at every chunk boundary; the batch
                        # sequence (and so the trajectory) is unchanged.
                        feed_src = (_chunk_batches_dynamic(
                            batch_iter, controller.current_k)
                            if controller is not None
                            else _chunk_batches(batch_iter, k))
                        shard_fn = shard_item
                    else:
                        feed_src, shard_fn = batch_iter, shard_single
                    feeder = _DeviceFeeder(
                        feed_src, shard_fn, depth=cfg.infeed_depth,
                        heartbeat=lambda: health.heartbeat("infeed"),
                        on_exit=lambda: health.unregister("infeed"),
                        metrics=step_metrics, context=feed_ctx)
                prof_active = False
                try:
                    feeder_iter = iter(feeder)
                    first = {"first": True}
                    while True:
                        t_iter0 = time.perf_counter()
                        with span("zoo.train.data_wait", args=first):
                            sharded = next(feeder_iter, _SENTINEL)
                        first = None
                        t_data = time.perf_counter()
                        if sharded is _SENTINEL:
                            break
                        if prof_at is not None and not prof_active \
                                and not self._profiled \
                                and self.global_step >= prof_at:
                            jax.profiler.start_trace(prof_dir)
                            prof_active = True
                            prof_at = self.global_step  # anchor the stop check
                        # span covers HOST-side dispatch only (the jitted
                        # step is async; device time shows in the
                        # jax.profiler capture, not here) — named to match
                        # zoo_train_step_dispatch_seconds
                        losses = None
                        # zoolint: disable=host-sync -- host int boxing of the step index, not a device fetch
                        step_arr = np.asarray(self.global_step, np.int32)
                        with span("zoo.train.step_dispatch",
                                  args={"step": self.global_step}):
                            if chunked:
                                kind, payload, nk = sharded
                                if kind == "scan":
                                    # ONE dispatch advances nk inner steps;
                                    # losses come back as a [nk] device
                                    # array.  Under autotune nk follows the
                                    # hill-climb, so the fused program is
                                    # looked up per-chunk (a dict hit after
                                    # each K's first compile).
                                    fn = fused_fn if controller is None \
                                        else self._train_step_for(
                                            dev_tf, nk, plan)
                                    params, opt_state, state, losses = \
                                        fn(
                                            params, opt_state, state,
                                            seed_arr, step_arr, payload)
                                    loss_dev = losses[nk - 1]
                                else:  # partial tail chunk: K=1 fallback
                                    params, opt_state, state, loss_dev = \
                                        step_fn(
                                            params, opt_state, state,
                                            seed_arr, step_arr, payload)
                            else:
                                nk = 1
                                params, opt_state, state, loss_dev = step_fn(
                                    params, opt_state, state, seed_arr,
                                    step_arr, sharded
                                )
                        t_disp = time.perf_counter()
                        with span("zoo.train.on_iteration"):
                            self.global_step += nk
                            if prof_active and self.global_step >= \
                                    prof_at + cfg.profile_steps:
                                # zoolint: disable=host-sync -- intentional: the trace must close on a completed step
                                jax.block_until_ready(loss_dev)
                                jax.profiler.stop_trace()
                                prof_active = False
                                self._profiled = True
                                logger.info("profiler trace written to %s", prof_dir)
                            bi += nk
                            n_records += batch_size * nk
                            tstate.iteration = self.global_step
                            tstate.epoch_finished = False
                            if losses is not None and self._writers:
                                # TB gets every inner step's loss, not just the
                                # boundary one: ONE device slice for the first
                                # nk-1 (the flush expands it; the last loss is
                                # buffered as a scalar by _on_iteration) —
                                # per-element indexing here would reintroduce
                                # nk host dispatches per fused step
                                base = self.global_step - nk
                                if nk > 1:
                                    self._loss_buffer.append(
                                        (base + 1, losses[: nk - 1]))
                            # Callbacks/triggers fire ONCE per dispatch, at the
                            # K-step boundary (docs/performance.md caveat):
                            # checkpoints, validation and loss flushes see
                            # iteration counts in strides of nk.
                            fired = self._on_iteration(
                                tstate, loss_dev, params, opt_state, state,
                                checkpoint_trigger, validation_set,
                                validation_trigger, epoch, bi, seed, batch_size,
                            )
                            params, opt_state, state = fired
                            # step-time breakdown: data-wait (infeed the feeder
                            # failed to hide) / dispatch / full iteration
                            step_s = time.perf_counter() - t_iter0
                            step_metrics.record_step(
                                t_data - t_iter0, t_disp - t_data,
                                step_s, batch_size * nk, steps=nk)
                            if controller is not None:
                                # one measured dispatch feeds the K hill-climb
                                # (full loop-iteration wall time — the quantity
                                # fusion amortizes)
                                controller.observe_dispatch(nk, step_s)
                            health.heartbeat("train_loop")
                            # flight recorder: one structured record per step
                            # (bounded ring — a postmortem shows the FINAL
                            # steps), stragglers flagged against rolling p50
                            flight.record(
                                "step", loop="train", step=self.global_step,
                                epoch=epoch, data_wait_s=round(t_data - t_iter0, 6),
                                dispatch_s=round(t_disp - t_data, 6),
                                step_s=round(step_s, 6),
                                **({"fused_steps": nk} if nk > 1 else {}))
                            # straggler detection on PER-STEP time: a K-step
                            # fused dispatch is ~K x a tail single dispatch by
                            # construction, so comparing raw dispatch times
                            # against one rolling p50 would flag every fused
                            # dispatch in epochs that end with a tail
                            if straggler.observe(step_s / nk):
                                step_metrics.stragglers.inc()
                                flight.record(
                                    "straggler", loop="train",
                                    step=self.global_step,
                                    step_s=round(step_s, 6),
                                    per_step_s=round(step_s / nk, 6),
                                    rolling_p50_s=round(
                                        straggler.rolling_p50(), 6))
                            if elastic is not None:
                                # The STEP BARRIER (ISSUE 16): the membership
                                # ledger's (generation, world, members) doc is
                                # the single source of truth, read once per
                                # dispatch; a generation change snapshots at
                                # this exact boundary and yields the fit.
                                newdoc = elastic.poll()
                                if newdoc is not None:
                                    self._elastic_yield(
                                        newdoc, params, opt_state, state,
                                        tstate, epoch, bi, seed, flight)
                finally:
                    feeder.stop()
                    if prof_active:
                        # epoch ended (or failed) mid-capture: close the trace
                        jax.profiler.stop_trace()
                        self._profiled = True
                        prof_at = None
                # epoch boundary (the only unconditional host sync per epoch)
                if loss_dev is not None:
                    # the span times the fetch and nothing else: it is how
                    # far this loop ran ahead of the device
                    with span("zoo.train.epoch_sync",
                              observe=step_metrics.epoch_sync.observe):
                        # zoolint: disable=host-sync -- deliberate once-per-epoch sync (the comment above is the contract)
                        tstate.loss = float(loss_dev)
                # read after the sync: before it the dispatches are only
                # queued, and the rate would be the host loop's
                dt = time.perf_counter() - epoch_t0
                with span("zoo.train.epoch_close"):
                    self._flush_loss_buffer()
                    throughput = n_records / max(dt, 1e-9)
                    logger.info(
                        "epoch %d done: loss=%.4f, %.1f records/s, step=%d",
                        epoch, tstate.loss if tstate.loss is not None else float("nan"),
                        throughput, self.global_step,
                    )
                    self.history.append(
                        {"epoch": epoch, "loss": tstate.loss,
                         "throughput": throughput}
                    )
                    if self._writers:
                        self._writers[0].add_scalar(
                            "Throughput", throughput, self.global_step
                        )
                    step_metrics.record_epoch(epoch, throughput)
                    _publish_state_gauges(state)
                    record_device_memory()  # HBM gauges (no-op on CPU backends)
                    tstate.epoch_finished = True
                    epoch += 1
                    tstate.epoch = epoch
                    start_batch = 0
                    params, opt_state, state = self._on_iteration(
                        tstate, loss_dev, params, opt_state, state,
                        checkpoint_trigger, validation_set, validation_trigger,
                        epoch, 0, seed, batch_size,
                    )
        self.epoch = epoch
        health.unregister("train_loop")  # finished on purpose, not wedged
        return params, opt_state, state

    def _flush_loss_buffer(self):
        """Convert buffered device loss scalars and write them to TB.

        Values are flushed well after their step was dispatched, so the
        float() conversions read already-computed results instead of forcing
        a device round-trip per iteration.
        """
        if not self._loss_buffer:
            return
        buf, self._loss_buffer = self._loss_buffer, []
        last = None
        for it, ld in buf:
            arr = np.asarray(ld)
            if arr.ndim == 0:
                vals = [(it, float(arr))]
            else:
                # fused dispatch buffered a [K-1] loss slice under its
                # FIRST inner step's iteration: one device fetch here
                # expands it
                vals = [(it + j, float(v)) for j, v in enumerate(arr)]
            for i, v in vals:
                last = v
                if self._writers:
                    self._writers[0].add_scalar("Loss", v, i)
        return last

    def _on_iteration(self, tstate, loss_dev, params, opt_state, state,
                      checkpoint_trigger, validation_set,
                      validation_trigger, epoch, next_batch, seed,
                      batch_size):
        if loss_dev is not None:
            # Keep the raw device scalar (no sync); loss-based triggers
            # comparing against it only pay the sync when actually used.
            tstate.loss = loss_dev
            if self._writers:
                self._loss_buffer.append((tstate.iteration, loss_dev))
                if len(self._loss_buffer) >= 50:
                    self._flush_loss_buffer()
        if validation_set is not None and validation_trigger is not None \
                and validation_trigger(tstate):
            # NOTE: do NOT attach the live buffers to the model here — the
            # next train step donates them, which would leave model.params
            # pointing at deleted arrays.
            results = self._evaluate_with(params, state, validation_set,
                                          batch_size=batch_size)
            tstate.score = next(
                (v for k, v in results.items() if k != "loss"),
                -results.get("loss", 0.0),
            )
            logger.info("validation @ step %d: %s", tstate.iteration,
                        results)
            if self._writers:
                for k, v in results.items():
                    self._writers[1].add_scalar(k, v, tstate.iteration)
        if checkpoint_trigger is not None and self._ckpt is not None \
                and checkpoint_trigger(tstate):
            opt_flat = jax.tree_util.tree_leaves(opt_state)
            self._ckpt.save(
                f"{tstate.iteration}",
                dict(params=params, state=state, opt_flat=opt_flat,
                     global_step=tstate.iteration, epoch=epoch,
                     next_batch=next_batch, seed=seed,
                     # the plan's spec trees (plain lists — safe_load
                     # clean): what layout this snapshot trained under,
                     # so elastic resume reshards knowingly through the
                     # partitioner
                     plan=getattr(self, "_plan_record", None)),
            )
        return params, opt_state, state

    def _elastic_yield(self, newdoc, params, opt_state, state, tstate,
                       epoch, next_batch, seed, flight):
        """Safe-snapshot at the step barrier and yield the fit to the
        elastic runtime (resume-at-new-world-size entry, ISSUE 16).

        The snapshot carries the exact iterator position
        (epoch/next_batch) and the plan record, so the successor leg —
        same process at a refolded mesh, or a fresh cohort — resumes
        mid-epoch from LATEST through the partitioner with the batch
        schedule (and so the RNG-folded trajectory) unchanged.  The
        flush before the raise makes the snapshot DURABLE before any
        worker acts on the new generation."""
        from analytics_zoo_tpu.elastic.membership import GenerationChange

        if self._ckpt is not None:
            opt_flat = jax.tree_util.tree_leaves(opt_state)
            self._ckpt.save(
                f"{tstate.iteration}",
                dict(params=params, state=state, opt_flat=opt_flat,
                     global_step=tstate.iteration, epoch=epoch,
                     next_batch=next_batch, seed=seed,
                     plan=getattr(self, "_plan_record", None)),
            )
            self._ckpt._wait()
        flight.record(
            "elastic", event="yield", step=tstate.iteration,
            generation=newdoc.get("generation"),
            world=newdoc.get("world"))
        self.epoch = epoch
        raise GenerationChange(newdoc)

    # ------------------------------------------------------------------
    # pure-device step timing (the bench decomposition hook)
    # ------------------------------------------------------------------
    def measure_pure_step(self, batch: dict, n_steps: int = 20,
                          device_transform=None) -> float:
        """Time the compiled train step on a device-resident batch.

        Returns seconds/step.  Uses FRESH device buffers (host round-trip
        copies) so the step's donation can never delete the live
        model/optimizer arrays, and a throwaway warm step so compile and
        transfer cost are excluded.  This is the "pure step" half of the
        bench's e2e-vs-compute decomposition; the difference to e2e is the
        infeed the feeder failed to hide.

        The compiled step is CACHED (keyed on transform + input
        signature, sharing the fit-loop cache), so repeated probes
        measure steady state: only the first call for a signature pays
        compile, and that warmup cost is reported separately in
        ``last_probe_warmup_seconds`` (0.0 on cached re-probes) instead
        of polluting the per-step figure.
        """
        if n_steps < 1:
            raise ValueError("n_steps must be >= 1")
        ctx = self.ctx
        plan = self._resolved_plan()
        step_fn = self._train_step_for(device_transform, 1, plan)
        params, state = self.model.build_params()
        host = jax.tree_util.tree_map(np.asarray, (params, state))
        params = self._place_params(host[0], plan)
        state = jax.device_put(host[1], ctx.replicated())
        opt_state = self._place_opt_state(self.optimizer.init(params),
                                          plan)
        sharded = ctx.shard_batch(batch, axes=plan.batch_axes)
        seed_arr = np.asarray(0, np.int32)
        sig = (device_transform, plan.cache_key(), tuple(
            (path, tuple(leaf.shape), str(leaf.dtype))
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(sharded)[0]))
        t_warm = time.perf_counter()
        params, opt_state, state, loss = step_fn(
            params, opt_state, state, seed_arr, np.asarray(0, np.int32),
            sharded)
        float(loss)  # ends the window: fetching a scalar that depends
        #              on the whole step returns only when it has run
        warm_s = time.perf_counter() - t_warm
        if sig not in self._pure_step_warm:
            # first probe at this signature: warm_s is compile + first
            # step; report it separately so callers can quote cold cost
            self._pure_step_warm[sig] = warm_s
            self.last_probe_warmup_seconds = warm_s
        else:
            self.last_probe_warmup_seconds = 0.0
        t0 = time.perf_counter()
        for i in range(n_steps):
            params, opt_state, state, loss = step_fn(
                params, opt_state, state, seed_arr,
                np.asarray(i + 1, np.int32), sharded)
        float(loss)
        return (time.perf_counter() - t0) / n_steps

    # ------------------------------------------------------------------
    # AOT warmup (the compile plane, common/compile_cache.py)
    # ------------------------------------------------------------------
    def warmup(self, batch: dict, device_transform=None,
               steps_per_dispatch: int | None = None, plan=None) -> dict:
        """Pay XLA compilation for the train step BEFORE the first real
        batch (``.lower().compile()`` through the compile plane).

        ``batch`` is an example host batch dict (``{"x": ..., "y": ...}``,
        leading dim = the GLOBAL batch size fit() will use).
        ``device_transform`` must be the SAME transform the training
        FeatureSet carries (``train_set.device_transform``; the step
        cache is keyed on it) — warming with the default ``None`` while
        fit() uses a transform compiles a program fit never dispatches.
        Compiles the K=1 step and — when ``steps_per_dispatch`` (default: the
        configured ``ZOO_STEPS_PER_DISPATCH``) is > 1 — the fused scan-K
        step too, then runs ONE throwaway dispatch (a full train step on
        the example batch against fresh random-init buffers; results
        discarded, live model state untouched) so the in-process jit
        dispatch cache is warm.  With ``ZOO_COMPILE_CACHE`` set, an AOT
        ``.lower().compile()`` additionally populates the persistent
        cache first — the throwaway dispatch (and every later process
        compiling the same program) then deserializes it instead of
        re-running XLA; without a cache dir the AOT pass is skipped so
        each program compiles exactly once.

        Returns ``{label: seconds_to_ready}`` per program (AOT compile,
        if any, plus the throwaway dispatch); AOT compiles are also
        recorded in ``zoo_compile_seconds``.
        """
        ctx = self.ctx
        from analytics_zoo_tpu.common.compile_cache import (
            maybe_enable_persistent_cache,
        )
        maybe_enable_persistent_cache(ctx.config.compile_cache)
        plan = self._resolved_plan(plan)
        k = steps_per_dispatch if steps_per_dispatch is not None \
            else int(ctx.config.steps_per_dispatch or 1)
        if int(k) < 1:
            # same contract as ZooConfig: misconfigured K fails loudly
            # on every entry point (and before touching the step cache)
            raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
        params, state = self.model.build_params()
        host = jax.tree_util.tree_map(np.asarray, (params, state))
        out = {}
        host_batch = jax.tree_util.tree_map(np.asarray, batch)
        # Multi-host: the batch arg is GLOBAL (the documented contract);
        # fit()'s shard path consumes process-LOCAL rows, so slice ours
        # out — otherwise the warm program's batch dim would be
        # process_count x fit's.
        from analytics_zoo_tpu.feature.dataset import _slice_batch_rows
        host_batch = _slice_batch_rows(host_batch, _process_shard())
        for kk in sorted({1, k}):
            step_fn = self._train_step_for(device_transform, kk, plan)
            # fresh device buffers per variant: the throwaway dispatch
            # donates them, and the live model buffers are never touched.
            # params/opt_state take the SAME plan placement fit() will
            # use: the compiled program specializes on input shardings,
            # so a replicated warm here would compile a program fit
            # never runs.
            params = self._place_params(host[0], plan)
            state = jax.device_put(host[1], ctx.replicated())
            opt_state = self._place_opt_state(
                self.optimizer.init(params), plan)
            if kk == 1:
                sharded = ctx.shard_batch(host_batch,
                                          axes=plan.batch_axes)
            else:
                sharded = ctx.shard_batch_stacked(
                    jax.tree_util.tree_map(
                        lambda x: np.stack([x] * kk), host_batch),
                    axes=plan.batch_axes)
            args = (params, opt_state, state, np.asarray(0, np.int32),
                    np.asarray(0, np.int32), sharded)
            t0 = time.perf_counter()
            # ONE dispatch: the PlannedStep (parallel/plan.py) AOT-
            # lowers through timed_compile on its first call — the
            # persistent cache is populated / hit and the HLO features
            # extracted right here — then the cached executable runs.
            res = step_fn(*args)
            jax.block_until_ready(res[-1])
            out[step_fn.label] = time.perf_counter() - t0
        logger.info("warmup compiled %s", out)
        return out

    # ------------------------------------------------------------------
    # evaluate (Estimator.scala:157-176; KerasNet.evaluate)
    # ------------------------------------------------------------------
    def evaluate(self, val_set: FeatureSet, batch_size: int = 32) -> dict:
        if getattr(self.model, "params", None) is None \
                and self.global_step == 0:
            # Matches model.evaluate-before-fit semantics, but loudly: the
            # metrics below are RANDOM-weight metrics (round-2 verdict
            # Weak #10 — silent before).
            logger.warning(
                "evaluate() called before any training: materializing "
                "fresh random weights; metrics reflect an untrained model")
        params, state = self.model.build_params()
        return self._evaluate_with(params, state, val_set, batch_size)

    def _evaluate_with(self, params, state, val_set: FeatureSet,
                       batch_size: int = 32) -> dict:
        ctx = self.ctx
        dev_tf = getattr(val_set, "device_transform", None)
        if self._eval_step_fn is None or self._eval_step_fn[0] is not dev_tf:
            self._eval_step_fn = (dev_tf, self._build_eval_step(dev_tf))
        accum = None
        for batch in val_set.batches(batch_size, shuffle=False,
                                     drop_last=False,
                                     pad_to_batch=ctx.data_parallel_size,
                                     process_shard=_process_shard()):
            sharded = ctx.shard_batch(batch)
            stats = self._eval_step_fn[1](params, state, sharded)
            host = [[np.asarray(s) for s in group] for group in stats]
            if accum is None:
                accum = host
            else:
                accum = [
                    [a + b for a, b in zip(ga, gb)]
                    for ga, gb in zip(accum, host)
                ]
        results = {}
        idx = 0
        if self.loss is not None:
            num, den = accum[idx]
            results["loss"] = float(num) / max(float(den), 1e-12)
            idx += 1
        for m in self.metrics:
            results[m.name] = m.finalize(accum[idx])
            idx += 1
        return results
