"""Step tracing — nested ``span()`` blocks exported as Chrome-trace JSON.

The role of the reference's ``Utils.timeIt`` logging, upgraded to a
structured timeline: every ``with span("zoo.train.step")`` records one
complete event (``ph: "X"``) into the process-global :class:`Tracer`;
``Tracer.to_chrome_trace()`` renders the ``chrome://tracing`` /
Perfetto-loadable document, the same format ``jax.profiler`` traces use
so the two timelines can be eyeballed side by side.

Spans nest through a :mod:`contextvars` variable, so nesting is correct
across threads (the serving loop thread and the infeed thread each get
their own span stack).  Each event carries an ``id`` unique in the
process, its parent's ``parent_id`` (and name, ``args.parent``) and
``fit``: the id of the outermost ``span(..., fit=True)`` open around it,
which every span of one ``Estimator.train`` call shares.  A thread
started under ``contextvars.copy_context().run`` (the estimator's infeed
feeder) inherits both, so its spans join the call's tree from their own
``tid``.

Two optional device hooks, both gated on jax being importable so the
module stays dependency-free:

- ``span(..., sync=tree)`` calls ``jax.block_until_ready`` on the tree
  before closing the span — an explicit device-sync point, because an
  async-dispatched step's host-side duration is otherwise just the
  dispatch cost (the same reason ``Estimator.measure_pure_step``
  fetch-forces its loss).
- ``Tracer(jax_bridge=True)`` (default) additionally wraps each span in
  ``jax.profiler.TraceAnnotation`` when jax is initialized, so zoo spans
  show up inside ``jax.profiler`` captures (ZOO_PROFILE_DIR).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import threading
import time

__all__ = ["Tracer", "span", "get_tracer", "set_tracer",
           "MIXER_SCOPE", "FFN_SCOPE", "HEAD_SCOPE", "SSD_SCOPE"]

#: The device's parts of a step, beside the host's spans: the names of the
#: ``jax.named_scope``s that the decoders open around a block's mixer
#: branch, its feed-forward branch and the head with its loss.  They are
#: metadata only (an operation's ``op_name`` in the compiled program, which
#: a profiler capture keeps), never an operation; the benchmark reads them
#: as ``step_mixer_ms``, ``step_ffn_ms`` and ``step_head_ms``.
MIXER_SCOPE, FFN_SCOPE, HEAD_SCOPE = "zoo.mixer", "zoo.ffn", "zoo.head"
#: Inside ``zoo.mixer``, around a Mamba-2 mixer's body (in-projection,
#: convolution, scan, gated norm, out-projection): ``step_ssd_ms``.
SSD_SCOPE = "zoo.ssd"

# Innermost open span as (name, id), per execution context / thread.
_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "zoo_current_span", default=None)
# Id of the span that opened the enclosing fit() call, None outside one.
_current_fit: contextvars.ContextVar = contextvars.ContextVar(
    "zoo.current_fit", default=None)
# next() on a count is atomic under the interpreter lock
_span_ids = itertools.count(1)


def _block_until_ready(tree):
    """Device-sync a pytree if jax is importable; no-op otherwise."""
    try:
        import jax
    except Exception:  # pragma: no cover - jax is always in this image
        return
    jax.block_until_ready(tree)


class Tracer:
    """Bounded in-memory event sink.

    ``max_events`` caps memory on multi-day jobs as a RING buffer: past
    the cap the OLDEST events are evicted and counted (``dropped``),
    never silently — the export carries the eviction count as metadata.
    Keeping the newest window is the debugging-shaped choice: the trace
    an operator saves after a day-2 anomaly must contain day 2, not the
    first hour of startup spans.
    """

    def __init__(self, enabled: bool = True, max_events: int = 50_000,
                 jax_bridge: bool = True):
        import collections

        self.enabled = bool(enabled)
        self.max_events = int(max_events)
        self.jax_bridge = bool(jax_bridge)
        self.dropped = 0  # guarded-by: _lock
        self._events: collections.deque = collections.deque(  # guarded-by: _lock
            maxlen=self.max_events)
        self._lock = threading.Lock()
        # registry counter mirroring `dropped`, resolved lazily on the
        # first eviction (constructing a Tracer must not force the
        # process-global registry into existence)
        self._drop_counter = None
        # perf_counter origin so ts fields are small positive
        # microseconds — plus a (monotonic, epoch) anchor captured at
        # the SAME instant, so tools/flight_merge.py can place this
        # process's µs timeline on the cluster-wide wall clock (each
        # process's trace clock alone is only self-consistent)
        self._t0 = time.perf_counter()
        self._t0_monotonic = time.monotonic()
        self._t0_epoch_ns = time.time_ns()
        self._t0_epoch = self._t0_epoch_ns / 1e9

    # -- recording ------------------------------------------------------
    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def add_event(self, name: str, ts_us: float, dur_us: float,
                  args: dict | None = None, *, id: int | None = None,
                  parent_id: int | None = None, fit: int | None = None):
        if not self.enabled:
            return
        ev = {
            "name": name,
            "ph": "X",
            "ts": ts_us,
            "dur": dur_us,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "cat": "zoo",
            "id": next(_span_ids) if id is None else id,
            "parent_id": parent_id,
            "fit": fit,
        }
        if args:
            ev["args"] = args
        evicting = False
        with self._lock:
            if len(self._events) == self.max_events:
                self.dropped += 1  # deque evicts the oldest on append
                evicting = True
            self._events.append(ev)
        if evicting:
            # ring evictions were silent before (ISSUE 2 satellite): a
            # scraper watching zoo_trace_spans_dropped_total now sees a
            # trace outgrowing its window without pulling /trace
            if self._drop_counter is None:
                from analytics_zoo_tpu.metrics.registry import get_registry

                self._drop_counter = get_registry().counter(
                    "zoo_trace_spans_dropped_total",
                    "span events evicted from the tracer ring buffer")
            self._drop_counter.inc()

    # -- export ---------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clock_anchor(self) -> dict:
        """The trace-origin instant on three clocks: ``ts=0`` µs of
        this trace corresponds to ``epoch`` wall time and ``monotonic``
        (CLOCK_MONOTONIC — shared by all processes of one boot, so
        same-host merges can sidestep wall-clock skew entirely)."""
        return {"epoch": self._t0_epoch,
                "monotonic": self._t0_monotonic,
                "pid": os.getpid()}

    def device_clock_ns(self, event: dict) -> tuple[int, int]:
        """``(start, end)`` of a recorded event in nanoseconds since the
        Unix epoch: the clock ``jax.profiler`` stamps a device plane's
        events with, so a span can be laid over the ``XLA Modules`` and
        ``XLA Ops`` lines of a capture of the same process."""
        start = self._t0_epoch_ns + int(event["ts"] * 1e3)
        return start, start + int(event["dur"] * 1e3)

    def to_chrome_trace(self) -> dict:
        """The ``chrome://tracing`` JSON object format."""
        doc = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "metadata": {"producer": "analytics_zoo_tpu.metrics.tracing",
                         "dropped_events": self.dropped,
                         "clock_anchor": self.clock_anchor()},
        }
        return doc

    def save(self, path: str) -> str:
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path

    def clear(self):
        with self._lock:
            self._events.clear()
            self.dropped = 0


@contextlib.contextmanager
def span(name: str, sync=None, args: dict | None = None,
         tracer: Tracer | None = None, observe=None, fit: bool = False):
    """Time a block as one trace event; nests via contextvars.  Also a
    decorator: ``@span("zoo.fit")`` times every call of the function.

    Args:
      name: event name (dotted convention: ``zoo.train.step``).
      sync: optional pytree passed to ``jax.block_until_ready`` before the
        span closes — makes the span cover device execution, not just the
        async dispatch.
      args: extra key/values attached to the event.  A block that raises
        still records its event, with the exception's type as
        ``args.error``.
      tracer: override the process-global tracer (tests).
      observe: called with the block's seconds (a histogram's
        ``observe``, a gauge's ``set``), from this span's own two clock
        reads and also with the tracer disabled, so the counter and the
        span cannot disagree.
      fit: this span is a ``fit()`` call's entry: unless a span around it
        already is, every span opened under it carries its ``id`` as
        ``fit``.
    """
    t = tracer if tracer is not None else get_tracer()
    if not t.enabled:
        # cheap disabled path: no contextvar churn, no event dict
        if observe is None:
            yield
        else:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                observe(time.perf_counter() - t0)
        if sync is not None:
            _block_until_ready(sync)
        return
    parent = _current_span.get()
    span_id = next(_span_ids)
    token = _current_span.set((name, span_id))
    fit_id = _current_fit.get()
    fit_token = None
    if fit and fit_id is None:
        fit_id = span_id
        fit_token = _current_fit.set(fit_id)
    annot = None
    if t.jax_bridge:
        try:
            import jax

            annot = jax.profiler.TraceAnnotation(name)
            annot.__enter__()
        except Exception:
            annot = None
    error = None
    t0 = t.now_us()
    try:
        yield
        if sync is not None:
            _block_until_ready(sync)
    except BaseException as e:
        error = type(e).__name__
        raise
    finally:
        dur = t.now_us() - t0
        if annot is not None:
            try:
                annot.__exit__(None, None, None)
            except Exception:
                pass
        _current_span.reset(token)
        if fit_token is not None:
            _current_fit.reset(fit_token)
        ev_args = dict(args) if args else {}
        if parent is not None:
            ev_args["parent"] = parent[0]
        if error is not None:
            ev_args["error"] = error
        t.add_event(name, t0, dur, ev_args or None, id=span_id,
                    parent_id=parent[1] if parent is not None else None,
                    fit=fit_id)
        if observe is not None:
            observe(dur / 1e6)


# ---------------------------------------------------------------------------
# Process-global default tracer.  ZOO_TRACE=0 disables span recording;
# ZOO_TRACE_EVENTS overrides the event cap.
# ---------------------------------------------------------------------------

_default: Tracer | None = None  # guarded-by: _default_lock
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                env = os.environ
                _default = Tracer(
                    enabled=env.get("ZOO_TRACE", "1") != "0",
                    max_events=int(env.get("ZOO_TRACE_EVENTS", "50000")),
                )
    return _default


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-global tracer; returns the previous one."""
    global _default
    with _default_lock:
        prev, _default = _default, tracer
    return prev
