"""Shared by the readers of the program's spans
(``analytics_zoo_tpu/metrics/tracing.py``): the window's ``fit`` calls
from the tracer's ring in the run's own process, a call's seconds by span,
and the spans laid over the device's clock.

A span is the tracer's event: ``name``, ``ts`` and ``dur`` in microseconds
of the host's clock, ``tid``, ``id``, ``parent_id`` and ``fit``, which
every span opened under one ``Estimator.train`` call shares.  A program
that records no ``fit`` (the commits before the spans) gives every reader
here nothing to read."""

from __future__ import annotations

import bisect
import math
from collections import defaultdict

from analytics_zoo_tpu.metrics import get_tracer

FIT, SYNC, DISPATCH = "zoo.fit", "zoo.train.epoch_sync", \
    "zoo.train.step_dispatch"
#: an idle second under no span of the main thread
UNDER_NO_SPAN = "(none)"


def window_calls(run: dict) -> list[list[dict]]:
    """The window's ``fit`` calls, oldest first, each the spans of one
    call in order of their start.  They are the last
    ``run["window"]["fits"]`` values of ``fit`` in the ring: the reference
    that runs after the window never enters ``Estimator.train``."""
    calls: dict = defaultdict(list)
    for e in get_tracer().events():
        if e.get("fit") is not None:
            calls[e["fit"]].append(e)
    fits = int(run["window"]["fits"])
    last = sorted(calls)[-fits:] if fits > 0 else []
    return [sorted(calls[fit], key=lambda e: e["ts"]) for fit in last]


def main_thread(call: list[dict]) -> list[dict]:
    """The spans of the thread that entered ``Estimator.train``."""
    tids = {e["tid"] for e in call if e["name"] == FIT}
    return [e for e in call if e["tid"] in tids]


def self_seconds(call: list[dict]) -> dict[tuple[bool, str], float]:
    """A call's seconds by (on the main thread, span name), each span less
    what its children on its own thread cover: the main thread's rows add
    up to its outermost span."""
    covered: dict = defaultdict(float)
    for e in call:
        covered[(e["tid"], e["parent_id"])] += e["dur"]
    main = {e["id"] for e in main_thread(call)}
    out: dict = defaultdict(float)
    for e in call:
        out[(e["id"] in main, e["name"])] += \
            (e["dur"] - covered[(e["tid"], e["id"])]) / 1e6
    return dict(out)


def leaf_cover(call: list[dict]) -> float | None:
    """The share of the call's ``zoo.fit`` span that the main thread's
    leaf spans under it cover."""
    spans = main_thread(call)
    parents = {e["parent_id"] for e in spans}
    fits = [e for e in spans if e["name"] == FIT]
    if not fits:
        return None
    lo, hi = fits[0]["ts"], fits[0]["ts"] + fits[0]["dur"]
    covered, at = 0.0, lo
    for e in spans:     # in order of start
        if e["id"] in parents or e["ts"] < lo:
            continue
        start, end = max(e["ts"], at), min(e["ts"] + e["dur"], hi)
        if end > start:
            covered, at = covered + end - start, end
    return covered / (hi - lo) if hi > lo else None


def clock_offset_ns(dispatch_ns: list[int], program_ns: list[float],
                    synced_ns: list[tuple[int, float]] = (),
                    agree_ns: int = 5_000_000
                    ) -> tuple[int, str, float, int] | None:
    """What to add to a span's nanoseconds to put it on the clock of the
    device's events, how it was got, and the two bounds it lies between.

    The k-th step program cannot start before the k-th dispatch span
    opens, so the offset is at most the least ``program - dispatch``:
    tight when a call's first step finds the chip idle and its batch on
    the device, loose by the batch's transfer when it does not.  A closing
    sync cannot return before the program it waits for has ended, so the
    offset is at least the most ``program's end - sync's end`` over
    ``synced_ns``: tight whenever the host was waiting, to the few
    milliseconds a scalar takes to come back.

    Where nought lies between the bounds and within ``agree_ns`` of one
    of them, the two clocks are one and the spans stand as they are
    (``anchor``).  Else the dispatch's bound is taken where the two lie
    within ``agree_ns`` of each other (``paired``), and the sync's where
    they do not: a launch waited for something (``paired-sync``)."""
    if not dispatch_ns or len(dispatch_ns) != len(program_ns):
        return None
    # whole nanoseconds: the epoch's are past what a float resolves
    upper = min(round(p) - d for d, p in zip(dispatch_ns, program_ns))
    lower = max((round(end) - sync for sync, end in synced_ns),
                default=-math.inf)
    if lower > upper:
        return None     # not the programs of these spans
    if lower <= 0 <= upper and min(-lower, upper) <= agree_ns:
        return 0, "anchor", lower, upper
    if upper - lower <= agree_ns or not synced_ns:
        return upper, "paired", lower, upper
    return lower, "paired-sync", lower, upper


def on_device_clock(run: dict, out=None) -> dict | None:
    """{device plane: the window's main-thread spans as (start, end, name)
    on the clock of that plane's events}: the k-th
    ``zoo.train.step_dispatch`` span of the window against the k-th step
    program of ``run["step_modules"]``, each ``zoo.train.epoch_sync``
    against the last program dispatched before it (``clock_offset_ns``).
    Nothing where there is no step program or no span, or where the spans
    are not these programs'.  Writes to ``out`` a line a plane, ``clock
    <anchor|paired|paired-sync> <offset ns> between <lower> <upper>``."""
    step_modules = run["step_modules"]
    if not step_modules or not all(step_modules.values()):
        return None
    main = [e for call in window_calls(run) for e in main_thread(call)]
    if not main:
        return None
    clock = get_tracer().device_clock_ns
    on_host = [(*clock(e), e["name"]) for e in main]
    dispatch = sorted(s[0] for s in on_host if s[2] == DISPATCH)
    # a sync waits for the last program dispatched before it
    syncs = [(end, bisect.bisect_left(dispatch, start))
             for start, end, name in on_host if name == SYNC]
    shifted = {}
    for plane, modules in step_modules.items():
        if len(modules) != len(dispatch):
            return None
        found = clock_offset_ns(
            dispatch, [m.start_ns for m in modules],
            [(end, modules[k - 1].end_ns) for end, k in syncs if k])
        if found is None:
            return None
        offset, how, lower, upper = found
        if out is not None:
            out.write(f"clock {how} {offset} between {lower} {upper}\n")
        shifted[plane] = [(s + offset, e + offset, name)
                          for s, e, name in on_host]
    return shifted


def idle_by_span(gaps: list[tuple[float, float]],
                 spans: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds of ``gaps`` by the innermost of ``spans`` (start, end,
    name; one thread's, so nested or apart) open at the time, all on one
    clock in nanoseconds."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    out: dict = defaultdict(float)
    for lo, hi in gaps:
        if hi <= lo:
            continue
        over = [s for s in spans[:bisect.bisect_left(starts, hi)]
                if s[1] > lo]
        cuts = sorted({lo, hi, *(t for s in over for t in s[:2]
                                 if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            # the innermost open over [a, b): the last to have started
            open_ = [s for s in over if s[0] <= a and s[1] >= b]
            name = max(open_, key=lambda s: (s[0], -s[1]))[2] \
                if open_ else UNDER_NO_SPAN
            out[name] += (b - a) / 1e9
    return dict(out)
