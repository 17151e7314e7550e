"""Device: of the device's idle seconds between consecutive step programs
of the traced window, between steps of one call and between calls alike,
the share during which a span of the program's main thread was open, each
second put down to the innermost one.

The spans are put on the clock of the device's events by
``_spans.clock_offset_ns``: the k-th ``zoo.train.step_dispatch`` span of
the window against the k-th step program of ``run["step_modules"]``, and
each ``zoo.train.epoch_sync`` against the last program dispatched before
it.

Writes to standard error ``clock <anchor|paired|paired-sync> <offset ns>
between <lower> <upper>`` and one line a span name, ``gap <span>
<seconds>``, most first."""

import bisect
import sys
from collections import defaultdict

from benchmark.manifest import sibling

spans = sibling(__file__, "_spans")


def read(run, out=sys.stderr):
    step_modules = run["step_modules"]
    if not step_modules or not all(step_modules.values()):
        return None
    main = [e for call in spans.window_calls(run)
            for e in spans.main_thread(call)]
    if not main:
        return None
    clock = spans.get_tracer().device_clock_ns
    on_host = [(*clock(e), e["name"]) for e in main]
    dispatch = sorted(s[0] for s in on_host if s[2] == spans.DISPATCH)
    # a sync waits for the last program dispatched before it
    syncs = [(end, bisect.bisect_left(dispatch, start))
             for start, end, name in on_host if name == spans.SYNC]
    by_span: dict = defaultdict(float)
    for modules in step_modules.values():
        if len(modules) != len(dispatch):
            return None
        found = spans.clock_offset_ns(
            dispatch, [m.start_ns for m in modules],
            [(end, modules[k - 1].end_ns) for end, k in syncs if k])
        if found is None:
            return None
        offset, how, lower, upper = found
        out.write(f"clock {how} {offset} between {lower} {upper}\n")
        gaps = [(a.end_ns, b.start_ns) for a, b in zip(modules, modules[1:])]
        shifted = [(s + offset, e + offset, name) for s, e, name in on_host]
        for name, seconds in spans.idle_by_span(gaps, shifted).items():
            by_span[name] += seconds
    for name, seconds in sorted(by_span.items(), key=lambda kv: -kv[1]):
        out.write(f"gap {name} {seconds:.6f}\n")
    idle = sum(by_span.values())
    if idle == 0.0:
        return None
    return 100.0 * (1.0 - by_span.get(spans.UNDER_NO_SPAN, 0.0) / idle)
