"""Device: of the device's idle seconds between consecutive step programs
of the traced window, between steps of one call and between calls alike,
the share during which a span of the program's main thread was open, each
second put down to the innermost one.

The spans are put on the clock of the device's events by
``_spans.on_device_clock``: the k-th ``zoo.train.step_dispatch`` span of
the window against the k-th step program of ``run["step_modules"]``, and
each ``zoo.train.epoch_sync`` against the last program dispatched before
it.

Writes to standard error ``clock <anchor|paired|paired-sync> <offset ns>
between <lower> <upper>`` and one line a span name, ``gap <span>
<seconds>``, most first."""

import sys
from collections import defaultdict

from benchmark.manifest import sibling

spans = sibling(__file__, "_spans")


def read(run, out=sys.stderr):
    shifted = spans.on_device_clock(run, out)
    if shifted is None:
        return None
    by_span: dict = defaultdict(float)
    for plane, modules in run["step_modules"].items():
        gaps = [(a.end_ns, b.start_ns) for a, b in zip(modules, modules[1:])]
        for name, seconds in spans.idle_by_span(gaps,
                                                shifted[plane]).items():
            by_span[name] += seconds
    for name, seconds in sorted(by_span.items(), key=lambda kv: -kv[1]):
        out.write(f"gap {name} {seconds:.6f}\n")
    idle = sum(by_span.values())
    if idle == 0.0:
        return None
    return 100.0 * (1.0 - by_span.get(spans.UNDER_NO_SPAN, 0.0) / idle)
