"""From a profiler capture to numbers: the device's busy union and idle
share, operation time by name, and the idle time by where in the job it
falls.

The capture holds the device's planes only.  With the host's tracer on,
the TPU runtime records an event for every few bytes it transposes on the
way to the device, 27 million of them in a 16 s window of 38.5 MB batches
(my chip run, PR 24): a gigabyte of trace and a host too busy to feed the
chip.  So the window's length comes from the host's clock around the
capture, and an idle gap is named by where it lies among the executed
programs, which the device's ``XLA Modules`` line gives one event each.

``load`` reads an ``.xplane.pb`` through ``jax.profiler.ProfileData`` into
plain tuples; everything else works on those, so it is tested on
hand-built traces."""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import NamedTuple

#: lines of a device plane: one event an executed operation, one event an
#: executed program
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"

INSIDE = "inside a step program"
BETWEEN_STEPS = "between steps of one call (feed, dispatch)"
BETWEEN_CALLS = "between calls (closing sync, re-entry, feeder restart)"
EDGES = "before the first step and after the last"


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Capture(NamedTuple):
    device_ops: dict        # device plane name -> [Event] of OPS_LINE
    modules: dict           # device plane name -> [Event] of MODULES_LINE


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


#: how the HLO line of a Pallas (Mosaic) kernel's call says what it is
KERNEL_TARGET = "tpu_custom_call"


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO line
    (``%fusion.3 = bf16[...] fusion(...)``): keep the instruction's name.
    A Pallas kernel's call is marked and keeps the number of arrays it
    returns, ``<instruction>/tpu_custom_call/<n>``: its ``pallas_call``
    carries no name, and its instruction is named after the transforms
    it was traced under."""
    head, _, rest = name.partition(" = ")
    short = head.lstrip("%")[:96]
    if KERNEL_TARGET in rest:
        outputs = rest.split(" custom-call(", 1)[0].count("[")
        return f"{short}/{KERNEL_TARGET}/{outputs}"
    return short


def load(path: str) -> Capture:
    from jax.profiler import ProfileData

    device_ops, modules = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                device_ops[plane.name] = [
                    Event(short_name(ev.name), ev.start_ns, ev.duration_ns)
                    for ev in line.events]
            elif line.name == MODULES_LINE:
                modules[plane.name] = sorted(
                    (Event(ev.name.split("(", 1)[0], ev.start_ns,
                           ev.duration_ns) for ev in line.events),
                    key=lambda e: e.start_ns)
    return Capture(device_ops, modules)


def busy_union(events) -> list[tuple[float, float]]:
    """The merged intervals in which any event ran."""
    merged: list[list[float]] = []
    for start, end in sorted((e.start_ns, e.end_ns) for e in events):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(events) -> float:
    return sum(b - a for a, b in busy_union(events)) / 1e9


def op_seconds(events) -> dict[str, float]:
    """Seconds by operation name."""
    out: dict[str, float] = defaultdict(float)
    for e in events:
        out[e.name] += e.dur_ns / 1e9
    return dict(out)


def idle_by_place(ops, modules, steps_per_call: int,
                  window_s: float) -> dict[str, float]:
    """The window's idle seconds by where they fall.  ``modules``: the
    executed step programs in order, ``steps_per_call`` of them to each
    call of the job.  What the capture does not span, the host's window
    less the stretch from the first program's start to the last one's end,
    lies at the window's two edges."""
    out: dict[str, float] = defaultdict(float)
    if not modules:
        return {EDGES: window_s}
    busy, at = busy_union(ops), 0
    for i, module in enumerate(modules):
        # both are in order of time: one sweep
        while at < len(busy) and busy[at][1] <= module.start_ns:
            at += 1
        inside, j = 0.0, at
        while j < len(busy) and busy[j][0] < module.end_ns:
            inside += min(busy[j][1], module.end_ns) \
                - max(busy[j][0], module.start_ns)
            j += 1
        out[INSIDE] += (module.dur_ns - inside) / 1e9
        if i + 1 < len(modules):
            gap = max(0.0, modules[i + 1].start_ns - module.end_ns) / 1e9
            boundary = (i + 1) % steps_per_call == 0
            out[BETWEEN_CALLS if boundary else BETWEEN_STEPS] += gap
    spanned = (modules[-1].end_ns - modules[0].start_ns) / 1e9
    out[EDGES] = max(0.0, window_s - spanned)
    return dict(out)


def top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
