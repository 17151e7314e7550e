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
hand-built traces.

An operation keeps what the trace says of it beside its name: its scope,
the path of JAX's name stack that XLA carries as the instruction's
``op_name`` (``jit(train_step)/transpose(jvp(...))/checkpoint/...``), and
the profiler's category where it gives one.  A ``while`` or a
``conditional`` is one event and every operation of its body another on
the same line, inside its interval: ``nest`` finds an operation's loop,
``owned`` gives every busy nanosecond to the innermost operation open
then, and ``op_seconds`` counts a loop once, its children inside it."""

from __future__ import annotations

import glob
import math
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
#: the two places between programs, short, before a host span's name
BETWEEN = {BETWEEN_STEPS: "between_steps", BETWEEN_CALLS: "between_calls"}


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""         # the instruction's ``op_name``; "" where unsaid
    category: str = ""      # the profiler's, where it gives one

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Capture(NamedTuple):
    device_ops: dict        # device plane name -> [Event] of OPS_LINE
    modules: dict           # device plane name -> [Event] of MODULES_LINE
    start_ns: float | None = None   # the capture's start on the host's
    #                                 clock, where a plane's stats hold it


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


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message, in
    order: an ``int`` for a varint or a fixed field, a ``memoryview`` for a
    length-delimited one (a string, bytes or a message, not copied).
    ``ProfileData`` gives an event's own stats and not its metadata's, and
    the installation's generated classes come with a half-minute import:
    what the trace says of an operation is read off the wire."""
    at, end = 0, len(buf)
    while at < end:
        tag, at = _varint(buf, at)
        kind = tag & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, at = int.from_bytes(buf[at:at + size], "little"), at + size
        else:
            raise ValueError(f"wire type {kind} at byte {at}")
        yield tag >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def module_scopes(module) -> dict[str, str]:
    """{instruction name: ``op_name``} of a serialized ``HloModuleProto``
    (computations 3, their instructions 2: name 1, metadata 7, whose
    ``op_name`` is 2): every computation's, a loop's body with the
    entry's."""
    out = {}
    for field, computation in _fields(memoryview(module)):
        if field != 3:
            continue
        for field, instruction in _fields(computation):
            if field != 2:
                continue
            name = scope = ""
            for field, value in _fields(instruction):
                if field == 1:
                    name = _text(value)
                elif field == 7:
                    scope = next((_text(v) for f, v in _fields(value)
                                  if f == 2), "")
            if scope:
                out[name] = scope
    return out


#: the stats of an event's metadata that say where the operation came
#: from and what kind it is, and the stat of the host's metadata plane that
#: holds a program's ``HloProto`` (its ``hlo_module`` is field 1)
SCOPE_STAT, CATEGORY_STAT, HLO_STAT = "tf_op", "hlo_category", "Hlo Proto"


def said_of_operations(space) -> dict[str, tuple[str, str]]:
    """{an event's name on a device plane: (scope, category)} from a
    serialized ``XSpace`` (planes 1; a plane's name 2, event metadata 4,
    stat metadata 5; a metadata's name 2, display name 4, stats 5; a
    stat's metadata 1, string 5, bytes 6).  The scope is the metadata's
    ``tf_op`` stat, the instruction's ``op_name`` (with the colon that
    ends it taken off); where XLA's profiler leaves that out, as it does
    for a ``while``, the ``op_name`` that the capture's own copy of the
    program gives the instruction of that name."""
    said, unsaid, programs = {}, {}, []
    for field, plane in _fields(memoryview(space)):
        if field != 1:
            continue
        name, stat_names, metadata = "", {}, []
        for field, value in _fields(plane):
            if field == 2:
                name = _text(value)
            elif field in (4, 5):      # a map's entry: key 1, value 2
                entry = next(v for f, v in _fields(value) if f == 2)
                if field == 4:
                    metadata.append(entry)
                else:
                    parts = dict(_fields(entry))
                    stat_names[parts.get(1, 0)] = _text(parts.get(2, b""))
        wanted = {i: n for i, n in stat_names.items()
                  if n in (SCOPE_STAT, CATEGORY_STAT, HLO_STAT)}
        for entry in metadata:
            event = display = ""
            found = {}
            for field, value in _fields(entry):
                if field == 2:
                    event = _text(value)
                elif field == 4:
                    display = _text(value)
                elif field == 5:
                    stat = dict(_fields(value))
                    which = wanted.get(stat.get(1))
                    if which is not None:
                        found[which] = stat.get(5, stat.get(6, b""))
            if HLO_STAT in found:
                programs.append(found[HLO_STAT])
            if name.startswith(DEVICE_PLANE):
                said[event] = (
                    _text(found.get(SCOPE_STAT, b"")).rstrip(":"),
                    _text(found.get(CATEGORY_STAT, b"")))
                if not said[event][0]:
                    unsaid[event] = display     # the instruction's name
    if unsaid:
        by_instruction = {}
        for program in programs:
            for field, module in _fields(program):
                if field == 1:
                    by_instruction.update(module_scopes(module))
        for event, instruction in unsaid.items():
            said[event] = (by_instruction.get(instruction, ""),
                           said[event][1])
    return said


DEVICE_PLANE = "/device:TPU"
#: the plane and its stat that hold the capture's start, nanoseconds of the
#: host's epoch; a device event's ``start_ns`` counts from there (on the
#: chip it lay 0.3 ms inside the bounds that the readers' clock pairing
#: gives: PERF.md, PR 37)
ENVIRONMENT_PLANE, START_STAT = "Task Environment", "profile_start_time"


def load(path: str) -> Capture:
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        space = fh.read()
    said = said_of_operations(space)
    device_ops, modules, start_ns = {}, {}, None
    named: dict = {}        # an event's name -> its Event's constant part
    for plane in ProfileData.from_serialized_xspace(space).planes:
        if plane.name == ENVIRONMENT_PLANE:
            start_ns = next((value for name, value in plane.stats
                             if name == START_STAT), None)
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = device_ops[plane.name] = []
                for ev in line.events:
                    name = ev.name
                    if name not in named:
                        named[name] = (short_name(name),
                                       *said.get(name, ("", "")))
                    short, scope, category = named[name]
                    ops.append(Event(short, ev.start_ns, ev.duration_ns,
                                     scope, category))
            elif line.name == MODULES_LINE:
                modules[plane.name] = sorted(
                    (Event(ev.name.split("(", 1)[0], ev.start_ns,
                           ev.duration_ns) for ev in line.events),
                    key=lambda e: e.start_ns)
    return Capture(device_ops, modules, start_ns)


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


#: the operations whose event spans their body's: a loop, a branch
CONTAINERS = ("while", "conditional")


def _in_order(events) -> list[int]:
    """The events' indices by start, the longer first where two start
    together: a loop before its first child."""
    return sorted(range(len(events)),
                  key=lambda i: (events[i].start_ns, -events[i].dur_ns))


def nest(events) -> list[int]:
    """For each event the index of the innermost ``while`` or
    ``conditional`` of the same line that its interval lies inside, or -1:
    the trace lists a loop's children beside the loop."""
    parent = [-1] * len(events)
    open_: list[int] = []       # the loops open at the sweep's place
    for i in _in_order(events):
        e = events[i]
        while open_ and events[open_[-1]].end_ns <= e.start_ns:
            open_.pop()
        if open_ and e.end_ns <= events[open_[-1]].end_ns:
            parent[i] = open_[-1]
        if e.name.split(".", 1)[0] in CONTAINERS and e.dur_ns > 0:
            open_.append(i)
    return parent


def owned(events):
    """``(index, start, end)`` pieces that cover the events' busy union
    once, each the innermost event's open then, the last to have started:
    a loop keeps what its children leave of it."""
    open_: list[int] = []
    at = 0.0
    for i in [*_in_order(events), None]:
        upto = math.inf if i is None else events[i].start_ns
        while open_:
            top = events[open_[-1]]
            if top.end_ns > at:
                end = min(top.end_ns, upto)
                if end > at:
                    yield open_[-1], at, end
                    at = end
            if top.end_ns > upto:
                break
            open_.pop()
        if i is not None:
            at = max(at, upto)
            open_.append(i)


def op_seconds(events) -> dict[str, float]:
    """Seconds by operation name, a loop's children inside the loop's and
    not beside it."""
    out: dict[str, float] = defaultdict(float)
    for e, parent in zip(events, nest(events)):
        if parent < 0:
            out[e.name] += e.dur_ns / 1e9
    return dict(out)


def idle_by_place(ops, modules, steps_per_call: int, window_s: float,
                  by_span=None) -> dict[str, float]:
    """The window's idle seconds by where they fall.  ``modules``: the
    executed step programs in order, ``steps_per_call`` of them to each
    call of the job.  What the capture does not span, the host's window
    less the stretch from the first program's start to the last one's end,
    lies at the window's two edges.  With ``by_span``, which takes (start,
    end) gaps between programs to seconds by the host's span open then, a
    place between programs is split by it:
    ``between_calls/zoo.train.data_wait``."""
    out: dict[str, float] = defaultdict(float)
    if not modules:
        return {EDGES: window_s}
    between: dict = {place: [] for place in BETWEEN}
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
        if i + 1 < len(modules) and modules[i + 1].start_ns > module.end_ns:
            boundary = (i + 1) % steps_per_call == 0
            between[BETWEEN_CALLS if boundary else BETWEEN_STEPS].append(
                (module.end_ns, modules[i + 1].start_ns))
    for place, gaps in between.items():
        if by_span is None:
            if gaps:
                out[place] = sum(b - a for a, b in gaps) / 1e9
            continue
        for name, seconds in by_span(gaps).items():
            out[f"{BETWEEN[place]}/{name}"] += seconds
    spanned = (modules[-1].end_ns - modules[0].start_ns) / 1e9
    out[EDGES] = max(0.0, window_s - spanned)
    return dict(out)


def top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
