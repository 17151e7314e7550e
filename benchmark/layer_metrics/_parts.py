"""Shared by the readers of the model step by part: every operation inside
a ``jit_train_step`` program is classed by the part of the model its scope
names.  The program opens three ``jax.named_scope``s
(``analytics_zoo_tpu/metrics/tracing.py``): ``zoo.mixer`` around a block's
mixer branch, ``zoo.ffn`` around its feed-forward branch and ``zoo.head``
around the head with its loss, where a decoder takes the loss inside the
model.  XLA carries JAX's name stack as the instruction's ``op_name``, in
the forward pass, the backward pass and what it makes again alike
(``jvp(zoo.mixer)/...``, ``transpose(jvp(zoo.ffn))/...``).

- the path holds one of the three names: **that part** (the last, the
  innermost, where a path holds more than one; the program never nests
  them);
- a scope and none of the names: **scoped rest** (the optimizer, the
  embedding, a head and a loss outside the model, what a checkpoint's own
  boundary moves);
- no scope at all: **no scope**, as in ``_phases.py``.

The names are spelled here and not imported from the program: a rename
there shows as a missing reading, not as a reading that follows it.  The
accounting is ``_phases.py``'s: every busy nanosecond inside a step program
goes to the innermost operation open then (``xplane.owned``), so a loop
keeps what its children leave and a child is classed by its own scope; the
three parts, the scoped rest, no scope and the idle inside add up to the
program.  Each class is also split by ``_phases.py``'s phase, for
``PERF.md``.

    python3 benchmark/layer_metrics/_parts.py <trace directory or .xplane.pb>

prints a kept capture's account (ms a step program) as JSON."""

from __future__ import annotations

import functools
import json
import os
import sys
from collections import defaultdict

if __name__ == "__main__":      # run as a script: the checkout's root
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark import xplane  # noqa: E402
from benchmark.manifest import sibling  # noqa: E402

phases = sibling(__file__, "_phases")

MIXER, FFN, HEAD = "zoo.mixer", "zoo.ffn", "zoo.head"
PARTS = (MIXER, FFN, HEAD)
REST, NO_SCOPE = "scoped rest", phases.NO_SCOPE
CLASSES = (*PARTS, REST, NO_SCOPE)
IDLE, PROGRAM = phases.IDLE, phases.PROGRAM


@functools.lru_cache(maxsize=None)
def part_of(scope: str) -> str:
    if not scope:
        return NO_SCOPE
    at, part = max((scope.rfind(p), p) for p in PARTS)
    return part if at >= 0 else REST


def account(ops, programs) -> dict:
    """The ``programs`` of one device plane (in order of time, apart) by
    class: ``ns`` {class, ``IDLE``, ``PROGRAM``: nanoseconds},
    ``by_phase`` {(class, phase): nanoseconds}, and nanoseconds by
    operation name of the scoped rest (``rest``, with each name's
    ``scopes``) and of what has no scope (``unnamed``)."""
    ns: dict = defaultdict(float)
    by_phase: dict = defaultdict(float)
    rest: dict = defaultdict(float)
    unnamed: dict = defaultdict(float)
    scopes: dict = {}
    at = 0
    for index, start, end in xplane.owned(ops):    # in order of time
        while at < len(programs) and programs[at].end_ns <= start:
            at += 1
        k = at
        while k < len(programs) and programs[k].start_ns < end:
            inside = min(end, programs[k].end_ns) \
                - max(start, programs[k].start_ns)
            op = ops[index]
            part = part_of(op.scope)
            ns[part] += inside
            by_phase[part, phases.phase_of(op.scope)] += inside
            if part == REST:
                rest[op.name] += inside
                scopes[op.name] = op.scope
            elif part == NO_SCOPE:
                unnamed[op.name] += inside
            k += 1
    ns[PROGRAM] = sum(p.dur_ns for p in programs)
    ns[IDLE] = ns[PROGRAM] - sum(ns[c] for c in CLASSES)
    return {"ns": ns, "by_phase": by_phase, "rest": rest,
            "unnamed": unnamed, "scopes": scopes}


def _summed(capture, step_modules) -> dict:
    total = {"programs": sum(map(len, step_modules.values())),
             "scopes": {}}
    for plane, programs in step_modules.items():
        found = account(capture.device_ops.get(plane, []), programs)
        total["scopes"].update(found.pop("scopes"))
        for table, part in found.items():
            into = total.setdefault(table, defaultdict(float))
            for key, value in part.items():
                into[key] += value
    return total


def of_run(run: dict) -> dict | None:
    """The traced window's account, summed over the device planes and kept
    on the run; nothing where the capture has no device plane or no step
    program."""
    if "step_parts" not in run:
        capture, step_modules = run["capture"], run["step_modules"]
        run["step_parts"] = None
        if capture is not None and capture.device_ops and step_modules \
                and all(step_modules.values()):
            run["step_parts"] = _summed(capture, step_modules)
    return run["step_parts"]


def mean_ms(run: dict, part: str) -> float | None:
    """Milliseconds of ``part`` a step program, the mean over the window's
    on every device plane; nothing where no operation names the part (a
    program without the scopes, or a model without that part)."""
    found = of_run(run)
    if found is None or not found["ns"].get(part):
        return None
    return found["ns"][part] / found["programs"] / 1e6


def report(found: dict, n: int = 10) -> dict:
    """An account as ``PERF.md`` gives it: ms a step program by class and
    by class and phase, and the ``n`` longest operations of the scoped rest
    (with their scopes) and of no scope."""
    def ms(value):
        return value / found["programs"] / 1e6

    return {
        "programs": found["programs"],
        "ms": {c: ms(found["ns"].get(c, 0.0))
               for c in (*CLASSES, IDLE, PROGRAM)},
        "by_phase": {c: {p: ms(v) for (c2, p), v in found["by_phase"].items()
                         if c2 == c} for c in CLASSES},
        "rest": [[name, ms(v), found["scopes"][name]]
                 for name, v in xplane.top(found["rest"], n)],
        "no_scope": [[name, ms(v)]
                     for name, v in xplane.top(found["unnamed"], n)]}


def main(argv) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    capture = xplane.load(path)
    step_modules = {plane: [m for m in modules
                            if m.name.startswith("jit_train_step")]
                    for plane, modules in capture.modules.items()}
    found = of_run({"capture": capture, "step_modules": step_modules})
    if found is None:
        print("no step program on a device plane", file=sys.stderr)
        return 1
    print(json.dumps(report(found), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
