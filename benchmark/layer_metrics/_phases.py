"""Shared by the readers of the model step by phase: every operation
inside a ``jit_train_step`` program is classed by its scope, the path of
JAX's name stack that XLA carries as the instruction's ``op_name``
(``benchmark/xplane.py`` keeps it on the event), with no name put into the
program:

- ``rematted_computation`` in the path: **made again**, what the backward
  pass computes a second time under a ``jax.checkpoint``;
- else ``transpose(``: **backward**;
- else ``jvp(``: **forward** (a ``custom_vjp``'s forward rule with it,
  whatever it prepares for the backward one);
- else **optimizer**: all of the step outside the gradient (the update,
  casts, clipping, the loss's bookkeeping);
- no scope at all: **no scope**, counted on its own and named in the run's
  result, never put into a phase (the copies and fills the compiler puts in
  have no ``op_name``).

Every busy nanosecond inside a step program goes to the innermost
operation open then (``xplane.owned``), so a loop's own time is its span
less its children's, and the five classes and the program's idle time add
up to the program.  A fusion carries one ``op_name`` (a product's fusion
its product's), so one that mixes phases lands in one: the Adam update that
XLA fuses into a weight's gradient product is backward, and the optimizer's
milliseconds are what it left on its own (PERF.md, PR 37).  What a
``custom_vjp``'s backward rule makes again by hand is backward too."""

from __future__ import annotations

import functools
from collections import defaultdict

from benchmark import xplane

MADE_AGAIN, BACKWARD, FORWARD, OPTIMIZER, NO_SCOPE = (
    "made again", "backward", "forward", "optimizer", "no scope")
PHASES = (FORWARD, BACKWARD, MADE_AGAIN, OPTIMIZER, NO_SCOPE)
IDLE, PROGRAM = "idle inside", "program"


@functools.lru_cache(maxsize=None)
def phase_of(scope: str) -> str:
    if not scope:
        return NO_SCOPE
    if "rematted_computation" in scope:
        return MADE_AGAIN
    if "transpose(" in scope:
        return BACKWARD
    if "jvp(" in scope:
        return FORWARD
    return OPTIMIZER


def account(ops, programs) -> tuple[dict[str, float], dict[str, float]]:
    """(nanoseconds by phase, ``IDLE`` and ``PROGRAM`` over the
    ``programs`` of one device plane, which are in order of time and
    apart; nanoseconds by name of the operations without a scope)."""
    by_phase: dict = defaultdict(float)
    unnamed: dict = defaultdict(float)
    at = 0
    for index, start, end in xplane.owned(ops):    # in order of time
        while at < len(programs) and programs[at].end_ns <= start:
            at += 1
        k = at
        while k < len(programs) and programs[k].start_ns < end:
            inside = min(end, programs[k].end_ns) \
                - max(start, programs[k].start_ns)
            phase = phase_of(ops[index].scope)
            by_phase[phase] += inside
            if phase == NO_SCOPE:
                unnamed[ops[index].name] += inside
            k += 1
    by_phase[PROGRAM] = sum(p.dur_ns for p in programs)
    by_phase[IDLE] = by_phase[PROGRAM] - sum(by_phase[p] for p in PHASES)
    return dict(by_phase), dict(unnamed)


def of_run(run: dict) -> dict | None:
    """The traced window's account, summed over the device planes and
    kept on the run: ``{"programs": their number, "ns": by phase,
    "unnamed": by name}``; nothing where the capture has no device plane
    or no step program."""
    if "step_phases" not in run:
        capture, step_modules = run["capture"], run["step_modules"]
        run["step_phases"] = None
        if capture is not None and capture.device_ops and step_modules \
                and all(step_modules.values()):
            ns: dict = defaultdict(float)
            unnamed: dict = defaultdict(float)
            for plane, programs in step_modules.items():
                by_phase, by_name = account(
                    capture.device_ops.get(plane, []), programs)
                for table, part in ((ns, by_phase), (unnamed, by_name)):
                    for key, value in part.items():
                        table[key] += value
            run["step_phases"] = {
                "programs": sum(map(len, step_modules.values())),
                "ns": dict(ns), "unnamed": dict(unnamed)}
    return run["step_phases"]


def mean_ms(run: dict, what: str) -> float | None:
    """Milliseconds of ``what`` a step program, the mean over the window's
    on every device plane."""
    found = of_run(run)
    if found is None:
        return None
    return found["ns"].get(what, 0.0) / found["programs"] / 1e6
