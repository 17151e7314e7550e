"""Step loop: the host's time from one ``fit`` call's closing sync to the
next call's first dispatch, the mean over the window's boundaries, from
the program's spans on the host's clock alone: the end of
``zoo.train.epoch_sync`` to the start of the next call's first
``zoo.train.step_dispatch``.

Writes to standard error where a call's seconds went, most first: one line
``fit <thread> <span> <seconds a call>`` a span name, each span less what
its children cover (the main thread's lines add up to a call), and
``cover <share>``, the least share of a call's ``zoo.fit`` span that the
main thread's leaf spans cover."""

import sys
from collections import defaultdict

from benchmark.manifest import sibling

spans = sibling(__file__, "_spans")
on_chip = sibling(__file__, "_chip").on_chip


def read(run):
    if not on_chip(run):
        return None
    calls = spans.window_calls(run)
    ends, firsts = [], []
    for call in calls:
        main = spans.main_thread(call)
        syncs = [e for e in main if e["name"] == spans.SYNC]
        steps = [e for e in main if e["name"] == spans.DISPATCH]
        if not syncs or not steps:
            return None
        ends.append(syncs[-1]["ts"] + syncs[-1]["dur"])
        firsts.append(steps[0]["ts"])
    account(calls)
    waits = [first - end for end, first in zip(ends, firsts[1:])]
    return sum(waits) / len(waits) / 1e3 if waits else None


def account(calls, out=sys.stderr):
    total: dict = defaultdict(float)
    for call in calls:
        for key, seconds in spans.self_seconds(call).items():
            total[key] += seconds / len(calls)
    for (main, name), seconds in sorted(total.items(),
                                        key=lambda kv: -kv[1]):
        out.write(f"fit {'main' if main else 'other'} {name} "
                  f"{seconds:.6f}\n")
    covers = [c for c in map(spans.leaf_cover, calls) if c is not None]
    if covers:
        out.write(f"cover {min(covers):.4f}\n")
