"""Compile plane (set-up): seconds in ``init_zoo_context``, backend start
(where a process reaches the chip) and mesh, from the program's gauge
``zoo_context_init_seconds`` at the end of set-up."""

from benchmark.manifest import sibling

on_chip = sibling(__file__, "_chip").on_chip


def read(run):
    if not on_chip(run):
        return None
    gauge = run["registry_before"].get(("zoo_context_init_seconds", ""))
    return gauge[0] if gauge is not None else None
