"""Shared by the readers that time the host against a chip: how long the
feeder takes to put a batch on the device, how far the loop runs ahead of
the device, how long a process takes to reach it.  At toy size on another
device (the tests' CPU runs) such a time says nothing about the system, so
these readers report only from a TPU."""

from __future__ import annotations


def on_chip(run: dict) -> bool:
    return run["device"]["platform"] == "tpu"


def grown(run: dict, name: str) -> tuple[float, int] | None:
    """(sum, count) that the program's family ``name`` gained between the
    window's two registry snapshots; nothing off the chip, or where the
    program has no such family."""
    after = run["registry_after"].get((name, ""))
    if after is None or not on_chip(run):
        return None
    before = run["registry_before"].get((name, ""), (0.0, 0))
    return after[0] - before[0], after[1] - before[1]
