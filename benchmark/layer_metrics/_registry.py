"""Shared by the readers of the program's registry: what a histogram
gained inside the window, a step."""

from __future__ import annotations


def per_step_ms(run: dict, name: str) -> float | None:
    """Milliseconds an observation, of what the histogram ``name`` gained
    between the window's two snapshots."""
    after = run["registry_after"].get((name, ""))
    if after is None:
        return None
    before = run["registry_before"].get((name, ""), (0.0, 0))
    count = after[1] - before[1]
    return (after[0] - before[0]) / count * 1e3 if count else None
