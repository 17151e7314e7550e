"""Model step: milliseconds of a step program outside the gradient, the
operations whose scope has neither ``jvp(`` nor ``transpose(``: the update,
casts, clipping, the loss's bookkeeping (``_phases.py``)."""

from benchmark.manifest import sibling

phases = sibling(__file__, "_phases")


def read(run):
    return phases.mean_ms(run, phases.OPTIMIZER)
