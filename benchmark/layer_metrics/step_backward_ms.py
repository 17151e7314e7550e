"""Model step: milliseconds of a step program in which the backward pass
ran, the operations whose scope has ``transpose(``, less what it makes again
under a checkpoint (``_phases.py``)."""

from benchmark.manifest import sibling

phases = sibling(__file__, "_phases")


def read(run):
    return phases.mean_ms(run, phases.BACKWARD)
