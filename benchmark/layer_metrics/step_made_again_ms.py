"""Model step: milliseconds of a step program in which the backward pass
made again what the forward pass had made, the operations whose scope has
``rematted_computation``: what the checkpoint policy costs.  0 where nothing
is recomputed (``_phases.py``)."""

from benchmark.manifest import sibling

phases = sibling(__file__, "_phases")


def read(run):
    return phases.mean_ms(run, phases.MADE_AGAIN)
