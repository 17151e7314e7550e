"""Model step: milliseconds of a step program in which the forward pass ran,
the operations whose scope has ``jvp(`` and neither ``transpose(`` nor
``rematted_computation``, each busy nanosecond given to the innermost
operation open then (``_phases.py``)."""

from benchmark.manifest import sibling

phases = sibling(__file__, "_phases")


def read(run):
    return phases.mean_ms(run, phases.FORWARD)
