"""Model step: milliseconds of a step program in the head of a decoder that
takes its loss inside the model, the operations whose scope holds
``zoo.head`` (the final norm, the products over the vocabulary with the
cross-entropy and its gradient, a looped decoder's exit gate), all phases
together (``_parts.py``)."""

from benchmark.manifest import sibling

parts = sibling(__file__, "_parts")


def read(run):
    return parts.mean_ms(run, parts.HEAD)
