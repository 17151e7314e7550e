"""Model step: milliseconds of a step program in a block's feed-forward
branch, the operations whose scope holds ``zoo.ffn`` (the branch's norms,
the dense feed-forward or the routed one with its route, walk, grouped
kernels and shared experts, the residual add), all phases together
(``_parts.py``)."""

from benchmark.manifest import sibling

parts = sibling(__file__, "_parts")


def read(run):
    return parts.mean_ms(run, parts.FFN)
