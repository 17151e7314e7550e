"""Model step: milliseconds of a step program in a block's mixer branch,
the operations whose scope holds ``zoo.mixer`` (the branch's norms, the
attention of any kind with its flash or KDA kernels, the residual add), in
the forward pass, the backward pass and what it makes again together
(``_parts.py``)."""

from benchmark.manifest import sibling

parts = sibling(__file__, "_parts")


def read(run):
    return parts.mean_ms(run, parts.MIXER)
