"""Model step: milliseconds of a step program in a Mamba-2 mixer's body,
the operations whose scope holds ``zoo.ssd`` (inside ``zoo.mixer``: the
in-projection, the convolution, the walk's kernels, the gated norm and the
out-projection), in the forward pass, the backward pass and what it makes
again together.  ``_parts.py``'s accounting, by import: an operation is
handed to it as a mixer's where its scope holds ``zoo.ssd`` and as
another part's where it does not, and the mixer's milliseconds are then
the SSD's.  Nothing where no operation names the scope (a program without
it, or a model without such a mixer)."""

from benchmark.manifest import sibling

parts = sibling(__file__, "_parts")

#: spelled here, not imported from the program (as ``_parts.py``'s names)
SSD = "zoo.ssd"


def read(run):
    if parts.of_run(run) is None:
        return None
    ns, programs = 0.0, 0
    for plane, modules in run["step_modules"].items():
        ops = [op._replace(scope=parts.MIXER if SSD in op.scope
                           else parts.FFN)
               for op in run["capture"].device_ops.get(plane, [])]
        ns += parts.account(ops, modules)["ns"].get(parts.MIXER, 0.0)
        programs += len(modules)
    return ns / programs / 1e6 if ns else None
