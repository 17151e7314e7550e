"""Model step: the device's milliseconds inside one ``jit_train_step``
program, its event on the trace's ``XLA Modules`` line, the mean over the
traced window's: the step as the device saw it, the waits between steps
and between calls left out (``_phases.py``)."""

from benchmark.manifest import sibling

phases = sibling(__file__, "_phases")


def read(run):
    return phases.mean_ms(run, phases.PROGRAM)
