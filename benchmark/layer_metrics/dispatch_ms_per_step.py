"""Step loop: the host's time to dispatch one jitted step of the window,
from the program's ``zoo_train_step_dispatch_seconds``."""

from benchmark.manifest import sibling

per_step_ms = sibling(__file__, "_registry").per_step_ms


def read(run):
    return per_step_ms(run, "zoo_train_step_dispatch_seconds")
