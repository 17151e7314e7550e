"""Input layer: the time a step of the window waited on the infeed queue,
from the program's ``zoo_train_data_wait_seconds``."""

from benchmark.manifest import sibling

per_step_ms = sibling(__file__, "_registry").per_step_ms


def read(run):
    return per_step_ms(run, "zoo_train_data_wait_seconds")
