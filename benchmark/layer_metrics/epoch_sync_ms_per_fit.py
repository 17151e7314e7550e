"""Step loop: how long a ``fit`` call of the window waited in its epochs'
closing loss fetch (``zoo_train_epoch_sync_seconds``): the lead of the
host's loop over the device.  Near 0 the host sets the pace; a faster step
lowers it by design, so read it beside ``train_examples_per_s``."""

from benchmark.manifest import sibling

grown = sibling(__file__, "_chip").grown


def read(run):
    waited = grown(run, "zoo_train_epoch_sync_seconds")
    fits = run["window"]["fits"]
    return waited[0] / fits * 1e3 if waited is not None and fits else None
