"""Device: the share of the traced window in which no operation ran on the
chip: 1 - the union of the device's operation intervals over the window's
length by the host's clock, averaged over the chips."""

from benchmark import xplane


def read(run):
    capture = run["capture"]
    if capture is None or not capture.device_ops:
        return None
    busy = [xplane.busy_seconds(ops) for ops in capture.device_ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / run["window"]["elapsed_s"])
