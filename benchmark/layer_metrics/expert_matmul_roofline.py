"""Kernels: the grouped products of the routed experts held here (the
Pallas kernels that ``ops/pallas/grouped_matmul.py`` wraps), their share of
their roofline.  A routed layer multiplies the rows that fell on its held
experts, which the program reports a layer in the gauge
``zoo_moe_held_assignments`` (the last step's count: the window repeats one
epoch's batches, so a layer's count swings by a few percent from step to
step and not from epoch to epoch).  Every call, forward, made again,
the rows' gradient or the experts' (the transposed form), is rows x hidden
x expert width multiply-accumulates; its bytes are the rows in and out and
the held experts' matrices once.  The least time of the calls in the traced
window over the device time the trace gives those kernels.  Nothing to read
where the program has no such gauge or the trace no such kernel."""

from benchmark import xplane

GAUGE = "zoo_moe_held_assignments"
#: the instruction of either kernel is named after ``gmm`` or ``tgmm``
NAMED = "gmm"


def call_costs(rows, hidden, width, held, itemsize=2):
    """(operations, bytes) of one grouped product of ``rows`` rows between
    ``hidden`` and ``width`` columns over ``held`` experts."""
    return (2.0 * rows * hidden * width,
            itemsize * (rows * (hidden + width) + held * hidden * width))


def read(run):
    capture = run["capture"]
    if capture is None or not capture.device_ops:
        return None
    rows = [value for (name, label), (value, _n)
            in run["registry_after"].items() if name == GAUGE and label]
    if not rows:
        return None
    sizes = run["sizes"]
    peaks = run["manifest"].peaks(run["device"]["kind"])
    marked = f"/{xplane.KERNEL_TARGET}/"
    spent, calls = 0.0, 0
    for ops in capture.device_ops.values():
        for e in ops:
            head, found, _ = e.name.rpartition(marked)
            if found and NAMED in head:
                spent += e.dur_ns / 1e9
                calls += 1
    if spent == 0.0:
        return None
    # every layer makes the same number of calls, each at that layer's rows
    least = 0.0
    for layer_rows in rows:
        flops, nbytes = call_costs(layer_rows, sizes["hidden_size"],
                                   sizes["moe_intermediate_size"],
                                   sizes["n_routed_experts"])
        least += calls / len(rows) * max(flops / peaks["bf16_flops_per_s"],
                                         nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
