"""Kernels: the grouped products of the routed experts held here (the
Pallas kernels that ``ops/pallas/grouped_matmul.py`` wraps), their share of
their roofline.  A routed layer multiplies the rows that fell on its held
experts, which the program reports a layer in the gauge
``zoo_moe_held_assignments`` (the last step's count: the window repeats one
epoch's batches, so a layer's count swings by a few percent from step to
step and not from epoch to epoch).  A window of a layer's walk makes
thirteen kernel calls: eight ``gmm`` (forward gate, up and down; in the
backward rule gate and up again, the cotangent through down, the rows'
gradient through gate and up) and five ``tgmm``, of which three are the
experts' gradients and two, one in each direction, the sums of a token's
rows, a transposed product of the rows with their place in a tile of 128
tokens, one-hot.  A product is rows x hidden x expert width
multiply-accumulates over the rows in and out and the held experts'
matrices once; a sum is rows x 128 x hidden over the rows in, the one-hot
and the float32 running sum of the tiles it visits, read and written.  The
least time of the calls in the traced window over the device time the trace
gives those kernels.  Nothing to read where the program has no such gauge,
the trace no such kernel, or the calls are not eight to five (another walk
than the one reckoned here)."""

import sys

from benchmark import xplane

GAUGE = "zoo_moe_held_assignments"
#: the instruction of either kernel is named after ``gmm`` or ``tgmm``
NAMED, TRANSPOSED = "gmm", "tgmm"
#: of a window's calls; of its ``tgmm`` calls the sums of a token's rows
GMM, TGMM, SUMS = 8, 5, 2
#: tokens a tile of the sum (``grouped_matmul.TOKENS``)
TOKEN_TILE = 128


def call_costs(rows, hidden, width, held, itemsize=2):
    """(operations, bytes) of one grouped product of ``rows`` rows between
    ``hidden`` and ``width`` columns over ``held`` experts."""
    return (2.0 * rows * hidden * width,
            itemsize * (rows * (hidden + width) + held * hidden * width))


def sum_costs(rows, hidden, tokens, itemsize=2):
    """(operations, bytes) of one sum of a token's rows: ``rows`` rows of
    ``hidden`` columns into the float32 running sum of ``tokens`` tokens,
    of which it visits the tiles that hold a row."""
    tiles = min(tokens // TOKEN_TILE, rows)
    return (2.0 * rows * TOKEN_TILE * hidden,
            itemsize * rows * (hidden + TOKEN_TILE)
            + 2 * 4 * tiles * TOKEN_TILE * hidden)


def read(run, out=sys.stderr):
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
    spent, calls = 0.0, {NAMED: 0, TRANSPOSED: 0}
    for ops in capture.device_ops.values():
        for e in ops:
            head, found, _ = e.name.rpartition(marked)
            if found and NAMED in head:
                spent += e.dur_ns / 1e9
                calls[TRANSPOSED if TRANSPOSED in head else NAMED] += 1
    if spent == 0.0:
        return None
    if calls[NAMED] * TGMM != calls[TRANSPOSED] * GMM:
        out.write(f"expert_matmul_roofline: {calls} calls are not "
                  f"{GMM} to {TGMM}: not the walk reckoned here\n")
        return None
    held = sizes.get("n_routed_experts", sizes.get("num_experts"))
    tokens = run["traffic"]["batch"] * sizes["n_positions"]
    windows = calls[TRANSPOSED] / TGMM     # over all layers and steps

    def least(costs):
        flops, nbytes = costs
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])

    # every layer runs the same number of windows, each at that layer's rows
    total = 0.0
    for layer_rows in rows:
        product = least(call_costs(layer_rows, sizes["hidden_size"],
                                   sizes["moe_intermediate_size"], held))
        a_sum = least(sum_costs(layer_rows, sizes["hidden_size"], tokens))
        total += windows / len(rows) * (
            (GMM + TGMM - SUMS) * product + SUMS * a_sum)
    return 100.0 * total / spent
