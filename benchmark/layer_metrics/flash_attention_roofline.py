"""Kernels: the flash-attention forward, dq and dk/dv kernels' share of
their roofline.  The least time the chip could take for each call, the
larger of operations over the peak FLOP/s and bytes over the peak bytes/s
from the call's shapes, summed over the calls in the traced window, over
the device time the trace gives those kernels."""

from benchmark import xplane

#: the three Mosaic kernels of ``ops/pallas/flash_attention.py`` by the
#: number of arrays each returns (``benchmark/xplane.py`` keeps it in the
#: event's name): the forward kernel the output and the two softmax
#: statistics, the dq kernel dq, the dk/dv kernel dk and dv.  No other
#: Pallas kernel is on the model's default path.
KERNELS = {3: "forward", 1: "dq", 2: "dkv"}


def call_costs(batch, heads, seq, head_dim, itemsize=2):
    """(operations, bytes) of one call of each kernel at (B, H, L, D),
    causal counted at half.  Matrix products: forward QK^T and PV;
    backward QK^T again, dP = dO V^T and dQ = dS K in the dq kernel, and
    dV = P^T dO, dK = dS^T Q in the dk/dv kernel, which has to recompute
    QK^T and dP for itself.  Bytes: each operand read and each result
    written once."""
    product = 2.0 * batch * heads * seq * seq * head_dim * 0.5
    tensor = batch * heads * seq * head_dim * itemsize
    return {"forward": (2 * product, 4 * tensor),
            "dq": (3 * product, 5 * tensor),
            "dkv": (4 * product, 6 * tensor)}


def read(run):
    capture = run["capture"]
    if capture is None or not capture.device_ops:
        return None
    sizes = run["sizes"]
    peaks = run["manifest"].peaks(run["device"]["kind"])
    costs = call_costs(run["traffic"]["batch"], sizes["n_head"],
                       sizes["n_positions"],
                       sizes["n_embd"] // sizes["n_head"])
    least = spent = 0.0
    for ops in capture.device_ops.values():
        for e in ops:
            _, marked, outputs = e.name.rpartition(
                f"/{xplane.KERNEL_TARGET}/")
            if not marked:
                continue
            flops, nbytes = costs[KERNELS[int(outputs)]]
            least += max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
            spent += e.dur_ns / 1e9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
