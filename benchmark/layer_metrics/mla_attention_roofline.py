"""Kernels: the flash-attention forward, dq and dk/dv kernels' share of
their roofline where q and k have one width and v another (latent
attention: 192 and 128).  As ``flash_attention_roofline.py`` reckons it:
the least time the chip could take for each call, the larger of operations
over the peak FLOP/s and bytes over the peak bytes/s, summed over the calls
in the traced window, over the device time the trace gives those kernels.
Each kernel's own products at their own widths and its bytes come from the
configuration's ``ops.py`` (``flash_call_costs``); a configuration without
one has nothing to read here."""

from benchmark import xplane

#: the three Mosaic kernels of ``ops/pallas/flash_attention.py``, by their
#: instruction's name and by the number of arrays each returns: a grouped
#: product is a Mosaic kernel too, and returns one array as the dq kernel
#: does
NAMED, KERNELS = "flash", {3: "forward", 1: "dq", 2: "dkv"}


def flash_kernel(name: str):
    """Which of the three kernels the device event ``name`` is, or None."""
    head, marked, outputs = name.rpartition(f"/{xplane.KERNEL_TARGET}/")
    if not marked or NAMED not in head:
        return None
    return KERNELS[int(outputs)]


def read(run):
    capture = run["capture"]
    if capture is None or not capture.device_ops:
        return None
    costs_of = getattr(run["configuration"].module("ops"),
                       "flash_call_costs", None)
    if costs_of is None:
        return None
    peaks = run["manifest"].peaks(run["device"]["kind"])
    costs = costs_of(run["traffic"]["batch"], run["sizes"])
    least = spent = 0.0
    for ops in capture.device_ops.values():
        for e in ops:
            kernel = flash_kernel(e.name)
            if kernel is None:
                continue
            flops, nbytes = costs[kernel]
            least += max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
            spent += e.dur_ns / 1e9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
