"""Kernels: the two Pallas kernels of Kimi Delta Attention's walk over a
sequence's chunks (``ops/pallas/kda_scan.py``: forward, and transposed),
their share of their roofline.  As ``mla_attention_roofline.py`` reckons
it: the least time the chip could take for each call, the larger of
operations over the peak FLOP/s and bytes over the peak bytes/s, summed
over the calls in the traced window, over the device time the trace gives
those kernels.  A call's operations and bytes come from the
configuration's ``ops.py`` (``kda_call_costs``) at the chunk the program
walked in, which its trace-time record states
(``ops.linear_attention.chunk_schedules``); a configuration without the
function, a program without the record or a trace without the kernels has
nothing to read here."""

from benchmark import xplane

#: both kernels' instructions are named after their jitted callers, and
#: told apart by the number of arrays each returns
NAMED, KERNELS = "kda_walk", {3: "forward", 5: "backward"}


def kda_kernel(name: str):
    """Which of the two kernels the device event ``name`` is, or None."""
    head, marked, outputs = name.rpartition(f"/{xplane.KERNEL_TARGET}/")
    if not marked or NAMED not in head:
        return None
    return KERNELS.get(int(outputs))


def walked_chunk():
    """Tokens a chunk of the program's last traced walk, or None."""
    try:
        from analytics_zoo_tpu.ops.linear_attention import chunk_schedules
    except ImportError:
        return None
    return chunk_schedules[-1]["chunk"] if chunk_schedules else None


def read(run, chunk=None):
    capture = run["capture"]
    if capture is None or not capture.device_ops:
        return None
    costs_of = getattr(run["configuration"].module("ops"),
                       "kda_call_costs", None)
    chunk = chunk or walked_chunk()
    if costs_of is None or chunk is None:
        return None
    peaks = run["manifest"].peaks(run["device"]["kind"])
    costs = costs_of(run["traffic"]["batch"], run["sizes"], chunk)
    least = spent = 0.0
    for ops in capture.device_ops.values():
        for e in ops:
            kernel = kda_kernel(e.name)
            if kernel is None:
                continue
            flops, nbytes = costs[kernel]
            least += max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
            spent += e.dur_ns / 1e9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
