"""Per-fusion roofline table for the ResNet-50 pure step.

For every device op in a jax.profiler trace of the step, joins its
measured ms/step against a roofline bound computed from the compiled
HLO's operand/result shapes at the ceilings below (HBM streaming and
sustained MXU rate — re-measure them on the machine the trace came
from).  An op whose achieved bandwidth/compute sits at the ceiling is
hardware-bound; anything far below ceiling is a framework target.

Usage: python tools/roofline_table.py [batch] [trace_dir] [--json out]
  trace_dir default PROFILE_r05 (or $ZOO_PROFILE_DIR).  Needs the same
  backend the trace came from (compiles the step to map op -> shapes).
"""

import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

HBM_CEILING_GBPS = 514.0   # measured (differential timing, r+w), 63% of spec
MXU_CEILING_TFLOPS = 192.6  # measured (chained 4096^3 bf16 matmuls)

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
                "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
                "pred": 1}


def shapes_in(line):
    """All dtype[shape] terms on an HLO line -> bytes each."""
    out = []
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", line):
        dt = _DTYPE_BYTES.get(m.group(1))
        if dt is None:
            continue
        dims = [int(x) for x in m.group(2).split(",") if x]
        out.append(dt * int(np.prod(dims)) if dims else dt)
    return out


def conv_flops(line):
    """2 * prod(out_dims) * Cin * kh * kw for a conv HLO line, reading
    Cin and the spatial kernel dims from the rhs dim_labels (layout-
    proof: 'i' marks in-features, digits mark spatial)."""
    shp = re.findall(r"\w+\[([\d,]+)\]", line)
    dl = re.search(r"dim_labels=[\w?]+_([\w?]+)->", line)
    if not (len(shp) >= 3 and dl):
        return None
    out_dims = [int(x) for x in shp[0].split(",")]
    rhs = [int(x) for x in shp[2].split(",")]
    cin, k = None, 1
    for ch, d in zip(dl.group(1), rhs):
        if ch == "i":
            cin = d
        elif ch.isdigit():
            k *= d
    if cin is None:
        return None
    return 2 * int(np.prod(out_dims)) * cin * k


def main():
    if "--cpu" in sys.argv:
        # must precede ANY backend touch
        import jax

        jax.config.update("jax_platforms", "cpu")
    argv = [a for a in sys.argv[1:] if a != "--cpu"]
    out_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            sys.exit("--json needs a path")
        out_path = argv[i + 1]
        del argv[i:i + 2]
    flag_steps = 5  # profile_step.py's loop count (fallback when the
    # trace carries no recognisable jit module events)
    if "--steps" in argv:
        i = argv.index("--steps")
        if i + 1 >= len(argv):
            sys.exit("--steps needs a value")
        flag_steps = int(argv[i + 1])
        del argv[i:i + 2]
    # reject unknown flags: an unrecognized '--flag value' pair would leave
    # the value behind to be misparsed as the positional batch/trace_dir
    unknown = [a for a in argv if a.startswith("--")]
    if unknown:
        sys.exit(f"unknown flags: {' '.join(unknown)}")
    args = argv
    batch = int(args[0]) if args else 256
    trace_dir = args[1] if len(args) > 1 else os.environ.get(
        "ZOO_PROFILE_DIR", "PROFILE_r05")

    # Trace first: fail on a bad/missing trace BEFORE the multi-minute
    # step compile.
    files = glob.glob(f"{trace_dir}/**/*.trace.json.gz", recursive=True)
    if not files:
        sys.exit(f"no trace under {trace_dir}/ — run tools/profile_step.py")
    with gzip.open(sorted(files)[-1], "rt") as f:
        data = json.load(f)
    pid_names = {}
    for ev in data["traceEvents"]:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev["pid"]] = ev.get("args", {}).get("name", "")
    tpu_pids = sorted(p for p, n in pid_names.items() if "TPU" in n)
    if not tpu_pids:
        sys.exit("no TPU process in trace")
    # ONE core only: multi-chip traces repeat every fusion name per core,
    # and summing across cores would inflate ms by the core count while
    # the HLO-derived bounds would not
    pid0 = tpu_pids[0]
    dur_total = defaultdict(float)
    for ev in data["traceEvents"]:
        if ev.get("ph") != "X" or ev.get("pid") != pid0:
            continue
        dur_total[ev.get("name", "")] += ev.get("dur", 0) / 1e3
    # per-step divisor: how many times the jitted step module ran on this
    # core (profile_step.py loops it); prefer a module named like a step,
    # fall back to --steps (default 5 = profile_step.py's loop count)
    mod_counts = defaultdict(int)
    for ev in data["traceEvents"]:
        if (ev.get("ph") == "X" and ev.get("pid") == pid0
                and str(ev.get("name", "")).startswith("jit")):
            mod_counts[ev["name"]] += 1
    step_mods = {n: c for n, c in mod_counts.items() if "step" in n.lower()}
    pick = step_mods or mod_counts
    steps = max(pick.values()) if pick else None
    if steps is None or not (1 <= steps <= 1000):
        steps = int(flag_steps)
    dur = {n: d / steps for n, d in dur_total.items()}

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.models.resnet import ResNet

    # --cpu (handled above): structural smoke-testing off-chip — op names
    # then only partially join a TPU trace; the real run needs a chip
    init_zoo_context(seed=0)
    net = ResNet.image_net(50, classes=1000, input_shape=(224, 224, 3))
    net.compile(optimizer=ResNet.imagenet_optimizer(
        batch_size=batch, steps_per_epoch=100),
        loss="sparse_categorical_crossentropy")
    est = net._make_estimator()
    params, state = est.model.build_params()
    opt_state = est.optimizer.init(params)
    step = est._build_train_step()
    b = {"x": np.zeros((batch, 224, 224, 3), np.float32),
         "y": np.zeros((batch,), np.int32)}
    hlo = step.lower(params, opt_state, state, np.int32(0), np.int32(0),
                     b).compile().as_text()

    # Two passes: HLO op lines carry only the RESULT shape inline —
    # operands are %name references.  Pass 1 maps name -> result bytes;
    # pass 2 sums result + operand buffers per op (the HBM traffic bound).
    result_bytes = {}
    lines = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        # rhs = "<type> <opcode>(<operands>), attrs..." where <type> may be
        # a tuple "(bf16[...], f32[...])" — split at the opcode call, not
        # at the first paren, or tuple-result ops (BN stats) undercount
        m2 = re.match(r"(.*?)\s([a-z][\w\-]*)\((.*)$", rhs)
        if not m2:
            continue
        type_part, _opcode, operand_part = m2.groups()
        rb = sum(shapes_in(type_part))
        result_bytes[name] = rb
        lines.append((name, line, rb, operand_part))
    info = {}
    for name, line, rb, operand_part in lines:
        operands = re.findall(r"%?([\w.\-]+)", operand_part.split(")", 1)[0])
        byts = rb + sum(result_bytes.get(o, 0) for o in operands)
        fl = conv_flops(line) if "convolution(" in line else None
        if byts:
            info[name] = (byts, fl)

    rows = []
    for name, ms in dur.items():
        if name not in info or ms <= 0.005:
            continue
        byts, fl = info[name]
        bound_ms_hbm = byts / (HBM_CEILING_GBPS * 1e6)
        row = {"op": name, "ms": round(ms, 3),
               "bytes_mb": round(byts / 1e6, 1),
               "achieved_gbps": round(byts / ms / 1e6, 1),
               "hbm_roofline_ms": round(bound_ms_hbm, 3),
               "x_hbm_roofline": round(ms / bound_ms_hbm, 2)
               if bound_ms_hbm else None}
        if fl:
            bound_ms_mxu = fl / (MXU_CEILING_TFLOPS * 1e9)
            row["gflop"] = round(fl / 1e9, 1)
            row["achieved_tflops"] = round(fl / ms / 1e9, 1)
            row["mxu_roofline_ms"] = round(bound_ms_mxu, 3)
            row["x_roofline"] = round(
                ms / max(bound_ms_mxu, bound_ms_hbm), 2)
        rows.append(row)
    rows.sort(key=lambda r: -r["ms"])

    total = sum(r["ms"] for r in rows)
    bound = sum(max(r.get("mxu_roofline_ms", 0), r["hbm_roofline_ms"])
                for r in rows)
    summary = {
        "trace": trace_dir, "batch": batch, "steps_divisor": steps,
        "tpu_processes_in_trace": len(tpu_pids),
        "attributed_ms_per_step": round(total, 1),
        "composite_roofline_ms": round(bound, 1),
        "x_composite_roofline": round(total / bound, 2) if bound else None,
        "ceilings": {"hbm_gbps_measured": HBM_CEILING_GBPS,
                     "mxu_tflops_measured": MXU_CEILING_TFLOPS},
    }
    print(json.dumps(summary))
    for r in rows[:40]:
        print(json.dumps(r))
    if out_path:
        with open(out_path, "w") as f:
            json.dump({"summary": summary, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
