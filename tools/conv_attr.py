"""Attribute profiled conv op times to conv shapes.

Compiles the ResNet-50 train step, dumps optimized HLO to map
convolution.N -> (operand shapes), then sums the profiled trace
durations per conv name and prints the per-shape cost ranking.
Usage: conv_attr.py [batch] [trace_dir]  (trace_dir default
$ZOO_PROFILE_DIR).
"""

import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.models.resnet import ResNet

    ctx = init_zoo_context(seed=0)
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
    compiled = step.lower(params, opt_state, state, np.int32(0), np.int32(0),
                          b).compile()
    hlo = compiled.as_text()

    # map op name -> shapes involved
    shape_of = {}
    for m in re.finditer(
            r"%?(convolution[\w.\-]*|fusion[\w.\-]*) = (\S+?) (convolution|fusion)\(",
            hlo):
        shape_of[m.group(1)] = m.group(2)
    conv_lines = {}
    for line in hlo.splitlines():
        m = re.search(r"%?([\w.\-]+) = \S+ convolution\(", line)
        if m:
            shapes = re.findall(r"(?:bf16|f32)\[[\d,]+\]", line)
            conv_lines[m.group(1)] = " ".join(shapes[:3])

    trace_dir = sys.argv[2] if len(sys.argv) > 2 \
        else os.environ["ZOO_PROFILE_DIR"]
    files = glob.glob(f"{trace_dir}/**/*.trace.json.gz", recursive=True)
    if not files:
        sys.exit(f"no trace under {trace_dir}/ — run tools/profile_step.py "
                 "first (usage: conv_attr.py [batch] [trace_dir])")
    with gzip.open(sorted(files)[-1], "rt") as f:
        data = json.load(f)
    pid_names = {}
    for ev in data["traceEvents"]:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev["pid"]] = ev.get("args", {}).get("name", "")
    dur = defaultdict(float)
    for ev in data["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        if "TPU" not in pid_names.get(ev.get("pid"), ""):
            continue
        n = ev.get("name", "")
        if n.startswith("convolution") or (
                n in conv_lines):
            dur[n] += ev.get("dur", 0) / 1e3 / 5  # per step (5 steps traced)
    rows = []
    for n, d in dur.items():
        rows.append((d, n, conv_lines.get(n, shape_of.get(n, "?"))))
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(json.dumps({"conv_total_ms_per_step": round(total, 1)}))
    for d, n, s in rows[:30]:
        print(json.dumps({"op": n, "ms": round(d, 2), "shapes": s}))


if __name__ == "__main__":
    main()
