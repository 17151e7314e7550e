"""The readings a cell's limits are set from, taken on the chip at the
cell's own size, many seeds in one process:

- the program's check steps against the plain reference (the lower
  reading: the largest over the seeds);
- the control, which is the reference computed in fp8 (the nearest
  precision below the bf16 the configurations state: ``narrow.py``),
  against the reference (the upper reading: the smallest over its seeds);
- the planted fault "half of the batch left out, the mean taken over the
  rest", in the reference put in the program's place.

    python3 benchmark/calibrate.py <cell> --seeds 12 --control-seeds 3 \
        [--set <configuration key>=<json value>] [--tag <name>]

Writes ``chiprun_out/calibrate-<cell><tag>.json`` and prints it.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON", help="a size of the configuration "
                    "read otherwise, for the look at a cause")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    overrides = {k: json.loads(v) for k, v in
                 (item.split("=", 1) for item in args.set)}

    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.common.compile_cache import (
        maybe_enable_persistent_cache,
    )
    from benchmark import compare, narrow
    from benchmark.manifest import Manifest
    from benchmark.run import device_facts

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.cell)
    configuration = manifest.configuration(cell["config"], overrides)
    # the check steps alone: no window's rows are made
    traffic = manifest.traffic(cell["traffic"], {"steps_per_epoch": 1})
    fit = manifest.job(traffic["job"])
    reference = configuration.module("reference")
    sizes = configuration.sizes

    init_zoo_context("calibrate " + args.cell, seed=0)
    device = device_facts()
    if device["platform"] != "tpu":
        sys.exit(f"calibrate: needs a TPU, JAX found {device}")
    maybe_enable_persistent_cache(os.path.join(ROOT, ".jax_cache"))

    def read(row, which, got, ref):
        gaps = compare.all_gaps(got, ref)
        row[which] = compare.summarise(gaps, ref["names"])
        row["gaps"][which] = {k: [float(f"{g:.4g}") for g in v]
                              for k, v in gaps.items()}

    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        job = fit.Job(configuration, traffic, seed, device["platform"])
        job.setup()
        program, batches = job.program, job._check_batches
        job.free()
        key = fit.seed_key(seed)
        ref = fit.follow(reference, sizes, key, batches)

        row = {"seed": seed, "losses": {"program": program["losses"],
                                        "reference": ref["losses"]},
               "gaps": {},
               "reference_norms": {
                   "grad": [float(f"{v:.4g}") for v in ref["grad_norms"]],
                   "delta": [float(f"{v:.4g}") for v in ref["delta_norms"]]}}
        read(row, "program", program, ref)
        del program
        if i < args.control_seeds:
            read(row, "control", fit.follow(reference, sizes, key, batches,
                                            round_to=narrow.CONTROL), ref)
            half = [(x[:len(x) // 2], y[:len(y) // 2]) for x, y in batches]
            read(row, "half_batch",
                 fit.follow(reference, sizes, key, half), ref)
        del ref["grads"]
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items()
                          if k not in ("gaps", "reference_norms")}),
              flush=True)

    def over(which, pick):
        found = [r[which] for r in rows if which in r]
        return {k: pick(f[k]["value"] for f in found)
                for k in (found[0] if found else {})}

    summary = {"cell": args.cell, "device": device, "set": overrides,
               "lower_reading_max_over_seeds": over("program", max),
               "control_min_over_seeds": over("control", min),
               "half_batch_min_over_seeds": over("half_batch", min),
               "state_unchanged": {"delta_gap": 1.0},
               "names": ref["names"], "rows": rows}
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"calibrate-{args.cell}{args.tag}.json"),
              "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps({k: v for k, v in summary.items()
                      if k not in ("rows", "names")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
