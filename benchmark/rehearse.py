"""Rehearsal without the chip (on-chip-measurement guide, section 2, third
rehearsal): compile a cell's step program and its reference's step at the
timed size for a described ``v5e:2x2`` chip, here, and print what each
holds on the device.  Nothing runs; no number printed here is a
measurement.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py <cell> [program|reference|control]
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

def _report(name, compiled):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{name}: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"outputs {mem.output_size_in_bytes / 1e9:.2f} GB, "
          f"aliased {mem.alias_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
          f"tpu_custom_calls {text.count('tpu_custom_call')}", flush=True)


def main(argv) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import data, narrow
    from benchmark.manifest import Manifest

    jax.config.update("jax_enable_compilation_cache", False)
    manifest = Manifest(ROOT)
    cell = manifest.cell(argv[0])
    which = argv[1:] or ["program", "reference", "control"]
    configuration = manifest.configuration(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    sizes, batch = configuration.sizes, traffic["batch"]
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    x, y = data.rows(0, 0, batch, sizes)
    reference = configuration.module("reference")
    params = jax.eval_shape(
        lambda k: reference.init_params(k, sizes), jax.random.PRNGKey(0))

    if "program" in which:
        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.ops.pallas import flash_attention as flash

        # what a TPU process would choose: bf16 compute, the flash kernel
        init_zoo_context("rehearsal", compute_dtype=sizes["compute_dtype"])
        flash._pallas_available = lambda: True
        model_py = configuration.module("model")
        model = model_py.build(sizes)
        est = model._make_estimator()
        feature_set = model_py.feature_set(x, y, sizes)
        step = est._build_train_step(
            getattr(feature_set, "device_transform", None), 1,
            est._resolved_plan())
        _, state = model.build_params()
        opt_state = jax.eval_shape(est.optimizer.init, params)
        args = described((params, opt_state, state, np.int32(0), np.int32(0),
                          {"x": x, "y": y}))
        _report(f"{cell['name']} program step",
                step._jitted.lower(*args).compile())
        print("flash routing at trace time:", dict(flash.invocation_counts))

    for name, round_to in (("reference", None),
                           ("control", narrow.CONTROL)):
        if name not in which:
            continue
        opt_state = jax.eval_shape(reference.init_opt_state, params)
        step = jax.jit(lambda p, o, i, bx, by: reference.train_step(
            p, o, i, bx, by, sizes, round_to))
        args = described((params, opt_state, np.int32(0), x, y))
        _report(f"{cell['name']} {name} step", step.lower(*args).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
