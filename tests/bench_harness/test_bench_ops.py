"""The operation counts against independent counts of the same shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_resnet50_forward_macs(manifest):
    cfg = manifest.configuration("resnet50")
    macs = cfg.module("ops").forward_macs_per_example(cfg.sizes)
    # 3.86 G: this net strides on a block's first 1x1 (ResNet v1).  The
    # 4.09 G often quoted is the variant that strides on the 3x3.
    assert macs == pytest.approx(3.86e9, rel=0.01)
    # XLA's own count of the reference's forward pass at batch 1: two
    # operations a multiply-accumulate, plus the elementwise work
    reference = cfg.module("reference")
    params = jax.eval_shape(lambda k: reference.init_params(k, cfg.sizes),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.uint8)
    y = jax.ShapeDtypeStruct((1,), jnp.int32)
    cost = jax.jit(lambda p, x, y: reference.loss_fn(
        p, x, y, cfg.sizes)).lower(params, x, y).cost_analysis()
    assert 2 * macs == pytest.approx(cost["flops"], rel=0.03)
    assert cfg.module("ops").train_flops_per_example(cfg.sizes) == 6 * macs


def test_gpt2_small_agrees_with_transformer_bench_formula(manifest):
    cfg = manifest.configuration("gpt2-small")
    sizes, ops = cfg.sizes, cfg.module("ops")
    reference = cfg.module("reference")
    params = jax.eval_shape(lambda k: reference.init_params(k, sizes),
                            jax.random.PRNGKey(0))
    core = params[reference.CORE]
    n_all = sum(int(np.prod(p.shape))
                for p in jax.tree_util.tree_leaves(params))
    # tools/transformer_bench.py: everything but the embeddings multiplies
    n_matmul = n_all - int(np.prod(core["tok_embed"].shape)) \
        - int(np.prod(core["pos_embed"].shape))
    b, s, d = 1, sizes["n_positions"], sizes["n_embd"]
    fwd = 2 * n_matmul * b * s + sizes["n_layer"] * 4 * b * s * s * d * 0.5
    assert ops.train_flops_per_example(sizes) == pytest.approx(3 * fwd,
                                                               rel=0.005)
    assert ops.train_flops_per_example(sizes) / s \
        == pytest.approx(0.8e9, rel=0.02)   # "about 0.8 GFLOP a token"


def test_flash_attention_roofline_from_a_hand_built_trace(manifest):
    from benchmark import xplane
    from benchmark.manifest import load_module
    import os

    reader = load_module(os.path.join(
        manifest.home, "layer_metrics", "flash_attention_roofline.py"))
    costs = reader.call_costs(8, 12, 1024, 64)
    product = 2 * 8 * 12 * 1024 * 1024 * 64 * 0.5
    assert costs["forward"] == (2 * product, 4 * 8 * 12 * 1024 * 64 * 2)
    assert costs["dq"][0] == 3 * product and costs["dkv"][0] == 4 * product
    peaks = manifest.peaks("TPU v5 lite")
    least = {k: max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
             for k, (f, b) in costs.items()}
    # compute-bound at these shapes
    assert all(least[k] == costs[k][0] / peaks["bf16_flops_per_s"]
               for k in costs)
    ops = [xplane.Event(xplane.short_name(line), start, least[k] * 4e9)
           for k, line, start in (
               ("forward", "%jvp___.3 = (bf16[8,12,1024,64]{3,2,1,0}, "
                "f32[8,12,1024,1]{3,2,1,0}, f32[8,12,1024,1]{3,2,1,0}) "
                "custom-call(bf16[8,12,1024,64]{3,2,1,0} %q), "
                'custom_call_target="tpu_custom_call"', 0.0),
               ("dq", "%transpose_jvp___.26 = bf16[8,12,1024,64]{3,2,1,0} "
                "custom-call(bf16[8,12,1024,64]{3,2,1,0} %q), "
                'custom_call_target="tpu_custom_call"', 2e7),
               ("dkv", "%transpose_jvp___.25 = (bf16[8,12,1024,64]{3,2,1,0}, "
                "bf16[8,12,1024,64]{3,2,1,0}) custom-call(bf16[8,12,1024,64]"
                '{3,2,1,0} %q), custom_call_target="tpu_custom_call"', 4e7))]
    assert [e.name for e in ops] == [
        "jvp___.3/tpu_custom_call/3", "transpose_jvp___.26/tpu_custom_call/1",
        "transpose_jvp___.25/tpu_custom_call/2"]
    ops.append(xplane.Event("fusion.1", 6e7, 1e6))     # not a flash kernel
    cfg = manifest.configuration("gpt2-small")
    run = {"capture": xplane.Capture({"/device:TPU:0": ops}, {}),
           "sizes": cfg.sizes,
           "manifest": manifest, "traffic": {"batch": 8},
           "device": {"kind": "TPU v5 lite"}}
    assert reader.read(run) == pytest.approx(25.0)
    run["capture"] = xplane.Capture({"/device:TPU:0": ops[-1:]}, {})
    assert reader.read(run) is None      # nothing to read: no number, not 0


def test_train_step_mfu_from_the_trace_s_step_programs(manifest):
    """The whole step's share of the peak is taken over the device's time
    inside the step programs, not over the host's window."""
    from benchmark import xplane

    read = manifest.reader("train_step_mfu")
    cfg = manifest.configuration("gpt2-small")
    flops = cfg.module("ops").train_flops_per_example(cfg.sizes)
    peak = manifest.peaks("TPU v5 lite")["bf16_flops_per_s"]
    step_s = 4.0 * 8 * flops / peak        # a step of 8 rows at 25%
    steps = [xplane.Event("jit_train_step", i * 2 * step_s * 1e9,
                          step_s * 1e9) for i in range(6)]
    run = {"step_modules": {"/device:TPU:0": steps},
           "window": {"steps": 6, "examples": 48, "elapsed_s": 99.0},
           "configuration": cfg, "sizes": cfg.sizes, "manifest": manifest,
           "device": {"kind": "TPU v5 lite"}}
    assert read(run) == pytest.approx(25.0)
    # two chips, each its half of every step in the same time: the same
    run["step_modules"]["/device:TPU:1"] = steps
    run["window"]["examples"] = 96
    assert read(run) == pytest.approx(25.0)
    # nothing to read: no number, not 0
    assert read({**run, "step_modules": {}}) is None
    # a capture that lost step programs would read too high: refused
    run["step_modules"] = {"/device:TPU:0": steps[:4]}
    with pytest.raises(ValueError, match="4 step programs"):
        read(run)
