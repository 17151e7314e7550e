"""The ``kanana-2-30b-a3b-fit`` cell through the harness at toy size on the
CPU, as ``test_bench_ouro.py`` drives its cell: a sound run is correct; the
held experts' part left out, a state left unchanged, half of the batch left
out and the fp8 control in the program's place are not.  Plus the
configuration's file against the published one, its operation count, and
what ``routing_fault`` catches."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare, run as bench_run
from benchmark.manifest import ROOT, Manifest

CELL = "kanana-2-30b-a3b-fit"
#: hidden 64, 4 heads of 16 + 8 (values 16), latent 32, 1 dense + 2 routed
#: layers, 16 routed experts of width 32 with 4 held, top-3, 2 shared,
#: vocabulary 128 sliced to 32, 32 tokens
TOY = dict(
    config_overrides={
        "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 16,
        "kv_lora_rank": 32, "num_hidden_layers": 3, "n_routed_experts": 4,
        "router_width": 16, "moe_intermediate_size": 32,
        "num_experts_per_tok": 3, "intermediate_size": 96, "vocab_size": 32,
        "n_positions": 32},
    traffic_overrides={"batch": 8, "steps_per_epoch": 2})
SEED = 2**31 + 83     # the driver's seeds pass 32 signed bits


def _run_toy(scratch, trace=False):
    return bench_run.run_cell(Manifest(), CELL, SEED, 0.3, trace,
                              require_tpu=False, scratch=str(scratch), **TOY)


def test_sound_run_is_correct(tmp_path):
    result, table = _run_toy(tmp_path, trace=True)
    assert result["correct"] is True, table
    assert result["attempted"] > 0 and result["failed"] == 0
    # float32 against float32 on the CPU: far inside the chip's limits
    assert all(row["value"] < 0.1 * row["limit"]
               for row in table.values()), table
    # no device plane on the CPU: the readers of the registry alone
    assert set(result["metrics"]) >= {
        "data_wait_ms_per_step", "dispatch_ms_per_step", "compile_s",
        "compiles_in_window", "step_lower_s"}
    assert not [name for name, metric in result["metrics"].items()
                if metric["unit"] == "%"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0


def test_held_experts_part_left_out_is_not_correct(tmp_path, monkeypatch):
    """The routed layer hands back the shared experts alone."""
    from analytics_zoo_tpu.ops import moe

    real = moe.held_experts_ffn

    def nothing_held(u, *args, **kwargs):
        y, stats = real(u, *args, **kwargs)
        return jax.numpy.zeros_like(y), stats

    monkeypatch.setattr(moe, "held_experts_ffn", nothing_held)
    result, table = _run_toy(tmp_path)
    assert result["correct"] is False, table


def test_state_left_unchanged_is_not_correct(tmp_path, monkeypatch):
    from analytics_zoo_tpu.pipeline.estimator import Estimator

    train = Estimator.train

    def train_and_forget(self, *args, **kwargs):
        params = jax.tree_util.tree_map(np.asarray, self.model.params)
        out = train(self, *args, **kwargs)
        self.model.params = jax.tree_util.tree_map(jax.numpy.asarray, params)
        return out

    monkeypatch.setattr(Estimator, "train", train_and_forget)
    result, table = _run_toy(tmp_path)
    assert result["correct"] is False
    assert table["delta_gap_median"]["value"] > 0.9   # nothing moved


def test_half_of_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    """The loss is taken inside the model: the fault is planted where the
    head's cross-entropy is, the mean taken over the first half of the
    batch."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder

    mean_ce = LatentMoEDecoder._mean_ce

    def mean_of_half(self, params, s, targets):
        n = s.shape[0] // 2
        return mean_ce(self, params, s[:n], targets[:n])

    monkeypatch.setattr(LatentMoEDecoder, "_mean_ce", mean_of_half)
    result, table = _run_toy(tmp_path)
    assert result["correct"] is False, table


def test_lower_precision_control_is_not_correct():
    """The reference in the program's place computed in fp8, at toy size,
    under the cell's own limits, through the cell's own ``follow``."""
    from benchmark import data
    from benchmark.narrow import CONTROL

    manifest = Manifest()
    cfg = manifest.configuration("kanana-2-30b-a3b", TOY["config_overrides"])
    fit = manifest.job(manifest.traffic("fit-b2-e8")["job"])
    x, y = data.rows(SEED, fit.CHECK_ROWS, 3 * 8, cfg.sizes)
    batches = [(x[i:i + 8], y[i:i + 8]) for i in (0, 8, 16)]
    key = fit.seed_key(SEED)
    reference = cfg.module("reference")
    ref = fit.follow(reference, cfg.sizes, key, batches)
    control = fit.follow(reference, cfg.sizes, key, batches,
                         round_to=CONTROL)
    correct, table = compare.verdict(
        compare.compare(control, ref, ref["names"]), manifest.limits(CELL))
    assert correct is False, table


def test_routing_fault_catches_a_wrong_layer_count_and_a_fallback(
        tmp_path, monkeypatch):
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash
    from analytics_zoo_tpu.ops.pallas import grouped_matmul as grouped
    from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

    _run_toy(tmp_path)      # traces the step: the records are the cell's
    model_py = Manifest().configuration(
        "kanana-2-30b-a3b", TOY["config_overrides"]).module("model")
    model_py.build({**Manifest().configuration(
        "kanana-2-30b-a3b", TOY["config_overrides"]).sizes})
    assert model_py.routing_fault("cpu") is None
    # on a TPU the toy run's fallbacks are faults: attention first
    assert "flash attention routing" in model_py.routing_fault("tpu")
    monkeypatch.setattr(flash, "invocation_counts",
                        {"pallas": 9, "fallback": 0})
    assert "grouped product routing" in model_py.routing_fault("tpu")
    monkeypatch.setattr(grouped, "invocation_counts",
                        {"pallas": 9, "fallback": 0})
    # ... and kernels that ran at other widths than the configuration's
    monkeypatch.setattr(flash, "tile_schedules", [
        {"shape": (8, 4, 32, 32, 24), "value_width": 24}])
    assert "widths" in model_py.routing_fault("tpu")
    monkeypatch.setattr(flash, "tile_schedules", [
        {"shape": (8, 4, 32, 32, 24), "value_width": 16}])
    assert model_py.routing_fault("tpu") is None
    # a step traced with one routed layer too few
    record = dict(self_attention.decoder_records[-1])
    record.update(routed_layers=1)
    self_attention.decoder_records.append(record)
    assert "decoder traced as" in model_py.routing_fault("cpu")
    self_attention.decoder_records.pop()
    assert model_py.routing_fault("cpu") is None


def test_the_file_holds_every_published_number_but_the_three_cuts():
    with open(os.path.join(ROOT, "benchmark", "configs", "kanana-2-30b-a3b",
                           "config.json")) as fh:
        sizes = json.load(fh)
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 128256}
    differ = sorted(k for k, v in published.items() if sizes[k] != v)
    assert differ == sorted(sizes["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert {k: sizes["published"][k] for k in differ} \
        == {k: published[k] for k in differ}
    # the guide's floors: a dense layer and four routed ones, 8 experts or
    # more, an eighth of the vocabulary or more
    assert sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] >= 4
    assert sizes["n_routed_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= published["vocab_size"]
    assert sizes["router_width"] == published["n_routed_experts"]
    assert 0 <= sizes["experts_held_from"] \
        <= sizes["router_width"] - sizes["n_routed_experts"]


def test_operation_count_against_the_parameter_tree():
    cfg = Manifest().configuration("kanana-2-30b-a3b")
    sizes, ops = cfg.sizes, cfg.module("ops")
    reference = cfg.module("reference")
    core = jax.eval_shape(lambda k: reference.init_params(k, sizes),
                          jax.random.PRNGKey(0))[reference.CORE]

    def count(tree, keep):
        return sum(int(np.prod(p.shape)) for path, p in
                   jax.tree_util.tree_flatten_with_path(tree)[0]
                   if keep(jax.tree_util.keystr(path), p))

    dense, routed = core["blocks"][0], core["blocks"][1]
    attention = count(dense, lambda name, p: p.ndim == 2 and any(
        k in name for k in ("q_kernel", "kv_a_kernel", "kv_b_kernel",
                            "o_kernel")))
    # (the issue's 26,345,984 counts the latent norm's 512 gains, which
    # multiply nothing)
    assert ops.attention_params(sizes) == attention == 26_345_472
    # a token meets every matrix of the dense layer ...
    assert count(dense, lambda name, p: p.ndim == 2) - attention \
        == 3 * 2048 * 6144 == 37_748_736
    # ... and of a routed layer the shared experts, the router and six of
    # the 128 experts, of which 16 are here
    shared = count(routed, lambda name, p: "shared_" in name)
    router = count(routed, lambda name, p: "router_kernel" in name)
    experts = count(routed, lambda name, p: "experts_" in name)
    assert (shared, router, experts // 16) == (9_437_184, 262_144, 4_718_592)
    assert ops.routed_layer_macs_per_token(sizes) \
        == shared + router + 6 * 16 / 128 * experts / 16
    assert ops.score_macs_per_token(sizes) == 20_971_520
    head = count(core, lambda name, p: "head_kernel" in name)
    assert ops.forward_macs_per_token(sizes) == pytest.approx(
        5 * (attention + 20_971_520) + 37_748_736
        + 4 * (shared + router + 3_538_944) + head) == 360_120_320
    # the issue's figures: 2.161 GFLOP a token, 8.85 TFLOP a sequence
    assert ops.train_flops_per_example(sizes) == pytest.approx(8.85e12,
                                                               rel=1e-3)
    # the whole tree: 576.0 M parameters
    assert count(core, lambda name, p: True) == pytest.approx(576.0e6,
                                                              rel=1e-3)
    # the flash kernels' products at latent attention's two widths
    costs = ops.flash_call_costs(2, sizes)
    unit = 2.0 * 2 * 32 * 4096 * 4096 * 0.5
    assert [costs[k][0] / unit for k in ("forward", "dq", "dkv")] \
        == [192 + 128, 192 + 128 + 192, 192 + 128 + 128 + 192]
    tensor = 2 * 32 * 4096 * 2
    assert costs["forward"][1] == tensor * (192 + 192 + 128 + 128)
    assert costs["dq"][1] == tensor * (3 * 192 + 2 * 128)
    assert costs["dkv"][1] == tensor * (3 * 192 + 3 * 128)


def _kernel_event(instruction, shapes, start, seconds):
    from benchmark import xplane

    result = shapes[0] if len(shapes) == 1 else "(" + ", ".join(shapes) + ")"
    line = (f"%{instruction} = {result} custom-call(bf16[2,32,4096,192]"
            '{3,2,1,0} %q), custom_call_target="tpu_custom_call"')
    return xplane.Event(xplane.short_name(line), start, seconds * 1e9)


def test_the_two_kernel_readers_from_a_hand_built_trace():
    """``mla_attention_roofline`` and ``expert_matmul_roofline`` tell the
    flash kernels from the grouped products by the instruction's name as
    well as by the arrays returned (a grouped product returns one, as the
    dq kernel does), each against its own least time; without its kernel
    or its gauge a reader has nothing to read."""
    from benchmark import xplane
    from benchmark.manifest import load_module

    manifest = Manifest()
    home = os.path.join(manifest.home, "layer_metrics")
    mla = load_module(os.path.join(home, "mla_attention_roofline.py"))
    experts = load_module(os.path.join(home, "expert_matmul_roofline.py"))
    cfg = manifest.configuration("kanana-2-30b-a3b")
    peaks = manifest.peaks("TPU v5 lite")

    def least(flops, nbytes):
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])

    flash = cfg.module("ops").flash_call_costs(2, cfg.sizes)
    wide, narrow = "bf16[2,32,4096,192]{3,2,1,0}", "bf16[2,32,4096,128]{3,2,1,0}"
    stat = "f32[2,32,1,4096]{3,2,1,0}"
    rows = "bf16[49152,768]{1,0}"
    grouped = experts.call_costs(6000.0, 2048, 768, 16)
    # a sum of a token's rows: rows x 128 x hidden, over the rows, their
    # places and all 64 tiles of the float32 running sum, read and written
    summed = experts.sum_costs(6000.0, 2048, 8192)
    assert summed == (2.0 * 6000 * 128 * 2048,
                      2 * 6000 * (2048 + 128) + 8 * 64 * 128 * 2048)
    # the experts' matrices once a call outweigh the products: memory-bound
    assert least(*grouped) == grouped[1] / peaks["hbm_bytes_per_s"]
    ops = [
        # each flash kernel at half of its roofline
        _kernel_event("jvp_jit__flash_fwd_pallas__.5", [narrow, stat, stat],
                      0.0, 2 * least(*flash["forward"])),
        _kernel_event("_flash_bwd_kernel.13", [wide], 1e8,
                      2 * least(*flash["dq"])),
        _kernel_event("_flash_bwd_kernel.10", [wide, narrow], 2e8,
                      2 * least(*flash["dkv"])),
        # a window of each layer's walk, every call at a quarter of its
        # roofline: eight ``gmm`` and five ``tgmm``, of which two are the
        # sums of a token's rows
        *(_kernel_event(name, [rows], 3e8 + i * 1e7, 4 * least(*costs))
          for i, (name, costs) in enumerate(
              [(f"gmm.{k}", grouped) for k in range(15)]
              + [("jvp_jit_gmm__.1", grouped)]
              + [(f"tgmm.{k}", grouped) for k in range(6)]
              + [(f"tgmm.{k}", summed) for k in range(6, 10)])),
        xplane.Event("fusion.1", 9e8, 1e6)]
    assert [mla.flash_kernel(e.name) for e in ops] \
        == ["forward", "dq", "dkv"] + [None] * 27
    run = {"capture": xplane.Capture({"/device:TPU:0": ops}, {}),
           "sizes": cfg.sizes, "configuration": cfg, "manifest": manifest,
           "traffic": {"batch": 2}, "device": {"kind": "TPU v5 lite"},
           "registry_after": {
               ("zoo_moe_held_assignments", "1"): (6000.0, 1),
               ("zoo_moe_held_assignments", "2"): (6000.0, 1),
               ("zoo_moe_dropped_assignments", ""): (0.0, 1)}}
    assert mla.read(run) == pytest.approx(50.0)
    assert experts.read(run) == pytest.approx(25.0)
    # a program without the gauge (the parent commit's), a trace without
    # the kernels, a configuration without the function: no number, not 0
    assert experts.read({**run, "registry_after": {}}) is None
    # another walk than the one reckoned (a call fewer): no number
    fewer = {**run, "capture": xplane.Capture(
        {"/device:TPU:0": ops[:3] + ops[4:]}, {})}
    assert experts.read(fewer) is None and mla.read(fewer) == mla.read(run)
    bare = {**run, "capture": xplane.Capture({"/device:TPU:0": ops[-1:]}, {})}
    assert mla.read(bare) is None and experts.read(bare) is None
    other = manifest.configuration("gpt2-small")
    assert mla.read({**run, "configuration": other,
                     "sizes": other.sizes}) is None
    assert mla.read({**run, "capture": None}) is None
