"""The ``kimi-linear-48b-a3b-fit`` cell through the harness at toy size on
the CPU, as ``test_bench_kanana.py`` drives its cell: a sound run is
correct; the decay left out of the KDA layers, a state left unchanged, half
of the batch left out and the fp8 control in the program's place are not.
Plus the configuration's file against the published one, its operation
count, what ``routing_fault`` catches, and the KDA kernels' reader on a
hand-built trace."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare, run as bench_run
from benchmark.manifest import ROOT, Manifest

CELL, CONFIG = "kimi-linear-48b-a3b-fit", "kimi-linear-48b-a3b"
#: hidden 64, 2 KDA heads of 16 (4 taps), 2 latent heads of 16 + 8 (values
#: 16), latent 32, the five layers in the configuration's pattern (1 dense +
#: 4 routed), 8 routed experts of width 32 with 2 held, top-2, 1 shared,
#: vocabulary sliced to 32, 64 tokens
TOY = dict(
    config_overrides={
        "hidden_size": 64, "num_attention_heads": 2, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
        "linear_attn_config": {
            "full_attn_layers": [4], "head_dim": 16,
            "kda_layers": [1, 2, 3, 5], "num_heads": 2,
            "short_conv_kernel_size": 4},
        "num_experts": 2, "router_width": 8, "moe_intermediate_size": 32,
        "num_experts_per_token": 2, "intermediate_size": 96,
        "vocab_size": 32, "n_positions": 64},
    traffic_overrides={"batch": 8, "steps_per_epoch": 2})
SEED = 2**31 + 83     # the driver's seeds pass 32 signed bits


def _run_toy(scratch, trace=False):
    return bench_run.run_cell(Manifest(), CELL, SEED, 0.3, trace,
                              require_tpu=False, scratch=str(scratch), **TOY)


def _toy_configuration():
    return Manifest().configuration(CONFIG, TOY["config_overrides"])


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


def test_decay_left_out_is_not_correct(tmp_path, monkeypatch):
    """alpha = 1 in every KDA layer: the plain delta rule."""
    from analytics_zoo_tpu.ops import linear_attention as linear

    real = linear.chunked_kda

    def no_decay(q, k, v, g, beta, **kwargs):
        return real(q, k, v, jax.numpy.zeros_like(g), beta, **kwargs)

    monkeypatch.setattr(linear, "chunked_kda", no_decay)
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
    cfg = _toy_configuration()
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


def test_routing_fault_catches_a_wrong_pattern_and_a_fallback(
        tmp_path, monkeypatch):
    from analytics_zoo_tpu.ops import linear_attention as linear
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash
    from analytics_zoo_tpu.ops.pallas import grouped_matmul as grouped
    from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

    _run_toy(tmp_path)      # traces the step: the records are the cell's
    model_py = _toy_configuration().module("model")
    kept = list(linear.chunk_schedules)
    model_py.build({**_toy_configuration().sizes})
    assert not linear.chunk_schedules   # a new model's records start anew
    linear.chunk_schedules.extend(kept)
    assert model_py.routing_fault("cpu") is None
    record = self_attention.decoder_records[-1]
    assert record["attention_by_layer"] == ["kda", "kda", "kda", "latent",
                                            "kda"]
    assert record["attention"] == "by_layer" and record["rotary"] is False
    assert record["kda"] == (2, 16, 4)
    assert "kda_state" in record["kept"]
    assert {(r["chunk"], r["chunks"], r["sub_blocks"], r["kernel"])
            for r in linear.chunk_schedules} == {(64, 1, 4, False)}
    # on a TPU the toy run's fallbacks are faults: attention first (the
    # toy's 64 tokens go through no flash kernel at all; whatever an
    # earlier test of this process left in the counts is set aside)
    monkeypatch.setattr(flash, "invocation_counts",
                        {"pallas": 0, "fallback": 0})
    assert "flash attention routing" in model_py.routing_fault("tpu")
    monkeypatch.setattr(flash, "invocation_counts",
                        {"pallas": 9, "fallback": 0})
    assert "grouped product routing" in model_py.routing_fault("tpu")
    monkeypatch.setattr(grouped, "invocation_counts",
                        {"pallas": 9, "fallback": 0})
    assert "KDA walk routing" in model_py.routing_fault("tpu")
    monkeypatch.setattr(linear, "invocation_counts",
                        {"pallas": 9, "fallback": 0})
    assert "KDA walks traced as" in model_py.routing_fault("tpu")
    monkeypatch.setattr(linear, "chunk_schedules", [
        {**r, "kernel": True} for r in linear.chunk_schedules])
    # ... and flash kernels that ran at other widths than the latent ones
    monkeypatch.setattr(flash, "tile_schedules", [
        {"shape": (8, 2, 64, 64, 24), "value_width": 24}])
    assert "widths" in model_py.routing_fault("tpu")
    monkeypatch.setattr(flash, "tile_schedules", [
        {"shape": (8, 2, 64, 64, 24), "value_width": 16}])
    assert model_py.routing_fault("tpu") is None
    # a KDA layer walked in another chunk than the program's
    monkeypatch.setattr(linear, "chunk_schedules", [
        {**r, "chunk": 32, "chunks": 2} for r in linear.chunk_schedules])
    assert "KDA traced in chunks" in model_py.routing_fault("cpu")
    monkeypatch.undo()
    # a step traced with the latent layer in another place
    wrong = dict(record, attention_by_layer=["kda", "kda", "latent", "kda",
                                             "kda"])
    self_attention.decoder_records.append(wrong)
    assert "decoder traced as" in model_py.routing_fault("cpu")
    self_attention.decoder_records.pop()
    assert model_py.routing_fault("cpu") is None


def test_the_file_holds_every_published_number_but_the_cuts():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG,
                           "config.json")) as fh:
        sizes = json.load(fh)
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    differ = sorted(k for k, v in published.items() if sizes[k] != v)
    assert differ == sorted(sizes["reduced"]) == [
        "linear_attn_config", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert {k: sizes["published"][k] for k in differ} \
        == {k: published[k] for k in differ}
    # of the nested group only the two lists differ: no width inside it
    lists = ("kda_layers", "full_attn_layers")
    group, was = sizes["linear_attn_config"], published["linear_attn_config"]
    assert {k: v for k, v in group.items() if k not in lists} \
        == {k: v for k, v in was.items() if k not in lists}
    # the guide's floors: a dense layer and a whole period of four after
    # it in the published order (KDA, KDA, MLA, KDA after a leading KDA),
    # 8 experts or more, an eighth of the vocabulary or more
    layers = sizes["num_hidden_layers"]
    assert layers - sizes["first_k_dense_replace"] >= 4
    assert sorted(group["kda_layers"] + group["full_attn_layers"]) \
        == list(range(1, layers + 1))
    assert [i for i in was["kda_layers"] if i <= layers] \
        == group["kda_layers"]
    assert [i for i in was["full_attn_layers"] if i <= layers] \
        == group["full_attn_layers"]
    assert sizes["num_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= published["vocab_size"]
    assert sizes["router_width"] == published["num_experts"]
    assert 0 <= sizes["experts_held_from"] \
        <= sizes["router_width"] - sizes["num_experts"]


def test_operation_count_against_the_parameter_tree():
    cfg = Manifest().configuration(CONFIG)
    sizes, ops = cfg.sizes, cfg.module("ops")
    reference = cfg.module("reference")
    core = jax.eval_shape(lambda k: reference.init_params(k, sizes),
                          jax.random.PRNGKey(0))[reference.CORE]

    def count(tree, keep):
        return sum(int(np.prod(p.shape)) for path, p in
                   jax.tree_util.tree_flatten_with_path(tree)[0]
                   if keep(jax.tree_util.keystr(path), p))

    assert reference.mixer_kinds(sizes) == ["kda", "kda", "kda", "latent",
                                            "kda"]
    dense, routed, latent = (core["blocks"][i] for i in (0, 1, 3))
    # a token is multiplied by every matrix of a KDA mixer and by its
    # convolutions' taps; A_log, dt_bias, b_g and the norm multiply nothing
    kda = count(dense, lambda name, p: "kda_" in name and p.ndim == 2)
    assert ops.kda_params(sizes) == kda == 39_510_016
    assert count(dense, lambda name, p: "kda_" in name) - kda \
        == 32 + 4096 + 4096 + 128
    attention = count(latent, lambda name, p: p.ndim == 2 and any(
        k in name for k in ("q_kernel", "kv_a_kernel", "kv_b_kernel",
                            "o_kernel")))
    assert ops.attention_params(sizes) == attention == 29_114_368
    assert count(dense, lambda name, p: p.ndim == 2 and "kda_" not in name) \
        == 3 * 2304 * 9216 == 63_700_992
    shared = count(routed, lambda name, p: "shared_" in name)
    router = count(routed, lambda name, p: "router_kernel" in name)
    experts = count(routed, lambda name, p: "experts_" in name)
    assert (shared, router, experts // 8) == (7_077_888, 589_824, 7_077_888)
    assert ops.routed_layer_macs_per_token(sizes) \
        == shared + router + 8 * 8 / 256 * experts / 8
    assert ops.score_macs_per_token(sizes) == 20_971_520
    # the chunk-parallel rule at chunks of 64: 2 x 64 x 128 of pair
    # products, 64 x 128 of the triangular system, 3 x 128^2 with the
    # state and 32 x 128 with the pair matrix, 32 heads
    assert ops.kda_scan_macs_per_token(sizes) \
        == 32 * (8192 + 8192 + 49152 + 4096) == 2_228_224
    head = count(core, lambda name, p: "head_kernel" in name)
    assert ops.forward_macs_per_token(sizes) == pytest.approx(
        4 * (kda + 2_228_224) + attention + 20_971_520 + 63_700_992
        + 4 * (shared + router + 1_769_472) + head) == 365_674_496
    # the issue's figures: 2.2 GFLOP a training token, 18 TFLOP a step
    assert ops.train_flops_per_example(sizes) == pytest.approx(8.99e12,
                                                               rel=1e-3)
    # the whole tree: 602.4 M parameters
    assert count(core, lambda name, p: True) == pytest.approx(602.4e6,
                                                              rel=1e-3)
    costs = ops.flash_call_costs(2, sizes)
    unit = 2.0 * 2 * 32 * 4096 * 4096 * 0.5
    assert [costs[k][0] / unit for k in ("forward", "dq", "dkv")] \
        == [192 + 128, 192 + 128 + 192, 192 + 128 + 128 + 192]
    # a KDA kernel's call at chunks of 128: 64 sequence-heads x 32 chunks
    walk = ops.kda_call_costs(2, sizes, 128)
    chunks, rows, state = 64 * 32, 128 * 128 * 2, 128 * 128 * 4
    local = 128 * 128 * 128 + 128 * 128 * 128
    assert walk["forward"] == (
        chunks * 2.0 * (local + 3 * 128 ** 3 + 128 ** 3 / 2),
        chunks * (7 * rows + state))
    assert walk["backward"] == (
        chunks * 2.0 * (3 * local + 7 * 128 ** 3 + 128 ** 3),
        chunks * (13 * rows + state))


def _kernel_event(instruction, outputs, start, seconds):
    from benchmark import xplane

    shapes = ["bf16[64,32,128,128]{3,2,1,0}"] * outputs
    line = (f"%{instruction} = ({', '.join(shapes)}) custom-call("
            'bf16[64,32,128,128]{3,2,1,0} %q), '
            'custom_call_target="tpu_custom_call"')
    return xplane.Event(xplane.short_name(line), start, seconds * 1e9)


def test_the_kda_reader_from_a_hand_built_trace(monkeypatch):
    """``kda_scan_roofline`` tells the walk's two kernels from the flash
    and the grouped ones by the instruction's name, and one from the other
    by the arrays returned, each against its own least time at the chunk
    the program's record states; ``mla_attention_roofline`` reads this
    configuration's one latent layer from its own ``flash_call_costs``.
    Without the kernels, the record or the function a reader has nothing
    to read.  Neither is an entry of ``BENCHMARK.json`` yet (``PERF.md``,
    Open questions): the readers are loaded by path."""
    from analytics_zoo_tpu.ops import linear_attention as linear
    from benchmark import xplane
    from benchmark.manifest import load_module

    manifest = Manifest()
    home = os.path.join(manifest.home, "layer_metrics")
    kda = load_module(os.path.join(home, "kda_scan_roofline.py"))
    mla = load_module(os.path.join(home, "mla_attention_roofline.py"))
    cfg = manifest.configuration(CONFIG)
    peaks = manifest.peaks("TPU v5 lite")

    def least(flops, nbytes):
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])

    walk = cfg.module("ops").kda_call_costs(2, cfg.sizes, 128)
    flash = cfg.module("ops").flash_call_costs(2, cfg.sizes)
    # both walks are bound by memory at these widths: the state a chunk
    assert least(*walk["forward"]) \
        == walk["forward"][1] / peaks["hbm_bytes_per_s"]
    ops = [
        # the forward walk at a fifth of its roofline, the backward at a
        # tenth, four layers each
        *(_kernel_event(f"jvp_jit__kda_walk_forward__.{i}", 3, i * 1e8,
                        5 * least(*walk["forward"])) for i in range(4)),
        *(_kernel_event(f"transpose_jvp_jit__kda_walk_backward___.{i}", 5,
                        (4 + i) * 1e8, 10 * least(*walk["backward"]))
          for i in range(4)),
        # the one latent layer's three flash kernels at half of theirs
        _kernel_event("jvp_jit__flash_fwd_pallas__.5", 3, 9e8,
                      2 * least(*flash["forward"])),
        _kernel_event("_flash_bwd_kernel.13", 1, 10e8,
                      2 * least(*flash["dq"])),
        _kernel_event("_flash_bwd_kernel.10", 2, 11e8,
                      2 * least(*flash["dkv"])),
        _kernel_event("gmm.22", 1, 12e8, 1e-3),
        xplane.Event("fusion.1", 13e8, 1e6)]
    assert [kda.kda_kernel(e.name) for e in ops] == \
        ["forward"] * 4 + ["backward"] * 4 + [None] * 5
    run = {"capture": xplane.Capture({"/device:TPU:0": ops}, {}),
           "sizes": cfg.sizes, "configuration": cfg, "manifest": manifest,
           "traffic": {"batch": 2}, "device": {"kind": "TPU v5 lite"}}
    want = 100.0 * (least(*walk["forward"]) + least(*walk["backward"])) \
        / (5 * least(*walk["forward"]) + 10 * least(*walk["backward"]))
    assert 10.0 < want < 20.0
    assert kda.read(run, chunk=128) == pytest.approx(want)
    assert mla.read(run) == pytest.approx(50.0)
    # the chunk comes from the program's own record where none is given
    monkeypatch.setattr(linear, "chunk_schedules", [{"chunk": 128}])
    assert kda.read(run) == pytest.approx(want)
    monkeypatch.setattr(linear, "chunk_schedules", [])
    assert kda.read(run) is None
    # a trace without the kernels, a configuration without the function
    bare = {**run, "capture": xplane.Capture({"/device:TPU:0": ops[-2:]},
                                             {})}
    assert kda.read(bare, chunk=128) is None
    other = manifest.configuration("kanana-2-30b-a3b")
    assert kda.read({**run, "configuration": other, "sizes": other.sizes},
                    chunk=128) is None
    assert kda.read({**run, "capture": None}, chunk=128) is None
