"""The ``nemotron-3-nano-30b-a3b-fit`` cell through the harness at toy size
on the CPU, as ``test_bench_kimi.py`` drives its cell: a sound run is
correct; the decay left out of the Mamba-2 layers, a state left unchanged,
half of the batch left out and the fp8 control in the program's place are
not.  Plus the configuration's file against the published one, its
operation count, what ``routing_fault`` catches, and the two readers this
configuration brought (``ssd_scan_roofline``, ``step_ssd_ms``) on a
hand-built trace."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare, run as bench_run
from benchmark.manifest import ROOT, Manifest, load_module
from benchmark.xplane import Capture, Event

CELL, CONFIG = "nemotron-3-nano-30b-a3b-fit", "nemotron-3-nano-30b-a3b"
#: hidden 64, 4 SSD heads of 8 (state 16, 2 groups, chunks of 16), 4
#: attention heads over 2 key-value heads of 16, the pattern's seven layers
#: MEMEM*E, 8 routed experts of width 32 with 2 held, top-2, a shared
#: expert of 64, vocabulary sliced to 32, 64 tokens; the steps dt drawn in
#: 0.05..0.5, so that a sequence this short forgets as a long one does
TOY = dict(
    config_overrides={
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 4,
        "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
        "chunk_size": 16, "n_routed_experts": 2, "router_width": 8,
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 64,
        "num_experts_per_tok": 2, "vocab_size": 32, "n_positions": 64,
        "time_step_min": 0.05, "time_step_max": 0.5},
    traffic_overrides={"batch": 8, "steps_per_epoch": 2})
SEED = 2**31 + 97     # a run's seed may pass 32 signed bits


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
    assert set(result["metrics"]) >= {
        "data_wait_ms_per_step", "dispatch_ms_per_step", "compile_s",
        "compiles_in_window", "step_lower_s"}
    assert not [name for name, metric in result["metrics"].items()
                if metric["unit"] == "%"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0


def test_decay_left_out_is_not_correct(tmp_path, monkeypatch):
    """exp(dt A) = 1 in every Mamba-2 layer: the rule without its decay."""
    from analytics_zoo_tpu.ops import ssd

    real = ssd.chunked_ssd

    def no_decay(x, dt, a, b, c, **kwargs):
        return real(x, dt, a * 1e-30, b, c, **kwargs)

    monkeypatch.setattr(ssd, "chunked_ssd", no_decay)
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
    # nothing moved: a leaf whose change the reference makes under the
    # median leaf's reads its share of that median, the others 1
    assert table["delta_gap_median"]["value"] > 0.5
    assert table["delta_gap"]["value"] == pytest.approx(1.0)


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


def test_routing_fault_catches_a_wrong_pattern_copied_groups_and_a_fallback(
        tmp_path, monkeypatch):
    from analytics_zoo_tpu.ops import ssd
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash
    from analytics_zoo_tpu.ops.pallas import grouped_matmul as grouped
    from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

    _run_toy(tmp_path)      # traces the step: the records are the cell's
    model_py = _toy_configuration().module("model")
    kept = list(ssd.chunk_schedules)
    model_py.build({**_toy_configuration().sizes})
    assert not ssd.chunk_schedules   # a new model's records start anew
    ssd.chunk_schedules.extend(kept)
    assert model_py.routing_fault("cpu") is None
    record = self_attention.decoder_records[-1]
    assert record["attention_by_layer"] == ["ssd", "ssd", "ssd", "full"]
    assert record["ffn_by_layer"] == ["routed", "routed", None, "routed"]
    assert (record["ssd"], record["kv_heads"], record["experts_gated"]) \
        == ((4, 8, 16, 2, 4), 2, False)
    assert "ssd_state" in record["kept"]
    assert {(r["rule"], r["groups"], r["chunk"], r["chunks"], r["kernel"])
            for r in ssd.chunk_schedules} == {("ssd", 2, 16, 4, False)}
    # B and C copied to every head: the walk would be traced with as many
    # groups as heads
    monkeypatch.setattr(ssd, "chunk_schedules", [
        {**r, "groups": 4} for r in ssd.chunk_schedules])
    assert "SSD traced in chunks" in model_py.routing_fault("cpu")
    monkeypatch.undo()
    # on a TPU the toy run's fallbacks are faults: attention first (the
    # toy's 64 tokens go through no flash kernel at all; whatever an
    # earlier test of this process left in the counts is set aside)
    monkeypatch.setattr(flash, "invocation_counts",
                        {"pallas": 0, "fallback": 0})
    assert "flash attention routing" in model_py.routing_fault("tpu")
    monkeypatch.setattr(flash, "invocation_counts",
                        {"pallas": 9, "fallback": 0})
    monkeypatch.setattr(grouped, "invocation_counts",
                        {"pallas": 9, "fallback": 1})
    assert "grouped product routing" in model_py.routing_fault("tpu")
    monkeypatch.setattr(grouped, "invocation_counts",
                        {"pallas": 9, "fallback": 0})
    assert "SSD walk routing" in model_py.routing_fault("tpu")
    monkeypatch.setattr(ssd, "invocation_counts",
                        {"pallas": 9, "fallback": 0})
    assert "SSD walks traced as" in model_py.routing_fault("tpu")
    monkeypatch.setattr(ssd, "chunk_schedules", [
        {**r, "kernel": True} for r in ssd.chunk_schedules])
    # ... and flash kernels that read every query head's own k and v
    monkeypatch.setattr(flash, "tile_schedules", [
        {"shape": (8, 4, 64, 64, 16), "kv_heads": 4, "value_width": 16}])
    assert "key-value heads" in model_py.routing_fault("tpu")
    monkeypatch.setattr(flash, "tile_schedules", [
        {"shape": (8, 4, 64, 64, 16), "kv_heads": 2, "value_width": 16}])
    assert model_py.routing_fault("tpu") is None
    monkeypatch.undo()
    # a step traced with the attention layer in another place
    wrong = dict(record, attention_by_layer=["ssd", "full", "ssd", "ssd"])
    self_attention.decoder_records.append(wrong)
    assert "decoder traced as" in model_py.routing_fault("cpu")
    self_attention.decoder_records.pop()
    assert model_py.routing_fault("cpu") is None


#: the catalog's ``config`` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, as read
#: from the model's published config.json
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def test_the_file_holds_every_published_number_but_the_cuts():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG,
                           "config.json")) as fh:
        sizes = json.load(fh)
    differ = sorted(k for k, v in PUBLISHED.items() if sizes[k] != v)
    assert differ == sorted(sizes["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert {k: sizes["published"][k] for k in differ} \
        == {k: PUBLISHED[k] for k in differ}
    # the guide's floors: a whole period of the pattern (the published
    # layers 0-6: every seven layers in a row hold 3 M, 3 E and 1 *), four
    # layers or more, 8 experts or more, an eighth of the vocabulary
    pattern, layers = sizes["hybrid_override_pattern"], \
        sizes["num_hidden_layers"]
    assert pattern == PUBLISHED["hybrid_override_pattern"][:layers]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (3, 3, 1) and layers >= 4
    assert sizes["n_routed_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert sizes["router_width"] == PUBLISHED["n_routed_experts"]
    assert 0 <= sizes["experts_held_from"] \
        <= sizes["router_width"] - sizes["n_routed_experts"]
    assert "16 chips share each layer" in sizes["deployment"]


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

    assert reference.blocks(sizes) == [("M", True), ("M", True),
                                       ("M", False), ("*", True)]
    mamba, no_ffn, attention = (core["blocks"][i] for i in (0, 2, 3))
    # a token is multiplied by the two projections and the convolution's
    # taps; A_log, dt_bias, D, the biases and the norm multiply nothing
    m = count(no_ffn, lambda name, p: "ssd_" in name and p.ndim == 2)
    assert ops.mamba_params(sizes) == m == 38_731_776
    assert count(no_ffn, lambda name, p: True) - m \
        == 6144 + 3 * 64 + 4096 + 2688     # + conv bias, A, dt, D, norm, ln1
    assert count(attention, lambda name, p: "attn_" in name) \
        == ops.attention_params(sizes) == 23_396_352
    shared = count(mamba, lambda name, p: "shared_" in name)
    router = count(mamba, lambda name, p: "router_kernel" in name)
    experts = count(mamba, lambda name, p: "experts_" in name)
    assert (shared, router, experts // 8) == (19_955_712, 344_064,
                                              9_977_856)
    assert ops.routed_layer_macs_per_token(sizes) \
        == shared + router + 6 * 8 / 128 * experts / 8
    assert ops.score_macs_per_token(sizes) == 32 * 4096 * 0.5 * 256
    # the rule at chunks of 128: a group's C B^T, 64 x 128 of it for 8
    # groups; a head's intra-chunk 64 x 64 and two 64 x 128 with the state
    assert ops.ssd_scan_macs_per_token(sizes) \
        == 8 * 64 * 128 + 64 * (64 * 64 + 2 * 64 * 128) == 1_376_256
    head = count(core, lambda name, p: "head_kernel" in name)
    assert ops.forward_macs_per_token(sizes) == pytest.approx(
        3 * (m + 1_376_256) + 23_396_352 + 16_777_216
        + 3 * (shared + router + 3_741_696) + head) == 276_662_272
    # 1.66 GFLOP a training token, 13.6 TFLOP a step of two sequences
    assert 2 * ops.train_flops_per_example(sizes) == pytest.approx(
        13.6e12, rel=2e-3)
    # the whole tree: 528.1 M parameters
    assert count(core, lambda name, p: True) == pytest.approx(528.1e6,
                                                              rel=1e-3)
    # an SSD kernel's call at chunks of 128: 2 sequences x 32 chunks
    walk = ops.ssd_call_costs(2, sizes, 128)
    chunks, pairs = 64, 128 * 128 / 2
    x, decay, state, bc = 128 * 64 * 2, 128 * 4, 64 * 128 * 4, 128 * 128 * 2
    assert walk["forward"] == (
        chunks * 2.0 * (8 * pairs * 128 + 64 * (pairs * 64
                                                + 2 * 128 * 64 * 128)),
        chunks * (64 * (2 * x + decay + state) + 8 * 2 * bc))
    assert walk["backward"][0] == chunks * 2.0 * (
        8 * pairs * 128 + 64 * (2 * pairs * 64 + 2 * pairs * 128
                                + 5 * 128 * 64 * 128))


# -- the two readers on a hand-built trace ----------------------------------

PLANE = "/device:TPU:0"
MS = 1_000_000
STEP = "jit(train_step)/"
SSD_FWD = STEP + "jvp(zoo.mixer)/zoo.ssd/jit(_ssd_walk_forward)/pallas_call"
SSD_PROJ = STEP + "transpose(jvp(zoo.mixer))/zoo.ssd/dot_general"
MIXER = STEP + "jvp(zoo.mixer)/add"
FFN = STEP + "jvp(zoo.ffn)/dot_general"


def _kernel(name, outputs, start, seconds, scope=""):
    from benchmark import xplane

    shapes = ["bf16[16,8,32,128,64]{4,3,2,1,0}"] * outputs
    line = (f"%{name} = ({', '.join(shapes)}) custom-call("
            'bf16[16,8,32,128,64]{4,3,2,1,0} %x), '
            'custom_call_target="tpu_custom_call"')
    return Event(xplane.short_name(line), start, seconds * 1e9, scope)


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def _reader(manifest, name):
    return load_module(os.path.join(manifest.home, "layer_metrics",
                                    name + ".py"))


def test_the_ssd_reader_from_a_hand_built_trace(manifest, monkeypatch):
    """``ssd_scan_roofline`` tells the walk's two kernels from the KDA,
    flash and grouped ones by the instruction's name, and one from the
    other by the arrays returned (3 and 4), each against its own least
    time at the chunk the program's record states; without the kernels,
    the record or the function it has nothing to read."""
    from analytics_zoo_tpu.ops import ssd

    reader = _reader(manifest, "ssd_scan_roofline")
    cfg = manifest.configuration(CONFIG)
    peaks = manifest.peaks("TPU v5 lite")

    def least(flops, nbytes):
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])

    walk = cfg.module("ops").ssd_call_costs(2, cfg.sizes, 128)
    # both kernels are bound by their bytes at these widths
    assert least(*walk["forward"]) \
        == walk["forward"][1] / peaks["hbm_bytes_per_s"]
    ops = [
        # the forward walk at a fifth of its roofline, the backward at a
        # tenth, three layers each
        *(_kernel(f"jvp_jit__ssd_walk_forward__.{i}", 3, i * 1e8,
                  5 * least(*walk["forward"])) for i in range(3)),
        *(_kernel(f"transpose_jvp_jit__ssd_walk_backward___.{i}", 4,
                  (3 + i) * 1e8, 10 * least(*walk["backward"]))
          for i in range(3)),
        _kernel("jvp_jit__kda_walk_forward__.7", 3, 7e8, 1e-3),
        _kernel("_flash_bwd_kernel.10", 2, 8e8, 1e-3),
        _kernel("gmm.22", 1, 9e8, 1e-3),
        Event("fusion.1", 10e8, 1e6)]
    assert [reader.ssd_kernel(e.name) for e in ops] == \
        ["forward"] * 3 + ["backward"] * 3 + [None] * 4
    run = {"capture": Capture({PLANE: ops}, {}), "sizes": cfg.sizes,
           "configuration": cfg, "manifest": manifest,
           "traffic": {"batch": 2}, "device": {"kind": "TPU v5 lite"}}
    want = 100.0 * (least(*walk["forward"]) + least(*walk["backward"])) \
        / (5 * least(*walk["forward"]) + 10 * least(*walk["backward"]))
    assert 10.0 < want < 20.0
    assert reader.read(run, chunk=128) == pytest.approx(want)
    # the chunk comes from the program's own record where none is given
    monkeypatch.setattr(ssd, "chunk_schedules", [{"rule": "ssd",
                                                  "chunk": 128}])
    assert reader.read(run) == pytest.approx(want)
    monkeypatch.setattr(ssd, "chunk_schedules", [])
    assert reader.read(run) is None
    bare = {**run, "capture": Capture({PLANE: ops[-4:]}, {})}
    assert reader.read(bare, chunk=128) is None
    other = manifest.configuration("kimi-linear-48b-a3b")
    assert reader.read({**run, "configuration": other,
                        "sizes": other.sizes}, chunk=128) is None
    assert reader.read({**run, "capture": None}, chunk=128) is None


def _program(t):
    """One step program of 10 ms at ``t`` ms: the SSD walk 2 (scoped
    ``zoo.ssd`` inside ``zoo.mixer``), a projection's backward product 1.5
    (the same), the mixer's residual add 1 (``zoo.mixer`` alone), the
    feed-forward 3, a copy with no scope 0.5, idle the rest."""
    return [Event("ssd.1/tpu_custom_call/3", t * MS, 2 * MS, SSD_FWD),
            Event("fusion.2", (t + 2) * MS, 1.5 * MS, SSD_PROJ),
            Event("fusion.3", (t + 3.5) * MS, 1 * MS, MIXER),
            Event("fusion.4", (t + 4.5) * MS, 3 * MS, FFN),
            Event("copy-done.5", (t + 7.5) * MS, 0.5 * MS)]


def test_the_ssd_part_from_a_hand_built_trace(manifest):
    """``step_ssd_ms`` reads the operations whose scope holds ``zoo.ssd``
    by ``_parts.py``'s accounting: 3.5 ms of each 10 ms step program, the
    mixer's own add and everything else left out; silent without the
    scope or without step programs."""
    reader = _reader(manifest, "step_ssd_ms")
    programs = [Event("jit_train_step", t * MS, 10 * MS) for t in (0, 10)]
    ops = _program(0) + _program(10)
    run = {"capture": Capture({PLANE: ops}, {}),
           "step_modules": {PLANE: programs}}
    assert reader.read(run) == pytest.approx(3.5)
    # the same scopes without the SSD's name: nothing to read
    plain = [e._replace(scope=e.scope.replace("zoo.ssd/", ""))
             for e in ops]
    assert reader.read({"capture": Capture({PLANE: plain}, {}),
                        "step_modules": {PLANE: programs}}) is None
    assert reader.read({"capture": Capture({PLANE: ops}, {}),
                        "step_modules": {}}) is None
    # the program's name, spelled in the reader and not imported
    from analytics_zoo_tpu.metrics.tracing import SSD_SCOPE

    assert reader.SSD == SSD_SCOPE


def test_the_two_entries_and_their_cell():
    """The configuration, the cell and the two metrics are appended to
    ``BENCHMARK.json``; the metrics name the one cell whose program has the
    kernels and the scope, and the cell joins no earlier metric's list."""
    manifest = Manifest()
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "fit-b2-e8", 1)
    entries = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name, unit, layer in (("ssd_scan_roofline", "%", "kernels"),
                              ("step_ssd_ms", "ms", "model step")):
        assert entries[name]["workloads"] == [CELL]
        assert (entries[name]["unit"], entries[name]["layer"],
                entries[name]["moves"]) == (unit, layer,
                                            "train_examples_per_s")
    names = [m["name"] for m in manifest.per_layer(CELL)]
    assert "train_step_mfu" in names and "ssd_scan_roofline" in names
    assert not {"step_mixer_ms", "flash_attention_roofline",
                "kda_scan_roofline"} & set(names)
