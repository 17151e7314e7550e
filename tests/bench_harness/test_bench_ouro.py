"""The ``ouro-2.6b-fit`` cell through the harness at toy size on the CPU,
as ``test_bench_harness.py`` drives the other two: a sound run is correct;
a state left unchanged, half of the batch left out and the fp8 control in
the program's place are not.  Plus the configuration's file against the
published one and its operation count."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare, run as bench_run
from benchmark.manifest import ROOT, Manifest

CELL = "ouro-2.6b-fit"
TOY = dict(
    config_overrides={"num_hidden_layers": 2, "total_ut_steps": 3,
                      "hidden_size": 64, "n_embd": 64,
                      "num_attention_heads": 2, "num_key_value_heads": 2,
                      "n_head": 2, "head_dim": 32, "intermediate_size": 96,
                      "vocab_size": 128, "n_positions": 32},
    traffic_overrides={"batch": 8, "steps_per_epoch": 2})
SEED = 2**31 + 79     # the driver's seeds pass 32 signed bits


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
    """The loss is taken inside the model: the fault is planted where a
    pass's cost is, the mean taken over the first half of the batch."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import LoopedDecoder

    tail = LoopedDecoder._exit_tail

    def tail_of_half(self, params, s, targets, log_survive, last):
        n = s.shape[0] // 2
        cost, mass, ce, survive = tail(self, params, s[:n], targets[:n],
                                       log_survive[:n], last)
        return cost, mass, ce, jax.numpy.concatenate(
            [survive, log_survive[n:]])

    monkeypatch.setattr(LoopedDecoder, "_exit_tail", tail_of_half)
    result, table = _run_toy(tmp_path)
    assert result["correct"] is False, table


def test_lower_precision_control_is_not_correct():
    """The reference in the program's place computed in fp8, at toy size,
    under the cell's own limits, through the cell's own ``follow``."""
    from benchmark import data
    from benchmark.narrow import CONTROL

    manifest = Manifest()
    cfg = manifest.configuration("ouro-2.6b", TOY["config_overrides"])
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
    # the lean follow gives what fit.py's gives
    stock = fit.fit.follow(reference, cfg.sizes, key, batches)
    assert stock["losses"] == ref["losses"]
    for k in ("grad_norms", "delta_norms"):
        np.testing.assert_array_equal(stock[k], ref[k])
    assert stock["names"] == ref["names"]


def test_the_file_holds_every_published_number_but_the_depth():
    with open(os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b",
                           "config.json")) as fh:
        sizes = json.load(fh)
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000, "total_ut_steps": 4,
        "early_exit_threshold": 1, "vocab_size": 49152,
        "num_hidden_layers": 48}
    differ = [k for k, v in published.items() if sizes[k] != v]
    assert differ == sizes["reduced"] == ["num_hidden_layers"]
    assert sizes["num_hidden_layers"] >= 6
    assert sizes["published"]["num_hidden_layers"] == 48
    assert len(sizes["layer_types"]) == 48
    assert (sizes["n_head"], sizes["n_embd"]) == (16, 2048)


def test_operation_count_against_the_parameter_tree():
    cfg = Manifest().configuration("ouro-2.6b")
    sizes, ops = cfg.sizes, cfg.module("ops")
    reference = cfg.module("reference")
    core = jax.eval_shape(lambda k: reference.init_params(k, sizes),
                          jax.random.PRNGKey(0))[reference.CORE]
    n_all = sum(int(np.prod(p.shape))
                for p in jax.tree_util.tree_leaves(core))
    # a token is multiplied by every matrix: all but the looked-up
    # embedding, the norms' gains and the gate
    n_matmul = n_all - int(np.prod(core["tok_embed"].shape)) \
        - sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(core)
              if len(p.shape) == 1) - int(np.prod(core["exit_kernel"].shape))
    assert ops.matmul_params_per_pass(sizes) == n_matmul
    t, s, d = sizes["total_ut_steps"], sizes["n_positions"], \
        sizes["hidden_size"]
    forward = 2 * t * (n_matmul * s
                       + sizes["num_hidden_layers"] * 2 * s * s * d * 0.5)
    assert ops.train_flops_per_example(sizes) == pytest.approx(3 * forward)
    # the issue's figure at 8 layers, and the head's share at this depth
    assert ops.train_flops_per_example({**sizes, "num_hidden_layers": 8}) \
        == pytest.approx(56.9e12, rel=2e-3)
    assert d * sizes["vocab_size"] / n_matmul == pytest.approx(0.246,
                                                               abs=1e-3)
