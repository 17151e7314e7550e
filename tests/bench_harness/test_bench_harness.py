"""The harness end to end at toy size on the CPU, with the look for a chip
skipped: a sound run is correct, and is not with the timed path broken
underneath or with the lower-precision control in the program's place.
Plus the exits a run has to take without a chip or without the program."""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import compare, run as bench_run
from benchmark.manifest import ROOT, Manifest

TOY = {
    "resnet50-fit": dict(
        config_overrides={"stage_blocks": [1, 1, 1, 1], "image_size": 32,
                          "num_classes": 10},
        traffic_overrides={"batch": 16, "steps_per_epoch": 2}),
    "gpt2-small-fit": dict(
        config_overrides={"n_layer": 2, "n_embd": 32, "n_head": 2,
                          "n_inner": 128, "n_positions": 16, "n_ctx": 16,
                          "vocab_size": 50},
        traffic_overrides={"batch": 16, "steps_per_epoch": 2}),
}
CELLS = sorted(TOY)
SEED = 2**31 + 77     # the driver's seeds pass 32 signed bits


def _run_toy(cell, scratch, trace=False):
    return bench_run.run_cell(
        Manifest(), cell, SEED, 0.5, trace, require_tpu=False,
        scratch=str(scratch), **TOY[cell])


@pytest.fixture
def toy(monkeypatch, tmp_path):
    """run_cell at toy size; ResNet-50 keeps its blocks and widths and
    loses depth (the test steers the program's stage table, as the
    configuration's ``stage_blocks`` steers the reference)."""
    from analytics_zoo_tpu.models import resnet

    monkeypatch.setitem(resnet._STAGES, 50, ("bottleneck", (1, 1, 1, 1)))
    return lambda cell, trace=False: _run_toy(cell, tmp_path, trace)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One sound run of each cell, shared by the tests that only read it."""
    from analytics_zoo_tpu.models import resnet

    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(resnet._STAGES, 50, ("bottleneck", (1, 1, 1, 1)))
        return {cell: _run_toy(cell, tmp_path_factory.mktemp(cell))
                for cell in CELLS}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(sound, cell):
    result, table = sound[cell]
    assert result["correct"] is True, table
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    # float32 against float32 on the CPU: far inside the chip's limits
    assert all(row["value"] < 0.5 * row["limit"]
               for row in table.values()), table
    assert result["device"]["platform"] == "cpu"


def test_traced_run_reports_the_layers_it_can_read(toy):
    result, _table = toy("gpt2-small-fit", trace=True)
    # no device plane on the CPU: the trace's readers return nothing, no
    # share of a peak is printed for a CPU, and the readers that time the
    # host against a chip (``layer_metrics/_chip.py``) stay silent
    printed = result["metrics"]
    assert {"data_wait_ms_per_step", "dispatch_ms_per_step", "compile_s",
            "compiles_in_window"} <= set(printed)
    assert printed["step_lower_s"]["value"] > 0
    entries = {m["name"]: m for m in Manifest().doc["per_layer"]}
    home = os.path.join(ROOT, "benchmark", "layer_metrics")
    for name, metric in printed.items():
        assert metric["unit"] != "%", name
        assert entries[name]["source"] != "device_trace", name
        with open(os.path.join(home, name + ".py")) as fh:
            assert '"_chip"' not in fh.read(), name
    assert printed["compiles_in_window"]["value"] == 0
    assert result["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_is_not_correct(toy, cell, monkeypatch):
    from analytics_zoo_tpu.pipeline.estimator import Estimator

    train = Estimator.train

    def train_and_forget(self, *args, **kwargs):
        params = jax.tree_util.tree_map(np.asarray, self.model.params)
        out = train(self, *args, **kwargs)
        self.model.params = jax.tree_util.tree_map(jax.numpy.asarray, params)
        return out

    monkeypatch.setattr(Estimator, "train", train_and_forget)
    result, table = toy(cell)
    assert result["correct"] is False
    assert table["delta_gap_median"]["value"] > 0.9   # nothing moved


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(toy, cell, monkeypatch):
    from analytics_zoo_tpu.pipeline.api.keras.objectives import LossFunction

    def mean_over_half(self, y_true, y_pred, sample_weight=None):
        per_sample = self(y_true, y_pred)
        return per_sample[: per_sample.shape[0] // 2].mean()

    monkeypatch.setattr(LossFunction, "mean", mean_over_half)
    result, table = toy(cell)
    assert result["correct"] is False, table


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_is_not_correct(cell, monkeypatch):
    """The reference in the program's place computed in fp8, at toy size,
    under the cell's own limits."""
    from analytics_zoo_tpu.models import resnet
    from benchmark import data
    from benchmark.narrow import CONTROL as CONTROL_DTYPE

    monkeypatch.setitem(resnet._STAGES, 50, ("bottleneck", (1, 1, 1, 1)))
    manifest = Manifest()
    entry = manifest.cell(cell)
    cfg = manifest.configuration(entry["config"],
                                 TOY[cell]["config_overrides"])
    fit = manifest.job("fit")
    x, y = data.rows(SEED, fit.CHECK_ROWS, 3 * 16, cfg.sizes)
    batches = [(x[i:i + 16], y[i:i + 16]) for i in (0, 16, 32)]
    key = fit.seed_key(SEED)
    reference = cfg.module("reference")
    ref = fit.follow(reference, cfg.sizes, key, batches)
    control = fit.follow(reference, cfg.sizes, key, batches,
                         round_to=CONTROL_DTYPE)
    correct, table = compare.verdict(
        compare.compare(control, ref, ref["names"]), manifest.limits(cell))
    assert correct is False, table


def test_the_control_rounds_both_passes():
    """fp8 forward and backward: a tensor a layer hands on is rounded and
    so is its cotangent; a parameter is rounded where it is used and its
    gradient is not."""
    from benchmark import narrow

    q, qw = narrow.rounders(narrow.CONTROL)
    x = np.linspace(0.011, 1.0, 90, dtype=np.float32)
    ct = np.linspace(1.0, 1.013, 90, dtype=np.float32)
    for f, rounds_back in ((q, True), (qw, False)):
        out, back = jax.vjp(f, x)
        # e4m3 scaled to the tensor: at most 2 ** 4 values to a binade
        assert len(np.unique(np.asarray(out))) < 60
        assert float(np.max(np.abs(np.asarray(out) - x) / x)) < 2.0 ** -4
        (got,) = back(ct)
        assert (len(np.unique(np.asarray(got))) < 5) is rounds_back
    same, also = narrow.rounders(None)
    assert same(x) is x and also(x) is x


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=cwd, env={**os.environ, **env}, capture_output=True, text=True,
        timeout=300)


ARGS = ["--workload", "resnet50-fit", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    done = _run(ARGS, ROOT, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    assert done.returncode == 2, done.stderr[-2000:]
    assert "needs 1 TPU chip" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_without_the_program_the_run_fails_and_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    done = _run(ARGS, str(tmp_path), JAX_PLATFORMS="cpu", PYTHONPATH="")
    assert done.returncode != 0
    assert "analytics_zoo_tpu" in done.stderr
    assert done.stdout.strip() == ""


def test_the_result_line_is_the_contracts(sound):
    result, _table = sound["gpt2-small-fit"]
    line = json.loads(json.dumps(result))
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
