"""Everything is found by the names in BENCHMARK.json, and a cell, a
configuration and a per-layer metric are added as files and entries
alone."""

import json
import os
import shutil

import pytest

from benchmark.manifest import ROOT, Manifest


def test_every_named_thing_is_found():
    manifest = Manifest()
    doc = manifest.doc
    for cell in doc["workloads"]:
        cfg = manifest.configuration(cell["config"])
        assert cfg.sizes["name"] == cell["config"]
        for module in ("model", "ops", "reference"):
            assert cfg.module(module) is not None
        traffic = manifest.traffic(cell["traffic"])
        assert hasattr(manifest.job(traffic["job"]), "Job")
        assert set(manifest.limits(cell["name"])) <= {
            "loss_gap", "grad_gap", "grad_gap_median", "delta_gap",
            "delta_gap_median", "grad_diff", "grad_diff_median",
            "grad_diff_whole"}
        assert [m["name"] for m in manifest.end_to_end(cell["name"])] \
            == ["train_examples_per_s", "setup_s"]
    cells = [cell["name"] for cell in doc["workloads"]]
    for metric in doc["per_layer"]:
        assert callable(manifest.reader(metric["name"]))
        if "workloads" not in metric:
            continue
        # a list names cells, and a cell reports the entry if and only if
        # the list names it
        assert set(metric["workloads"]) <= set(cells), metric
        for cell in cells:
            reported = {m["name"] for m in manifest.per_layer(cell)}
            assert (metric["name"] in reported) \
                == (cell in metric["workloads"]), (metric["name"], cell)
    for entry in doc["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as fh:
            sizes = json.load(fh)
        assert sizes["reduced"] == entry["reduced"]
        assert sizes["source"] == entry["source"]
    with pytest.raises(KeyError):
        manifest.peaks("TPU v9 imaginary")
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    # a made-up configuration, traffic mix, cell and per-layer metric
    new_cfg = root / "benchmark" / "configs" / "gpt2-tiny"
    shutil.copytree(root / "benchmark" / "configs" / "gpt2-small", new_cfg)
    sizes = json.loads((new_cfg / "config.json").read_text())
    sizes.update(name="gpt2-tiny", n_layer=2)
    (new_cfg / "config.json").write_text(json.dumps(sizes))
    (root / "benchmark" / "traffic" / "fit-b4-e2.json").write_text(
        json.dumps({"job": "fit", "batch": 4, "steps_per_epoch": 2,
                    "check_steps": 3}))
    (root / "benchmark" / "limits" / "gpt2-tiny-fit.json").write_text(
        json.dumps({"limits": {"loss_gap": 1e-3, "grad_gap": 1e-2,
                               "delta_gap": 1e-2}}))
    (root / "benchmark" / "layer_metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return float(run['window']['steps'])\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({
        "name": "gpt2-tiny", "source": "made up", "reduced": ["n_layer"],
        "file": "benchmark/configs/gpt2-tiny/config.json", "why": "test"})
    doc["workloads"].append({
        "name": "gpt2-tiny-fit", "config": "gpt2-tiny",
        "traffic": "fit-b4-e2", "chips": 1, "why": "test"})
    doc["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "step loop",
        "moves": "train_examples_per_s", "workloads": ["gpt2-tiny-fit"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    manifest = Manifest(str(root))
    cell = manifest.cell("gpt2-tiny-fit")
    cfg = manifest.configuration(cell["config"])
    assert cfg.sizes["n_layer"] == 2 and cfg.module("ops").matmul_params
    assert manifest.traffic(cell["traffic"])["batch"] == 4
    assert manifest.limits("gpt2-tiny-fit")["loss_gap"] == 1e-3
    names = [m["name"] for m in manifest.per_layer("gpt2-tiny-fit")]
    assert "steps_in_window" in names and "device_idle_pct" in names
    assert "flash_attention_roofline" not in names
    assert manifest.reader("steps_in_window")({"window": {"steps": 7}}) == 7
    assert "steps_in_window" not in [
        m["name"] for m in manifest.per_layer("gpt2-small-fit")]
    # no file that was there changed
    assert all(p.read_bytes() == data for p, data in before.items())
