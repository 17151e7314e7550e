"""chip_smoke.py on the CPU: its phase functions at toy size on the test
mesh, and the contract that without a TPU it fails and prints no result.
Plus the three behaviours the chip run leans on that nothing else pins:
the v5e peak row, an unknown TPU kind, and a compile cache placed from
outside."""

import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Phase 2 at toy size: a ResNet-8 at 32 px, 10 classes."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.models.resnet import ResNet
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        BatchNormalization,
    )

    out_dir = str(tmp_path_factory.mktemp("smoke"))
    ctx = zoo.init_zoo_context(seed=0)
    model = ResNet.cifar(depth=8, classes=10)
    # the serve phase first lets the BatchNorm statistics settle: at the
    # layer's own momentum of 0.99 that is a few hundred steps, which the
    # chip takes in seconds and eight virtual devices do not
    for layer in model.layers:
        if isinstance(layer, BatchNormalization):
            layer.momentum = 0.9
    model.compile(optimizer=ResNet.imagenet_optimizer(batch_size=2),
                  loss="sparse_categorical_crossentropy")
    report = chip_smoke.phase_train(
        model, out_dir, image_size=32, classes=10, per_chip_batch=2,
        warmup_batches=1, steps=2, first_loss_range=(1.0, 6.0),
        n_eval=16)
    return model, out_dir, report, ctx


def test_train_phase(trained):
    _model, _out, report, ctx = trained
    assert report["batch"] == 2 * ctx.data_parallel_size
    assert len(report["losses"]) == 3
    assert report["compiles_timed"] == 0
    assert report["predict_shape"] == [16, 10]


def test_serve_phase(trained):
    model, out_dir, _report, _ctx = trained
    report = chip_smoke.phase_serve(
        model, out_dir, image_size=32, classes=10, per_chip_batch=2,
        settle_batches=4, settle_epochs=8, n_records=8, batch_size=2,
        timeout_s=60.0)
    assert report["answered"] == 8
    # the answers tell the records apart: each lies by far nearest its
    # own record's reference
    assert report["distinct_top1"] > 1
    for ref in ("against_float32_predict", "against_predict_as_configured"):
        got = report[ref]   # the CPU context is float32: the same twice
        assert got["top1_equal"] == 8
        assert got["logprob_rms_own_max"] < 1e-3
        assert got["logprob_rms_other_min"] > chip_smoke.SERVE_LOGPROB_RMS
    assert report["warmup_buckets"] == [1, 2]
    # no *-ClusterServing directory outside the phase's own output
    assert os.path.isdir(os.path.join(out_dir, "serving_logs"))


def test_transformer_phase(tmp_path):
    import analytics_zoo_tpu as zoo

    ctx = zoo.init_zoo_context(seed=0)
    report = chip_smoke.phase_transformer(
        str(tmp_path), blocks=2, hidden=32, heads=2, seq=16, vocab=64,
        batch=ctx.data_parallel_size, steps=2, expect_pallas=False)
    assert len(report["losses"]) == 2
    # ln(64) = 4.16 for an untrained 64-way model
    assert 3.0 < report["losses"][0] < 6.0


def test_mesh_phase_on_the_virtual_devices(tmp_path):
    """The ``--chips 4`` path, which the driver never runs: here across
    all eight virtual devices against one of them."""
    import jax

    from analytics_zoo_tpu.models.resnet import ResNet

    def make_model():
        model = ResNet.cifar(depth=8, classes=10)
        model.compile(optimizer=ResNet.imagenet_optimizer(batch_size=8),
                      loss="sparse_categorical_crossentropy")
        return model

    chips = len(jax.devices())
    report = chip_smoke.phase_mesh(
        make_model, str(tmp_path), chips=chips, image_size=32, classes=10,
        global_batch=chips, steps=2, fsdp_steps=1)
    assert report["batch_on_devices"] == list(range(chips))
    assert report["fsdp_params_on_devices"] == list(range(chips))
    assert report["max_rel_diff"] < 1e-5  # f32 on the CPU
    # conv kernels (3, 3, Cin, Cout) shard on Cin: 1/8 plus the few leaves
    # that eight devices cannot divide
    assert report["per_chip_bytes"]["ratio"] < 0.2


def test_kernels_phase_compares_every_kernel():
    report = chip_smoke.phase_kernels(
        flash_shape=(1, 2, 128, 64), xent_shape=(16, 256),
        int8_shape=(8, 128, 128), adam_shapes=((3, 3, 8, 8), (256,)),
        adam_updates=2, expect_pallas=False)
    assert {"flash_fwd", "flash_dq", "flash_dk", "flash_dv", "xent_fwd",
            "xent_grad", "int8_matmul", "adam_leaf0", "adam_leaf1"} \
        <= set(report["errors"])
    # on the CPU every kernel is its reference
    assert all(c["pallas"] == 0 for c in report["invocations"].values())


def test_expect_pallas_fails_on_the_reference_route():
    """What the chip run asserts: a kernel that answered through its
    reference fails the phase."""
    with pytest.raises(AssertionError, match="routing"):
        chip_smoke.phase_kernels(
            flash_shape=(1, 1, 128, 64), xent_shape=(8, 128),
            int8_shape=(8, 128, 128), adam_shapes=((128,),),
            adam_updates=1, expect_pallas=True)


def test_without_a_tpu_the_script_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_v5e_reports_itself_as_v5_lite():
    from analytics_zoo_tpu.analysis.costmodel import resolve_peaks

    peaks = resolve_peaks("tpu", "TPU v5 lite")
    assert peaks.source == "tpu-v5e"
    assert peaks.hbm_bytes == float(16 << 30)


def test_unknown_tpu_kind_is_an_error():
    from analytics_zoo_tpu.analysis.costmodel import resolve_peaks

    with pytest.raises(ValueError, match="TPU v9"):
        resolve_peaks("tpu", "TPU v9")
    with pytest.raises(ValueError, match="no peak table row"):
        resolve_peaks("tpu")


def test_cache_placed_from_outside_wins(tmp_path, monkeypatch):
    import jax

    from analytics_zoo_tpu.common import compile_cache

    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.setenv("ZOO_COMPILE_CACHE", str(tmp_path / "zoo"))
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.maybe_enable_persistent_cache(
            str(tmp_path / "explicit"))
        assert got == outside == compile_cache.cache_dir()
        # JAX's own setting is left as JAX read it: nothing re-pointed
        assert jax.config.jax_compilation_cache_dir == before
        assert not os.path.exists(tmp_path / "zoo")
        assert not os.path.exists(tmp_path / "explicit")
    finally:
        compile_cache.disable_persistent_cache()
    assert jax.config.jax_compilation_cache_dir == before
