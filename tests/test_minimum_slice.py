"""Minimum end-to-end slice (SURVEY.md §7 step 4): LeNet-style models via
Sequential + compile/fit on a CPU mesh — the analogue of the reference's
test_simple_integration.py (pyzoo/test/zoo/pipeline/api/test_simple_integration.py)."""

import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras import Input, Model, Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import (
    Convolution2D,
    Dense,
    Dropout,
    Flatten,
    MaxPooling2D,
)


def make_blobs(n=512, dim=12, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(classes, dim)) * 3.0
    y = rng.integers(0, classes, size=n)
    x = centers[y] + rng.normal(size=(n, dim))
    return x.astype(np.float32), y.astype(np.int32)


def test_mlp_fit_learns(zoo_ctx):
    x, y = make_blobs()
    model = Sequential()
    model.add(Dense(32, activation="relu", input_shape=(12,)))
    model.add(Dropout(0.1))
    model.add(Dense(4, activation="softmax"))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y, batch_size=64, nb_epoch=8)
    results = model.evaluate(x, y, batch_size=64)
    assert results["accuracy"] > 0.9, results
    # fit must actually reduce loss
    hist = model._estimator.history
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_lenet_conv_fit(zoo_ctx):
    rng = np.random.default_rng(1)
    n = 256
    x = rng.normal(size=(n, 12, 12, 1)).astype(np.float32)
    # learnable rule: class = quadrant with the largest mean intensity
    q = np.stack([
        x[:, :6, :6, 0].mean(axis=(1, 2)),
        x[:, :6, 6:, 0].mean(axis=(1, 2)),
        x[:, 6:, :6, 0].mean(axis=(1, 2)),
        x[:, 6:, 6:, 0].mean(axis=(1, 2)),
    ], axis=1)
    y = np.argmax(q, axis=1).astype(np.int32)

    model = Sequential()
    model.add(Convolution2D(8, 3, 3, activation="relu",
                            input_shape=(12, 12, 1)))
    model.add(MaxPooling2D())
    model.add(Flatten())
    model.add(Dense(32, activation="relu"))
    model.add(Dense(4, activation="softmax"))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y, batch_size=32, nb_epoch=15)
    results = model.evaluate(x, y, batch_size=32)
    assert results["accuracy"] > 0.8, results


def test_functional_model_multi_input(zoo_ctx):
    from analytics_zoo_tpu.pipeline.api.keras import merge

    n = 256
    rng = np.random.default_rng(2)
    a = rng.normal(size=(n, 8)).astype(np.float32)
    b = rng.normal(size=(n, 8)).astype(np.float32)
    y = (np.sum(a * b, axis=1) > 0).astype(np.float32)[:, None]

    ia, ib = Input(shape=(8,)), Input(shape=(8,))
    h = merge([ia, ib], mode="mul")
    h = Dense(16, activation="relu")(h)
    out = Dense(1, activation="sigmoid")(h)
    model = Model([ia, ib], out)
    model.compile(optimizer="adam", loss="binary_crossentropy",
                  metrics=["binary_accuracy"])
    model.fit([a, b], y, batch_size=32, nb_epoch=30)
    results = model.evaluate([a, b], y, batch_size=32)
    assert results["binary_accuracy"] > 0.85, results


def test_predict_shapes_and_padding(zoo_ctx):
    x, y = make_blobs(n=130)  # not a multiple of 8 devices
    model = Sequential()
    model.add(Dense(4, activation="softmax", input_shape=(12,)))
    model.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    preds = model.predict(x, batch_size=64)
    assert preds.shape == (130, 4)
    np.testing.assert_allclose(preds.sum(axis=1), 1.0, rtol=1e-4)


def test_save_load_roundtrip(zoo_ctx, tmp_path):
    x, y = make_blobs(n=128)
    model = Sequential()
    model.add(Dense(16, activation="relu", input_shape=(12,)))
    model.add(Dense(4, activation="softmax"))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    model.fit(x, y, batch_size=32, nb_epoch=2)
    p1 = model.predict(x, batch_size=32)

    path = str(tmp_path / "model.zoo")
    model.save(path)
    from analytics_zoo_tpu.pipeline.api.keras import KerasNet

    loaded = KerasNet.load(path)
    p2 = loaded.predict(x, batch_size=32)
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-6)


def test_evaluate_padding_unbiased(zoo_ctx):
    """Padded rows in the last eval batch must not bias loss/metrics."""
    x, y = make_blobs(n=130)  # 130 % 64 = 2 → last batch padded to 8
    model = Sequential()
    model.add(Dense(4, activation="softmax", input_shape=(12,)))
    model.compile(optimizer="sgd", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    res = model.evaluate(x, y, batch_size=64)

    # manual reference with numpy
    probs = model.predict(x, batch_size=64)
    eps = 1e-7
    ll = -np.log(np.clip(probs[np.arange(130), y], eps, 1.0))
    acc = float(np.mean(np.argmax(probs, -1) == y))
    np.testing.assert_allclose(res["loss"], ll.mean(), rtol=1e-4)
    np.testing.assert_allclose(res["accuracy"], acc, rtol=1e-6)


def test_fit_with_validation(zoo_ctx):
    x, y = make_blobs(n=256)
    model = Sequential()
    model.add(Dense(16, activation="relu", input_shape=(12,)))
    model.add(Dense(4, activation="softmax"))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y, batch_size=64, nb_epoch=5,
              validation_data=(x[:100], y[:100]))
    # model usable after training with validation enabled (no deleted
    # donated buffers)
    preds = model.predict(x[:10], batch_size=64)
    assert preds.shape == (10, 4)


def test_duplicate_layer_names_rejected(zoo_ctx):
    a = Sequential()
    a.add(Dense(4, input_shape=(3,)))
    b = Sequential()
    b.add(Dense(4, input_shape=(3,)))
    c = Sequential()
    c.add(a.layers[0])
    with pytest.raises(ValueError, match="duplicate layer names"):
        c.add(b.layers[0])


def test_summary_runs(zoo_ctx):
    model = Sequential()
    model.add(Dense(16, input_shape=(12,)))
    model.add(Dense(4))
    text = model.summary()
    assert "Total params" in text


def test_consecutive_fits_both_train(zoo_ctx):
    """Each fit() call must train nb_epoch MORE epochs (Keras semantics).
    Regression: MaxEpoch was absolute, so a second fit(nb_epoch=1) trained
    zero steps — which would have silently voided warm-up + timed benchmark
    patterns."""
    import numpy as np

    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)
    model = Sequential()
    model.add(Dense(2, activation="softmax", input_shape=(8,)))
    model.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    model.fit(x, y, batch_size=16, nb_epoch=1)
    est = model._estimator
    steps_after_first = est.global_step
    assert steps_after_first == 4
    model.fit(x, y, batch_size=16, nb_epoch=1)
    assert est.global_step == 2 * steps_after_first, (
        "second fit() trained zero steps")
