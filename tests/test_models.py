"""Model-zoo tests — each model trains on a tiny learnable task, the
analogue of the reference's model specs (e.g. NeuralCFSpec, KNRMSpec,
AnomalyDetectorSpec under zoo/src/test)."""

import numpy as np
import pytest


def test_lenet_builds_and_fits(zoo_ctx):
    from analytics_zoo_tpu.models import build_lenet

    model = build_lenet()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 28, 28, 1)).astype(np.float32)
    y = (x[:, :14].mean(axis=(1, 2, 3)) >
         x[:, 14:].mean(axis=(1, 2, 3))).astype(np.int32)
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y, batch_size=32, nb_epoch=25)
    assert model.evaluate(x, y, batch_size=32)["accuracy"] > 0.85


def test_resnet_cifar_trains(zoo_ctx):
    from analytics_zoo_tpu.models import ResNet

    model = ResNet.cifar(depth=8, classes=4)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 4, size=(64,)).astype(np.int32)
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    model.fit(x, y, batch_size=16, nb_epoch=2)
    hist = model._estimator.history
    assert hist[-1]["loss"] < hist[0]["loss"]  # memorizing random labels


def test_neural_cf_learns_and_recommends(zoo_ctx):
    from analytics_zoo_tpu.models import NeuralCF

    n_users, n_items = 30, 40
    rng = np.random.default_rng(2)
    users = rng.integers(0, n_users, size=(2048,))
    items = rng.integers(0, n_items, size=(2048,))
    # learnable rule: like if (user + item) even
    labels = ((users + items) % 2 == 0).astype(np.int32)

    ncf = NeuralCF(n_users, n_items, class_num=2, user_embed=8, item_embed=8,
                   hidden_layers=(16, 8), mf_embed=8)
    ncf.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                metrics=["accuracy"])
    ncf.fit([users, items], labels, batch_size=128, nb_epoch=20)
    res = ncf.evaluate([users, items], labels, batch_size=128)
    assert res["accuracy"] > 0.9, res

    recs = ncf.recommend_for_user(3, np.arange(n_items), max_items=5)
    assert len(recs) == 5
    # top recommendations should be items with (3+item) even
    assert all((3 + item) % 2 == 0 for item, _ in recs[:3])


def test_wide_and_deep(zoo_ctx):
    from analytics_zoo_tpu.models import (
        ColumnFeatureInfo,
        WideAndDeep,
        to_wide_deep_features,
    )

    info = ColumnFeatureInfo(
        wide_base_cols=["gender"], wide_base_dims=[2],
        embed_cols=["occupation"], embed_in_dims=[10], embed_out_dims=[4],
        continuous_cols=["age"],
    )
    rng = np.random.default_rng(3)
    n = 1024
    rows = {
        "gender": rng.integers(0, 2, n),
        "occupation": rng.integers(0, 10, n),
        "age": rng.normal(size=n).astype(np.float32),
    }
    # rule: positive iff (occupation<5) xor age>0 — both features reach the
    # deep arm, so the MLP can express the interaction
    labels = ((rows["occupation"] < 5) ^ (rows["age"] > 0)).astype(np.int32)
    feats = to_wide_deep_features(rows, info)
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    wnd = WideAndDeep(class_num=2, column_info=info, hidden_layers=(16, 8))
    wnd.compile(optimizer=Adam(lr=0.01),
                loss="sparse_categorical_crossentropy",
                metrics=["accuracy"])
    wnd.fit(feats, labels, batch_size=128, nb_epoch=25)
    res = wnd.evaluate(feats, labels, batch_size=128)
    assert res["accuracy"] > 0.9, res


def test_session_recommender(zoo_ctx):
    from analytics_zoo_tpu.models import SessionRecommender

    n_items = 20
    rng = np.random.default_rng(4)
    sess = rng.integers(1, n_items, size=(512, 4))
    labels = sess[:, -1]  # predict the last item seen

    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    sr = SessionRecommender(n_items, item_embed=16, rnn_hidden_layers=(16,),
                            session_length=4)
    sr.compile(optimizer=Adam(lr=0.01),
               loss="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    sr.fit(sess, labels, batch_size=64, nb_epoch=30)
    res = sr.evaluate(sess, labels, batch_size=64)
    assert res["accuracy"] > 0.9, res
    recs = sr.recommend_for_session(sess[:3], max_items=3)
    assert len(recs) == 3 and len(recs[0]) == 3


def test_text_classifier_cnn(zoo_ctx):
    from analytics_zoo_tpu.models import TextClassifier

    rng = np.random.default_rng(5)
    x = rng.integers(0, 50, size=(512, 20))
    y = (np.sum(x == 7, axis=1) > 0).astype(np.int32)  # contains token 7

    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    tc = TextClassifier(class_num=2, token_length=16, sequence_length=20,
                        encoder="cnn", encoder_output_dim=32, vocab_size=50)
    tc.compile(optimizer=Adam(lr=0.01),
               loss="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    tc.fit(x, y, batch_size=64, nb_epoch=25)
    assert tc.evaluate(x, y, batch_size=64)["accuracy"] > 0.9


def test_anomaly_detector(zoo_ctx):
    from analytics_zoo_tpu.models import AnomalyDetector

    t = np.arange(600, dtype=np.float32)
    series = np.sin(t / 10.0)
    series[450] = 5.0  # planted anomaly
    x, y = AnomalyDetector.unroll(series, 10)
    ad = AnomalyDetector(feature_shape=(10, 1), hidden_layers=(8, 8),
                         dropouts=(0.0, 0.0))
    ad.compile(optimizer="adam", loss="mse")
    ad.fit(x, y, batch_size=64, nb_epoch=10)
    preds = np.asarray(ad.predict(x, batch_size=64)).reshape(-1)
    flagged = ad.detect_anomalies(y, preds, anomaly_size=3)
    anomaly_idx = [i for i, (_, _, a) in enumerate(flagged) if a]
    assert any(abs(i - 440) < 12 for i in anomaly_idx), anomaly_idx[:5]


def test_knrm_ranking(zoo_ctx):
    from analytics_zoo_tpu.models import KNRM
    from analytics_zoo_tpu.pipeline.api.keras.objectives import RankHinge

    rng = np.random.default_rng(6)
    vocab, lq, ld = 30, 4, 6
    n_pairs = 256
    # positive doc contains the query tokens, negative doc is random
    q = rng.integers(1, vocab, size=(n_pairs, lq))
    pos = np.concatenate([q, rng.integers(1, vocab, (n_pairs, ld - lq))], 1)
    neg = rng.integers(1, vocab, size=(n_pairs, ld))
    # interleave (pos, neg) pairs for RankHinge
    qs = np.repeat(q, 2, axis=0)
    ds = np.empty((2 * n_pairs, ld), dtype=np.int64)
    ds[0::2], ds[1::2] = pos, neg
    labels = np.zeros((2 * n_pairs, 1), np.float32)

    knrm = KNRM(lq, ld, vocab_size=vocab, embed_size=16)
    knrm.compile(optimizer="adam", loss=RankHinge())
    knrm.fit([qs, ds], labels, batch_size=64, nb_epoch=10)
    s_pos = np.asarray(knrm.predict([q, pos], batch_size=64)).reshape(-1)
    s_neg = np.asarray(knrm.predict([q, neg], batch_size=64)).reshape(-1)
    assert (s_pos > s_neg).mean() > 0.9

    ndcg = knrm.ndcg([[1, 0]], [[2.0, 1.0]], k=2)
    assert ndcg == 1.0


def test_seq2seq_copy_task(zoo_ctx):
    from analytics_zoo_tpu.models import Seq2seq

    rng = np.random.default_rng(7)
    vocab, le, ld = 12, 5, 5
    n = 512
    enc = rng.integers(2, vocab, size=(n, le))
    # target: copy the input sequence; decoder input is shifted (teacher)
    dec_in = np.concatenate([np.ones((n, 1), np.int64), enc[:, :-1]], 1)
    target = enc

    model = Seq2seq(vocab_size=vocab, embed_dim=16, hidden_sizes=(32,))
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model

    e_in = Input(shape=(le,), name="enc_in")
    d_in = Input(shape=(ld,), name="dec_in")
    out = model([e_in, d_in])
    net = Model([e_in, d_in], out)
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    net.compile(optimizer=Adam(lr=0.01),
                loss="sparse_categorical_crossentropy",
                metrics=["accuracy"])
    net.fit([enc, dec_in], target, batch_size=64, nb_epoch=30)
    res = net.evaluate([enc, dec_in], target, batch_size=64)
    assert res["accuracy"] > 0.8, res

    # greedy inference emits the copy
    toks = model.infer(net.params[model.name], enc[:4], start_sign=1,
                       max_len=le)
    assert (toks == enc[:4]).mean() > 0.5


class TestInceptionV1:
    def test_shapes_and_param_count(self):
        from analytics_zoo_tpu.models.inception import Inception

        import jax

        net = Inception.v1(classes=1000)
        # shapes alone: traced, neither drawn nor run
        params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree_util.tree_leaves(params))
        # GoogLeNet no-aux has ~7.0M params (6.99M conv/fc + biases)
        assert 6.5e6 < n_params < 7.5e6, n_params
        x = jax.ShapeDtypeStruct((2, 224, 224, 3), np.float32)
        out, _ = jax.eval_shape(
            lambda p, x: net.forward(p, x, state=net.init_state()), params, x)
        assert out.shape == (2, 1000)

    def test_trains_on_tiny_task(self):
        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.models.inception import Inception

        init_zoo_context(seed=0)
        net = Inception.v1(classes=2, input_shape=(64, 64, 3),
                           has_dropout=False)
        rng = np.random.default_rng(0)
        n = 32
        x = np.zeros((n, 64, 64, 3), np.float32)
        y = rng.integers(0, 2, size=(n,)).astype(np.int32)
        x[np.arange(n), :, :, 0] += y[:, None, None] * 1.0
        net.compile(optimizer="adam",
                    loss="sparse_categorical_crossentropy",
                    metrics=["accuracy"])
        net.fit(x, y, batch_size=8, nb_epoch=8)
        res = net.evaluate(x, y, batch_size=8)
        assert res["accuracy"] > 0.8, res


def test_resnet_space_to_depth_stem(zoo_ctx):
    """The TPU stem variant: same downstream network, same output shape,
    trains; stem kernel is 4x4x12 instead of 7x7x3."""
    import jax

    from analytics_zoo_tpu.models.resnet import ResNet

    net = ResNet.image_net(18, classes=4, input_shape=(32, 32, 3),
                           stem="space_to_depth")
    params, state = net.build_params(jax.random.PRNGKey(0))
    assert params["stem_conv"]["kernel"].shape == (4, 4, 12, 64)
    rng = np.random.default_rng(0)
    n = 16
    x = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    y = (x.mean(axis=(1, 2, 3)) > 0).astype(np.int32) * 3
    out, _ = net.forward(params, x, state=state, training=False)
    assert out.shape == (n, 4)
    net.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    net.fit(x, y, batch_size=8, nb_epoch=6)
    hist = net._estimator.history
    assert hist[-1]["loss"] < 0.8 * hist[0]["loss"], hist
