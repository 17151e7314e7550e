"""Layer oracle tests — torch (CPU) as the reference implementation, the
analogue of the reference's KerasBaseSpec oracle strategy (SURVEY.md §4:
spawn real Keras, compare outputs per layer; here torch is in-process)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def apply_layer(layer, x, params=None, rng=None, training=False):
    layer.ensure_built(tuple(np.shape(x))[1:])
    if params is None:
        # PRNG keys are arrays — `rng or default` truthiness would raise
        params = layer.init_params(
            rng if rng is not None else jax.random.PRNGKey(0)
        )
    state = layer.init_state()
    out, _ = layer.apply(params, jnp.asarray(x), state=state or None,
                         training=training, rng=rng)
    return np.asarray(out), params


class TestDenseOracle:
    def test_vs_torch_linear(self):
        import torch

        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

        x = np.random.default_rng(0).normal(size=(4, 7)).astype(np.float32)
        layer = Dense(5, activation="tanh")
        out, params = apply_layer(layer, x)

        lin = torch.nn.Linear(7, 5)
        with torch.no_grad():
            lin.weight.copy_(torch.from_numpy(
                np.asarray(params["kernel"]).T))
            lin.bias.copy_(torch.from_numpy(np.asarray(params["bias"])))
            ref = torch.tanh(lin(torch.from_numpy(x))).numpy()
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


class TestConvOracle:
    def test_conv2d_vs_torch(self):
        import torch

        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Convolution2D,
        )

        x = np.random.default_rng(0).normal(
            size=(2, 9, 9, 3)).astype(np.float32)
        layer = Convolution2D(4, 3, 3, subsample=(2, 2))
        out, params = apply_layer(layer, x)

        conv = torch.nn.Conv2d(3, 4, 3, stride=2)
        with torch.no_grad():
            # HWIO -> OIHW
            w = np.transpose(np.asarray(params["kernel"]), (3, 2, 0, 1))
            conv.weight.copy_(torch.from_numpy(w))
            conv.bias.copy_(torch.from_numpy(np.asarray(params["bias"])))
            ref = conv(torch.from_numpy(
                np.transpose(x, (0, 3, 1, 2)))).numpy()
        ref = np.transpose(ref, (0, 2, 3, 1))
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
        assert out.shape[1:] == layer.compute_output_shape(
            (None, 9, 9, 3))[1:]
        assert out.shape[0] == 2

    def test_maxpool_vs_torch(self):
        import torch

        from analytics_zoo_tpu.pipeline.api.keras.layers import MaxPooling2D

        x = np.random.default_rng(1).normal(
            size=(2, 8, 8, 3)).astype(np.float32)
        layer = MaxPooling2D(pool_size=(2, 2))
        out, _ = apply_layer(layer, x)
        ref = torch.nn.functional.max_pool2d(
            torch.from_numpy(np.transpose(x, (0, 3, 1, 2))), 2
        ).numpy()
        np.testing.assert_allclose(
            out, np.transpose(ref, (0, 2, 3, 1)), rtol=1e-6)


class TestRecurrentOracle:
    def test_lstm_vs_torch(self):
        import torch

        from analytics_zoo_tpu.pipeline.api.keras.layers import LSTM

        b, t, f, u = 3, 6, 5, 4
        x = np.random.default_rng(2).normal(size=(b, t, f)).astype(
            np.float32)
        layer = LSTM(u, activation="tanh", inner_activation="sigmoid",
                     return_sequences=True)
        out, params = apply_layer(layer, x)

        ref_lstm = torch.nn.LSTM(f, u, batch_first=True)
        with torch.no_grad():
            # ours: i,f,g,o fused (in, 4u); torch: (4u, in) order i,f,g,o
            ref_lstm.weight_ih_l0.copy_(torch.from_numpy(
                np.asarray(params["kernel"]).T))
            ref_lstm.weight_hh_l0.copy_(torch.from_numpy(
                np.asarray(params["recurrent_kernel"]).T))
            ref_lstm.bias_ih_l0.copy_(torch.from_numpy(
                np.asarray(params["bias"])))
            ref_lstm.bias_hh_l0.zero_()
            ref, _ = ref_lstm(torch.from_numpy(x))
        np.testing.assert_allclose(out, ref.numpy(), rtol=1e-4, atol=1e-5)

    def test_gru_shapes_and_last_step(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import GRU

        x = np.random.default_rng(3).normal(size=(2, 5, 3)).astype(
            np.float32)
        seq_layer = GRU(4, return_sequences=True)
        seq, params = apply_layer(seq_layer, x)
        last_layer = GRU(4, return_sequences=False)
        last_layer.ensure_built((5, 3))
        last, _ = last_layer.apply(params, jnp.asarray(x))
        np.testing.assert_allclose(seq[:, -1], np.asarray(last), rtol=1e-5)

    def test_bidirectional_concat(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            LSTM,
            Bidirectional,
        )

        x = np.random.default_rng(4).normal(size=(2, 5, 3)).astype(
            np.float32)
        layer = Bidirectional(LSTM(4, return_sequences=True))
        out, _ = apply_layer(layer, x)
        assert out.shape == (2, 5, 8)

    def test_time_distributed_dense(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Dense,
            TimeDistributed,
        )

        x = np.random.default_rng(5).normal(size=(2, 5, 3)).astype(
            np.float32)
        layer = TimeDistributed(Dense(7))
        out, params = apply_layer(layer, x)
        assert out.shape == (2, 5, 7)
        # same as applying dense per step
        ref = x @ np.asarray(params["inner"]["kernel"]) + np.asarray(
            params["inner"]["bias"])
        np.testing.assert_allclose(out, ref, rtol=1e-5)


class TestBatchNorm:
    def test_train_eval_and_stats(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            BatchNormalization,
        )

        x = np.random.default_rng(6).normal(
            loc=3.0, scale=2.0, size=(16, 4)).astype(np.float32)
        layer = BatchNormalization(momentum=0.0)  # new stats = batch stats
        layer.ensure_built((4,))
        params = layer.init_params(jax.random.PRNGKey(0))
        state = layer.init_state()
        out, new_state = layer.call(params, jnp.asarray(x), state=state,
                                    training=True)
        np.testing.assert_allclose(np.asarray(out).mean(0), 0.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out).std(0), 1.0, atol=1e-2)
        np.testing.assert_allclose(np.asarray(new_state["moving_mean"]),
                                   x.mean(0), rtol=1e-5)
        # eval mode uses moving stats
        out_eval, _ = layer.call(params, jnp.asarray(x), state=new_state,
                                 training=False)
        np.testing.assert_allclose(np.asarray(out_eval).mean(0), 0.0,
                                   atol=1e-4)


class TestEmbeddingAndAdvanced:
    def test_embedding_lookup(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import Embedding

        w = np.random.default_rng(7).normal(size=(10, 4)).astype(np.float32)
        layer = Embedding(10, 4, weights=w)
        ids = np.array([[1, 2], [9, 0]], dtype=np.int32)
        out, _ = apply_layer(layer, ids)
        np.testing.assert_allclose(out, w[ids], rtol=1e-6)

    def test_prelu_leakyrelu(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            LeakyReLU,
            PReLU,
        )

        x = np.array([[-2.0, 3.0]], dtype=np.float32)
        out, _ = apply_layer(LeakyReLU(alpha=0.1), x)
        np.testing.assert_allclose(out, [[-0.2, 3.0]], rtol=1e-6)
        out, params = apply_layer(PReLU(), x)
        np.testing.assert_allclose(out, [[-0.5, 3.0]], rtol=1e-6)


class TestTransformer:
    def test_transformer_forward_and_causality(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            TransformerLayer,
        )

        layer = TransformerLayer(vocab=50, seq_len=8, n_block=2, n_head=2,
                                 hidden_size=16, hidden_drop=0.0,
                                 attn_drop=0.0, embedding_drop=0.0)
        tokens = np.array([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=np.int32) - 1
        pos = np.arange(8, dtype=np.int32)[None]
        params = layer.init_params(jax.random.PRNGKey(0))
        out = layer.call(params, [jnp.asarray(tokens), jnp.asarray(pos)])
        assert out.shape == (1, 8, 16)
        # causality: changing a later token must not affect earlier outputs
        tokens2 = tokens.copy()
        tokens2[0, -1] = 40
        out2 = layer.call(params, [jnp.asarray(tokens2), jnp.asarray(pos)])
        np.testing.assert_allclose(np.asarray(out)[:, :-1],
                                   np.asarray(out2)[:, :-1], atol=1e-5)
        assert not np.allclose(np.asarray(out)[:, -1],
                               np.asarray(out2)[:, -1])

    def test_bert_outputs_and_mask(self):
        from analytics_zoo_tpu.pipeline.api.keras.layers import BERT

        layer = BERT(vocab=30, hidden_size=16, n_block=2, n_head=2,
                     seq_len=10, intermediate_size=32, hidden_p_drop=0.0,
                     attn_p_drop=0.0)
        b, l = 2, 10
        tokens = np.random.default_rng(8).integers(0, 30, (b, l))
        types = np.zeros((b, l), np.int32)
        pos = np.tile(np.arange(l), (b, 1))
        mask = np.ones((b, l), np.float32)
        mask[:, 6:] = 0.0
        params = layer.init_params(jax.random.PRNGKey(0))
        seq, pooled = layer.call(
            params, [jnp.asarray(tokens), jnp.asarray(types),
                     jnp.asarray(pos), jnp.asarray(mask)])
        assert seq.shape == (b, l, 16) and pooled.shape == (b, 16)
        # masked positions must not influence visible outputs
        tokens2 = tokens.copy()
        tokens2[:, 7] = (tokens2[:, 7] + 5) % 30
        seq2, _ = layer.call(
            params, [jnp.asarray(tokens2), jnp.asarray(types),
                     jnp.asarray(pos), jnp.asarray(mask)])
        np.testing.assert_allclose(np.asarray(seq)[:, :6],
                                   np.asarray(seq2)[:, :6], atol=1e-5)


class TestAutograd:
    def test_custom_loss_trains(self, zoo_ctx):
        from analytics_zoo_tpu.pipeline.api import autograd as A
        from analytics_zoo_tpu.pipeline.api.autograd import CustomLoss
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

        def mean_absolute_error(y_true, y_pred):
            return A.mean(A.abs(y_true - y_pred), axis=1)

        rng = np.random.default_rng(9)
        x = rng.normal(size=(256, 6)).astype(np.float32)
        w = rng.normal(size=(6, 2)).astype(np.float32)
        y = x @ w
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

        model = Sequential()
        model.add(Dense(2, input_shape=(6,)))
        model.compile(optimizer=Adam(lr=0.05),
                      loss=CustomLoss(mean_absolute_error, [2]))
        model.fit(x, y, batch_size=64, nb_epoch=30)
        hist = model._estimator.history
        assert hist[-1]["loss"] < 0.25 * hist[0]["loss"]

    def test_lambda_layer_in_graph(self, zoo_ctx):
        from analytics_zoo_tpu.pipeline.api.autograd import Lambda
        from analytics_zoo_tpu.pipeline.api.keras import Input, Model

        inp = Input(shape=(4,))
        doubled = Lambda(lambda v: v * 2.0)(inp)
        model = Model(inp, doubled)
        params, state = model.build_params()
        x = np.ones((2, 4), np.float32)
        out, _ = model.forward(params, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out), 2 * x)

    def test_variable_math_graph(self, zoo_ctx):
        from analytics_zoo_tpu.pipeline.api import autograd as A
        from analytics_zoo_tpu.pipeline.api.keras import Input, Model

        ia, ib = Input(shape=(3,)), Input(shape=(3,))
        out = A.sum((ia - ib) ** 2.0, axis=1, keepdims=True)
        model = Model([ia, ib], out)
        params, _ = model.build_params()
        a = np.array([[1.0, 2.0, 3.0]], np.float32)
        b = np.array([[1.0, 0.0, 0.0]], np.float32)
        res, _ = model.forward(params, [jnp.asarray(a), jnp.asarray(b)])
        np.testing.assert_allclose(np.asarray(res), [[13.0]], rtol=1e-6)


def test_from_logits_losses_registered():
    """Registry names for the from-logits variants (used by the
    transformer bench and tfpark) match their probability twins."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.pipeline.api.keras.objectives import get_loss

    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 6)),
                         jnp.float32)
    y = jnp.asarray([0, 2, 5, 1], jnp.int32)
    a = get_loss("sparse_categorical_crossentropy_from_logits")
    b = get_loss("sparse_categorical_crossentropy")
    np.testing.assert_allclose(
        np.asarray(a.fn(y, logits)),
        np.asarray(b.fn(y, jax.nn.softmax(logits, axis=-1))),
        rtol=1e-5, atol=1e-6)
    yb = jnp.asarray([0.0, 1.0, 1.0, 0.0])
    lb = jnp.asarray([-2.0, 3.0, 0.5, -0.5])
    c = get_loss("binary_crossentropy_from_logits")
    d = get_loss("binary_crossentropy")
    np.testing.assert_allclose(
        np.asarray(c.fn(yb, lb)),
        np.asarray(d.fn(yb, jax.nn.sigmoid(lb))), rtol=1e-5, atol=1e-6)


def test_transformer_remat_matches_baseline(zoo_ctx):
    """remat=True (jax.checkpoint per block) must be a pure memory/FLOP
    trade: identical outputs AND gradients to the non-remat stack."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.pipeline.api.keras.layers import TransformerLayer

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 50, size=(2, 12)), jnp.int32)

    base = TransformerLayer(vocab=50, seq_len=12, n_block=2, n_head=2,
                            hidden_size=16, embedding_drop=0.0,
                            hidden_drop=0.0, attn_drop=0.0)
    params = base.init_params(jax.random.PRNGKey(0))
    def loss(layer, p):
        return jnp.sum(layer.call(p, toks, training=True,
                                  rng=jax.random.PRNGKey(1)) ** 2)

    la, ga = jax.value_and_grad(lambda p: loss(base, p))(params)
    # every checkpoint policy must be a pure memory/FLOP trade
    for policy in (True, "dots", "attn"):
        rem = TransformerLayer(vocab=50, seq_len=12, n_block=2, n_head=2,
                               hidden_size=16, embedding_drop=0.0,
                               hidden_drop=0.0, attn_drop=0.0,
                               remat=policy)
        lb, gb = jax.value_and_grad(lambda p: loss(rem, p))(params)
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-6,
                                   err_msg=str(policy))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5), ga, gb)
    import pytest

    with pytest.raises(ValueError, match="remat"):
        TransformerLayer(vocab=50, seq_len=12, n_block=1, n_head=2,
                         hidden_size=16, remat="bogus")


def _make_attention_block():
    """qkv product, causal flash attention through its ``custom_vjp``, an
    output product: what a layer application holds of attention.  A new
    function each time: ``jax.checkpoint`` caches a function's trace, and
    which path the attention takes is decided while tracing."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import flash_attention

    def block(w, x):
        b, l, d = x.shape

        def heads(t):
            return t.reshape(b, l, 2, d // 2).transpose(0, 2, 1, 3)

        q, k, v = jnp.split(x @ w["qkv"], 3, axis=-1)
        a = flash_attention(heads(q), heads(k), heads(v), causal=True)
        return a.transpose(0, 2, 1, 3).reshape(b, l, d) @ w["out"]

    return block


@pytest.mark.parametrize("path", ["reference", "interpreted_pallas"])
def test_attn_policy_keeps_what_the_flash_backward_reads(path, monkeypatch,
                                                         capsys):
    """Under ``"attn"`` the attention forward is in a layer application's
    gradient once, as without a checkpoint, where ``"full"`` has it twice;
    what is kept is the output the ``custom_vjp``'s forward rule names and,
    where the kernels return them, both softmax statistics."""
    from jax.ad_checkpoint import print_saved_residuals

    from analytics_zoo_tpu.parallel.plan import apply_remat

    if path == "interpreted_pallas":
        monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("ZOO_FLASH_INTERPRET", raising=False)
        monkeypatch.delenv("ZOO_FLASH_FORCE_PALLAS", raising=False)
    rng = np.random.default_rng(3)
    w = {"qkv": jnp.asarray(rng.normal(size=(32, 96)) * 0.1, jnp.float32),
         "out": jnp.asarray(rng.normal(size=(32, 32)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(1, 64, 32)), jnp.float32)
    block = _make_attention_block()

    def counts(policy):
        fn = apply_remat(block, policy)
        text = str(jax.make_jaxpr(
            jax.grad(lambda w: jnp.sum(fn(w, x) ** 2)))(w))
        return {"dot": text.count("dot_general"), "exp": text.count(" exp "),
                "kernel": text.count("pallas_call")}

    plain, full, attn = counts(None), counts("full"), counts("attn")
    if path == "reference":
        # the attention forward: two products and one exponential
        assert plain["kernel"] == 0
        assert full["exp"] == plain["exp"] + 1 and attn["exp"] == plain["exp"]
        assert full["dot"] - attn["dot"] == 2
        # the qkv product is still made again: q, k, v are not kept
        assert attn["dot"] == plain["dot"] + 1
    else:
        # forward, dq, dk/dv; "full" runs the forward kernel a second time
        assert (plain["kernel"], full["kernel"], attn["kernel"]) == (3, 4, 3)
        assert attn["dot"] == plain["dot"] + 1

    def kept(policy):
        capsys.readouterr()
        print_saved_residuals(apply_remat(block, policy), w, x)
        lines = capsys.readouterr().out.splitlines()
        return [ln.split()[0] for ln in lines if " from the argument " not in ln]

    assert kept("full") == []
    want = ["f32[1,2,64,16]"]           # the context, (B, H, L, D)
    if path == "interpreted_pallas":    # and m, l, a row a head
        want += ["f32[1,2,64]"] * 2
    assert sorted(kept("attn")) == sorted(want)

    ga = jax.grad(lambda w: jnp.sum(block(w, x) ** 2))(w)
    gb = jax.grad(lambda w: jnp.sum(
        apply_remat(block, "attn")(w, x) ** 2))(w)
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), ga, gb)


def test_from_logits_losses_are_f32_under_bf16():
    """VERDICT r03 item 2: the from-logits CE must compute in f32 even
    when the model computes in bf16 — a bf16 log-softmax over a wide
    vocab axis corrupts the normalizer tail."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.pipeline.api.keras.objectives import (
        binary_crossentropy_from_logits,
        sparse_categorical_crossentropy_from_logits,
    )

    rng = np.random.default_rng(0)
    logits32 = jnp.asarray(rng.normal(size=(4, 32768)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 32768, size=(4,)))
    want = sparse_categorical_crossentropy_from_logits(labels, logits32)
    got = sparse_categorical_crossentropy_from_logits(
        labels, logits32.astype(jnp.bfloat16))
    # bf16 INPUT quantization costs a little; the f32 softmax keeps the
    # error at input-precision scale instead of normalizer-accumulation
    # scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2)
    assert got.dtype == jnp.float32

    blog = jnp.asarray(rng.normal(size=(8, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, size=(8, 1)).astype(np.float32))
    got_b = binary_crossentropy_from_logits(y, blog.astype(jnp.bfloat16))
    assert got_b.dtype == jnp.float32
