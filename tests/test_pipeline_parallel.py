"""Pipeline parallelism (GPipe over the ``pipe`` mesh axis) vs a sequential
oracle — the reference has no PP (SURVEY.md §2.4), so dense math is the
oracle, as for TP/SP/EP."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _oracle(stage_params, x):
    for i in range(stage_params["w"].shape[0]):
        x = np.tanh(x @ stage_params["w"][i] + stage_params["b"][i])
    return x


def _make(rng, n_stages, d):
    return {
        "w": rng.normal(0, 0.5, (n_stages, d, d)).astype(np.float32),
        "b": rng.normal(0, 0.1, (n_stages, d)).astype(np.float32),
    }


@pytest.fixture()
def pipe_ctx():
    from analytics_zoo_tpu import init_zoo_context

    return init_zoo_context(
        mesh_shape={"data": 2, "pipe": 4},
        mesh_axes=("data", "pipe"), seed=0,
    )


class TestGPipe:
    def test_forward_matches_sequential(self, pipe_ctx):
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        rng = np.random.default_rng(0)
        params = _make(rng, 4, 8)
        x = rng.normal(size=(16, 8)).astype(np.float32)
        out = gpipe(_stage_fn, params, jnp.asarray(x), n_microbatch=8)
        np.testing.assert_allclose(
            np.asarray(out), _oracle(params, x), atol=1e-5)

    def test_forward_under_jit_with_sharded_stages(self, pipe_ctx):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from analytics_zoo_tpu.parallel.pipeline import gpipe

        mesh = pipe_ctx.mesh
        rng = np.random.default_rng(1)
        params = _make(rng, 4, 8)
        sharded = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P("pipe"))),
            params,
        )
        x = rng.normal(size=(32, 8)).astype(np.float32)
        out = jax.jit(
            lambda p, x: gpipe(_stage_fn, p, x, n_microbatch=8)
        )(sharded, jnp.asarray(x))
        np.testing.assert_allclose(
            np.asarray(out), _oracle(params, x), atol=1e-5)

    def test_grad_is_reverse_pipeline(self, pipe_ctx):
        """jax.grad through the scanned ppermute schedule must equal the
        sequential model's gradients, for stage params AND input."""
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        rng = np.random.default_rng(2)
        params = _make(rng, 4, 6)
        x = jnp.asarray(rng.normal(size=(8, 6)).astype(np.float32))
        tgt = jnp.asarray(rng.normal(size=(8, 6)).astype(np.float32))

        def piped_loss(p, x):
            return jnp.mean((gpipe(_stage_fn, p, x, n_microbatch=4)
                             - tgt) ** 2)

        def seq_loss(p, x):
            for i in range(4):
                x = jnp.tanh(x @ p["w"][i] + p["b"][i])
            return jnp.mean((x - tgt) ** 2)

        gp, gx = jax.grad(piped_loss, argnums=(0, 1))(params, x)
        rp, rx = jax.grad(seq_loss, argnums=(0, 1))(params, x)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-5)
        for k in gp:
            np.testing.assert_allclose(
                np.asarray(gp[k]), np.asarray(rp[k]), atol=1e-5, err_msg=k)

    def test_training_step_converges(self, pipe_ctx):
        """Full pipelined train step: gpipe forward, grad, sgd — loss falls
        on a learnable mapping."""
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        rng = np.random.default_rng(3)
        params = jax.tree_util.tree_map(jnp.asarray, _make(rng, 4, 4))
        x = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
        w_true = rng.normal(size=(4, 4)).astype(np.float32)
        y = jnp.tanh(jnp.asarray(x @ w_true))

        @jax.jit
        def step(p, x, y):
            def loss(p):
                return jnp.mean((gpipe(_stage_fn, p, x, n_microbatch=8)
                                 - y) ** 2)

            l, g = jax.value_and_grad(loss)(p)
            return jax.tree_util.tree_map(
                lambda a, b: a - 0.3 * b, p, g), l

        losses = []
        for _ in range(60):
            params, l = step(params, x, y)
            losses.append(float(l))
        assert losses[-1] < 0.2 * losses[0], losses[::15]

    def test_single_stage_fallback(self):
        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        init_zoo_context(mesh_shape={"data": 8}, seed=0)
        rng = np.random.default_rng(4)
        params = _make(rng, 1, 5)
        x = rng.normal(size=(6, 5)).astype(np.float32)
        out = gpipe(_stage_fn, params, jnp.asarray(x), n_microbatch=2)
        np.testing.assert_allclose(
            np.asarray(out), _oracle(params, x), atol=1e-6)

    def test_stack_stage_params(self, pipe_ctx):
        from analytics_zoo_tpu.parallel.pipeline import (
            gpipe,
            stack_stage_params,
        )

        rng = np.random.default_rng(5)
        per_stage = [
            {"w": rng.normal(0, 0.5, (4, 4)).astype(np.float32),
             "b": np.zeros(4, np.float32)}
            for _ in range(4)
        ]
        stacked = stack_stage_params(per_stage)
        assert stacked["w"].shape == (4, 4, 4)
        x = rng.normal(size=(8, 4)).astype(np.float32)
        out = gpipe(_stage_fn, stacked, jnp.asarray(x), n_microbatch=4)
        ref = x
        for p in per_stage:
            ref = np.tanh(ref @ p["w"] + p["b"])
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    def test_shape_errors(self, pipe_ctx):
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        rng = np.random.default_rng(6)
        params = _make(rng, 3, 4)  # wrong: pipe axis is 4
        x = jnp.zeros((8, 4), jnp.float32)
        with pytest.raises(ValueError, match="pipe axis size"):
            gpipe(_stage_fn, params, x, n_microbatch=4)
        good = _make(rng, 4, 4)
        with pytest.raises(ValueError, match="not divisible"):
            gpipe(_stage_fn, good, x, n_microbatch=3)


class TestGPipeDataParallel:
    def test_batch_axis_shards_rows_and_matches_oracle(self, pipe_ctx):
        """PP x DP: microbatch rows sharded over `data`; forward and the
        DP-summed parameter grads must equal the sequential oracle."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from analytics_zoo_tpu.parallel.pipeline import gpipe

        mesh = pipe_ctx.mesh
        rng = np.random.default_rng(7)
        params = jax.device_put(
            _make(rng, 4, 6), NamedSharding(mesh, P("pipe")))
        host = jax.tree_util.tree_map(np.asarray, params)
        x = rng.normal(size=(16, 6)).astype(np.float32)
        tgt = rng.normal(size=(16, 6)).astype(np.float32)
        xd = jax.device_put(x, NamedSharding(mesh, P("data")))
        td = jax.device_put(tgt, NamedSharding(mesh, P("data")))

        @jax.jit
        def loss_and_grad(p, x, t):
            def loss(p):
                out = gpipe(_stage_fn, p, x, n_microbatch=4,
                            batch_axis="data")
                return jnp.mean((out - t) ** 2), out

            (l, out), g = jax.value_and_grad(loss, has_aux=True)(p)
            return l, out, g

        l, out, g = loss_and_grad(params, xd, td)
        # forward oracle
        np.testing.assert_allclose(
            np.asarray(out), _oracle(host, x), atol=1e-5)
        # the output stays row-sharded over data (no all-gather of compute)
        assert out.sharding.spec[0] in (P("data")[0], "data")

        def seq_loss(p):
            a = jnp.asarray(x)
            for i in range(4):
                a = jnp.tanh(a @ p["w"][i] + p["b"][i])
            return jnp.mean((a - tgt) ** 2)

        ref = jax.grad(seq_loss)(host)
        for k in g:
            np.testing.assert_allclose(
                np.asarray(g[k]), np.asarray(ref[k]), atol=1e-5, err_msg=k)


class TestTransformerGPipe:
    def test_block_stack_matches_sequential(self, pipe_ctx):
        """A real TransformerLayer's blocks pipelined over pipe=4 must
        reproduce the sequential stack (fwd + grads)."""
        from analytics_zoo_tpu.parallel.pipeline import transformer_gpipe
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            TransformerLayer,
        )

        layer = TransformerLayer(vocab=64, seq_len=8, n_block=4, n_head=2,
                                 hidden_size=16, embedding_drop=0.0,
                                 hidden_drop=0.0, attn_drop=0.0)
        params = layer.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        h = jnp.asarray(rng.normal(size=(8, 8, 16)).astype(np.float32))

        def piped(params, h):
            return jnp.mean(
                transformer_gpipe(layer, params, h, n_microbatch=4) ** 2)

        def seq(params, h):
            return jnp.mean(
                layer._run_blocks(params["blocks"], h, None, False,
                                  None) ** 2)

        @jax.jit
        def both(params, h):
            # each traced and compiled once, not run op by op
            return (layer._run_blocks(params["blocks"], h, None, False, None),
                    transformer_gpipe(layer, params, h, n_microbatch=4),
                    jax.grad(piped)(params, h), jax.grad(seq)(params, h))

        ref, out, gp, gs = both(params, h)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5), gp, gs)

    def test_structural_mask_and_remat(self, pipe_ctx):
        """Batch-independent mask is honored; remat=True stays exact;
        per-sample masks are rejected."""
        from analytics_zoo_tpu.parallel.pipeline import transformer_gpipe
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            TransformerLayer,
        )

        layer = TransformerLayer(vocab=64, seq_len=8, n_block=4, n_head=2,
                                 hidden_size=16, embedding_drop=0.0,
                                 hidden_drop=0.0, attn_drop=0.0,
                                 bidirectional=True, remat=True)
        params = layer.init_params(jax.random.PRNGKey(1))
        rng = np.random.default_rng(1)
        h = jnp.asarray(rng.normal(size=(8, 8, 16)).astype(np.float32))
        # structural mask (1, 1, Lq, Lk): block attention to the last two
        # key positions for every query
        mask = jnp.broadcast_to(
            jnp.where(jnp.arange(8) < 6, 0.0, -1e9), (8, 8))[None, None]

        @jax.jit
        def both(params):
            # each traced and compiled once, not run op by op
            return (layer._run_blocks(params["blocks"], h, mask, False,
                                      None),
                    transformer_gpipe(layer, params, h, n_microbatch=4,
                                      mask=mask),
                    jax.grad(lambda p: jnp.mean(transformer_gpipe(
                        layer, p, h, n_microbatch=4, mask=mask) ** 2))(
                            params),
                    jax.grad(lambda p: jnp.mean(layer._run_blocks(
                        p["blocks"], h, mask, False, None) ** 2))(params))

        ref, out, g, gr = both(params)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5), g, gr)

        with pytest.raises(ValueError, match="per-sample masks"):
            transformer_gpipe(layer, params, h, n_microbatch=4,
                              mask=jnp.zeros((8, 1, 8, 8)))


class TestGPipeHetero:
    """Non-shape-preserving pipelines (VERDICT r03 weak #6): stage
    boundaries change shape/dtype; union-buffer carry + lax.switch."""

    def test_changing_shapes_match_sequential(self, pipe_ctx):
        from analytics_zoo_tpu.parallel.pipeline import gpipe_hetero

        rng = np.random.default_rng(0)
        w0 = rng.normal(0, .5, (4, 10)).astype(np.float32)
        w1 = rng.normal(0, .5, (10, 6)).astype(np.float32)
        w2 = rng.normal(0, .5, (6, 6)).astype(np.float32)
        w3 = rng.normal(0, .5, (6, 3)).astype(np.float32)
        edge = [{"w": w0}, {"w": w1}, {"w": w2}, {"w": w3}]
        fns = [lambda e, s, a: jnp.tanh(a @ e["w"])] * 4
        x = rng.normal(size=(16, 4)).astype(np.float32)

        def seq(x):
            a = jnp.asarray(x)
            for wi in (w0, w1, w2, w3):
                a = jnp.tanh(a @ wi)
            return a

        out = gpipe_hetero(fns, edge, {}, jnp.asarray(x), n_microbatch=8)
        assert out.shape == (16, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(seq(x)),
                                   atol=1e-5)

    def test_int_tokens_and_pytree_boundary(self, pipe_ctx):
        """Stage 0 consumes int32 tokens (bitcast through the f32 union
        buffer must be exact) and emits a pytree boundary."""
        from analytics_zoo_tpu.parallel.pipeline import gpipe_hetero

        rng = np.random.default_rng(1)
        table = rng.normal(0, .5, (50, 8)).astype(np.float32)
        w = rng.normal(0, .5, (8, 8)).astype(np.float32)
        wh = rng.normal(0, .5, (8, 5)).astype(np.float32)
        toks = rng.integers(0, 50, size=(8, 6)).astype(np.int32)

        def f0(e, s, t):
            h = jnp.take(e["tbl"], t, axis=0)
            return {"h": h, "t": t}

        def f1(e, s, d):
            return {"h": jnp.tanh(d["h"] @ e["w"]), "t": d["t"]}

        def f2(e, s, d):
            return d["h"] + jnp.take(e["tbl"], d["t"], axis=0)

        def f3(e, s, h):
            return h @ e["wh"]

        edge = [{"tbl": table}, {"w": w}, {"tbl": table}, {"wh": wh}]
        out = gpipe_hetero([f0, f1, f2, f3], edge, {}, jnp.asarray(toks),
                           n_microbatch=4)
        emb = table[toks]
        ref = (np.tanh(emb @ w) + emb) @ wh
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    def test_grads_match_sequential(self, pipe_ctx):
        from analytics_zoo_tpu.parallel.pipeline import gpipe_hetero

        rng = np.random.default_rng(2)
        edge = [{"w": rng.normal(0, .5, (4, 7)).astype(np.float32)},
                {"w": rng.normal(0, .5, (7, 5)).astype(np.float32)},
                {"w": rng.normal(0, .5, (5, 5)).astype(np.float32)},
                {"w": rng.normal(0, .5, (5, 2)).astype(np.float32)}]
        fns = [lambda e, s, a: jnp.tanh(a @ e["w"])] * 4
        x = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))

        def piped(edge, x):
            return jnp.mean(gpipe_hetero(fns, list(edge), {}, x,
                                         n_microbatch=4) ** 2)

        def seq(edge, x):
            a = x
            for e in edge:
                a = jnp.tanh(a @ e["w"])
            return jnp.mean(a ** 2)

        gp, gx = jax.jit(jax.grad(piped, argnums=(0, 1)))(tuple(edge), x)
        rp, rx = jax.jit(jax.grad(seq, argnums=(0, 1)))(tuple(edge), x)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   atol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5), gp, rp)

    def test_full_lm_embed_blocks_head(self, pipe_ctx):
        """The GPT stack (tools/transformer_bench.py shape) pipelined
        end-to-end: tokens -> embed -> 4 blocks -> LM head, vs the
        sequential model.  Forward and grads."""
        from analytics_zoo_tpu.parallel.pipeline import transformer_gpipe_lm
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            TransformerLayer,
        )

        layer = TransformerLayer(vocab=32, seq_len=8, n_block=4, n_head=2,
                                 hidden_size=16, embedding_drop=0.0,
                                 hidden_drop=0.0, attn_drop=0.0)
        params = layer.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        head_w = jnp.asarray(rng.normal(0, .2, (16, 32)).astype(np.float32))
        head_b = jnp.zeros((32,), jnp.float32)
        toks = jnp.asarray(rng.integers(0, 32, size=(8, 8)).astype(np.int32))

        def seq(params, head_w):
            h = layer.call(params, toks, training=False)
            return h @ head_w + head_b

        def piped(params, head_w):
            return transformer_gpipe_lm(layer, params, head_w, head_b,
                                        toks, n_microbatch=4)

        @jax.jit
        def both(params, head_w):
            # each traced and compiled once, not run op by op
            return seq(params, head_w), piped(params, head_w), jax.grad(
                lambda p, w: jnp.mean(piped(p, w) ** 2), argnums=(0, 1))(
                    params, head_w), jax.grad(
                lambda p, w: jnp.mean(seq(p, w) ** 2), argnums=(0, 1))(
                    params, head_w)

        ref, out, (gp, gh), (rp, rh) = both(params, head_w)
        assert out.shape == (8, 8, 32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(rh),
                                   atol=2e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5), gp, rp)

    def test_full_lm_with_data_parallel(self, pipe_ctx):
        """PP x DP composition for the hetero pipeline."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from analytics_zoo_tpu.parallel.pipeline import transformer_gpipe_lm
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            TransformerLayer,
        )

        layer = TransformerLayer(vocab=16, seq_len=4, n_block=4, n_head=2,
                                 hidden_size=8, embedding_drop=0.0,
                                 hidden_drop=0.0, attn_drop=0.0)
        params = layer.init_params(jax.random.PRNGKey(1))
        rng = np.random.default_rng(4)
        head_w = jnp.asarray(rng.normal(0, .2, (8, 16)).astype(np.float32))
        head_b = jnp.zeros((16,), jnp.float32)
        toks = rng.integers(0, 16, size=(8, 4)).astype(np.int32)
        mesh = pipe_ctx.mesh
        toks_d = jax.device_put(jnp.asarray(toks),
                                NamedSharding(mesh, P("data")))

        out = jax.jit(lambda p, w, t: transformer_gpipe_lm(
            layer, p, w, head_b, t, n_microbatch=4,
            batch_axis="data"))(params, head_w, toks_d)
        ref = layer.call(params, jnp.asarray(toks),
                         training=False) @ head_w + head_b
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


class TestGPipeCircular:
    """Interleaved/circular schedule (virtual stages): shard i hosts
    stages i, i+S, ... and the ring is traversed v times."""

    def test_matches_sequential(self, pipe_ctx):
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        rng = np.random.default_rng(5)
        params = _make(rng, 8, 6)  # 8 virtual stages on pipe=4, v=2
        x = rng.normal(size=(16, 6)).astype(np.float32)
        out = gpipe(_stage_fn, params, jnp.asarray(x), n_microbatch=8,
                    circular_repeats=2)
        np.testing.assert_allclose(
            np.asarray(out), _oracle(params, x), atol=1e-5)

    def test_grads_match_sequential(self, pipe_ctx):
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        rng = np.random.default_rng(6)
        params = _make(rng, 8, 5)
        x = jnp.asarray(rng.normal(size=(8, 5)).astype(np.float32))

        def piped(p, x):
            return jnp.mean(gpipe(_stage_fn, p, x, n_microbatch=4,
                                  circular_repeats=2) ** 2)

        def seq(p, x):
            for i in range(8):
                x = jnp.tanh(x @ p["w"][i] + p["b"][i])
            return jnp.mean(x ** 2)

        gp, gx = jax.jit(jax.grad(piped, argnums=(0, 1)))(params, x)
        rp, rx = jax.jit(jax.grad(seq, argnums=(0, 1)))(params, x)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   atol=1e-5)
        for k in gp:
            np.testing.assert_allclose(
                np.asarray(gp[k]), np.asarray(rp[k]), atol=1e-5, err_msg=k)

    def test_exact_microbatch_equals_pipe_size(self, pipe_ctx):
        """M == S: the delay line degenerates to a direct hand-off."""
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        rng = np.random.default_rng(7)
        params = _make(rng, 12, 4)  # v=3
        x = rng.normal(size=(8, 4)).astype(np.float32)
        out = gpipe(_stage_fn, params, jnp.asarray(x), n_microbatch=4,
                    circular_repeats=3)
        np.testing.assert_allclose(
            np.asarray(out), _oracle(params, x), atol=1e-5)

    def test_requires_enough_microbatches(self, pipe_ctx):
        from analytics_zoo_tpu.parallel.pipeline import gpipe

        rng = np.random.default_rng(8)
        params = _make(rng, 8, 4)
        with pytest.raises(ValueError, match="circular"):
            gpipe(_stage_fn, params, jnp.zeros((4, 4)), n_microbatch=2,
                  circular_repeats=2)


class Test1F1B:
    """Explicit-backward 1F1B schedule (gpipe_1f1b_grads): grads must equal
    the sequential reference, and — the point of the schedule — the
    compiled temp footprint must be flat in the microbatch count while
    jax.grad(gpipe)'s grows linearly (VERDICT r4 weak #9)."""

    def _loss(self, o, t):
        return jnp.mean((o - t) ** 2)

    def test_matches_sequential_loss_and_grads(self, pipe_ctx):
        from analytics_zoo_tpu.parallel import gpipe_1f1b_grads

        S, M, B, D = 4, 8, 32, 16
        rng = np.random.default_rng(0)
        sp = _make(rng, S, D)
        x = rng.normal(0, 1, (B, D)).astype(np.float32)
        y = rng.normal(0, 1, (B, D)).astype(np.float32)

        loss, grads = jax.jit(lambda sp, x, y: gpipe_1f1b_grads(
            _stage_fn, self._loss, sp, x, y, n_microbatch=M,
            batch_axis="data"))(sp, x, y)

        def ref(sp):
            out = jnp.asarray(x)
            for j in range(S):
                out = _stage_fn(
                    jax.tree_util.tree_map(lambda a, _j=j: a[_j], sp), out)
            om = out.reshape(M, B // M, D)
            ym = y.reshape(M, B // M, D)
            return jnp.mean(jax.vmap(self._loss)(om, ym))

        rl, rg = jax.value_and_grad(ref)(
            jax.tree_util.tree_map(jnp.asarray, sp))
        np.testing.assert_allclose(float(loss), float(rl), rtol=1e-5)
        for k in grads:
            np.testing.assert_allclose(np.asarray(grads[k]),
                                       np.asarray(rg[k]),
                                       rtol=1e-4, atol=1e-5)

    def test_sgd_with_1f1b_converges(self, pipe_ctx):
        from analytics_zoo_tpu.parallel import gpipe_1f1b_grads

        S, M, B, D = 4, 8, 32, 8
        rng = np.random.default_rng(1)
        sp = jax.tree_util.tree_map(jnp.asarray, _make(rng, S, D))
        x = jnp.asarray(rng.normal(0, 1, (B, D)), jnp.float32)
        y = jnp.asarray(rng.normal(0, 1, (B, D)), jnp.float32)

        @jax.jit
        def step(sp):
            l, g = gpipe_1f1b_grads(_stage_fn, self._loss, sp, x, y,
                                    n_microbatch=M, batch_axis="data")
            return jax.tree_util.tree_map(
                lambda p, d: p - 0.5 * d, sp, g), l

        losses = []
        for _ in range(30):
            sp, l = step(sp)
            losses.append(float(l))
        assert losses[-1] < 0.6 * losses[0], losses
        assert losses[-1] == min(losses)

    def test_temp_memory_flat_in_microbatches(self):
        """The memory claim itself, from XLA's own accounting: growing M
        4x grows jax.grad(gpipe) temps ~linearly but leaves the 1F1B
        schedule's temps flat (ring buffer is O(S), not O(M))."""
        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel import gpipe, gpipe_1f1b_grads

        init_zoo_context(mesh_shape={"pipe": 4}, mesh_axes=("pipe",),
                         seed=0)
        S, D = 4, 128
        rng = np.random.default_rng(0)
        sp = jax.tree_util.tree_map(jnp.asarray, _make(rng, S, D))

        def temps(M, mode):
            B = 8 * M
            x = jax.ShapeDtypeStruct((B, D), jnp.float32)
            y = jax.ShapeDtypeStruct((B, D), jnp.float32)
            if mode == "1f1b":
                def f(sp, x, y):
                    return gpipe_1f1b_grads(_stage_fn, self._loss, sp, x,
                                            y, n_microbatch=M)
            else:
                def f(sp, x, y):
                    def loss(sp):
                        out = gpipe(_stage_fn, sp, x, n_microbatch=M)
                        return self._loss(out, y)
                    return jax.value_and_grad(loss)(sp)
            c = jax.jit(f).lower(sp, x, y).compile()
            ma = c.memory_analysis()
            if ma is None:  # backend without memory accounting
                pytest.skip("memory_analysis unavailable")
            return ma.temp_size_in_bytes

        g8, g32 = temps(8, "gpipe"), temps(32, "gpipe")
        f8, f32 = temps(8, "1f1b"), temps(32, "1f1b")
        assert g32 > 2.0 * g8          # GPipe backward temps scale with M
        assert f32 < 1.2 * f8          # 1F1B stays flat
        assert f32 < 0.5 * g32         # and wins outright at M=32

    def test_stage_dim_validation(self, pipe_ctx):
        from analytics_zoo_tpu.parallel import gpipe_1f1b_grads

        rng = np.random.default_rng(0)
        sp = _make(rng, 3, 8)  # wrong: pipe axis is 4
        with pytest.raises(ValueError, match="leading dim"):
            gpipe_1f1b_grads(_stage_fn, self._loss, sp,
                             jnp.zeros((8, 8)), jnp.zeros((8, 8)),
                             n_microbatch=2)


class TestHetero1F1B:
    """1F1B over heterogeneous stages (embed -> blocks -> head): the
    union-buffer carry of gpipe_hetero under the explicit-backward
    schedule — grads must equal the sequential reference, temps must
    stay flat in M (the LM shape is exactly where PP memory matters)."""

    def _setup(self, S=4, B=16, L=6, D=8, V=12, seed=0):
        rng = np.random.default_rng(seed)
        edge = [
            {"tok": jnp.asarray(rng.normal(0, .5, (V, D)), jnp.float32)},
            None, None,
            {"w": jnp.asarray(rng.normal(0, .5, (D, V)), jnp.float32)},
        ]
        stacked = {
            "w": jnp.asarray(rng.normal(0, .4, (S, D, D)), jnp.float32),
            "b": jnp.zeros((S, D), jnp.float32)}

        def f0(e, sl, t):
            h = jnp.take(e["tok"], t, axis=0)
            return jnp.tanh(h @ sl["w"] + sl["b"])

        def fmid(e, sl, h):
            return jnp.tanh(h @ sl["w"] + sl["b"])

        def flast(e, sl, h):
            h = jnp.tanh(h @ sl["w"] + sl["b"])
            return h @ e["w"]

        fns = [f0] + [fmid] * (S - 2) + [flast]
        toks = jnp.asarray(rng.integers(0, V, (B, L)), jnp.int32)
        y = jnp.asarray(rng.integers(0, V, (B, L)), jnp.int32)
        return fns, edge, stacked, toks, y

    @staticmethod
    def _loss(logits, labels):
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))

    def test_matches_sequential_lm_grads(self, pipe_ctx):
        from analytics_zoo_tpu.parallel import gpipe_hetero_1f1b_grads

        S, M = 4, 8
        fns, edge, stacked, toks, y = self._setup(S=S)
        loss, ge, gs = jax.jit(
            lambda e, s, x, yy: gpipe_hetero_1f1b_grads(
                fns, e, s, x, yy, self._loss, n_microbatch=M))(
            tuple(edge), stacked, toks, y)

        def ref(params):
            e, sl = params
            h = jnp.take(e[0]["tok"], toks, axis=0)
            for j in range(S):
                slj = jax.tree_util.tree_map(lambda a, _j=j: a[_j], sl)
                h = jnp.tanh(h @ slj["w"] + slj["b"])
            logits = h @ e[S - 1]["w"]
            B, L, V = logits.shape
            lm = logits.reshape(M, B // M, L, V)
            ym = y.reshape(M, B // M, L)
            return jnp.mean(jax.vmap(self._loss)(lm, ym))

        rl, (rge, rgs) = jax.value_and_grad(ref)((tuple(edge), stacked))
        np.testing.assert_allclose(float(loss), float(rl), rtol=1e-5)
        for got, want in ((ge[0]["tok"], rge[0]["tok"]),
                          (ge[S - 1]["w"], rge[S - 1]["w"]),
                          (gs["w"], rgs["w"]), (gs["b"], rgs["b"])):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-5)

    def test_temp_memory_beats_grad_at_fixed_batch(self, pipe_ctx):
        """Fixed global batch, growing M: the 1F1B live set (in-flight
        frames, O(S) of them) must stay well under grad-of-gpipe_hetero's
        per-tick saves at every microbatch count, and shrink as frames
        get finer — the O(in-flight) behavior.  (Unlike the homogeneous
        test, input frames are staged in-graph here, so 'flat in M with
        growing B' is not the right invariant.)"""
        from analytics_zoo_tpu.parallel import gpipe_hetero_1f1b_grads
        from analytics_zoo_tpu.parallel.pipeline import gpipe_hetero

        S, L, D, V, B = 4, 6, 64, 32, 128

        def temps(M, mode):
            fns, edge, stacked, _, _ = self._setup(S=S, B=B, L=L, D=D,
                                                   V=V)
            toks = jax.ShapeDtypeStruct((B, L), jnp.int32)
            y = jax.ShapeDtypeStruct((B, L), jnp.int32)
            if mode == "1f1b":
                def f(e, s, x, yy):
                    return gpipe_hetero_1f1b_grads(
                        fns, e, s, x, yy, self._loss, n_microbatch=M)
            else:
                def f(e, s, x, yy):
                    def loss(params):
                        ee, ss = params
                        out = gpipe_hetero(fns, list(ee), ss, x,
                                           n_microbatch=M)
                        om = out.reshape((M, B // M) + out.shape[1:])
                        ym = yy.reshape(M, B // M, L)
                        return jnp.mean(jax.vmap(self._loss)(om, ym))
                    return jax.value_and_grad(loss)((e, s))
            c = jax.jit(f).lower(tuple(edge), stacked, toks, y).compile()
            ma = c.memory_analysis()
            if ma is None:
                pytest.skip("memory_analysis unavailable")
            return ma.temp_size_in_bytes

        for M in (8, 32):
            assert temps(M, "1f1b") < 0.5 * temps(M, "grad"), M
        assert temps(32, "1f1b") < temps(8, "1f1b")

    def test_stacked_dim_validation_and_single_stage(self):
        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel import gpipe_hetero_1f1b_grads

        init_zoo_context(mesh_shape={"data": 8}, seed=0)  # no pipe axis
        rng = np.random.default_rng(0)
        D, V, B, L = 8, 12, 8, 6
        edge1 = [{"tok": jnp.asarray(rng.normal(0, .5, (V, D)),
                                     jnp.float32),
                  "w": jnp.asarray(rng.normal(0, .5, (D, V)),
                                   jnp.float32)}]
        st1 = {"w": jnp.asarray(rng.normal(0, .4, (1, D, D)),
                                jnp.float32)}

        def whole_lm(e, sl, t):
            h = jnp.take(e["tok"], t, axis=0)
            return jnp.tanh(h @ sl["w"]) @ e["w"]

        toks = jnp.asarray(rng.integers(0, V, (B, L)), jnp.int32)
        y = jnp.asarray(rng.integers(0, V, (B, L)), jnp.int32)
        # single-stage fallback works without a pipe axis
        loss, ge, gs = gpipe_hetero_1f1b_grads(
            [whole_lm], edge1, st1, toks, y, self._loss, n_microbatch=2)
        assert np.isfinite(float(loss))
        assert gs["w"].shape == (1, D, D)

        init_zoo_context(mesh_shape={"data": 2, "pipe": 4},
                         mesh_axes=("data", "pipe"), seed=0)
        fns4, edge4, stacked4, toks4, y4 = self._setup(S=4)
        bad = jax.tree_util.tree_map(  # 8 blocks on a 4-stage pipe
            lambda a: jnp.concatenate([a, a]), stacked4)
        with pytest.raises(ValueError, match="leading dim"):
            gpipe_hetero_1f1b_grads(fns4, edge4, bad, toks4, y4,
                                    self._loss, n_microbatch=4)
