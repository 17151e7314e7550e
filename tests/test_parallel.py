"""Parallelism tests on the 8-device CPU mesh — ring attention vs dense,
explicit shard_map training step vs the jit+sharding path, TP dense blocks
(the reference has no TP/SP to compare against; dense math is the oracle)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture()
def seq_ctx():
    from analytics_zoo_tpu import init_zoo_context

    return init_zoo_context(
        mesh_shape={"data": 2, "seq": 4},
        mesh_axes=("data", "model", "seq"), seed=0,
    )


class TestRingAttention:
    def test_matches_dense(self, seq_ctx):
        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.parallel import ring_attention

        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(rng.normal(size=(2, 3, 32, 8)).astype(np.float32))
            for _ in range(3)
        )
        for causal in (False, True):
            out = ring_attention(q, k, v, causal=causal)
            ref = dot_product_attention(q, k, v, causal=causal,
                                        use_flash=False)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)

    def test_gradients_flow_through_ring(self, seq_ctx):
        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.parallel import ring_attention

        rng = np.random.default_rng(1)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 2, 16, 4)).astype(np.float32))
            for _ in range(3)
        )
        g = jax.jit(jax.grad(lambda q: jnp.sum(
            ring_attention(q, k, v, causal=True) ** 2)))(q)
        gr = jax.jit(jax.grad(lambda q: jnp.sum(dot_product_attention(
            q, k, v, causal=True, use_flash=False) ** 2)))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4)

    def test_sharded_inputs_under_jit(self, seq_ctx):
        """Ring attention with L actually sharded over the seq axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.parallel import ring_attention

        mesh = seq_ctx.mesh
        rng = np.random.default_rng(2)
        q, k, v = (
            jax.device_put(
                rng.normal(size=(2, 2, 64, 8)).astype(np.float32),
                NamedSharding(mesh, P(None, None, "seq", None)),
            )
            for _ in range(3)
        )
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=True))(
            q, k, v)
        ref = dot_product_attention(q, k, v, causal=True, use_flash=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


class TestShardMapStep:
    def test_explicit_psum_step_trains(self, zoo_ctx):
        from analytics_zoo_tpu.feature.dataset import FeatureSet
        from analytics_zoo_tpu.parallel import make_shard_map_train_step
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.objectives import get_loss
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
            get_optimizer,
        )

        rng = np.random.default_rng(3)
        x = rng.normal(size=(256, 8)).astype(np.float32)
        w = rng.normal(size=(8, 1)).astype(np.float32)
        y = (x @ w).astype(np.float32)

        from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

        model = Sequential()
        model.add(Dense(1, input_shape=(8,)))
        params, state = model.build_params(jax.random.PRNGKey(0))
        opt = Adam(lr=0.05)
        loss = get_loss("mse")
        step = make_shard_map_train_step(model, loss, opt)
        opt_state = opt.init(params)
        ctx = zoo_ctx
        losses = []
        fs = FeatureSet.of(x, y)
        for epoch in range(40):
            for batch in fs.batches(64, seed=0, epoch=epoch):
                sharded = ctx.shard_batch(batch)
                params, opt_state, state, l = step(
                    params, opt_state, state, jax.random.PRNGKey(0), sharded
                )
            losses.append(float(l))
        assert losses[-1] < 0.05 * losses[0], losses[::10]

    def test_matches_jit_sharding_path(self, zoo_ctx):
        """Explicit psum and implicit jit-sharding must produce identical
        updates (same math, different formulation)."""
        from analytics_zoo_tpu.feature.dataset import FeatureSet
        from analytics_zoo_tpu.parallel import make_shard_map_train_step
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.objectives import get_loss
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
            get_optimizer,
        )

        rng = np.random.default_rng(4)
        x = rng.normal(size=(64, 4)).astype(np.float32)
        y = rng.normal(size=(64, 1)).astype(np.float32)

        model = Sequential()
        model.add(Dense(1, input_shape=(4,)))
        params, state = model.build_params(jax.random.PRNGKey(5))
        params0 = jax.tree_util.tree_map(jnp.copy, params)

        # path A: explicit shard_map psum
        opt = get_optimizer("sgd")
        step = make_shard_map_train_step(model, get_loss("mse"), opt)
        opt_state = opt.init(params)
        batch = next(FeatureSet.of(x, y).batches(64, shuffle=False))
        pa, _, _, la = step(params, opt_state, state,
                            jax.random.PRNGKey(0),
                            zoo_ctx.shard_batch(batch))

        # path B: estimator's jit + NamedSharding step
        model.params = params0
        model.state = dict(state)
        model.compile(optimizer="sgd", loss="mse")
        model.fit(x, y, batch_size=64, nb_epoch=1)
        pb = model.params
        for ka in pa:
            np.testing.assert_allclose(
                np.asarray(pa[ka]["kernel"]),
                np.asarray(pb[ka]["kernel"]), rtol=1e-5)
        np.testing.assert_allclose(float(la),
                                   model._estimator.history[0]["loss"],
                                   rtol=1e-4)


class TestTensorParallel:
    def test_tp_mlp_matches_dense(self, zoo_ctx):
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel import (
            column_parallel_dense,
            row_parallel_dense,
        )
        from analytics_zoo_tpu.parallel.strategies import tp_mlp

        ctx = init_zoo_context(mesh_shape={"data": 2, "model": 4}, seed=0)
        mesh = ctx.mesh
        rng = np.random.default_rng(5)
        x = rng.normal(size=(8, 16)).astype(np.float32)
        w1 = rng.normal(size=(16, 32)).astype(np.float32)
        b1 = rng.normal(size=(32,)).astype(np.float32)
        w2 = rng.normal(size=(32, 16)).astype(np.float32)
        b2 = rng.normal(size=(16,)).astype(np.float32)

        ref = (jax.nn.gelu(x @ w1 + b1)) @ w2 + b2

        fn = jax.shard_map(
            lambda x, w1, b1, w2, b2: tp_mlp(x, w1, b1, w2, b2),
            mesh=mesh,
            in_specs=(P(), P(None, "model"), P("model"),
                      P("model", None), P()),
            out_specs=P(),
            check_vma=False,
        )
        out = fn(x, w1, b1, w2, b2)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)


class TestExpertParallelMoE:
    def test_matches_dense_oracle(self):
        import jax
        import numpy as np
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel.strategies import ep_moe_mlp

        ctx = init_zoo_context(
            mesh_shape={"data": 1, "expert": 4},
            mesh_axes=("data", "expert"), seed=0)
        mesh = ctx.mesh
        rng = np.random.default_rng(9)
        T, D, F, E = 6, 8, 16, 4
        x = rng.normal(size=(T, D)).astype(np.float32)
        gate = rng.normal(size=(D, E)).astype(np.float32)
        ew1 = rng.normal(size=(E, D, F)).astype(np.float32)
        eb1 = rng.normal(size=(E, F)).astype(np.float32)
        ew2 = rng.normal(size=(E, F, D)).astype(np.float32)
        eb2 = rng.normal(size=(D,)).astype(np.float32)

        # dense single-device oracle
        logits = x @ gate
        g = np.exp(logits - logits.max(-1, keepdims=True))
        g = g / g.sum(-1, keepdims=True)
        h = np.stack([
            np.asarray(jax.nn.gelu(x @ ew1[e] + eb1[e])) @ ew2[e]
            for e in range(E)
        ], axis=1)  # (T, E, D)
        ref = (h * g[..., None]).sum(1) + eb2

        fn = jax.shard_map(
            lambda x, gw, w1, b1, w2, b2: ep_moe_mlp(x, gw, w1, b1, w2, b2),
            mesh=mesh,
            in_specs=(P(), P(None, "expert"), P("expert"), P("expert"),
                      P("expert"), P()),
            out_specs=P(),
            check_vma=False,
        )
        out = fn(x, gate, ew1, eb1, ew2, eb2)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-4)

    def test_differentiable(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel.strategies import ep_moe_mlp

        ctx = init_zoo_context(
            mesh_shape={"data": 1, "expert": 2},
            mesh_axes=("data", "expert"), seed=0)
        rng = np.random.default_rng(2)
        T, D, F, E = 4, 6, 8, 2
        x = rng.normal(size=(T, D)).astype(np.float32)
        args = dict(
            gw=rng.normal(size=(D, E)).astype(np.float32),
            w1=rng.normal(size=(E, D, F)).astype(np.float32),
            b1=np.zeros((E, F), np.float32),
            w2=rng.normal(size=(E, F, D)).astype(np.float32),
            b2=np.zeros((D,), np.float32),
        )

        def loss(p, x):
            y = ep_moe_mlp(x, p["gw"], p["w1"], p["b1"], p["w2"], p["b2"])
            return jax.lax.pmean(jnp.mean(y ** 2), "expert")

        pspec = dict(gw=P(None, "expert"), w1=P("expert"), b1=P("expert"),
                     w2=P("expert"), b2=P())
        fn = jax.jit(jax.shard_map(
            jax.grad(loss), mesh=ctx.mesh,
            in_specs=(pspec, P()), out_specs=pspec, check_vma=False))
        grads = fn(args, x)
        for k, g in grads.items():
            assert np.isfinite(np.asarray(g)).all(), k
        assert float(np.abs(np.asarray(grads["w1"])).sum()) > 0


class TestRoutedMoETopK:
    """moe_mlp_topk: GShard/Switch top-k routing + capacity + all_to_all
    (VERDICT r03 item 6).  ep_moe_mlp (dense dispatch) is the oracle."""

    def _params(self, T=8, D=8, F=16, E=4, seed=9):
        import numpy as np

        rng = np.random.default_rng(seed)
        return dict(
            x=rng.normal(size=(T, D)).astype(np.float32),
            gate=rng.normal(size=(D, E)).astype(np.float32),
            ew1=rng.normal(size=(E, D, F)).astype(np.float32),
            eb1=rng.normal(size=(E, F)).astype(np.float32),
            ew2=rng.normal(size=(E, F, D)).astype(np.float32),
            eb2=rng.normal(size=(D,)).astype(np.float32),
        )

    def _dense_oracle(self, p):
        import jax
        import numpy as np

        x, gate = p["x"], p["gate"]
        E = gate.shape[1]
        logits = x @ gate
        g = np.exp(logits - logits.max(-1, keepdims=True))
        g = g / g.sum(-1, keepdims=True)
        h = np.stack([
            np.asarray(jax.nn.gelu(x @ p["ew1"][e] + p["eb1"][e]))
            @ p["ew2"][e] for e in range(E)
        ], axis=1)  # (T, E, D)
        return (h * g[..., None]).sum(1) + p["eb2"], g, h

    def _run(self, p, top_k, capacity_factor, n_shards=4, tokens_sharded=True):
        import jax
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel.strategies import moe_mlp_topk

        ctx = init_zoo_context(
            mesh_shape={"data": 1, "expert": n_shards},
            mesh_axes=("data", "expert"), seed=0)
        fn = jax.shard_map(
            lambda x, gw, w1, b1, w2, b2: moe_mlp_topk(
                x, gw, w1, b1, w2, b2, top_k=top_k,
                capacity_factor=capacity_factor),
            mesh=ctx.mesh,
            in_specs=(P("expert") if tokens_sharded else P(), P(),
                      P("expert"), P("expert"), P("expert"), P()),
            out_specs=P("expert") if tokens_sharded else P(),
            check_vma=False,
        )
        return fn(p["x"], p["gate"], p["ew1"], p["eb1"], p["ew2"], p["eb2"])

    def test_topk_equals_dense_dispatch_oracle(self):
        """top_k=E + enough capacity == the dense-dispatch oracle exactly
        (every token reaches every expert with full softmax gates)."""
        import numpy as np

        p = self._params(T=8, E=4)
        ref, _, _ = self._dense_oracle(p)
        out = self._run(p, top_k=4, capacity_factor=1.0)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-4)

    def test_top1_routes_to_argmax_expert(self):
        import numpy as np

        p = self._params(T=8, E=4, seed=3)
        _, g, h = self._dense_oracle(p)
        top1 = g.argmax(-1)
        ref = h[np.arange(8), top1] * g[np.arange(8), top1][:, None] \
            + p["eb2"]
        out = self._run(p, top_k=1, capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-4)

    def test_capacity_drops_lowest_priority(self):
        """With per-shard capacity C=1 and top_k=1, only the first token
        (in priority order) routed to each expert on each shard survives;
        dropped tokens output exactly b2."""
        import numpy as np

        p = self._params(T=8, E=4, seed=5)
        _, g, h = self._dense_oracle(p)
        top1 = g.argmax(-1)
        out = np.asarray(self._run(p, top_k=1, capacity_factor=1e-9))
        # per shard of 2 tokens (T=8 over 4 shards): cap = 1 slot/expert
        kept = np.zeros(8, bool)
        for sh in range(4):
            seen = set()
            for t in range(sh * 2, sh * 2 + 2):
                if top1[t] not in seen:
                    seen.add(top1[t])
                    kept[t] = True
        assert kept.any() and (~kept).any(), "test needs both cases"
        for t in range(8):
            if kept[t]:
                ref = h[t, top1[t]] * g[t, top1[t]] + p["eb2"]
            else:
                ref = p["eb2"]
            np.testing.assert_allclose(out[t], ref, rtol=1e-4, atol=1e-4,
                                       err_msg=f"token {t} kept={kept[t]}")

    def test_differentiable_and_aux_loss(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel.strategies import moe_mlp_topk

        ctx = init_zoo_context(
            mesh_shape={"data": 1, "expert": 2},
            mesh_axes=("data", "expert"), seed=0)
        p = self._params(T=8, E=2, D=6, F=8, seed=7)
        params = {k: p[k] for k in ("gate", "ew1", "eb1", "ew2", "eb2")}

        def loss(params, x):
            y, aux = moe_mlp_topk(
                x, params["gate"], params["ew1"], params["eb1"],
                params["ew2"], params["eb2"], top_k=1, return_aux=True)
            return (jax.lax.pmean(jnp.mean(y ** 2), "expert")
                    + 0.01 * aux)

        pspec = dict(gate=P(), ew1=P("expert"), eb1=P("expert"),
                     ew2=P("expert"), eb2=P())
        fn = jax.jit(jax.shard_map(
            jax.value_and_grad(loss), mesh=ctx.mesh,
            in_specs=(pspec, P("expert")),
            out_specs=(P(), pspec), check_vma=False))
        val, grads = fn(params, p["x"])
        assert np.isfinite(float(val))
        for k, g in grads.items():
            assert np.isfinite(np.asarray(g)).all(), k
        # routing grads reach the gate (via gate values + aux loss)
        assert float(np.abs(np.asarray(grads["gate"])).sum()) > 0
        assert float(np.abs(np.asarray(grads["ew1"])).sum()) > 0

    def test_aux_loss_balanced_is_one(self):
        """Uniform router -> aux == 1.0 (perfect balance)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.parallel.strategies import moe_mlp_topk

        ctx = init_zoo_context(
            mesh_shape={"data": 1, "expert": 2},
            mesh_axes=("data", "expert"), seed=0)
        p = self._params(T=8, E=2, D=6, F=8, seed=1)
        p["gate"] = np.zeros((6, 2), np.float32)  # uniform router

        def run(x):
            _, aux = moe_mlp_topk(
                x, jnp.asarray(p["gate"]), p["ew1"], p["eb1"], p["ew2"],
                p["eb2"], top_k=1, return_aux=True)
            return aux

        fn = jax.shard_map(run, mesh=ctx.mesh, in_specs=(P("expert"),),
                           out_specs=P(), check_vma=False)
        # ties all route to expert 0 -> ce=(1,0), me=(.5,.5): aux = 1.0
        assert abs(float(fn(p["x"])) - 1.0) < 1e-5


class TestRingAttentionPallasInner:
    """Ring attention with the Pallas flash inner kernel (interpret mode
    forced on CPU): VERDICT r03 weak #8 — the seq-parallel path streams
    K/V through VMEM and skips fully-masked causal hops."""

    def _data(self, L=512, d=64, seed=0):
        import numpy as np

        rng = np.random.default_rng(seed)
        return tuple(
            jnp.asarray(rng.normal(size=(1, 2, L, d)).astype(np.float32)
                        * 0.5)
            for _ in range(3))

    def test_pallas_inner_matches_dense(self, seq_ctx, monkeypatch):
        import analytics_zoo_tpu.ops.pallas.flash_attention as fa
        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.parallel import ring_attention

        monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
        q, k, v = self._data()
        for causal in (False, True):
            before = fa.invocation_counts["pallas"]
            out = ring_attention(q, k, v, causal=causal)
            assert fa.invocation_counts["pallas"] > before, (
                "ring inner did not use the Pallas kernel")
            ref = dot_product_attention(q, k, v, causal=causal,
                                        use_flash=False)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-4, err_msg=str(causal))

    def test_grads_with_pallas_forward(self, seq_ctx, monkeypatch):
        """custom-VJP backward (reverse ring, jnp remat) against dense
        autodiff while the forward runs the Pallas inner kernel."""
        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.parallel import ring_attention

        monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
        q, k, v = self._data(seed=1)

        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, causal=True, use_flash=False) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, err_msg=name)

    def test_kv_grads_ride_the_ring_home(self, seq_ctx):
        """dK/dV from remote hops must land on the owning shard: compare
        vs dense autodiff with the jnp inner (no interpret env)."""
        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.parallel import ring_attention

        q, k, v = self._data(L=32, d=8, seed=2)
        g = jax.jit(jax.grad(lambda k: jnp.sum(
            ring_attention(q, k, v, causal=False) ** 2)))(k)
        gr = jax.jit(jax.grad(lambda k: jnp.sum(dot_product_attention(
            q, k, v, causal=False, use_flash=False) ** 2)))(k)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   atol=1e-4)


def test_ring_backward_chunk_padding(seq_ctx):
    """lc not a multiple of the 256 backward chunk (here lc=320): the
    padded last chunk must not corrupt dK/dV (zero-padding masked)."""
    from analytics_zoo_tpu.ops.attention import dot_product_attention
    from analytics_zoo_tpu.parallel import ring_attention

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, 1280, 8))
                           .astype(np.float32) * 0.5) for _ in range(3))
    g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        ring_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2)))(
            q, k, v)
    gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
        q, k, v, causal=True, use_flash=False) ** 2), argnums=(0, 1, 2)))(
            q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, err_msg=name)


class TestZigzagRingAttention:
    """Causal-load-balanced variant (VERDICT r03 weak #8): same contract
    as ring_attention (contiguous sharding in/out), balanced work."""

    def test_matches_dense(self, seq_ctx):
        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.parallel.ring_attention import (
            zigzag_ring_attention,
        )

        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(rng.normal(size=(2, 3, 32, 8)).astype(np.float32))
            for _ in range(3)
        )
        for causal in (False, True):
            out = zigzag_ring_attention(q, k, v, causal=causal)
            ref = dot_product_attention(q, k, v, causal=causal,
                                        use_flash=False)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5, err_msg=str(causal))

    def test_gradients_match_dense(self, seq_ctx):
        from analytics_zoo_tpu.ops.attention import dot_product_attention
        from analytics_zoo_tpu.parallel.ring_attention import (
            zigzag_ring_attention,
        )

        rng = np.random.default_rng(3)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 2, 32, 8))
                        .astype(np.float32) * 0.5)
            for _ in range(3)
        )
        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            zigzag_ring_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, causal=True, use_flash=False) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, err_msg=name)

    def test_layout_roundtrip(self, seq_ctx):
        """to-zigzag -> from-zigzag is the identity on any sharded block."""
        import jax.sharding as shd

        from analytics_zoo_tpu.common.engine import get_zoo_context
        from analytics_zoo_tpu.parallel.ring_attention import (
            _zz_from,
            _zz_to,
        )

        ctx = get_zoo_context()
        mesh = ctx.mesh
        n = mesh.shape["seq"]
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(1, 1, 8 * n, 4))
                        .astype(np.float32))
        spec = shd.PartitionSpec(None, None, "seq", None)

        def body(xl):
            return _zz_from(_zz_to(xl, "seq", n), "seq", n)

        out = jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                            out_specs=spec, check_vma=False)(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    def test_odd_local_length_rejected(self, seq_ctx):
        from analytics_zoo_tpu.parallel.ring_attention import (
            zigzag_ring_attention,
        )

        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 1, 36, 8)).astype(np.float32))
        with pytest.raises(ValueError, match="even local sequence"):
            zigzag_ring_attention(q, q, q, causal=True)


def test_ring_pallas_backward_fires_and_matches(seq_ctx, monkeypatch):
    """The reverse-ring backward must route through the Pallas bwd
    kernels (not the jnp chunk scan) when the inner kernel is available,
    and still match dense autodiff — contiguous causal ring."""
    import analytics_zoo_tpu.ops.pallas.flash_attention as fa
    from analytics_zoo_tpu.ops.attention import dot_product_attention
    from analytics_zoo_tpu.parallel import ring_attention

    monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 512, 64))
                           .astype(np.float32) * 0.5) for _ in range(3))
    before = fa.invocation_counts["pallas"]
    g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        ring_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2)))(
            q, k, v)
    # fwd hops + bwd hop kernels all counted at trace time; the bwd
    # contributes at least one pallas invocation beyond the forward's 2
    assert fa.invocation_counts["pallas"] >= before + 3, (
        "ring backward did not route through the Pallas kernels")
    gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
        q, k, v, causal=True, use_flash=False) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, err_msg=name)


def test_zigzag_pallas_backward_matches(seq_ctx, monkeypatch):
    """Zigzag reverse ring through the Pallas quadrant backward (piece
    length >= 128 so the gate opens) vs dense autodiff."""
    from analytics_zoo_tpu.ops.attention import dot_product_attention
    from analytics_zoo_tpu.parallel import zigzag_ring_attention

    monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    rng = np.random.default_rng(12)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, 1024, 64))
                           .astype(np.float32) * 0.5) for _ in range(3))
    g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        zigzag_ring_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
        q, k, v, causal=True, use_flash=False) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, err_msg=name)


def test_zero1_step_matches_plain_dp(zoo_ctx):
    """ZeRO-1 sharded-optimizer step (reduce-scatter grads, 1/n-shard
    Adam state, all-gather params) must produce the SAME parameters as
    the plain replicated-optimizer step — identical math, sharded
    layout.  Also asserts the memory win: each optimizer-state leaf is
    1/n of the flat parameter size."""
    from analytics_zoo_tpu.parallel import (
        make_shard_map_train_step,
        make_zero1_train_step,
    )
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.objectives import get_loss
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    rng_np = np.random.default_rng(9)
    x = rng_np.normal(size=(64, 10)).astype(np.float32)
    w = rng_np.normal(size=(10, 3)).astype(np.float32)
    y = (x @ w).astype(np.float32)

    model = Sequential()
    model.add(Dense(7, activation="tanh", input_shape=(10,)))
    model.add(Dense(3))
    params, state = model.build_params(jax.random.PRNGKey(1))
    loss = get_loss("mse")

    plain = make_shard_map_train_step(model, loss, Adam(lr=0.03))
    z_step, z_init = make_zero1_train_step(model, loss, Adam(lr=0.03))

    opt_plain = Adam(lr=0.03).init(params)
    opt_z = z_init(params)

    n = zoo_ctx.data_parallel_size
    flat_size = sum(int(np.prod(v.shape)) for v in
                    jax.tree_util.tree_leaves(params))
    padded = flat_size + ((-flat_size) % n)
    for leaf in jax.tree_util.tree_leaves(opt_z):
        if hasattr(leaf, "shape") and leaf.ndim == 1 and leaf.size > 1:
            assert leaf.shape[0] == padded, (leaf.shape, padded)

    p1, p2 = params, jax.tree_util.tree_map(jnp.copy, params)
    s1 = s2 = state
    key = jax.random.PRNGKey(0)
    batch = zoo_ctx.shard_batch({"x": x, "y": y})
    for _ in range(4):
        p1, opt_plain, s1, l1 = plain(p1, opt_plain, s1, key, batch)
        p2, opt_z, s2, l2 = z_step(p2, opt_z, s2, key, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("clip", [("l2norm", 0.05), ("const", -0.001, 0.001)])
def test_zero1_grad_clip_contract_matches_plain(zoo_ctx, clip):
    """Both train-step factories accept the SAME grad_clip spec
    (('l2norm', max) | ('const', lo, hi) — the Estimator's _clip_grads
    format) and produce identical parameters; a tight clip makes the
    assertion sensitive to the clip actually being applied."""
    from analytics_zoo_tpu.parallel import (
        make_shard_map_train_step,
        make_zero1_train_step,
    )
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.objectives import get_loss
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    rng_np = np.random.default_rng(3)
    x = rng_np.normal(size=(32, 6)).astype(np.float32)
    y = (x[:, :2] * 5.0).astype(np.float32)

    model = Sequential()
    model.add(Dense(2, input_shape=(6,)))
    params, state = model.build_params(jax.random.PRNGKey(1))
    loss = get_loss("mse")

    plain = make_shard_map_train_step(model, loss, Adam(lr=0.05),
                                      grad_clip=clip)
    z_step, z_init = make_zero1_train_step(model, loss, Adam(lr=0.05),
                                           grad_clip=clip)
    opt_plain = Adam(lr=0.05).init(params)
    opt_z = z_init(params)
    p1, p2 = params, jax.tree_util.tree_map(jnp.copy, params)
    s1 = s2 = state
    key = jax.random.PRNGKey(0)
    batch = zoo_ctx.shard_batch({"x": x, "y": y})
    for _ in range(3):
        p1, opt_plain, s1, l1 = plain(p1, opt_plain, s1, key, batch)
        p2, opt_z, s2, l2 = z_step(p2, opt_z, s2, key, batch)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="grad clip"):
        make_zero1_train_step(model, loss, Adam(lr=0.05),
                              grad_clip=("bogus", 1.0))


def test_estimator_zero1_shards_opt_state_and_matches():
    """ZOO_SHARD_OPTIMIZER through the real Estimator path (GSPMD
    sharding constraints): optimizer moments end up sharded over the
    data axis, and training matches the replicated-optimizer run
    bit-for-equal math."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    rng_np = np.random.default_rng(21)
    x = rng_np.normal(size=(128, 16)).astype(np.float32)
    w = rng_np.normal(size=(16, 1)).astype(np.float32)
    y = (x @ w).astype(np.float32)

    def run(shard):
        init_zoo_context({"shard_optimizer": shard}, seed=3)
        m = Sequential()
        m.add(Dense(8, activation="tanh", input_shape=(16,)))
        m.add(Dense(1))
        m.compile(optimizer="adam", loss="mse")
        m.fit(x, y, batch_size=32, nb_epoch=3)
        est = m._estimator
        return m.params, est._opt_state

    p_ref, _ = run(False)
    p_sh, opt_sh = run(True)

    # moments sharded over data where dim0 divides; scalars replicated
    from analytics_zoo_tpu.common.engine import get_zoo_context

    dp = get_zoo_context().data_parallel_size
    assert dp > 1
    sharded_leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(opt_sh)
        if hasattr(leaf, "sharding") and leaf.ndim >= 1
        and leaf.shape[0] % dp == 0 and leaf.shape[0] > 0
    ]
    assert sharded_leaves, "no shardable optimizer leaves found"
    assert any(
        any(s is not None for s in (leaf.sharding.spec or ()))
        for leaf in sharded_leaves
    ), "optimizer state is fully replicated despite ZOO_SHARD_OPTIMIZER"

    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
