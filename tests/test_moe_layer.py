"""Model-surface routed MoE (ops/moe.py + TransformerLayer moe_experts).

Pins the three contracts VERDICT r4 asked for:
- the routed FFN equals the dense mixture when nothing is dropped
  (dense-dispatch oracle, same role as ep_moe_mlp for moe_mlp_topk);
- under adversarially skewed routing, over-capacity tokens lose their
  expert contribution but are NOT silently zeroed at the block output
  (residual passthrough), the drop fraction is reported exactly, and the
  load-balancing aux loss flags the collapse;
- the aux loss reaches the estimator's training loss through the layer
  state channel and its gradient actually pushes the router toward
  balance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops.moe import routed_ffn


def _moe_params(rng, d, e, m, gate_bias_to=None, gate_bias=10.0):
    ks = jax.random.split(rng, 4)
    gate = 0.1 * jax.random.normal(ks[0], (d, e))
    if gate_bias_to is not None:
        # force every token's softmax onto one expert
        gate = gate.at[:, gate_bias_to].add(gate_bias)
    return dict(
        gate_w=gate,
        w1=0.1 * jax.random.normal(ks[1], (e, d, m)),
        b1=jnp.zeros((e, m)),
        w2=0.1 * jax.random.normal(ks[2], (e, m, d)),
        b2=jnp.zeros((d,)),
    )


class TestRoutedFFN:
    def test_full_dispatch_matches_dense_mixture(self):
        """top_k=E with capacity >= S is exact dense MoE: the routed path
        must equal sum_e prob_e * MLP_e(x)."""
        d, e, m, b, s = 8, 4, 16, 2, 12
        p = _moe_params(jax.random.PRNGKey(0), d, e, m)
        x = jax.random.normal(jax.random.PRNGKey(1), (b, s, d))
        y, aux, drop = routed_ffn(x, p["gate_w"], p["w1"], p["b1"],
                                  p["w2"], p["b2"], top_k=e,
                                  capacity_factor=float(e))
        probs = jax.nn.softmax(x @ p["gate_w"], axis=-1)
        h1 = jax.nn.gelu(jnp.einsum("bsd,edf->bsef", x, p["w1"])
                         + p["b1"][None, None])
        dense = jnp.einsum("bsef,efd->bsed", h1, p["w2"])
        ref = jnp.einsum("bsed,bse->bsd", dense, probs) + p["b2"]
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        assert float(drop) == 0.0

    def test_skewed_routing_exact_drop_fraction_and_aux(self):
        """Every token wants expert 0: capacity keeps the first C tokens
        of each row, the rest are dropped — and the op SAYS so."""
        d, e, m, b, s = 8, 4, 16, 2, 64
        p = _moe_params(jax.random.PRNGKey(0), d, e, m, gate_bias_to=0)
        # positive tokens: the +10 column bias then dominates every
        # token's logit 0 (x @ (g0 + 10) ~ 10 * sum(x) > 0)
        x = jax.random.uniform(jax.random.PRNGKey(1), (b, s, d),
                               minval=0.5, maxval=1.5)
        cap = 16  # ceil(1.0 * 1 * 64 / 4)
        y, aux, drop = routed_ffn(x, p["gate_w"], p["w1"], p["b1"],
                                  p["w2"], p["b2"], top_k=1,
                                  capacity_factor=1.0)
        np.testing.assert_allclose(float(drop), 1.0 - cap / s, atol=1e-6)
        # balance loss ~ E when collapsed (vs ~1.0 balanced)
        assert float(aux) > 0.9 * e
        # kept tokens (first C of each row, priority = token order)
        # produce output; dropped tokens produce EXACT zero from the op
        norms = np.linalg.norm(np.asarray(y), axis=-1)
        assert (norms[:, :cap] > 1e-6).all()
        np.testing.assert_allclose(norms[:, cap:], 0.0, atol=1e-6)

    def test_balanced_routing_low_aux(self):
        d, e, m, b, s = 8, 4, 32, 4, 64
        p = _moe_params(jax.random.PRNGKey(3), d, e, m)
        x = jax.random.normal(jax.random.PRNGKey(4), (b, s, d))
        _, aux, drop = routed_ffn(x, p["gate_w"], p["w1"], p["b1"],
                                  p["w2"], p["b2"], top_k=2,
                                  capacity_factor=1.5)
        assert float(aux) < 1.3      # near 1.0 when balanced
        assert float(drop) < 0.15

    def test_aux_gradient_pushes_toward_balance(self):
        """d aux / d gate_w must be a real signal: one SGD step on the
        aux loss alone reduces it from a skewed start."""
        d, e, m, b, s = 8, 4, 16, 2, 32
        # mild skew: a saturated softmax would have a vanishing gradient
        p = _moe_params(jax.random.PRNGKey(0), d, e, m, gate_bias_to=0,
                        gate_bias=0.5)
        x = jax.random.uniform(jax.random.PRNGKey(1), (b, s, d),
                               minval=0.5, maxval=1.5)

        def aux_of(gate):
            return routed_ffn(x, gate, p["w1"], p["b1"], p["w2"], p["b2"],
                              top_k=2, capacity_factor=1.25)[1]

        a0, g = jax.value_and_grad(aux_of)(p["gate_w"])
        assert float(jnp.abs(g).max()) > 0.0
        a1 = aux_of(p["gate_w"] - 0.5 * g)
        assert float(a1) < float(a0)


class TestMoETransformerLayer:
    def _layer(self, **kw):
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            TransformerLayer,
        )

        kw.setdefault("hidden_drop", 0.0)
        kw.setdefault("attn_drop", 0.0)
        kw.setdefault("embedding_drop", 0.0)
        return TransformerLayer(vocab=32, seq_len=16, n_block=2, n_head=2,
                                hidden_size=16, moe_experts=4, moe_top_k=1,
                                moe_capacity_factor=1.0, **kw)

    def test_dropped_tokens_survive_via_residual(self):
        """The VERDICT r4 concern: at capacity, a degenerate router must
        not zero tokens at the BLOCK level.  Collapse the router post-init
        and check every output row keeps a healthy norm."""
        ly = self._layer()
        params = ly.init_params(jax.random.PRNGKey(0))
        for bp in params["blocks"]:
            # zero router -> all logits tie -> top_k picks expert 0 for
            # EVERY token (index tie-break): total collapse, input-free
            bp["moe_gate"] = jnp.zeros_like(bp["moe_gate"])
        tok = jnp.arange(16)[None, :].astype(jnp.int32).repeat(2, 0)
        out, st = ly.call(params, tok, training=False)
        # top_k=1, cf=1.0, E=4: capacity ceil(16/4)=4 of 16 -> 75% dropped
        np.testing.assert_allclose(float(st["moe_drop_fraction"]), 0.75,
                                   atol=1e-6)
        norms = np.linalg.norm(np.asarray(out), axis=-1)
        assert (norms > 1e-3).all()  # ...but no token was zeroed

    def test_state_structure_matches_init(self):
        ly = self._layer()
        params = ly.init_params(jax.random.PRNGKey(0))
        tok = jnp.zeros((2, 16), jnp.int32)
        _, st = ly.call(params, tok, training=True,
                        rng=jax.random.PRNGKey(1))
        init = ly.init_state()
        assert (jax.tree_util.tree_structure(st)
                == jax.tree_util.tree_structure(init))
        np.testing.assert_allclose(
            float(st["moe_aux_cost"]),
            0.01 * float(st["moe_aux_loss"]), rtol=1e-6)

    def test_moe_composes_with_remat(self):
        """jax.checkpoint around the block body must thread the routed
        FFN's aux outputs through the recompute unchanged."""
        ly = self._layer(remat="full")
        params = ly.init_params(jax.random.PRNGKey(0))
        tok = jnp.arange(16)[None, :].astype(jnp.int32).repeat(2, 0)

        def loss(p):
            out, st = ly.call(p, tok, training=True,
                              rng=jax.random.PRNGKey(1))
            return jnp.mean(out ** 2) + st["moe_aux_cost"], st

        (l, st), g = jax.value_and_grad(loss, has_aux=True)(params)
        assert np.isfinite(float(l))
        assert float(st["moe_aux_loss"]) > 0.0
        gate_g = g["blocks"][0]["moe_gate"]
        assert float(jnp.abs(gate_g).max()) > 0.0  # router still learns

        # remat off at identical params = identical forward
        ly2 = self._layer()
        out1, _ = ly.call(params, tok, training=False)
        out2, _ = ly2.call(params, tok, training=False)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6)

    def test_param_count_matches_tree(self):
        ly = self._layer()
        params = ly.init_params(jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(params))
        assert n == ly.param_count()

    def test_pipeline_builders_reject_moe(self):
        """The GPipe schedule would silently drop the aux loss; the stage
        builders must refuse MoE stacks outright."""
        from analytics_zoo_tpu.parallel.pipeline import (
            transformer_gpipe,
            transformer_gpipe_lm,
        )

        ly = self._layer()
        params = ly.init_params(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="dense blocks only"):
            transformer_gpipe(ly, params, jnp.zeros((2, 16, 16)),
                              n_microbatch=2)
        with pytest.raises(ValueError, match="dense blocks only"):
            transformer_gpipe_lm(ly, params, jnp.zeros((16, 32)),
                                 jnp.zeros((32,)),
                                 jnp.zeros((2, 16), jnp.int32),
                                 n_microbatch=2)

    def test_strategies_steps_include_aux(self):
        """make_shard_map_train_step must also add the state-channel aux
        cost — every model.forward-based loss does, not just the
        estimator's (review finding, round 5)."""
        import optax

        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.parallel.strategies import (
            make_shard_map_train_step,
        )
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Dense,
            Flatten,
        )
        from analytics_zoo_tpu.pipeline.api.keras.objectives import (
            get_loss,
        )

        zoo.init_zoo_context(seed=5, mesh_shape={"data": 8})
        m = Sequential()
        m.add(self._layer(input_shape=(16,)))
        m.add(Flatten())
        m.add(Dense(2, activation="softmax"))
        params, state = m.build_params()
        loss_fn = get_loss("sparse_categorical_crossentropy")
        opt = optax.sgd(0.0)  # lr 0: params unchanged, loss comparable
        step = make_shard_map_train_step(m, loss_fn, opt)

        rng = np.random.default_rng(0)
        x = rng.integers(0, 32, size=(16, 16)).astype(np.int32)
        y = rng.integers(0, 2, size=(16,)).astype(np.int32)
        batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
        p2, _, new_state, l = step(params, opt.init(params), state,
                                   jax.random.PRNGKey(0), batch)
        preds, st2 = m.forward(p2, batch["x"], state=state, training=True,
                               rng=jax.random.PRNGKey(0))
        task = float(loss_fn.mean(batch["y"], preds))
        aux_cost = [float(v["moe_aux_cost"]) for v in st2.values()
                    if isinstance(v, dict) and "moe_aux_cost" in v][0]
        assert aux_cost > 0.0
        np.testing.assert_allclose(float(l), task + aux_cost, rtol=1e-5)

    def test_shard_map_step_with_expert_axis_runs(self):
        """Review finding (r5): with an expert axis in the mesh, the
        sharding constraint inside routed_ffn must not blow up the
        shard_map train steps (manual axes reject constraints — the op
        falls back to shard-local compute there)."""
        import optax

        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.parallel.strategies import (
            make_shard_map_train_step,
        )
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Dense,
            Flatten,
        )
        from analytics_zoo_tpu.pipeline.api.keras.objectives import (
            get_loss,
        )

        zoo.init_zoo_context(seed=5, mesh_shape={"data": 4, "expert": 2},
                             mesh_axes=("data", "expert"))
        m = Sequential()
        m.add(self._layer(input_shape=(16,)))
        m.add(Flatten())
        m.add(Dense(2, activation="softmax"))
        params, state = m.build_params()
        opt = optax.sgd(0.1)
        step = make_shard_map_train_step(
            m, get_loss("sparse_categorical_crossentropy"), opt)
        rng = np.random.default_rng(0)
        batch = {"x": jnp.asarray(
            rng.integers(0, 32, size=(8, 16)).astype(np.int32)),
            "y": jnp.asarray(rng.integers(0, 2, size=(8,))
                             .astype(np.int32))}
        p2, _, _, l = step(params, opt.init(params), state,
                           jax.random.PRNGKey(0), batch)
        assert np.isfinite(float(l))

    def test_fit_includes_aux_and_learns(self):
        """End to end through the estimator: the training loss includes
        the pre-weighted aux cost, and a tiny copy task still learns."""
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import (
            Dense,
            Flatten,
        )

        zoo.init_zoo_context(seed=11)
        rng = np.random.default_rng(0)
        x = rng.integers(0, 32, size=(128, 16)).astype(np.int32)
        y = (x[:, 0] % 2).astype(np.int32)  # depends on token 0 identity

        m = Sequential()
        m.add(self._layer(input_shape=(16,)))
        m.add(Flatten())
        m.add(Dense(2, activation="softmax"))
        m.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        m.fit(x, y, batch_size=32, nb_epoch=8)
        ev = m.evaluate(x, y)
        assert ev["accuracy"] > 0.8, ev
        # the stack's state leaves surfaced through fit
        st = m.state
        (tl_state,) = [v for k, v in st.items() if "moe_aux_loss" in v]
        assert float(tl_state["moe_aux_loss"]) > 0.0


# -- experts without a gate (relu² experts of nemotron-3-nano-30b-a3b) ------

NEMOTRON_TOY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "chunk_size": 16,
    "n_routed_experts": 128, "router_width": 128,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 64,
    "num_experts_per_tok": 6, "vocab_size": 32, "n_positions": 32}


@pytest.fixture(scope="module")
def nemotron():
    from benchmark.manifest import Manifest

    cfg = Manifest().configuration("nemotron-3-nano-30b-a3b", NEMOTRON_TOY)
    reference = cfg.module("reference")
    # the first block, (M, E): a routed layer that holds all 128 experts
    bp = reference.init_params(jax.random.PRNGKey(9),
                               cfg.sizes)[reference.CORE]["blocks"][0]
    u = jnp.asarray(np.random.default_rng(1).normal(
        size=(96, 64)).astype(np.float32))
    return cfg.sizes, reference, bp, u


def _relu2_shared(bp, u):
    return jnp.square(jax.nn.relu(u @ bp["shared_fc_kernel"])) \
        @ bp["shared_out_kernel"]


def test_the_sixteen_shares_add_up_to_the_uncut_layer(nemotron):
    """Nemotron's routing: 128 relu² experts without a gate, the top 6 a
    token.  16 workers hold experts 0-7, 8-15, ..., 120-127.  The routed
    parts that the 16 give, with the shared expert counted once, are what
    the uncut reference gives for the whole layer; and the reference's own
    share of 8 is the program's."""
    from analytics_zoo_tpu.ops.activations import get_activation
    from analytics_zoo_tpu.ops.moe import held_experts_ffn
    from benchmark import narrow

    sizes, reference, bp, u = nemotron
    assert "experts_gate" not in bp
    qs = narrow.rounders(None)
    whole = reference._routed(qs, sizes, bp, u)
    total, held = _relu2_shared(bp, u), 0.0
    relu2 = get_activation("relu2")

    @jax.jit
    def held_part(first):
        # one compiled part, the share it holds an argument
        return held_experts_ffn(
            u, bp["router_kernel"], bp["router_bias"], None,
            *(jax.lax.dynamic_slice_in_dim(bp[k], first, 8)
              for k in ("experts_up", "experts_down")), first_held=first,
            top_k=6, routed_scale=sizes["routed_scaling_factor"],
            activation=relu2)

    parts = {}
    for first in range(0, 128, 8):
        part, stats = held_part(first)
        parts[first] = part
        total = total + part
        held += float(stats["held_assignments"])
        assert float(stats["dropped_assignments"]) == 0.0
    assert held == 6 * 96       # every assignment fell on one of the 16
    np.testing.assert_allclose(total, whole, atol=2e-6)
    share = {**bp, "experts_up": bp["experts_up"][40:48],
             "experts_down": bp["experts_down"][40:48]}
    got = reference._routed(
        qs, {**sizes, "n_routed_experts": 8, "experts_held_from": 40},
        share, u) - _relu2_shared(bp, u)
    np.testing.assert_allclose(got, parts[40], atol=2e-6)


@pytest.mark.parametrize("route", ["fallback", "interpret"])
def test_experts_without_a_gate_and_their_gradients(nemotron, route,
                                                    monkeypatch):
    """act(u Wup) Wdown a held expert, through the walk and its backward
    rule (two grouped products a window forward): the part and the
    gradients of the tokens, the router and both matrices against the
    plain masked sum over the held experts; in interpret mode through the
    grouped kernels, rows they must not write left unread."""
    from analytics_zoo_tpu.ops.activations import get_activation
    from analytics_zoo_tpu.ops.moe import held_experts_ffn, sigmoid_route

    if route == "interpret":
        monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
    sizes, _, bp, u = nemotron
    relu2 = get_activation("relu2")
    up, down = bp["experts_up"][:8], bp["experts_down"][:8]

    def program(u, router, up, down):
        return held_experts_ffn(u, router, bp["router_bias"], None, up, down,
                                first_held=0, top_k=6, routed_scale=2.5,
                                activation=relu2)[0]

    def plain(u, router, up, down):
        experts, weights = sigmoid_route(u, router, bp["router_bias"],
                                         top_k=6, routed_scale=2.5)
        out = jnp.zeros_like(u)
        for e in range(8):
            w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
            out = out + w[:, None] * (relu2(u @ up[e]) @ down[e])
        return out

    g = jnp.asarray(np.random.default_rng(3).normal(
        size=u.shape).astype(np.float32))
    args = (u, bp["router_kernel"], up, down)
    np.testing.assert_allclose(program(*args), plain(*args), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * g), argnums=(0, 1, 2, 3))(
        *args)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * g), argnums=(0, 1, 2, 3))(
        *args)
    for name, a, b in zip(("u", "router", "up", "down"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)
