"""Latent attention, the routed feed-forward without dropped tokens and the
decoder built from them (``keras/layers/self_attention.py``,
``ops/moe.py``), at toy size on the CPU in float32, against the plain
reference of the ``kanana-2-30b-a3b`` configuration
(``benchmark/configs/kanana-2-30b-a3b/reference.py``, which imports nothing
of the program)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import moe
from analytics_zoo_tpu.ops.pallas import grouped_matmul as grouped
from analytics_zoo_tpu.pipeline.api.keras.engine import training_targets
from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention
from benchmark import narrow
from benchmark.manifest import Manifest

#: hidden 64, 4 heads of 16 + 8 (values 16), latent 32, 1 dense + 2 routed
#: layers, 16 routed experts of width 32 with 4 held, top-3, 2 shared,
#: vocabulary 128 sliced to 32, 32 tokens
TOY = {"hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 16,
       "kv_lora_rank": 32, "num_hidden_layers": 3, "n_routed_experts": 4,
       "router_width": 16, "moe_intermediate_size": 32,
       "num_experts_per_tok": 3, "intermediate_size": 96, "vocab_size": 32,
       "n_positions": 32}
BATCH = 4


@pytest.fixture(scope="module")
def cfg():
    return Manifest().configuration("kanana-2-30b-a3b", TOY)


@pytest.fixture(scope="module")
def reference(cfg):
    return cfg.module("reference")


@pytest.fixture(scope="module")
def batch(cfg):
    rng = np.random.default_rng(13)
    shape = (BATCH, cfg.sizes["n_positions"])
    return (jnp.asarray(rng.integers(0, 32, shape).astype(np.int32)),
            jnp.asarray(rng.integers(0, 32, shape).astype(np.int32)))


@pytest.fixture(scope="module")
def weights(cfg, reference):
    return reference.init_params(jax.random.PRNGKey(5), cfg.sizes)


@pytest.fixture(scope="module")
def net(cfg):
    from analytics_zoo_tpu import init_zoo_context

    init_zoo_context("latent moe decoder", seed=3)
    model = cfg.module("model").build(cfg.sizes)
    _built, state = model.build_params()
    return model, state


def _program_loss(net, params, x, y):
    model, state = net
    with training_targets(y):
        _, new_state = model.forward(params, x, state=state, training=True)
    return moe.collect_aux_cost(new_state), new_state


def _against_the_reference(net, reference, sizes, weights, x, y):
    """The program's and the reference's logits, then each one's loss and
    gradient of every leaf, traced and compiled as one program:
    ``((logits, reference logits), ((loss, new state), grads), (reference
    loss, reference grads))``."""
    model, state = net

    def both(p):
        logits, _ = model.forward(p, x, state=state, training=False)
        return (logits, reference.logits(p, x, sizes)), \
            jax.value_and_grad(lambda p: _program_loss(net, p, x, y),
                               has_aux=True)(p), \
            jax.value_and_grad(reference.loss_fn)(p, x, y, sizes)

    return jax.jit(both)(weights)


def test_logits_loss_and_every_gradient_against_the_reference(
        net, cfg, reference, weights, batch):
    model, state = net
    built, _ = model.build_params()
    assert jax.tree_util.tree_structure(built) \
        == jax.tree_util.tree_structure(weights)
    x, y = batch
    (logits, want), ((loss, new_state), grads), (ref_loss, ref_grads) = \
        _against_the_reference(net, reference, cfg.sizes, weights, x, y)
    np.testing.assert_allclose(logits, want, atol=2e-6)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            # b picks, by integers: it is a leaf and takes no gradient
            assert not np.any(got) and not np.any(ref), name
            continue
        assert float(jnp.abs(ref).max()) > 0, name
        np.testing.assert_allclose(
            got, ref, atol=2e-5 * float(jnp.abs(ref).max()), err_msg=name)
    layer = new_state["kanana"]
    assert layer["moe_held_assignments"].shape == (2,)
    assert float(layer["moe_dropped_assignments"]) == 0.0
    record = self_attention.decoder_records[-1]
    assert (record["dense_layers"], record["routed_layers"]) == (1, 2)
    assert (record["router_width"], record["experts_held"],
            record["experts_held_from"], record["experts_per_token"],
            record["capacity_factor"]) == (16, 4, 0, 3, None)
    assert (record["qk_width"], record["value_width"]) == (24, 16)
    assert "moe_route" in record["kept"] and record["loss_blocks"] == 1


def _layer_inputs(cfg, reference, weights, seed=0):
    """One routed layer's leaves and (T, d) tokens to feed it."""
    bp = weights[reference.CORE]["blocks"][1]
    u = jnp.asarray(np.random.default_rng(seed).normal(
        size=(BATCH * 32, cfg.sizes["hidden_size"])).astype(np.float32))
    return bp, u


def _whole_layer(cfg, reference, key):
    """A routed layer that holds all 16 experts: the uncut reference."""
    sizes = {**cfg.sizes, "n_routed_experts": 16}
    bp = reference.init_params(key, sizes)[reference.CORE]["blocks"][1]
    return sizes, bp


def test_the_shares_add_up_to_the_uncut_layer(cfg, reference):
    """Four workers hold experts 0-3, 4-7, 8-11, 12-15.  The routed parts
    that the four give, with the shared experts counted once, are what the
    uncut reference gives for the whole layer."""
    sizes, bp = _whole_layer(cfg, reference, jax.random.PRNGKey(9))
    u = jnp.asarray(np.random.default_rng(1).normal(
        size=(128, 64)).astype(np.float32))
    qs = narrow.rounders(None)
    whole = reference._feed_forward(qs, sizes, bp, u)
    shared = (jax.nn.silu(u @ bp["shared_gate_kernel"])
              * (u @ bp["shared_fc_kernel"])) @ bp["shared_out_kernel"]
    total, held = shared, 0.0
    for first in (0, 4, 8, 12):
        part, stats = moe.held_experts_ffn(
            u, bp["router_kernel"], bp["router_bias"],
            *(bp[k][first:first + 4] for k in
              ("experts_gate", "experts_up", "experts_down")),
            first_held=first, top_k=3,
            routed_scale=sizes["routed_scaling_factor"])
        total = total + part
        held += float(stats["held_assignments"])
        assert float(stats["dropped_assignments"]) == 0.0
    assert held == 3 * 128      # every assignment fell on one of the four
    np.testing.assert_allclose(total, whole, atol=2e-6)
    # the reference's own share is the same part
    for first in (0, 8):
        share = {**bp, **{k: bp[k][first:first + 4] for k in
                          ("experts_gate", "experts_up", "experts_down")}}
        got = reference._feed_forward(
            qs, {**sizes, "experts_held_from": first}, share, u) - shared
        want, _ = moe.held_experts_ffn(
            u, bp["router_kernel"], bp["router_bias"],
            share["experts_gate"], share["experts_up"],
            share["experts_down"], first_held=first, top_k=3,
            routed_scale=sizes["routed_scaling_factor"])
        np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("favoured, rows", [((2, 9, 10), 128),
                                            ((0, 1, 2), 384)],
                         ids=["one_held_expert", "the_whole_buffer"])
def test_a_skewed_router_drops_nothing(cfg, reference, weights, favoured,
                                       rows):
    """Every token picks the same three experts: expert 2 alone of the
    held ones takes all 128 tokens, or the three held ones fill the buffer
    of 3 x 128 rows.  Nothing is dropped and the result is the
    reference's."""
    bp, u = _layer_inputs(cfg, reference, weights)
    bias = jnp.zeros((16,)).at[jnp.asarray(favoured)].set(4.0)
    bp = {**bp, "router_bias": bias}
    got, stats = moe.held_experts_ffn(
        u, bp["router_kernel"], bias, bp["experts_gate"], bp["experts_up"],
        bp["experts_down"], first_held=0, top_k=3,
        routed_scale=cfg.sizes["routed_scaling_factor"])
    assert float(stats["held_assignments"]) == rows
    assert float(stats["dropped_assignments"]) == 0.0
    held_favoured = sum(e < 4 for e in favoured)
    assert float(stats["load_max_over_mean"]) == pytest.approx(
        4 / held_favoured)
    want = _routed_part(cfg.sizes, reference, bp, u)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(want).max()) > 1e-3


def test_the_bias_picks_and_does_not_weigh(cfg, reference, weights):
    bp, u = _layer_inputs(cfg, reference, weights, seed=2)
    scale = cfg.sizes["routed_scaling_factor"]
    s = jax.nn.sigmoid(u @ bp["router_kernel"])
    plain, _ = moe.sigmoid_route(u, bp["router_kernel"], jnp.zeros((16,)),
                                 top_k=3, routed_scale=scale)
    picked, w = moe.sigmoid_route(u, bp["router_kernel"], bp["router_bias"],
                                  top_k=3, routed_scale=scale)
    # the seeded b changes the selection of some tokens ...
    changed = np.any(np.sort(np.asarray(plain), -1)
                     != np.sort(np.asarray(picked), -1), axis=-1)
    assert 0 < changed.sum() < len(changed)
    # ... and the weights are the scores themselves at the picked experts
    at = jnp.take_along_axis(s, picked, axis=-1)
    np.testing.assert_allclose(
        w, at / at.sum(-1, keepdims=True) * scale, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), scale, rtol=1e-6)
    # the reference notices its absence
    with_b = reference.route(cfg.sizes, bp, u)
    without = reference.route(
        cfg.sizes, {**bp, "router_bias": jnp.zeros((16,))}, u)
    assert np.any(np.asarray(with_b) != np.asarray(without))


def test_the_grouped_kernel_in_interpret_mode(cfg, reference, weights,
                                              monkeypatch):
    """The Pallas grouped product (interpret mode) gives the fallback's
    result and gradients, rows past the held ones masked both ways."""
    bp, u = _layer_inputs(cfg, reference, weights, seed=3)
    g = jnp.asarray(np.random.default_rng(4).normal(
        size=u.shape).astype(np.float32))

    def run(u, gate, up, down):
        y, _ = moe.held_experts_ffn(
            u, bp["router_kernel"], bp["router_bias"], gate, up, down,
            first_held=0, top_k=3, routed_scale=2.0)
        return jnp.sum(y * g)

    args = (u, bp["experts_gate"], bp["experts_up"], bp["experts_down"])
    want = jax.value_and_grad(run, argnums=(0, 1, 2, 3))(*args)
    before = dict(grouped.invocation_counts)
    monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
    got = jax.value_and_grad(run, argnums=(0, 1, 2, 3))(*args)
    # the one walk as traced: three products and the sum of a token's rows
    # in the forward rule; in the backward rule the gate and up products
    # made again, the cotangent through the down product, the three
    # experts' gradients, the rows' two and the sum of a token's rows
    assert grouped.invocation_counts["pallas"] == before["pallas"] + 4 + 9
    assert grouped.invocation_counts["fallback"] == before["fallback"]
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))


#: 384 tokens (three tiles of the row sum), top-3 of 16 experts, 4 held:
#: 1,152 sorted rows, walked in windows of R = ``walk_bound`` of them
WALK_TOKENS = 384


def _bias_holding(s, rows):
    """A ``score_bias`` under which exactly ``rows`` of the 3 x 384
    assignments fall on held experts (0-3): ``rows // 384`` of a token's
    picks are held experts for every token, the others experts 10 and 11
    (held elsewhere), and the last is expert 9 (held elsewhere) but for
    the ``rows % 384`` tokens whose score of expert 3 is furthest above
    that of expert 9: they pick expert 3."""
    sure, extra = divmod(rows, WALK_TOKENS)
    if sure == 3:
        return jnp.zeros((16,)).at[jnp.arange(3)].set(4.0)
    lead = np.sort(np.asarray(s[:, 3] - s[:, 9]))[::-1]
    margin = 1.0 if extra == 0 \
        else float(lead[extra - 1] + lead[extra]) / 2
    picked = list(range(sure)) + [10, 11][:2 - sure]
    return jnp.zeros((16,)).at[jnp.asarray(picked)].set(4.0) \
        .at[9].set(2.0).at[3].set(2.0 - margin)


#: case -> (held rows as a function of R, or None for the seeded bias;
#: windows as reckoned at R = 512)
WALKS = {
    "nothing_held": (lambda r: 0, 0),
    "held_under_a_window": (None, 1),
    "held_rows_fill_a_window": (lambda r: r, 1),
    "one_row_past_a_window": (lambda r: r + 1, 2),
    "every_pick_held": (lambda r: 3 * WALK_TOKENS, 3),
}


def _routed_part(cfg, reference, bp, u):
    """The per-expert oracle: the reference's feed-forward less its shared
    experts."""
    shared = (jax.nn.silu(u @ bp["shared_gate_kernel"])
              * (u @ bp["shared_fc_kernel"])) @ bp["shared_out_kernel"]
    return reference._feed_forward(narrow.rounders(None), cfg, bp, u) \
        - shared


LEAVES = ("router_kernel", "experts_gate", "experts_up", "experts_down")


def _against_the_oracle(sizes, reference, bp, u, windows, rows=None):
    """Values and the five gradients (tokens, router, the three expert
    matrices) of ``held_experts_ffn`` against the per-expert oracle."""
    g = jnp.asarray(np.random.default_rng(8).normal(
        size=u.shape).astype(np.float32))

    def program(u, *leaves):
        y, stats = moe.held_experts_ffn(
            u, leaves[0], bp["router_bias"], *leaves[1:], first_held=0,
            top_k=3, routed_scale=sizes["routed_scaling_factor"])
        return jnp.sum(y * g), (y, stats)

    def oracle(u, *leaves):
        y = _routed_part(sizes, reference,
                         {**bp, **dict(zip(LEAVES, leaves))}, u)
        return jnp.sum(y * g), y

    args = (u,) + tuple(bp[k] for k in LEAVES)
    (_, (y, stats)), got = jax.value_and_grad(
        program, argnums=range(5), has_aux=True)(*args)
    (_, want_y), want = jax.value_and_grad(
        oracle, argnums=range(5), has_aux=True)(*args)
    assert float(stats["dropped_assignments"]) == 0.0
    assert float(stats["walk_windows"]) == windows
    if rows is not None:
        assert float(stats["held_assignments"]) == rows
    np.testing.assert_allclose(y, want_y, atol=2e-6)
    for name, a, b in zip(("u",) + LEAVES, got, want):
        assert np.all(np.isfinite(a)), name
        if rows == 0:
            # no window runs: zeros, where the oracle leaves rounding
            assert not np.any(a) and float(jnp.abs(b).max()) < 1e-8, name
            continue
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(
            a, b, atol=2e-5 * float(jnp.abs(b).max()), err_msg=name)
    if rows != 0:
        assert float(jnp.abs(want_y).max()) > 1e-3


@pytest.mark.parametrize("kernel", ["fallback", "interpret"])
@pytest.mark.parametrize("case", list(WALKS) + ["every_expert_held"])
def test_the_walk_runs_as_many_windows_as_the_held_rows_fill(
        cfg, reference, weights, monkeypatch, case, kernel):
    """``held_experts_ffn`` walks its 1,152 sorted rows in windows of R =
    512, one compiled walk run ceil(held / R) times: equal to the
    per-expert oracle in value and in all five gradients with nothing
    held (no window), at seeded routing, with the held rows filling a
    window and one past it (a second window for one row), and with every
    pick on a held expert (three windows); nothing is dropped.  A worker
    that holds all sixteen experts has R = 1,152, one window and no
    loop."""
    if kernel == "interpret":
        monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
    u = jnp.asarray(np.random.default_rng(7).normal(
        size=(WALK_TOKENS, 64)).astype(np.float32))
    if case == "every_expert_held":
        sizes, bp = _whole_layer(cfg, reference, jax.random.PRNGKey(9))
        assert moe.walk_bound(3 * WALK_TOKENS, 16, 16) == 3 * WALK_TOKENS
        _against_the_oracle(sizes, reference, bp, u, 1, 3 * WALK_TOKENS)
        return
    bound = moe.walk_bound(3 * WALK_TOKENS, 4, 16)
    assert bound == 512
    bp = weights[reference.CORE]["blocks"][1]
    held, windows = WALKS[case]
    rows = None if held is None else held(bound)
    if rows is not None:
        bp = {**bp, "router_bias": _bias_holding(
            jax.nn.sigmoid(u @ bp["router_kernel"]), rows)}
    _against_the_oracle(cfg.sizes, reference, bp, u, windows, rows)


def _equations(jaxpr):
    """Every equation of a jaxpr and of what it calls, a kernel's own body
    left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("held, loops", [(4, 2), (16, 0)])
def test_the_traced_gradient_holds_one_walk(cfg, reference, weights, held,
                                            loops):
    """The gradient's trace of a routed layer, under a checkpoint that
    keeps the route as the decoder's does: no ``cond``, one ``while`` in
    the forward rule and one in the backward rule (none for a worker that
    holds every expert), and no array of all 1,152 sorted rows by the
    hidden (64) or the experts' (32) width: the walk's arrays have R = 512
    rows."""
    if held == 16:
        _sizes, bp = _whole_layer(cfg, reference, jax.random.PRNGKey(9))
    else:
        bp = weights[reference.CORE]["blocks"][1]
    u = jnp.zeros((WALK_TOKENS, 64))

    def loss(u, *leaves):
        run = jax.checkpoint(
            lambda u, *leaves: moe.held_experts_ffn(
                u, leaves[0], bp["router_bias"], *leaves[1:], first_held=0,
                top_k=3, routed_scale=2.0)[0],
            policy=jax.checkpoint_policies.save_only_these_names(
                moe.ROUTE_NAME))
        return jnp.sum(jnp.square(run(u, *leaves)))

    eqns = list(_equations(jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(
        u, *(bp[k] for k in LEAVES)).jaxpr))
    names = [eqn.primitive.name for eqn in eqns]
    assert names.count("cond") == 0
    assert names.count("while") == loops
    shapes = {v.aval.shape for eqn in eqns for v in eqn.outvars}
    wide = {(3 * WALK_TOKENS, 64), (3 * WALK_TOKENS, 32)}
    if held == 16:
        assert wide <= shapes
    else:
        assert not wide & shapes
        assert {(512, 64), (512, 32)} <= shapes


@pytest.mark.parametrize("policy, sorts", [("attn", 3), ("full", 4)])
def test_the_route_is_kept_and_not_sorted_twice(net, weights, policy, sorts):
    """Under the decoder's policy the backward pass of a routed layer
    application reads the sort's permutation from what was kept: its
    gradient holds the route's sort once, under ``"full"`` twice.  Beside
    it a window puts its R rows in their tokens' order, in the forward
    rule's walk and in the backward rule's (0.02 ms on the chip; PERF.md,
    PR 33); under ``"full"`` the forward rule's walk made again is dead
    code and is not traced."""
    from analytics_zoo_tpu.parallel.plan import apply_remat

    layer = net[0].layers[-1]
    bp = weights["kanana"]["blocks"][1]
    h = jnp.asarray(np.random.default_rng(6).normal(
        size=(BATCH, 32, 64)).astype(np.float32))
    body = apply_remat(layer._block_forward_aux, policy, static_argnums=(3,))
    text = str(jax.make_jaxpr(jax.grad(lambda bp, h: jnp.sum(jnp.square(
        body(bp, h, None, True, None)[0])), argnums=(0, 1)))(bp, h))
    assert text.count(" sort[") == sorts


@pytest.mark.parametrize("favoured", [(), (0, 1)],
                         ids=["seeded", "past_a_window"])
def test_fit_with_the_models_own_loss_publishes_the_gauges(cfg, weights,
                                                           favoured):
    """Two epochs of one batch through ``fit``: the loss falls and the
    routed layers' counts of the last step are published.  8 x 32 tokens x
    3 picks over 16 experts, 4 held: 192 rows expected under the seeded
    bias, in one window of R = 256 (a layer that holds more, in two); with
    a bias that sends every token to
    held experts 0 and 1 a layer holds 512 rows and more and walks them in
    two windows or three."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.metrics import snapshot

    init_zoo_context("latent moe decoder fit", seed=3)
    model_py = cfg.module("model")
    model = model_py.build(cfg.sizes)
    model.build_params()
    model.params = jax.tree_util.tree_map(jnp.array, weights)
    for bp in model.params["kanana"]["blocks"][1:]:
        bp["router_bias"] = bp["router_bias"].at[jnp.asarray(
            favoured, jnp.int32)].add(4.0)
    rng = np.random.default_rng(5)
    x = rng.integers(0, 32, (8, 32)).astype(np.int32)
    y = rng.integers(0, 32, (8, 32)).astype(np.int32)
    model.fit(model_py.feature_set(x, y, cfg.sizes), batch_size=8,
              nb_epoch=2)
    history = [h["loss"] for h in model._estimator.history]
    assert history[1] < history[0] < np.log(32) + 0.2
    assert model_py.routing_fault("cpu") is None
    gauges = {(s["name"], (s.get("labels") or {}).get("label", "")):
              s["value"] for s in snapshot()["samples"]
              if s["name"].startswith("zoo_moe_")}
    assert gauges[("zoo_moe_dropped_assignments", "")] == 0.0
    bound = moe.walk_bound(3 * 8 * 32, 4, 16)
    assert bound == 256
    for layer in ("1", "2"):
        held = gauges[("zoo_moe_held_assignments", layer)]
        windows = gauges[("zoo_moe_walk_windows", layer)]
        assert windows == -(-held // bound)
        if favoured:
            assert 512 <= held <= 768 and windows >= 2
        else:
            assert 96 < held < 288 and windows <= 2
            assert 1.0 <= gauges[("zoo_moe_load_max_over_mean", layer)] <= 4.0
    # evaluate and predict read the logits, as with any other loss
    assert model.predict(x[:4]).shape == (4, 32, 32)


# -- the mixer by layer: Kimi Delta Attention and latent attention without
# -- positions, against the ``kimi-linear-48b-a3b`` configuration's reference

#: hidden 64, 2 KDA heads of 16 (4 taps), 2 latent heads of 16 + 8 (values
#: 16), latent 32, five layers KDA, KDA, KDA, MLA, KDA (1 dense + 4 routed),
#: 8 routed experts of width 32 with 2 held, top-2, 1 shared, vocabulary 32,
#: 80 tokens: a chunk of 80 tokens in five sub-blocks
KIMI_TOY = {
    "hidden_size": 64, "num_attention_heads": 2, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "linear_attn_config": {"full_attn_layers": [4], "head_dim": 16,
                           "kda_layers": [1, 2, 3, 5], "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "num_experts": 2, "router_width": 8, "moe_intermediate_size": 32,
    "num_experts_per_token": 2, "intermediate_size": 96, "vocab_size": 32,
    "n_positions": 80}


@pytest.fixture(scope="module")
def kimi():
    return Manifest().configuration("kimi-linear-48b-a3b", KIMI_TOY)


@pytest.fixture(scope="module")
def kimi_weights(kimi):
    return kimi.module("reference").init_params(jax.random.PRNGKey(7),
                                                kimi.sizes)


@pytest.fixture()
def kimi_net(kimi):
    from analytics_zoo_tpu import init_zoo_context

    init_zoo_context("kimi decoder", seed=3)
    model = kimi.module("model").build(kimi.sizes)
    _built, state = model.build_params()
    return model, state


def test_the_mixer_by_layer_against_the_reference(kimi_net, kimi,
                                                  kimi_weights):
    """Logits, loss and every leaf's gradient of the decoder whose layers
    mix by KDA, KDA, KDA, latent attention without positions, KDA."""
    model, state = kimi_net
    reference = kimi.module("reference")
    built, _ = model.build_params()
    assert jax.tree_util.tree_structure(built) \
        == jax.tree_util.tree_structure(kimi_weights)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(built),
        jax.tree_util.tree_leaves(kimi_weights)))
    layer = model.layers[-1]
    assert layer.param_count() == sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(built))
    rng = np.random.default_rng(17)
    x, y = (jnp.asarray(rng.integers(0, 32, (BATCH, 80)).astype(np.int32))
            for _ in range(2))
    (logits, want), ((loss, new_state), grads), (ref_loss, ref_grads) = \
        _against_the_reference(kimi_net, reference, kimi.sizes,
                               kimi_weights, x, y)
    np.testing.assert_allclose(logits, want, atol=3e-6)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(got) and not np.any(ref), name
            continue
        assert float(jnp.abs(ref).max()) > 0, name
        np.testing.assert_allclose(
            got, ref, atol=3e-5 * float(jnp.abs(ref).max()), err_msg=name)
    numbers = new_state["kimi"]
    assert numbers["moe_held_assignments"].shape == (4,)
    assert numbers["kda_chunk_log_decay_min"].shape == (4,)
    # a chunk of 80 tokens at the seeded decays: A up to 16, dt up to 0.1
    assert np.all(np.asarray(numbers["kda_chunk_log_decay_min"]) < -20.0)
    assert np.all(np.asarray(numbers["kda_chunk_log_decay_min"]) > -400.0)
    # a sub-block of 16 tokens stays inside the form's exact range
    sub = np.asarray(numbers["kda_sub_block_log_decay_min"])
    assert sub.shape == (4,) and np.all(sub < -4.0) and np.all(sub > -80.0)
    assert np.all(np.asarray(numbers["kda_state_rms"]) > 0.0)
    record = self_attention.decoder_records[-1]
    assert record["attention_by_layer"] == ["kda", "kda", "kda", "latent",
                                            "kda"]
    assert (record["attention"], record["rotary"]) == ("by_layer", False)
    assert (record["dense_layers"], record["routed_layers"]) == (1, 4)


def test_at_one_kind_of_attention_the_tree_and_the_outputs_are_the_same(
        net, cfg, weights, batch):
    """``attention="latent"`` with rotary positions is the decoder as it
    was: the leaves it had, no KDA number in its state, and the same
    outputs to the bit whether the kind is given once or a layer."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder

    model, state = net
    layer = model.layers[-1]
    assert layer.attention == "latent" and layer.kda is None
    assert sorted(weights["kanana"]["blocks"][1]) == [
        "experts_down", "experts_gate", "experts_up", "kv_a_kernel",
        "kv_a_norm", "kv_b_kernel", "ln1_gamma", "ln2_gamma", "o_kernel",
        "q_kernel", "router_bias", "router_kernel", "shared_fc_kernel",
        "shared_gate_kernel", "shared_out_kernel"]
    assert sorted(state["kanana"]) == [
        "lm_loss_cost", "moe_dropped_assignments", "moe_held_assignments",
        "moe_load_max_over_mean", "moe_walk_windows"]
    options = dict(
        vocab=32, n_block=3, n_head=4, hidden_size=64, intermediate_size=96,
        kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        routed_experts=16, experts_held=4, experts_per_token=3,
        expert_size=32, shared_experts=2, routed_scale=2.448,
        rotary_theta=1e6, name="kanana")
    x, _ = batch
    outputs = []
    for kinds in ("latent", ["latent"] * 3):
        one = LatentMoEDecoder(attention=kinds, **options)
        params = one.init_params(jax.random.PRNGKey(2))
        outputs.append((params, one.call(weights["kanana"], x)[0]))
    for a, b in zip(jax.tree_util.tree_leaves(outputs[0]),
                    jax.tree_util.tree_leaves(outputs[1])):
        np.testing.assert_array_equal(a, b)
    logits, _ = model.forward(weights, x, state=state, training=False)
    np.testing.assert_array_equal(outputs[0][1], logits)


def test_latent_attention_takes_rotary_or_none():
    """Without rotary positions latent attention takes any ``qk_rope_dim``
    (the slice is a width like another); with them an odd one has no
    pairs to turn, and says so."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder

    options = dict(
        vocab=32, n_block=2, n_head=2, hidden_size=32, intermediate_size=48,
        kv_latent_rank=16, qk_nope_dim=8, qk_rope_dim=5, v_head_dim=8,
        routed_experts=4, experts_per_token=2, expert_size=16)
    with pytest.raises(ValueError, match="qk_rope_dim 5 is odd"):
        LatentMoEDecoder(rotary_theta=1e4, **options)
    with pytest.raises(ValueError, match="for each of the 2 blocks"):
        LatentMoEDecoder(attention=["kda"], rotary_theta=None, **options)
    with pytest.raises(ValueError, match="latent attention or by KDA"):
        LatentMoEDecoder(attention=["full", "kda"], rotary_theta=None,
                         **options)
    layer = LatentMoEDecoder(rotary_theta=None, **options)
    params = jax.eval_shape(layer.init_params, jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).integers(0, 32, (2, 12)))
    assert jax.eval_shape(layer.call, params, x)[0].shape == (2, 12, 32)
    # no positions anywhere: through one layer a sequence's last token
    # sees a set of keys, whatever their order
    one = LatentMoEDecoder(rotary_theta=None, **{**options, "n_block": 1})
    params = jax.jit(one.init_params)(jax.random.PRNGKey(0))
    call = jax.jit(one.call)
    swapped = x.at[:, :11].set(x[:, 10::-1])
    np.testing.assert_allclose(call(params, swapped)[0][:, -1],
                               call(params, x)[0][:, -1], atol=1e-6)
    turned = LatentMoEDecoder(rotary_theta=1e4, **{
        **options, "n_block": 1, "qk_rope_dim": 4})
    params = jax.jit(turned.init_params)(jax.random.PRNGKey(0))
    call = jax.jit(turned.call)
    assert float(jnp.abs(call(params, swapped)[0][:, -1]
                         - call(params, x)[0][:, -1]).max()) > 1e-4


def test_a_window_is_four_thirds_of_the_even_share_or_an_eighth_of_all():
    """R from the shapes: four thirds of the even share, no fewer than an
    eighth of the assignments, up to the row tile, at most every
    assignment."""
    # kanana-2-30b-a3b-fit: 8,192 x 6 over 16 of 128, by its share
    assert moe.walk_bound(49152, 16, 128) == 8192
    # kimi-linear-48b-a3b-fit: 8,192 x 8 over 8 of 256: the share's 2,816
    # is under the eighth
    assert moe.walk_bound(65536, 8, 256) == 8192
    assert moe.walk_bound(65536, 64, 256) == 22016
    assert moe.walk_bound(768, 4, 16) == 256
    assert moe.walk_bound(768, 1, 64) == 256
    assert moe.walk_bound(768, 16, 16) == 768


def test_the_thirty_two_shares_add_up_to_the_uncut_layer(kimi):
    """This model's routing: 256 experts, the top 8 a token.  32 workers
    hold experts 0-7, 8-15, ..., 248-255.  The routed parts that the 32
    give, with the shared expert counted once, are what the uncut
    reference gives for the whole layer."""
    reference = kimi.module("reference")
    sizes = {**kimi.sizes, "num_experts": 256, "router_width": 256,
             "num_experts_per_token": 8}
    bp = reference.init_params(jax.random.PRNGKey(9),
                               sizes)[reference.CORE]["blocks"][1]
    u = jnp.asarray(np.random.default_rng(1).normal(
        size=(96, 64)).astype(np.float32))
    qs = narrow.rounders(None)
    whole = reference._feed_forward(qs, sizes, bp, u)
    total = (jax.nn.silu(u @ bp["shared_gate_kernel"])
             * (u @ bp["shared_fc_kernel"])) @ bp["shared_out_kernel"]

    @jax.jit
    def held_part(first):
        return moe.held_experts_ffn(
            u, bp["router_kernel"], bp["router_bias"],
            *(jax.lax.dynamic_slice_in_dim(bp[k], first, 8) for k in
              ("experts_gate", "experts_up", "experts_down")),
            first_held=first, top_k=8,
            routed_scale=sizes["routed_scaling_factor"])

    parts = [held_part(first) for first in range(0, 256, 8)]
    held = 0.0
    for part, stats in parts:
        total = total + part
        held += float(stats["held_assignments"])
        assert float(stats["dropped_assignments"]) == 0.0
    assert held == 8 * 96       # every assignment fell on one of the 32
    np.testing.assert_allclose(total, whole, atol=2e-6)
    # the reference's own share is the same part
    share = {**bp, **{k: bp[k][40:48] for k in
                      ("experts_gate", "experts_up", "experts_down")}}
    got = reference._feed_forward(
        qs, {**sizes, "num_experts": 8, "experts_held_from": 40}, share, u)
    want = parts[40 // 8][0]
    shared = total - sum(part for part, _ in parts)
    np.testing.assert_allclose(got - shared, want, atol=2e-6)


def test_fit_publishes_a_kda_layers_numbers(kimi, kimi_weights):
    """One epoch through ``fit``: the four KDA layers' two gauges of the
    last step are published beside the routed layers' counts."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.metrics import snapshot

    init_zoo_context("kimi decoder fit", seed=3)
    model_py = kimi.module("model")
    model = model_py.build(kimi.sizes)
    model.build_params()
    model.params = jax.tree_util.tree_map(jnp.array, kimi_weights)
    rng = np.random.default_rng(5)
    x = rng.integers(0, 32, (8, 80)).astype(np.int32)
    y = rng.integers(0, 32, (8, 80)).astype(np.int32)
    model.fit(model_py.feature_set(x, y, kimi.sizes), batch_size=8,
              nb_epoch=2)
    history = [h["loss"] for h in model._estimator.history]
    assert history[1] < history[0] < np.log(32) + 0.2
    assert model_py.routing_fault("cpu") is None
    gauges = {(s["name"], (s.get("labels") or {}).get("label", "")):
              s["value"] for s in snapshot()["samples"]
              if s["name"].startswith(("zoo_kda_", "zoo_moe_"))}
    for layer in ("1", "2", "3", "4"):
        assert -400.0 < gauges[("zoo_kda_chunk_log_decay_min", layer)] < -20.0
        assert -80.0 < gauges[("zoo_kda_sub_block_log_decay_min", layer)] \
            < -4.0
        assert 0.0 < gauges[("zoo_kda_state_rms", layer)] < 10.0
        assert gauges[("zoo_moe_walk_windows", layer)] >= 0.0
    assert ("zoo_kda_state_rms", "5") not in gauges
    assert gauges[("zoo_moe_dropped_assignments", "")] == 0.0


# -- blocks as records: Mamba-2 mixers, grouped-query attention without
# -- positions, relu² experts without a gate and a block without a
# -- feed-forward, against the ``nemotron-3-nano-30b-a3b`` reference

#: hidden 64, 4 SSD heads of 8 (state 16, 2 groups, 4 taps, chunks of 16),
#: 4 attention heads over 2 key-value heads of 16, the pattern's 7 layers
#: MEMEM*E as blocks (M, E) (M, E) (M, -) (*, E), 8 routed experts of width
#: 32 with 2 held, top-2, a shared expert of 64, vocabulary 32, 72 tokens
NEMOTRON_TOY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "chunk_size": 16,
    "n_routed_experts": 2, "router_width": 8, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64, "num_experts_per_tok": 2,
    "vocab_size": 32, "n_positions": 72}


@pytest.fixture(scope="module")
def nemotron():
    return Manifest().configuration("nemotron-3-nano-30b-a3b", NEMOTRON_TOY)


@pytest.fixture(scope="module")
def nemotron_weights(nemotron):
    return nemotron.module("reference").init_params(jax.random.PRNGKey(11),
                                                    nemotron.sizes)


@pytest.fixture()
def nemotron_net(nemotron):
    from analytics_zoo_tpu import init_zoo_context

    init_zoo_context("nemotron decoder", seed=3)
    model = nemotron.module("model").build(nemotron.sizes)
    _built, state = model.build_params()
    return model, state


def test_blocks_as_records_against_the_reference(nemotron_net, nemotron,
                                                 nemotron_weights):
    """Logits, loss and every leaf's gradient of the decoder whose blocks
    are (Mamba-2, relu² experts), twice, (Mamba-2, none) and (grouped-query
    attention, relu² experts), off the chunk grid (72 tokens in chunks of
    16); the tree, the parameter count, the state and the records."""
    from analytics_zoo_tpu.ops import ssd

    model, state = nemotron_net
    reference = nemotron.module("reference")
    built, _ = model.build_params()
    assert jax.tree_util.tree_structure(built) \
        == jax.tree_util.tree_structure(nemotron_weights)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(built),
        jax.tree_util.tree_leaves(nemotron_weights)))
    blocks = nemotron_weights["nemotron"]["blocks"]
    assert ["ssd_in_kernel" in bp for bp in blocks] == [True] * 3 + [False]
    assert "attn_q_kernel" in blocks[3]
    assert "ln2_gamma" not in blocks[2] and "router_kernel" not in blocks[2]
    assert "experts_gate" not in blocks[0] \
        and "shared_gate_kernel" not in blocks[0]
    layer = model.layers[-1]
    assert layer.param_count() == sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(built))
    rng = np.random.default_rng(19)
    x, y = (jnp.asarray(rng.integers(0, 32, (BATCH, 72)).astype(np.int32))
            for _ in range(2))
    with jax.default_matmul_precision("highest"):
        (logits, want), ((loss, new_state), grads), \
            (ref_loss, ref_grads) = _against_the_reference(
                nemotron_net, reference, nemotron.sizes, nemotron_weights,
                x, y)
    np.testing.assert_allclose(logits, want, atol=3e-6)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(got) and not np.any(ref), name
            continue
        assert float(jnp.abs(ref).max()) > 0, name
        np.testing.assert_allclose(
            got, ref, atol=3e-5 * float(jnp.abs(ref).max()), err_msg=name)
    numbers = new_state["nemotron"]
    assert numbers["moe_held_assignments"].shape == (3,)
    assert numbers["ssd_chunk_log_decay_min"].shape == (3,)
    assert np.all(np.asarray(numbers["ssd_chunk_log_decay_min"]) < 0.0)
    assert np.all(np.asarray(numbers["ssd_dt_max"]) > 0.0)
    assert np.all(np.asarray(numbers["ssd_state_rms"]) > 0.0)
    record = self_attention.decoder_records[-1]
    assert record["attention_by_layer"] == ["ssd", "ssd", "ssd", "full"]
    assert record["ffn_by_layer"] == ["routed", "routed", None, "routed"]
    assert (record["dense_layers"], record["routed_layers"]) == (0, 3)
    assert (record["kv_heads"], record["ssd"], record["experts_gated"]) \
        == (2, (4, 8, 16, 2, 4), False)
    assert "ssd_state" in record["kept"] and record["rotary"] is False
    assert {(r["rule"], r["groups"], r["chunk"], r["chunks"])
            for r in ssd.chunk_schedules} == {("ssd", 2, 16, 5)}


def test_the_pattern_reads_as_blocks(nemotron):
    """Two layers in a row, a mixer and the "E" after it, are one block;
    a mixer with no "E" after it a block without a feed-forward."""
    model_py = nemotron.module("model")
    assert model_py.blocks("MEMEM*E") == [("M", True), ("M", True),
                                          ("M", False), ("*", True)]
    assert model_py.blocks("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEM"
                           "EME")[-2:] == [("M", True), ("M", True)]
    with pytest.raises(ValueError, match="a mixer"):
        model_py.blocks("EM")
    assert nemotron.module("reference").blocks(
        {"hybrid_override_pattern": "MEMEM*E", "num_hidden_layers": 7}) \
        == model_py.blocks("MEMEM*E")


def test_a_layout_whose_records_of_a_kind_differ_is_refused():
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import (
        Experts,
    )
    from analytics_zoo_tpu.pipeline.api.keras.layers.ssd_mixer import (
        SSDMixer,
    )

    options = dict(vocab=32, n_block=2, n_head=2, hidden_size=32,
                   intermediate_size=48, routed_experts=4,
                   experts_per_token=2, expert_size=16)
    with pytest.raises(ValueError, match="the 'ssd' blocks differ"):
        LatentMoEDecoder(layout=[(SSDMixer(2, 8, 16, 1), None),
                                 (SSDMixer(2, 8, 16, 2), None)], **options)
    with pytest.raises(ValueError, match="a layout of 1 blocks for 2"):
        LatentMoEDecoder(layout=[(SSDMixer(2, 8, 16, 1), Experts())],
                         **options)
    # without a layout, routed experts are gated as gated_ffn says
    with pytest.raises(ValueError, match="ungated act"):
        self_attention.TransformerLayer(
            vocab=32, seq_len=8, n_block=1, n_head=2, hidden_size=32,
            routed_experts=4, experts_per_token=2, expert_size=16,
            use_bias=False)


def test_fit_publishes_an_ssd_layers_numbers(nemotron, nemotron_weights):
    """One epoch through ``fit``: the three SSD layers' gauges of the last
    step are published beside the routed layers' counts."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.metrics import snapshot

    init_zoo_context("nemotron decoder fit", seed=3)
    model_py = nemotron.module("model")
    model = model_py.build(nemotron.sizes)
    model.build_params()
    model.params = jax.tree_util.tree_map(jnp.array, nemotron_weights)
    rng = np.random.default_rng(5)
    x = rng.integers(0, 32, (8, 72)).astype(np.int32)
    y = rng.integers(0, 32, (8, 72)).astype(np.int32)
    model.fit(model_py.feature_set(x, y, nemotron.sizes), batch_size=8,
              nb_epoch=2)
    history = [h["loss"] for h in model._estimator.history]
    assert history[1] < history[0] < np.log(32) + 0.2
    assert model_py.routing_fault("cpu") is None
    gauges = {(s["name"], (s.get("labels") or {}).get("label", "")):
              s["value"] for s in snapshot()["samples"]
              if s["name"].startswith(("zoo_ssd_", "zoo_moe_"))}
    for layer in ("1", "2", "3"):
        assert gauges[("zoo_ssd_chunk_log_decay_min", layer)] < 0.0
        assert 0.0 < gauges[("zoo_ssd_dt_max", layer)] < 10.0
        assert 0.0 < gauges[("zoo_ssd_state_rms", layer)] < 100.0
        assert gauges[("zoo_moe_walk_windows", layer)] >= 0.0
    assert ("zoo_ssd_state_rms", "4") not in gauges
    assert gauges[("zoo_moe_dropped_assignments", "")] == 0.0


#: sha256 of the lowered text (CPU) of a toy decoder's logits, loss and
#: gradient at ``kanana-2-30b-a3b``'s and at ``kimi-linear-48b-a3b``'s
#: options, as the decoder lowered them before blocks could be records
WITHOUT_RECORDS = {
    "kanana": "f1840b58bdd872e100d98754f0ccd68e24a31edc1b2cd6fc70c1f559e05e170c",
    "kimi": "07a7ea7c021e93a7f412b745869f18232b41554983a26a0ec5559fdddbc01778"}


@pytest.mark.parametrize("which", sorted(WITHOUT_RECORDS))
def test_without_records_the_trees_and_programs_are_the_earlier_ones(
        which, cfg, kimi):
    """At the two earlier routed configurations' options (no layout) the
    decoder's tree is the reference's and its program, logits, loss and
    gradient, lowers to the text it lowered to before a block could be
    described by records."""
    import hashlib

    from analytics_zoo_tpu import init_zoo_context

    sizes = (cfg if which == "kanana" else kimi)
    init_zoo_context("pin", seed=3)
    model = sizes.module("model").build(sizes.sizes)
    built, state = model.build_params()
    w = sizes.module("reference").init_params(jax.random.PRNGKey(5),
                                              sizes.sizes)
    assert jax.tree_util.tree_structure(built) \
        == jax.tree_util.tree_structure(w)
    n = sizes.sizes["n_positions"]
    rng = np.random.default_rng(13)
    x, y = (jnp.asarray(rng.integers(0, 32, (4, n)).astype(np.int32))
            for _ in range(2))

    def step(p):
        logits, _ = model.forward(p, x, state=state, training=False)
        return logits, jax.value_and_grad(
            lambda p: _program_loss((model, state), p, x, y)[0])(p)

    text = jax.jit(step).lower(w).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == WITHOUT_RECORDS[which]
