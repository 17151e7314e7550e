"""The configurable transformer block and the looped decoder built from it
(``keras/layers/self_attention.py``), at toy size on the CPU in float32,
against the plain reference of the ``ouro-2.6b`` configuration
(``benchmark/configs/ouro-2.6b/reference.py``, which imports nothing of the
program) and, for ``TransformerLayer`` and ``BERT``, against the block as it
was before it became configurable."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops.attention import (
    dot_product_attention,
    merge_heads,
    split_heads,
)
from analytics_zoo_tpu.pipeline.api.keras.engine import training_targets
from analytics_zoo_tpu.pipeline.api.keras.layers import (
    BERT,
    LoopedDecoder,
    TransformerLayer,
)
from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention
from benchmark.manifest import Manifest

#: 2 layers, T = 3, hidden 64, 2 heads of 32, vocabulary 128, 32 tokens
TOY = {"num_hidden_layers": 2, "total_ut_steps": 3, "hidden_size": 64,
       "n_embd": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
       "n_head": 2, "head_dim": 32, "intermediate_size": 96,
       "vocab_size": 128, "n_positions": 32}
BATCH = 8


@pytest.fixture(scope="module")
def cfg():
    return Manifest().configuration("ouro-2.6b", TOY)


@pytest.fixture(scope="module")
def reference(cfg):
    return cfg.module("reference")


@pytest.fixture(scope="module")
def batches(cfg):
    rng = np.random.default_rng(11)
    shape = (3, BATCH, cfg.sizes["n_positions"])
    return (rng.integers(0, 128, shape).astype(np.int32),
            rng.integers(0, 128, shape).astype(np.int32))


@pytest.fixture(scope="module")
def weights(cfg, reference):
    """Seeded weights, the gate's away from its symmetric start so that
    every pass's exit probability differs."""
    params = reference.init_params(jax.random.PRNGKey(5), cfg.sizes)
    core = dict(params[reference.CORE])
    core["exit_bias"] = jnp.full((1,), 0.3, jnp.float32)
    core["exit_kernel"] = core["exit_kernel"] * 20.0
    return {reference.CORE: core}


def _layer(cfg, **kw):
    s = getattr(cfg, "sizes", cfg)
    return LoopedDecoder(
        vocab=s["vocab_size"], n_block=s["num_hidden_layers"],
        n_head=s["num_attention_heads"], hidden_size=s["hidden_size"],
        intermediate_size=s["intermediate_size"],
        passes=s["total_ut_steps"], rotary_theta=s["rope_theta"],
        norm_eps=s["rms_norm_eps"], exit_beta=s["exit_beta"],
        loss_block=64, name="ouro", **kw)


def _program_loss(layer, core, x, y):
    with training_targets(y):
        _, state = layer.call(core, x, state=layer.init_state(),
                              training=True)
    return state["loop_exit_cost"], state


def _close(got, want, rtol, what, norm=lambda a: jnp.max(jnp.abs(a))):
    """Every leaf within ``rtol`` of the reference leaf's largest value
    (or, with another ``norm``, of its length)."""
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        err = float(norm(g - w)) / (float(norm(w)) or 1.0)
        assert err < rtol, (what, jax.tree_util.keystr(path), err)


@pytest.fixture(scope="module")
def program_first(cfg, reference, weights, batches):
    """The program's loss, state and gradient of the first batch."""
    layer = _layer(cfg)
    return jax.jit(jax.value_and_grad(
        lambda core, x, y: _program_loss(layer, core, x, y), has_aux=True))(
            weights[reference.CORE], batches[0][0], batches[1][0])


@pytest.fixture(scope="module")
def reference_first(cfg, reference, weights, batches):
    """The reference's loss and gradient of the first batch."""
    return jax.jit(jax.value_and_grad(lambda p, x, y: reference.loss_fn(
        p, x, y, cfg.sizes)))(weights, batches[0][0], batches[1][0])


# float32 on the CPU on both sides: the program sums in another order
# (fused QKV, blocked cross-entropy, log-space exit distribution), which
# costs 1e-6; bfloat16 in float32's place reads 3e-3 or more on each of
# these, a dropped pass changes the loss in its second digit.
def test_predict_gives_the_last_pass_logits(cfg, reference, weights,
                                            batches):
    layer = _layer(cfg)
    x = batches[0][0]
    got, _ = jax.jit(lambda core: layer.call(core, x, state=None,
                                             training=False))(
        weights[reference.CORE])
    want = jax.jit(lambda p: reference.logits(p, x, cfg.sizes))(weights)
    assert got.shape == (BATCH, 32, 128)
    _close(got, want, 2e-5, "logits_T")


def test_training_loss_and_first_gradient_of_every_leaf(
        cfg, reference, weights, batches, program_first, reference_first):
    x, y = batches[0][0], batches[1][0]
    (loss, state), grads = program_first
    want, want_grads = reference_first
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    _close(grads, want_grads[reference.CORE], 2e-4, "gradient")
    # the per-pass numbers the gauges publish
    ce, lam, _ = jax.jit(lambda p, x, y: reference.passes(
        p, x, y, cfg.sizes))(weights, x, y)
    p = reference.exit_distribution(lam)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(state["loop_pass_loss"],
                               ce.mean(axis=(1, 2)), rtol=1e-5)
    np.testing.assert_allclose(state["loop_exit_mass"],
                               p.mean(axis=(1, 2)), rtol=1e-5)
    assert float(jnp.std(p.mean(axis=(1, 2)))) > 0.05   # no uniform exit


@pytest.mark.parametrize("compute, loss_rtol, grad_rtol", [
    ("float32", 2e-6, 2e-5), ("bfloat16", 1e-4, 6e-2)])
def test_the_head_keeps_its_gradient_in_either_compute_dtype(
        compute, loss_rtol, grad_rtol, cfg, reference, weights, batches,
        reference_first):
    """Float32 master weights cast down on use, as the train step casts
    them: the loss and every leaf's gradient, by the leaf's length, against
    the float32 reference.  The head's gradient is the float32 sum that
    ``_deliver`` hands over, rounded once to the compute dtype.  bfloat16
    reads 2e-5 on the loss, 0.012 on the head and 0.037 on the widest leaf
    (the gate's bias), the same as with the logits made again."""
    layer = _layer(cfg)
    x, y = batches[0][0], batches[1][0]

    def loss(core):
        core = jax.tree_util.tree_map(lambda a: a.astype(compute), core)
        return _program_loss(layer, core, x, y)[0]

    got, grads = jax.jit(jax.value_and_grad(loss))(weights[reference.CORE])
    want, want_grads = reference_first
    assert abs(float(got) - float(want)) < loss_rtol * float(want)
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))
    _close(grads, want_grads[reference.CORE], grad_rtol,
           f"gradient under {compute}", norm=jnp.linalg.norm)


def test_a_cotangent_other_than_one_scales_every_leaf(cfg, reference,
                                                      weights, batches,
                                                      program_first):
    """The backward pass of the head only scales what the forward pass
    kept: 2.5 on ``loop_exit_cost`` gives every leaf 2.5 times its
    gradient, the head's (which ``_deliver`` hands over) and the exit
    gate's (through the weights p / N) included."""
    layer = _layer(cfg)
    x, y = batches[0][0], batches[1][0]
    cost, pull = jax.vjp(
        lambda core: _program_loss(layer, core, x, y)[0],
        weights[reference.CORE])
    (loss, _), grads = program_first
    np.testing.assert_allclose(float(cost), float(loss), rtol=1e-6)
    got, = jax.jit(pull)(jnp.float32(2.5))
    for leaf in ("head_kernel", "exit_kernel", "exit_bias"):
        assert float(jnp.max(jnp.abs(grads[leaf]))) > 0, leaf
    # another compiled program sums in another order (2e-6); a leaf that
    # missed the factor reads 0.6
    _close(got, jax.tree_util.tree_map(lambda g: 2.5 * g, grads), 1e-5,
           "gradient at cotangent 2.5")


def test_three_adam_steps_through_fit(cfg, reference, weights, batches):
    """``compile``/``fit`` on the normal path: three one-batch calls
    against three steps of the reference's written-out Adam.  Adam divides
    by the root of the second moment, so float32 rounding of a gradient
    near zero moves an element's step by as much as the step: a leaf's
    change is compared by its length, at 1e-3 (bfloat16 reads 1e-2)."""
    from analytics_zoo_tpu.feature.dataset import FeatureSet

    model = cfg.module("model").build(cfg.sizes)
    model.params = jax.tree_util.tree_map(jnp.array, weights)
    params, opt_state = weights, reference.init_opt_state(weights)
    step = jax.jit(lambda p, o, i, x, y: reference.train_step(
        p, o, i, x, y, cfg.sizes))
    for i in range(3):
        x, y = batches[0][i], batches[1][i]
        model.fit(FeatureSet.of(x, y), batch_size=BATCH, nb_epoch=1)
        params, opt_state, loss, _ = step(params, opt_state, np.int32(i),
                                          x, y)
        got = model._estimator.history[-1]["loss"]
        assert abs(got - float(loss)) < 5e-6 * float(loss), i
    moved = jax.tree_util.tree_map(lambda a, b: a - b, params, weights)
    got = jax.tree_util.tree_map(lambda a, b: a - b, model.params, weights)
    _close(got, moved, 1e-3, "change after three steps",
           norm=jnp.linalg.norm)
    assert cfg.module("model").routing_fault("cpu") is None
    assert model.predict(batches[0][0], batch_size=BATCH).shape \
        == (BATCH, 32, 128)
    assert model.evaluate(batches[0][0], batches[1][0],
                          batch_size=BATCH)["loss"] > 0


def test_the_tie_sums_the_gradients_of_untied_copies(
        cfg, reference, weights, batches, program_first, reference_first):
    """T copies of the stack, one a pass, hold the same weights: the
    gradient of the shared stack is the sum of the copies' gradients."""
    x, y = batches[0][0], batches[1][0]
    blocks = weights[reference.CORE]["blocks"]
    copies = jax.tree_util.tree_map(lambda w: jnp.stack([w] * 3), blocks)
    untied = jax.jit(jax.grad(lambda stacks: reference.loss_fn(
        weights, x, y, cfg.sizes, stacks=stacks)))(copies)
    tied = reference_first[1]
    summed = jax.tree_util.tree_map(lambda g: g.sum(axis=0), untied)
    _close(summed, tied[reference.CORE]["blocks"], 1e-5, "tied gradient")
    # and no copy's share is nought: every pass reaches the loss
    assert all(float(jnp.min(jnp.max(jnp.abs(g.reshape(3, -1)), axis=1))) > 0
               for g in jax.tree_util.tree_leaves(untied))
    _close(program_first[1]["blocks"], summed, 2e-4,
           "the program's tied gradient")


def test_the_loss_with_the_gate_shut_is_the_last_pass_alone(cfg, reference,
                                                            weights, batches):
    x, y = batches[0][0], batches[1][0]
    # lambda forced to 0 and beta 0: all the mass on the last pass
    shut = {reference.CORE: {**weights[reference.CORE],
                             "exit_kernel": jnp.zeros((64, 1)),
                             "exit_bias": jnp.full((1,), -1e4)}}
    sizes = {**cfg.sizes, "exit_beta": 0.0}
    (ce, _, _), got = jax.jit(lambda p: (
        reference.passes(p, x, y, sizes),
        reference.loss_fn(p, x, y, sizes)))(shut)
    want = float(ce[-1].mean())
    assert abs(float(got) - want) < 1e-6 * want
    layer = _layer(sizes)
    loss, state = jax.jit(lambda core: _program_loss(layer, core, x, y))(
        shut[reference.CORE])
    assert abs(float(loss) - want) < 2e-6 * want
    np.testing.assert_allclose(state["loop_exit_mass"], [0, 0, 1], atol=1e-7)


def _shapes(text, pattern):
    return [tuple(int(n) for n in re.split(r"[x,]", dims) if n)
            for dims in re.findall(pattern, text)]


def test_no_array_of_passes_x_tokens_x_vocabulary_in_the_step(cfg, weights,
                                                              reference):
    """One pass's head and cross-entropy at a time, a block of tokens at a
    time: the step as lowered has no (T x tokens x vocabulary) array, and
    in the step as compiled nothing over the vocabulary is larger than one
    block's logits (the output logits_T is dead code under the in-model
    loss).  128, the toy vocabulary, is no other size of the toy."""
    layer = _layer(cfg)
    core = weights[reference.CORE]
    x = jnp.zeros((BATCH, 32), jnp.int32)

    def step(core, x, y):
        return jax.value_and_grad(
            lambda c: _program_loss(layer, c, x, y)[0])(core)

    lowered = jax.jit(step).lower(core, x, x)
    tokens, vocab, passes, block = BATCH * 32, 128, 3, 64
    shapes = _shapes(lowered.as_text(), r"tensor<((?:\d+x)+)[a-z]")
    assert max(np.prod(s) for s in shapes) < passes * tokens * vocab
    assert (BATCH, block // BATCH, vocab) in shapes   # a block's logits
    compiled = _shapes(lowered.compile().as_text(), r"[a-z]\d+\[([\d,]+)\]")
    over_vocab = [s for s in compiled if vocab in s]
    assert over_vocab and max(np.prod(s) for s in over_vocab) \
        <= block * vocab, sorted(set(over_vocab))
    record = self_attention.loop_records[-1]
    assert record["loss_blocks"] == 4 and record["head_evaluations"] == 3
    assert record["layer_applications"] == 6 and record["loop"] == "unrolled"
    assert record["remat"] == "attn"
    # the policy's names, wherever they occur: the looped stack has no
    # routed layer, so nothing of it carries the third
    assert record["kept"] == ["attn_context", "ffn_out", "moe_route",
                              "kda_state"]


def _products_over(jaxpr, size, in_scan=False):
    """(inside a scan?, output shape) of every ``dot_general`` of the
    jaxpr that has ``size`` among an operand's or its output's dims, in
    program order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                size in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            found.append((in_scan, eqn.outvars[0].aval.shape))
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (list, tuple)) else [sub]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    found += _products_over(
                        inner, size, in_scan or eqn.primitive.name == "scan")
    return found


def test_no_product_over_the_vocabulary_in_the_backward_pass(cfg, weights,
                                                             reference):
    """A block's logits, ``dlogits @ W^T`` and ``s^T @ dlogits`` are made
    in one scan body a pass, in the forward pass; the only other product
    over the vocabulary in the step is the output logits_T (dead code under
    the in-model loss), the forward pass's last, and nothing follows it:
    the backward pass scales what was kept.  128, the toy vocabulary, is no
    other size of the toy."""
    layer = _layer(cfg)
    x = jnp.zeros((BATCH, 32), jnp.int32)
    step = jax.make_jaxpr(jax.grad(
        lambda core: _program_loss(layer, core, x, x)[0]))(
            weights[reference.CORE])
    passes, vocab = 3, 128
    found = _products_over(step.jaxpr, vocab)
    assert [scanned for scanned, _ in found] == [True] * 3 * passes + [False]
    assert found[-1][1] == (BATCH, 32, vocab)
    assert sorted(shape for _, shape in found[:3]) == sorted(
        [(BATCH, 64 // BATCH, vocab), (BATCH, 64 // BATCH, 64), (64, vocab)])
    record = self_attention.loop_records[-1]
    assert record["head_products"] == 3 * passes * record["loss_blocks"] \
        == 36
    assert record["head_evaluations"] == passes


@pytest.mark.parametrize("remat", ["full", "dots", None])
def test_the_default_policy_is_a_trade_of_memory_alone(
        remat, cfg, reference, weights, batches, program_first):
    """A layer application keeps the attention's context under the layer's
    own default; loss, state and every leaf's gradient are those of the
    other policies and of no checkpoint at all, and the record of what was
    traced names the policy and what it keeps."""
    assert _layer(cfg).remat == "attn"
    layer = _layer(cfg, remat=remat)
    (loss, state), grad = jax.jit(jax.value_and_grad(
        lambda core, x, y: _program_loss(layer, core, x, y), has_aux=True))(
            weights[reference.CORE], batches[0][0], batches[1][0])
    record = self_attention.loop_records[-1]
    assert (record["remat"], record["kept"]) == (remat, [])
    (loss_default, state_default), grad_default = program_first
    np.testing.assert_allclose(float(loss_default), float(loss), rtol=1e-6)
    _close(state_default, state, 1e-6, "state")
    _close(grad_default, grad, 1e-6, "gradient")


def test_an_application_keeps_its_context_and_feed_forward_output(cfg, capsys):
    """What the default policy keeps of one layer application besides its
    arguments: the attention's context (B, heads, L, head) and the
    feed-forward's output (B, L, hidden), each from the op that names it;
    so the gradient has two products fewer than under ``"full"`` (PV and
    the down-projection; on the dense path q k^T is still made again)."""
    from jax.ad_checkpoint import print_saved_residuals

    from analytics_zoo_tpu.parallel.plan import apply_remat

    layer = _layer(cfg)
    bp = layer._block_params(jax.random.PRNGKey(0))
    h = jnp.ones((BATCH, 32, 64), jnp.float32)

    def application(bp, h):
        return layer._block_forward_aux(bp, h, None, True, None)[0]

    capsys.readouterr()
    print_saved_residuals(apply_remat(application, layer.remat), bp, h)
    kept = [ln for ln in capsys.readouterr().out.splitlines()
            if " from the argument " not in ln and "constant" not in ln]
    assert len(kept) == 2, kept
    assert any(ln.startswith("f32[8,2,32,32]")
               and "dot_product_attention" in ln for ln in kept), kept
    assert any(ln.startswith("f32[8,32,64]") and "feed_forward" in ln
               for ln in kept), kept

    def products(policy):
        fn = apply_remat(application, policy)
        return str(jax.make_jaxpr(jax.grad(
            lambda bp: jnp.sum(fn(bp, h) ** 2)))(bp)).count("dot_general")

    assert products("full") - products("attn") == 2
    # made again: qkv, q k^T, the attention's projection, gate, up, and
    # the rotate-half products of q and k (signed permutations: data
    # movement on the MXU, no arithmetic)
    assert products("attn") - products(None) == 7


def test_a_plan_rule_overrides_the_layers_policy(cfg, reference, weights,
                                                 batches):
    """``remat_rules`` of the plan being compiled say ``"full"`` to a layer
    built with the default: the way a user with less room asks for it."""
    from analytics_zoo_tpu.parallel import plan as plan_mod

    layer = _layer(cfg)
    rules = plan_mod.with_remat(plan_mod.data_parallel(), "full", r"ouro")
    with plan_mod._active_plan(rules):
        jax.make_jaxpr(lambda core: _program_loss(
            layer, core, batches[0][0], batches[1][0])[0])(
                weights[reference.CORE])
    record = self_attention.loop_records[-1]
    assert (record["remat"], record["kept"]) == ("full", [])


# -- the block before it became configurable ------------------------------

def _old_block(bp, h, mask, n_head, causal, act):
    """``_TransformerCore._block_forward_aux`` of the parent commit, for a
    dense block in inference."""
    def ln(x, gamma, beta, eps=1e-5):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * jnp.reciprocal(jnp.sqrt(var + eps)) * gamma \
            + beta

    qkv = h @ bp["qkv_kernel"] + bp["qkv_bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    a = dot_product_attention(split_heads(q, n_head), split_heads(k, n_head),
                              split_heads(v, n_head), mask=mask,
                              dropout_p=0.0, rng=None, causal=causal)
    a = merge_heads(a) @ bp["proj_kernel"] + bp["proj_bias"]
    h = ln(h + a, bp["ln1_gamma"], bp["ln1_beta"])
    f = act(h @ bp["fc_kernel"] + bp["fc_bias"])
    f = f @ bp["out_kernel"] + bp["out_bias"]
    return ln(h + f, bp["ln2_gamma"], bp["ln2_beta"])


def _old_block_params(rng, d, m, std):
    ks = jax.random.split(rng, 6)

    def init(k, shape):
        return std * jax.random.normal(k, shape)

    return {"qkv_kernel": init(ks[0], (d, 3 * d)),
            "qkv_bias": jnp.zeros((3 * d,)),
            "proj_kernel": init(ks[1], (d, d)), "proj_bias": jnp.zeros((d,)),
            "ln1_gamma": jnp.ones((d,)), "ln1_beta": jnp.zeros((d,)),
            "ln2_gamma": jnp.ones((d,)), "ln2_beta": jnp.zeros((d,)),
            "fc_kernel": init(ks[2], (d, m)), "fc_bias": jnp.zeros((m,)),
            "out_kernel": init(ks[3], (m, d)), "out_bias": jnp.zeros((d,))}


def _same_tree(got, want):
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("kind", ["TransformerLayer", "BERT"])
def test_tree_and_outputs_bitwise_as_before_the_refactor(kind):
    rng = jax.random.PRNGKey(3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 50, (2, 12)))
    if kind == "TransformerLayer":
        layer = TransformerLayer(vocab=50, seq_len=12, n_block=2, n_head=2,
                                 hidden_size=16)
        params = layer.init_params(rng)
        ks = jax.random.split(rng, 4)
        want = {"tok_embed": 0.02 * jax.random.normal(ks[0], (50, 16)),
                "pos_embed": 0.02 * jax.random.normal(ks[1], (12, 16)),
                "blocks": [_old_block_params(ks[2 + i], 16, 64, 0.02)
                           for i in range(2)]}
        _same_tree(params, want)
        h = jnp.take(want["tok_embed"], tokens, axis=0) \
            + want["pos_embed"][None]
        for bp in want["blocks"]:
            h = _old_block(bp, h, None, 2, True, layer.act)
        got = layer.call(params, tokens)
    else:
        layer = BERT(vocab=50, hidden_size=16, n_block=2, n_head=2,
                     seq_len=12, intermediate_size=32)
        params = layer.init_params(rng)
        ks = jax.random.split(rng, 6)
        assert sorted(params) == sorted(
            ["tok_embed", "pos_embed", "type_embed", "embed_ln_gamma",
             "embed_ln_beta", "pooler_kernel", "pooler_bias", "blocks"])
        _same_tree(params["blocks"],
                   [_old_block_params(ks[4 + i], 16, 32, 0.02)
                    for i in range(2)])
        attn = jnp.ones((2, 12)).at[:, 9:].set(0.0)
        got = layer.call(params, [tokens, None, None, attn])[0]
        h = jnp.take(params["tok_embed"], tokens, axis=0) \
            + params["pos_embed"][None]
        h = layer._ln(h, params["embed_ln_gamma"], params["embed_ln_beta"])
        mask = (1.0 - attn[:, None, None, :]) * jnp.finfo(h.dtype).min
        for bp in params["blocks"]:
            h = _old_block(bp, h, mask, 2, False, layer.act)
    assert np.array_equal(np.asarray(got), np.asarray(h))
    assert layer.param_count() == sum(
        int(p.size) for p in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("options", [
    dict(norm="rms", norm_placement="around", rotary_theta=1e4,
         gated_ffn=True, use_bias=False, activation="silu"),
    dict(norm="layer", norm_placement="before"),
    dict(norm="rms", norm_placement="after", use_bias=False),
    dict(norm="layer", norm_placement="around", gated_ffn=True,
         rotary_theta=1e6),
], ids=["ouro", "pre-ln", "rms-post", "sandwich-ln-gated"])
def test_the_block_is_assembled_from_its_options(options):
    layer = TransformerLayer(vocab=30, seq_len=8, n_block=2, n_head=2,
                             hidden_size=16, hidden_drop=0.0, attn_drop=0.0,
                             embedding_drop=0.0, **options)
    params = layer.init_params(jax.random.PRNGKey(0))
    block = params["blocks"][0]
    norms = 4 if options["norm_placement"] == "around" else 2
    assert sum(k.endswith("_gamma") for k in block) == norms
    assert sum(k.endswith("_beta") for k in block) \
        == (norms if options["norm"] == "layer" else 0)
    assert any(k.endswith("_bias") for k in block) \
        == options.get("use_bias", True)
    assert ("gate_kernel" in block) == options.get("gated_ffn", False)
    assert layer.param_count() == sum(
        int(p.size) for p in jax.tree_util.tree_leaves(params))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 30, (2, 8)))
    call = jax.jit(layer.call)
    out = call(params, tokens)
    assert out.shape == (2, 8, 16) and bool(jnp.all(jnp.isfinite(out)))
    # causal: a later token does not move an earlier position
    other = call(params, tokens.at[:, -1].set(0))
    assert np.array_equal(np.asarray(out[:, :-1]), np.asarray(other[:, :-1]))
    grads = jax.jit(jax.grad(
        lambda p: jnp.sum(layer.call(p, tokens) ** 2)))(params)
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(grads["blocks"]))


def test_rotary_turns_pairs_by_position():
    q = jnp.ones((1, 1, 4, 8))
    turned = self_attention.rotary(q, 100.0)
    np.testing.assert_allclose(turned[0, 0, 0], 1.0)        # position 0
    angle = 3 * 100.0 ** (-2 / 8)                           # pair 1 at 3
    np.testing.assert_allclose(
        [turned[0, 0, 3, 1], turned[0, 0, 3, 5]],
        [np.cos(angle) - np.sin(angle), np.cos(angle) + np.sin(angle)],
        rtol=1e-5)
    # a rotation keeps each pair's length, and q.k depends on the distance
    np.testing.assert_allclose(jnp.sum(turned ** 2, -1), 8.0, rtol=1e-5)


def test_options_that_do_not_combine_are_refused():
    with pytest.raises(ValueError, match="norm_placement"):
        TransformerLayer(vocab=9, seq_len=4, norm_placement="between")
    with pytest.raises(ValueError, match="norm must"):
        TransformerLayer(vocab=9, seq_len=4, norm="batch")
    with pytest.raises(ValueError, match="routed feed-forward"):
        TransformerLayer(vocab=9, seq_len=4, moe_experts=2, gated_ffn=True)
    with pytest.raises(ValueError, match="passes"):
        LoopedDecoder(vocab=9, n_block=1, n_head=1, hidden_size=8,
                      intermediate_size=8, passes=0)


def test_an_in_model_loss_is_kept_off_the_paths_that_cannot_hand_targets():
    from analytics_zoo_tpu.parallel import strategies
    from analytics_zoo_tpu.pipeline.api.keras.objectives import get_loss

    loss = get_loss("looped_exit_cross_entropy")
    assert loss.in_model and not get_loss("mse").in_model
    for make in (strategies.make_shard_map_train_step,
                 strategies.make_zero1_train_step):
        with pytest.raises(NotImplementedError, match="inside the model"):
            make(None, loss, None)
    # evaluation reads the model's output alone: the last pass's CE
    logits = jnp.zeros((2, 3, 5))
    np.testing.assert_allclose(loss(jnp.zeros((2, 3), jnp.int32), logits),
                               np.log(5.0), rtol=1e-6)


def test_another_loss_trains_the_last_pass_alone(cfg, reference, weights,
                                                 batches):
    layer = _layer(cfg)
    out, state = layer.call(weights[reference.CORE], batches[0][0],
                            state=layer.init_state(), training=True)
    assert out.shape == (BATCH, 32, 128)
    assert float(state["loop_exit_cost"]) == 0.0
    record = self_attention.loop_records[-1]
    assert (record["head_evaluations"], record["head_products"]) == (1, 1)


def test_fit_publishes_the_loop_gauges_and_the_lowering_seconds(cfg,
                                                                batches):
    from analytics_zoo_tpu.feature.dataset import FeatureSet
    from analytics_zoo_tpu.metrics import (
        MetricsRegistry,
        get_registry,
        set_registry,
        snapshot,
    )

    was = get_registry()
    set_registry(MetricsRegistry())
    try:
        model = cfg.module("model").build(cfg.sizes)
        model.fit(FeatureSet.of(batches[0][0], batches[1][0]),
                  batch_size=BATCH, nb_epoch=1)
        samples = snapshot()["samples"]
    finally:
        set_registry(was)
    by_name = {}
    for s in samples:
        by_name.setdefault(s["name"], {})[
            (s.get("labels") or {}).get("label")] = s
    mass = by_name["zoo_loop_exit_mass"]
    assert sorted(mass) == ["1", "2", "3"]
    assert abs(sum(s["value"] for s in mass.values()) - 1.0) < 1e-5
    assert all(s["value"] > 3.0
               for s in by_name["zoo_loop_pass_loss"].values())
    # the step's label carries the plan's name when a context left by
    # another file on this worker has one: any train_step label will do
    def train_step(name):
        found = [s for label, s in by_name[name].items()
                 if label.startswith("train_step")]
        assert len(found) == 1, sorted(by_name[name])
        return found[0]

    lower = train_step("zoo_lower_seconds")
    assert lower["count"] == 1 and lower["sum"] > 0
    assert train_step("zoo_compile_seconds")["count"] == 1
