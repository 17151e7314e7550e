"""Rotary positions and the head split as one pass each way
(``self_attention.rotary`` on the head-major output of
``ops.attention.project_heads``), against the two passes they replace:
``split_heads`` of the projection, then the rotate-half form in float32,
kept here as the oracle (``_split_then_rotary``, the program's ``_rotary``
before PR 39).  The turned lanes are rounded once, where the oracle rounds
them; the gradient is the same single pass back."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from analytics_zoo_tpu.ops.attention import project_heads, split_heads
from analytics_zoo_tpu.pipeline.api.keras.layers import (
    LatentMoEDecoder,
    LoopedDecoder,
    TransformerLayer,
)
from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

THETA = 1e4


def _oracle_rotary(x, theta):
    """The rotate-half form over the whole head of (B, H, L, hd), taken in
    float32 and handed back in x's dtype (PR 38's ``_rotary``, one of its
    two arguments)."""
    l, hd = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    half = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * jnp.cos(angles) + half * jnp.sin(angles)).astype(x.dtype)


def _pairs_to_halves(x):
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _split_then_rotary(u, kernel, heads, theta, rope=None):
    """The oracle's q: the projection, its heads split, the last ``rope``
    lanes of each taken from adjacent pairs to halves and turned (all of
    them, in halves already, without ``rope``)."""
    q = split_heads(u @ kernel, heads)
    if rope is None:
        return _oracle_rotary(q, theta)
    turned = _oracle_rotary(_pairs_to_halves(q[..., -rope:]), theta)
    return jnp.concatenate([q[..., :-rope], turned], axis=-1)


def _one_pass(u, kernel, heads, theta, rope=None):
    if rope is None:
        return self_attention.rotary(project_heads(u, kernel, heads), theta)
    return self_attention.rotary(
        project_heads(u, self_attention._rope_halves(kernel, heads, rope),
                      heads), theta, rope)


def _ordered(x):
    """bfloat16 bits as integers in the order of the values."""
    bits = np.asarray(x, ml_dtypes.bfloat16).view(np.uint16).astype(np.int64)
    return np.where(bits & 0x8000, -(bits & 0x7FFF), bits)


def _within_ulps(got, want):
    """float32 within 2 ulps; bfloat16 equal or one ulp apart."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    if got.dtype == np.float32:
        np.testing.assert_array_max_ulp(got, want, maxulp=2)
    else:
        assert int(np.max(np.abs(_ordered(got) - _ordered(want)))) <= 1


#: (batch, heads, length, width of a head, turned lanes or None for all):
#: ouro's whole head at 128, latent attention's q (nope 128 + rope 64 in
#: adjacent pairs) and its shared key slice (one head of rope 64), and the
#: small width the block's other tests use
CASES = {"whole-head": (2, 16, 64, 128, None),
         "latent-q": (2, 4, 64, 192, 64),
         "latent-k": (2, 1, 64, 64, 64),
         "width-8": (2, 2, 16, 8, None)}


def _oracle_turn(x, theta, rope):
    """The oracle on head-major x already in halves: the last ``rope``
    lanes turned, the others as they are."""
    if rope is None:
        return _oracle_rotary(x, theta)
    return jnp.concatenate([x[..., :-rope], _oracle_rotary(x[..., -rope:],
                                                           theta)], axis=-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_one_pass_is_the_oracle_and_so_is_its_gradient(case, dtype):
    b, h, l, w, rope = CASES[case]
    d = 32
    kx, ku, kw, kg = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    dy = jax.random.normal(kg, (b, h, l, w)).astype(dtype)
    # the pass itself, forward and back, on the same heads
    x = jax.random.normal(kx, (b, h, l, w)).astype(dtype)
    got, pull = jax.vjp(lambda x: self_attention.rotary(x, THETA, rope), x)
    want, pull_want = jax.vjp(lambda x: _oracle_turn(x, THETA, rope), x)
    _within_ulps(got, want)
    _within_ulps(pull(dy)[0], pull_want(dy)[0])
    # from the projection's input and kernel: the heads as the product
    # writes them (the pairs in the kernel's columns) against the split;
    # the two products may sum in another order, so within a share of the
    # largest value (float32's 1e-6, bfloat16's half ulp)
    u = jax.random.normal(ku, (b, l, d)).astype(dtype)
    kernel = (jax.random.normal(kw, (d, h * w)) * d ** -0.5).astype(dtype)
    got, pull = jax.vjp(lambda u, k: _one_pass(u, k, h, THETA, rope),
                        u, kernel)
    want, pull_want = jax.vjp(
        lambda u, k: _split_then_rotary(u, k, h, THETA, rope), u, kernel)
    share = 1e-6 if dtype == jnp.float32 else 2.0 ** -9
    for g, gw in zip((got, *pull(dy)), (want, *pull_want(dy))):
        g, gw = np.asarray(g, np.float32), np.asarray(gw, np.float32)
        np.testing.assert_allclose(g, gw, rtol=0,
                                   atol=share * float(np.max(np.abs(gw))))


def test_the_rotation_rounds_once_where_the_oracle_does():
    """bfloat16 in, bfloat16 out: the turned lanes are the float32
    arithmetic rounded once, bit for bit the oracle's on the CPU."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 32, 128),
                          jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(self_attention.rotary(x, THETA), np.float32),
        np.asarray(_oracle_rotary(x, THETA), np.float32))


def test_project_heads_is_the_split_projection():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (16, 3 * 4 * 8))
    bias = jax.random.normal(jax.random.PRNGKey(2), (3 * 4 * 8,))
    got = project_heads(u, kernel, 4, parts=3, bias=bias)
    want = [split_heads(p, 4) for p in jnp.split(u @ kernel + bias, 3, -1)]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


def _looped():
    return LoopedDecoder(vocab=32, n_block=2, n_head=2, hidden_size=64,
                         intermediate_size=96, passes=3, rotary_theta=THETA,
                         name="looped")


def test_gradient_through_an_ouro_block_is_the_old_forms(monkeypatch):
    """``jax.grad`` of one ouro-shaped block (RMSNorm around both
    branches, fused QKV without bias, rotary over the whole head, gated
    SiLU) against the same block with the heads split and turned in the
    two passes of before."""
    layer = _looped()
    bp = layer._block_params(jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))

    def loss(bp, h):
        out = layer._block_forward_aux(bp, h, None, True, None)[0]
        return jnp.sum(out ** 2)

    got = jax.jit(jax.grad(loss, argnums=(0, 1)))(bp, h)
    monkeypatch.setattr(
        self_attention, "project_heads",
        lambda u, kernel, heads, parts, bias: tuple(
            split_heads(p, heads) for p in jnp.split(u @ kernel, parts, -1)))
    monkeypatch.setattr(self_attention, "rotary",
                        lambda x, theta: _oracle_rotary(x, theta))
    want = jax.jit(jax.grad(loss, argnums=(0, 1)))(bp, h)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) / scale < 1e-5, \
            jax.tree_util.keystr(path)


def _records_of(call):
    self_attention.rotary_records.clear()
    call()
    return list(self_attention.rotary_records)


def test_every_rotation_of_a_looped_decoder_is_fused():
    layer = _looped()
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).integers(0, 32, (2, 16)))
    records = _records_of(lambda: jax.jit(layer.call)(params, x))
    # q and k of each traced layer application (the checkpointed block is
    # traced once for all its applications of a shape)
    assert records and len(records) % 2 == 0
    assert all(r == {"form": "fused", "width": 32, "heads": 2}
               for r in records), records


def test_every_rotation_of_a_latent_decoder_is_fused():
    layer = LatentMoEDecoder(
        vocab=32, n_block=3, n_head=4, hidden_size=32, intermediate_size=48,
        kv_latent_rank=16, qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8,
        routed_experts=4, experts_per_token=2, expert_size=16,
        rotary_theta=THETA)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).integers(0, 32, (2, 12)))
    records = _records_of(lambda: jax.jit(layer.call)(params, x))
    # each traced layer (the dense one and the routed ones): q's rope lanes
    # of 4 heads (its nope lanes passed through) and the one shared key
    assert records and records == [
        {"form": "fused", "width": 8, "heads": 4},
        {"form": "fused", "width": 8, "heads": 1}] * (len(records) // 2)


def test_a_block_without_rotary_positions_records_none():
    layer = TransformerLayer(vocab=30, seq_len=8, n_block=2, n_head=2,
                             hidden_size=16, hidden_drop=0.0, attn_drop=0.0,
                             embedding_drop=0.0)
    params = layer.init_params(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 30, (2, 8)))
    assert _records_of(lambda: jax.jit(layer.call)(params, tokens)) == []
