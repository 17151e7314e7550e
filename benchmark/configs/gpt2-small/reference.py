"""Plain reference of the ``gpt2-small`` configuration: the post-LN
decoder block of the source system at GPT-2 small's sizes, in
``jax.numpy``, float32, every matrix product at ``highest`` precision,
attention by its definition (full score matrix, causal mask, softmax).

It imports nothing of the program (``benchmark.narrow`` is the
control's rounding, the benchmark's own).  The weights are made here from the
seed; the harness hands the same tree to the program, whose layer names
the tree follows so that the two can be compared leaf by leaf.

``round_to``: as in the other references, the same mathematics in a
narrower type as the program computes in bfloat16: the operands of every
matrix product, every tensor a layer hands on and every cotangent a layer
hands back rounded to it (an 8-bit type per-tensor scaled), sums in
float32, a parameter's gradient not rounded (``benchmark/narrow.py``):
the lower-precision control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.narrow import rounders

HIGHEST = lax.Precision.HIGHEST
CORE = "transformer"
HEAD = "lm_head"


def init_params(key, cfg):
    """The whole parameter tree from one key, float32."""
    d, m, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    std = cfg["initializer_range"]

    def normal(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    keys = iter(jax.random.split(key, 3 + 4 * cfg["n_layer"]))
    blocks = []
    for _ in range(cfg["n_layer"]):
        blocks.append({
            "qkv_kernel": normal(next(keys), (d, 3 * d)),
            "qkv_bias": jnp.zeros((3 * d,), jnp.float32),
            "proj_kernel": normal(next(keys), (d, d)),
            "proj_bias": jnp.zeros((d,), jnp.float32),
            "ln1_gamma": jnp.ones((d,), jnp.float32),
            "ln1_beta": jnp.zeros((d,), jnp.float32),
            "fc_kernel": normal(next(keys), (d, m)),
            "fc_bias": jnp.zeros((m,), jnp.float32),
            "out_kernel": normal(next(keys), (m, d)),
            "out_bias": jnp.zeros((d,), jnp.float32),
            "ln2_gamma": jnp.ones((d,), jnp.float32),
            "ln2_beta": jnp.zeros((d,), jnp.float32),
        })
    return {
        CORE: {"tok_embed": normal(next(keys), (v, d)),
               "pos_embed": normal(next(keys), (cfg["n_positions"], d)),
               "blocks": blocks},
        HEAD: {"kernel": normal(next(keys), (d, v)),
               "bias": jnp.zeros((v,), jnp.float32)},
    }


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def _block(qs, n_head, eps, bp, h):
    q, qw = qs
    b, l, d = h.shape
    hd = d // n_head

    def mm(a, w, bias):
        return q(jnp.matmul(q(a), qw(w), precision=HIGHEST) + bias)

    qkv = mm(h, bp["qkv_kernel"], bp["qkv_bias"])
    heads = [t.reshape(b, l, n_head, hd).transpose(0, 2, 1, 3)
             for t in jnp.split(qkv, 3, axis=-1)]
    qh, kh, vh = heads
    scores = jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh),
                        precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((l, l), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = q(jnp.einsum("bhqk,bhkd->bhqd", q(probs), q(vh),
                       precision=HIGHEST))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, d)
    a = mm(ctx, bp["proj_kernel"], bp["proj_bias"])
    h = q(_layer_norm(h + a, bp["ln1_gamma"], bp["ln1_beta"], eps))
    f = q(jax.nn.gelu(mm(h, bp["fc_kernel"], bp["fc_bias"]),
                      approximate=True))
    f = mm(f, bp["out_kernel"], bp["out_bias"])
    return q(_layer_norm(h + f, bp["ln2_gamma"], bp["ln2_beta"], eps))


def loss_fn(params, tokens, targets, cfg, round_to=None):
    """Mean next-token cross-entropy of one batch of token ids."""
    qs = q, qw = rounders(round_to)
    core = params[CORE]
    h = q(core["tok_embed"][tokens] + core["pos_embed"][:tokens.shape[1]])
    # one block's activations at a time in the backward pass
    block = jax.checkpoint(functools.partial(
        _block, qs, cfg["n_head"], cfg["layer_norm_epsilon"]))
    for bp in core["blocks"]:
        h = block(bp, h)
    logits = q(jnp.matmul(h, qw(params[HEAD]["kernel"]), precision=HIGHEST)
               + params[HEAD]["bias"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets.astype(jnp.int32)[..., None],
                                 axis=-1)
    return -jnp.mean(picked)


def init_opt_state(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros}


def train_step(params, opt_state, step, tokens, targets, cfg, round_to=None):
    """One Adam step (bias-corrected, epsilon outside the root).  Returns
    the new parameters, the new moments, the loss and the gradient as the
    optimizer got it."""
    opt = cfg["optimizer"]
    b1, b2 = opt["beta_1"], opt["beta_2"]
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, cfg,
                                              round_to)
    t = jnp.asarray(step, jnp.float32) + 1.0
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                opt_state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                opt_state["nu"], grads)

    def update(p, m, n):
        m_hat = m / (1 - b1 ** t)
        n_hat = n / (1 - b2 ** t)
        return p - opt["lr"] * m_hat / (jnp.sqrt(n_hat) + opt["epsilon"])

    params = jax.tree_util.tree_map(update, params, mu, nu)
    return params, {"mu": mu, "nu": nu}, loss, grads
