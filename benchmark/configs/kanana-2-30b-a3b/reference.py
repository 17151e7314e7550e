"""Plain reference of the ``kanana-2-30b-a3b`` configuration: a
DeepSeek-V3-shaped decoder (``model_type`` ``deepseek_v3``) in
``jax.numpy``, float32, every matrix product at ``highest`` precision, no
kernel, no sort, no groups: attention by its definition (scores over
(L, L), causal mask, softmax), rotary positions on adjacent pairs written
out, the routed layer as a masked sum over the experts held, the loss and
its gradient by ``jax.grad``, Adam written out.

    h = E[x]; for layer l = 0..N-1:
      u = RMSNorm(h; g1)
      q = u Wq, heads of nope + rope = 192, split q_n, q_r
      [c, k_r] = u Wkva (kv_lora_rank + rope; ONE rope key for all heads)
      [k_n, v] = RMSNorm(c; gkv) Wkvb, heads of nope + v_head_dim
      rotary (theta, adjacent pairs) on q_r and k_r
      a = softmax([q_n, q_r] [k_n, k_r]^T / sqrt(192) + causal) v
      h = h + a Wo
      u = RMSNorm(h; g2)
      l < first_k_dense_replace:  h = h + (silu(u Wg) * (u Wu)) Wd
      else:  s = sigmoid(u Wr) in float32 over all router_width experts;
             the token's experts: top-k of s + b; their weights: s at those
             k, over their sum + 1e-20, times routed_scaling_factor;
             h = h + sum_{e held} w_e Expert_e(u) + Shared(u)
    logits = RMSNorm(h; g_final) W_head; loss = mean CE(logits, y)

It imports nothing of the program (``benchmark.narrow`` is the control's
rounding, the benchmark's own).  The weights are made here from the seed;
the harness hands the same tree to the program, whose layer names the tree
follows so that the two can be compared leaf by leaf.

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``config.json``:
- 1 dense + 4 routed of the 48 layers; of the 128 routed experts the 16
  that this worker holds (``experts_held_from`` .. + 15): what the other
  112 would add to a token is left out, here as in the program, and the
  partial sum goes on to the next layer; the vocabulary's slice of 16,032
  rows, ids and targets drawn inside it;
- b (``router_bias``, the published ``e_score_correction_bias``) is drawn
  from the seed at 0.01: the top scores of 128 lie that close together at
  these weights, so it changes the picks of about four tokens in ten
  without deciding the load by itself (the held experts' rows read 4,000 to
  7,000 a layer on the chip, by the tokens' shared state); it is held fixed
  (the family's load-balance update of b between steps is left out, as is
  any sequence-wise auxiliary loss) and rounded to bfloat16's grid, so
  that the program's cast of it changes nothing.  It is a leaf of the tree
  and takes a zero gradient: it picks, by integers;
- ``initializer_range`` 0.02 for every matrix and the embedding, every
  norm's gain 1.

So that it fits one chip beside 16 bytes a parameter of float32 state: one
``jax.checkpoint`` a layer, attention four heads of a sequence at a time,
the head's cross-entropy in blocks of 1,024 positions, each recomputed in
the backward pass; the held experts are walked in ``lax.scan``, so one
expert's hidden activations are live at a time.

``round_to``: as in the other references, the same mathematics in a
narrower type as the program computes in bfloat16: the operands of every
matrix product, every tensor a layer hands on and every cotangent a layer
hands back rounded to it (an 8-bit type per-tensor scaled), sums in
float32, a parameter's gradient not rounded (``benchmark/narrow.py``): the
lower-precision control.  The router's product, its sigmoid and the norms
stay float32 there as in the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.narrow import rounders

HIGHEST = lax.Precision.HIGHEST
CORE = "kanana"
#: heads of one sequence whose (L, L) scores are live at a time, and
#: positions whose logits are
HEAD_GROUP, LOSS_BLOCK = 4, 1024
#: the scale b is drawn at
BIAS_SCALE = 0.01


def init_params(key, cfg):
    """The whole parameter tree from one key, float32."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_attention_heads"]
    rank, nope, rope, vd = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, held, width = cfg["moe_intermediate_size"], cfg["n_routed_experts"], \
        cfg["router_width"]
    shared = cfg["n_shared_experts"] * f
    std = cfg["initializer_range"]

    def normal(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    keys = iter(jax.random.split(key, 2 + 12 * cfg["num_hidden_layers"]))
    blocks = []
    for index in range(cfg["num_hidden_layers"]):
        bp = {
            "q_kernel": normal(next(keys), (d, h * (nope + rope))),
            "kv_a_kernel": normal(next(keys), (d, rank + rope)),
            "kv_a_norm": jnp.ones((rank,), jnp.float32),
            "kv_b_kernel": normal(next(keys), (rank, h * (nope + vd))),
            "o_kernel": normal(next(keys), (h * vd, d)),
            "ln1_gamma": jnp.ones((d,), jnp.float32),
            "ln2_gamma": jnp.ones((d,), jnp.float32),
        }
        if index < cfg["first_k_dense_replace"]:
            m = cfg["intermediate_size"]
            bp.update({"gate_kernel": normal(next(keys), (d, m)),
                       "fc_kernel": normal(next(keys), (d, m)),
                       "out_kernel": normal(next(keys), (m, d))})
        else:
            bias = BIAS_SCALE * jax.random.normal(next(keys), (width,),
                                                  jnp.float32)
            bp.update({
                "router_kernel": normal(next(keys), (d, width)),
                "router_bias": bias.astype(jnp.bfloat16).astype(jnp.float32),
                "experts_gate": normal(next(keys), (held, d, f)),
                "experts_up": normal(next(keys), (held, d, f)),
                "experts_down": normal(next(keys), (held, f, d)),
                "shared_gate_kernel": normal(next(keys), (d, shared)),
                "shared_fc_kernel": normal(next(keys), (d, shared)),
                "shared_out_kernel": normal(next(keys), (shared, d)),
            })
        blocks.append(bp)
    return {CORE: {
        "tok_embed": normal(next(keys), (v, d)),
        "blocks": blocks,
        "final_gamma": jnp.ones((d,), jnp.float32),
        "head_kernel": normal(next(keys), (d, v)),
    }}


def _rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gamma


def _rotary_pairs(x, theta):
    """(..., L, 2n): adjacent pair i, (x[2i], x[2i + 1]), turned at
    position p by the angle p * theta^(-2i / 2n) (``rope_interleave``)."""
    l, n2 = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, n2, 2, dtype=jnp.float32) / n2)
    angle = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.reshape(x.shape[:-1] + (n2 // 2, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, qh, kh, vh):
    """softmax(q k^T / sqrt(width of q) + causal) v by its definition, for
    (G, L, .) heads of one sequence."""
    l, width = qh.shape[-2], qh.shape[-1]
    scores = jnp.einsum("gqd,gkd->gqk", q(qh), q(kh),
                        precision=HIGHEST) / math.sqrt(width)
    causal = jnp.tril(jnp.ones((l, l), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return q(jnp.einsum("gqk,gkd->gqd", q(probs), q(vh), precision=HIGHEST))


def route(cfg, bp, u):
    """(T, router_width) weights of every expert for every token: w_e at
    the token's top-k experts, 0 elsewhere.  Float32 throughout."""
    s = jax.nn.sigmoid(jnp.matmul(u, bp["router_kernel"], precision=HIGHEST))
    _, picked = lax.top_k(lax.stop_gradient(s + bp["router_bias"]),
                          cfg["num_experts_per_tok"])
    at = jnp.take_along_axis(s, picked, axis=-1)
    w = at / (jnp.sum(at, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, picked].set(w)


def _feed_forward(qs, cfg, bp, u):
    """The layer's feed-forward of (T, d) tokens: the dense one, or the
    part that the experts in ``bp`` give (those from
    ``cfg["experts_held_from"]`` on) with the shared experts."""
    q, qw = qs

    # rounded once, where it is handed over: the experts below all read it
    u = q(u)

    def mm(a, w):
        return q(jnp.matmul(a, qw(w), precision=HIGHEST))

    def gated(u, wg, wu, wd):
        return mm(q(jax.nn.silu(mm(u, wg)) * mm(u, wu)), wd)

    if "router_kernel" not in bp:
        return gated(u, bp["gate_kernel"], bp["fc_kernel"], bp["out_kernel"])
    first = cfg["experts_held_from"]
    held = bp["experts_gate"].shape[0]
    weights = lax.dynamic_slice_in_dim(route(cfg, bp, u), first, held, 1)
    # an expert at a time, its hidden activations made again in the
    # backward pass: nothing of an expert's is kept sixteen times over
    expert = jax.checkpoint(
        lambda u, w_e, wg, wu, wd: q(w_e[:, None] * gated(u, wg, wu, wd)))

    def add_expert(total, xs):
        return total + expert(u, *xs), None

    routed, _ = lax.scan(
        add_expert, jnp.zeros_like(u),
        (weights.T, bp["experts_gate"], bp["experts_up"],
         bp["experts_down"]))
    return q(routed + gated(u, bp["shared_gate_kernel"],
                            bp["shared_fc_kernel"], bp["shared_out_kernel"]))


def _layer(qs, cfg, bp, h):
    q, qw = qs
    b, l, d = h.shape
    n_head, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    rank, nope, rope, vd = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"], cfg["v_head_dim"]

    def mm(a, w):
        return q(jnp.matmul(q(a), qw(w), precision=HIGHEST))

    def heads(x):
        return x.reshape(b, l, n_head, -1).transpose(0, 2, 1, 3)

    u = q(_rms_norm(h, bp["ln1_gamma"], eps))
    qh = heads(mm(u, bp["q_kernel"]))
    ckr = mm(u, bp["kv_a_kernel"])
    c, k_r = ckr[..., :rank], ckr[..., rank:]
    kv = heads(mm(q(_rms_norm(c, bp["kv_a_norm"], eps)), bp["kv_b_kernel"]))
    q_r = _rotary_pairs(qh[..., nope:], cfg["rope_theta"])
    k_r = _rotary_pairs(k_r[:, None], cfg["rope_theta"])
    qh = jnp.concatenate([qh[..., :nope], q_r], axis=-1)
    kh = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, n_head, l, rope))],
        axis=-1)
    group = math.gcd(n_head, HEAD_GROUP)
    grouped = [t.reshape(b * n_head // group, group, l, t.shape[-1])
               for t in (qh, kh, kv[..., nope:])]
    ctx = lax.map(lambda t: jax.checkpoint(functools.partial(
        _attention, q))(*t), grouped)
    ctx = ctx.reshape(b, n_head, l, vd).transpose(0, 2, 1, 3) \
        .reshape(b, l, n_head * vd)
    h = q(h + mm(ctx, bp["o_kernel"]))
    u = q(_rms_norm(h, bp["ln2_gamma"], eps))
    f = _feed_forward(qs, cfg, bp, u.reshape(b * l, d))
    return q(h + f.reshape(b, l, d))


def _token_ce(qs, kernel, s, targets):
    """CE(s W_head, y), a token each, for one block of positions."""
    q, qw = qs
    logits = q(jnp.matmul(s, qw(kernel), precision=HIGHEST))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def final_state(params, tokens, cfg, round_to=None):
    """RMSNorm(h_N; g_final), (B, L, d): what the head reads."""
    qs = q, _ = rounders(round_to)
    core = params[CORE]
    layer = jax.checkpoint(functools.partial(_layer, qs, cfg))
    h = q(core["tok_embed"][tokens])
    for bp in core["blocks"]:
        h = layer(bp, h)
    return q(_rms_norm(h, core["final_gamma"], cfg["rms_norm_eps"]))


def loss_fn(params, tokens, targets, cfg, round_to=None):
    """Mean over tokens of CE(logits, y), the head a block of positions
    at a time."""
    qs = rounders(round_to)
    s = final_state(params, tokens, cfg, round_to)
    b, l = tokens.shape
    n = l // math.gcd(l, LOSS_BLOCK)
    token_ce = jax.checkpoint(functools.partial(
        _token_ce, qs, params[CORE]["head_kernel"]))

    def blocked(x):     # (B, L, ...) -> (n, B, L / n, ...)
        return jnp.moveaxis(x.reshape((b, n, l // n) + x.shape[2:]), 1, 0)

    ce = lax.map(lambda sy: token_ce(*sy),
                 (blocked(s), blocked(targets.astype(jnp.int32))))
    return jnp.mean(ce)


def logits(params, tokens, cfg):
    return jnp.matmul(final_state(params, tokens, cfg),
                      params[CORE]["head_kernel"], precision=HIGHEST)


def init_opt_state(params):
    # two trees of their own: a caller may donate them
    return {"mu": jax.tree_util.tree_map(jnp.zeros_like, params),
            "nu": jax.tree_util.tree_map(jnp.zeros_like, params)}


def train_step(params, opt_state, step, tokens, targets, cfg, round_to=None):
    """One Adam step (bias-corrected, epsilon outside the root, no weight
    decay).  Returns the new parameters, the new moments, the loss and the
    gradient as the optimizer got it."""
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
