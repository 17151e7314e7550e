"""Plain reference of the ``kimi-linear-48b-a3b`` configuration: a decoder
(``model_type`` ``kimi_linear``, arXiv:2510.26692) whose layers mix by Kimi
Delta Attention (KDA) or by latent attention without positions, in
``jax.numpy``, float32, every matrix product at ``highest`` precision, no
kernel, no chunk, no sort, no groups: KDA as its recurrence, token by token
(``lax.scan``), latent attention by its definition (scores over (L, L),
causal mask, softmax), the routed layer as a masked sum over the experts
held, the loss and its gradient by ``jax.grad``, Adam written out.

    h = E[x]; for layer l = 1..N (``linear_attn_config`` lists the kinds):
      u = RMSNorm(h; g1)
      l in kda_layers, per head of 128 (32 heads):
        q~ = SiLU(conv4(u Wq)), k~ = SiLU(conv4(u Wk)), v = SiLU(conv4(u Wv))
          conv4: y_t = sum_{i=0..3} w_i z_{t-3+i} a channel, zeros before
          the start, no bias
        q = q~ / sqrt(|q~|^2 + 1e-6), k likewise
        g_t = -exp(A_log) softplus(u Wf1 Wf2 + dt_bias)   (a key channel)
        beta_t = sigmoid(u Wb)                            (a head)
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
          S_0 = 0;  o_t = S_t^T q_t / sqrt(128)
        a = [RMSNorm_head(o; gamma) * sigmoid(u Wg1 Wg2 + b_g)] Wo
      l in full_attn_layers (no rotary step: ``mla_use_nope``):
        q = u Wq, heads of nope + rope = 192
        [c, k_r] = u Wkva (kv_lora_rank + rope; ONE k_r for all heads)
        [k_n, v] = RMSNorm(c; gkv) Wkvb, heads of nope + v_head_dim
        a = softmax(q [k_n, k_r]^T / sqrt(192) + causal) v Wo
      h = h + a
      u = RMSNorm(h; g2)
      l <= first_k_dense_replace:  h = h + (silu(u Wg) * (u Wu)) Wd
      else:  s = sigmoid(u Wr) in float32 over all router_width experts;
             the token's experts: top-k of s + b; their weights: s at those
             k, over their sum + 1e-20, times routed_scaling_factor;
             h = h + sum_{e held} w_e Expert_e(u) + Shared(u)
    logits = RMSNorm(h; g_final) W_head; loss = mean CE(logits, y)

It imports nothing of the program (``benchmark.narrow`` is the control's
rounding, the benchmark's own).  The weights are made here from the seed;
the harness hands the same tree to the program, whose layer names the tree
follows so that the two can be compared leaf by leaf.  Latent attention,
the routed layer and the head's blocks are ``kanana-2-30b-a3b``'s
reference's, copied: a configuration carries its own reference.

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``config.json``:
- 5 of the 27 layers (KDA + dense, then KDA, KDA, MLA, KDA, each routed);
  of the 256 routed experts the 8 that this worker holds
  (``experts_held_from`` .. + 7): what the other 248 would add to a token
  is left out, here as in the program, and the partial sum goes on to the
  next layer; the vocabulary's slice of 20,480 rows, ids and targets drawn
  inside it;
- the sizes the published file does not state (the gates' rank 128, b_g,
  ``A_log`` a head and ``dt_bias`` a channel and their draws, the unit
  length's eps, the convolutions' draw) are this file's ``init_params`` and
  ``_kda``; b (``router_bias``) as ``kanana-2-30b-a3b`` draws it;
- the scan over a sequence's tokens is nested, ``SCAN_INNER`` tokens inside
  one ``jax.checkpoint``, because the states of a whole sequence of 4,096
  tokens are 17 GB a layer; the arithmetic is the recurrence's.

So that it fits one chip beside 16 bytes a parameter of float32 state: one
``jax.checkpoint`` a layer, attention four heads of a sequence at a time,
the head's cross-entropy in blocks of 1,024 positions, each recomputed in
the backward pass; the held experts are walked in ``lax.scan``.

``round_to``: as in the other references, the same mathematics in a
narrower type as the program computes in bfloat16: the operands of every
matrix product, every tensor a layer hands on and every cotangent a layer
hands back rounded to it (``benchmark/narrow.py``): the lower-precision
control.  The router's product, its sigmoid, the norms, the decay and the
recurrence's state stay float32 there as in the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.narrow import rounders

HIGHEST = lax.Precision.HIGHEST
CORE = "kimi"
#: heads of one sequence whose (L, L) scores are live at a time, and
#: positions whose logits are
HEAD_GROUP, LOSS_BLOCK = 4, 1024
#: the scale b is drawn at
BIAS_SCALE = 0.01
#: tokens of the recurrence inside one ``jax.checkpoint``
SCAN_INNER = 64
#: A = exp(A_log) a head is drawn uniform in this range, and the step dt
#: (``dt_bias`` = softplus^-1(dt)) a channel log-uniform in that one: a
#: token's log-decay -A softplus(. + dt_bias) lies between about -2 and
#: -0.001, a chunk of 64 tokens' between about -130 and -0.06
A_RANGE, DT_RANGE = (1.0, 16.0), (1e-3, 0.1)
#: added to the sum of squares under the unit length's root
UNIT_EPS = 1e-6


def mixer_kinds(cfg):
    """"kda" or "latent" for layers 1..num_hidden_layers."""
    lists = cfg["linear_attn_config"]
    kda, full = set(lists["kda_layers"]), set(lists["full_attn_layers"])
    layers = set(range(1, cfg["num_hidden_layers"] + 1))
    if kda & full or kda | full != layers:
        raise ValueError(f"kda_layers {sorted(kda)} and full_attn_layers "
                         f"{sorted(full)} do not part layers 1.."
                         f"{cfg['num_hidden_layers']}")
    return ["kda" if i in kda else "latent" for i in sorted(layers)]


def init_params(key, cfg):
    """The whole parameter tree from one key, float32."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_attention_heads"]
    rank, nope, rope, vd = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, held, width = cfg["moe_intermediate_size"], cfg["num_experts"], \
        cfg["router_width"]
    shared = cfg["num_shared_experts"] * f
    std = cfg["initializer_range"]
    kda = cfg["linear_attn_config"]
    kh, kw, taps = kda["num_heads"], kda["head_dim"], \
        kda["short_conv_kernel_size"]
    wide, gate_rank = kh * kw, kda["head_dim"]

    def normal(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    def conv(k):
        return jax.random.uniform(k, (taps, wide), jnp.float32, -1.0,
                                  1.0) / math.sqrt(taps)

    keys = iter(jax.random.split(key, 2 + 20 * cfg["num_hidden_layers"]))
    blocks = []
    for index, kind in enumerate(mixer_kinds(cfg)):
        bp = {"ln1_gamma": jnp.ones((d,), jnp.float32),
              "ln2_gamma": jnp.ones((d,), jnp.float32)}
        if kind == "kda":
            dt = jnp.exp(jax.random.uniform(
                next(keys), (wide,), jnp.float32, *map(math.log, DT_RANGE)))
            bp.update({
                "kda_q_kernel": normal(next(keys), (d, wide)),
                "kda_k_kernel": normal(next(keys), (d, wide)),
                "kda_v_kernel": normal(next(keys), (d, wide)),
                "kda_q_conv": conv(next(keys)),
                "kda_k_conv": conv(next(keys)),
                "kda_v_conv": conv(next(keys)),
                "kda_f_a_kernel": normal(next(keys), (d, gate_rank)),
                "kda_f_b_kernel": normal(next(keys), (gate_rank, wide)),
                "kda_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "kda_a_log": jnp.log(jax.random.uniform(
                    next(keys), (kh,), jnp.float32, *A_RANGE)),
                "kda_b_kernel": normal(next(keys), (d, kh)),
                "kda_g_a_kernel": normal(next(keys), (d, gate_rank)),
                "kda_g_b_kernel": normal(next(keys), (gate_rank, wide)),
                "kda_g_bias": jnp.zeros((wide,), jnp.float32),
                "kda_o_norm": jnp.ones((kw,), jnp.float32),
                "kda_o_kernel": normal(next(keys), (wide, d)),
            })
        else:
            bp.update({
                "q_kernel": normal(next(keys), (d, h * (nope + rope))),
                "kv_a_kernel": normal(next(keys), (d, rank + rope)),
                "kv_a_norm": jnp.ones((rank,), jnp.float32),
                "kv_b_kernel": normal(next(keys), (rank, h * (nope + vd))),
                "o_kernel": normal(next(keys), (h * vd, d)),
            })
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
                          cfg["num_experts_per_token"])
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


def delta_rule(q, k, v, g, beta, scale):
    """The gated delta rule by its recurrence, a token at a time, for
    (B, H, L, .) heads from a zero state (float32, no matrix unit: sums of
    products written out).  The scan is nested, ``SCAN_INNER`` tokens
    inside one ``jax.checkpoint``."""
    b, h, l, dk = q.shape
    inner = math.gcd(l, SCAN_INNER)

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.sum(k_t[..., None] * state, axis=-2)
        state = state + (beta_t[..., None] * k_t)[..., None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2) * scale

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    def by_token(x):    # (B, H, L, ...) -> (L / inner, inner, B, H, ...)
        x = jnp.moveaxis(x, 2, 0)
        return x.reshape((l // inner, inner) + x.shape[1:])

    _, o = lax.scan(tokens, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                    tuple(by_token(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((l,) + o.shape[2:]), 0, 2)


def _kda(qs, cfg, bp, u):
    """One KDA mixer of the normed state ``u`` (B, L, D)."""
    q, qw = qs
    b, l, _ = u.shape
    kda = cfg["linear_attn_config"]
    heads, width = kda["num_heads"], kda["head_dim"]

    def mm(a, w):
        return q(jnp.matmul(q(a), qw(w), precision=HIGHEST))

    def heads_of(x):
        return x.reshape(b, l, heads, -1).transpose(0, 2, 1, 3)

    def conv_heads(name):
        z, taps = mm(u, bp[f"kda_{name}_kernel"]), bp[f"kda_{name}_conv"]
        n = taps.shape[0]
        past = jnp.concatenate([jnp.zeros((b, n - 1, z.shape[-1])), z], 1)
        y = sum(taps[i] * past[:, i:i + l] for i in range(n))
        return heads_of(q(jax.nn.silu(y)))

    def unit(x):
        return q(x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                               + UNIT_EPS))

    g = -jnp.exp(bp["kda_a_log"])[:, None, None] * heads_of(jax.nn.softplus(
        mm(mm(u, bp["kda_f_a_kernel"]), bp["kda_f_b_kernel"])
        + bp["kda_dt_bias"]))
    beta = jax.nn.sigmoid(mm(u, bp["kda_b_kernel"])).transpose(0, 2, 1)
    o = q(delta_rule(unit(conv_heads("q")), unit(conv_heads("k")),
                     conv_heads("v"), g, beta, 1.0 / math.sqrt(width)))
    gate = jax.nn.sigmoid(
        mm(mm(u, bp["kda_g_a_kernel"]), bp["kda_g_b_kernel"])
        + bp["kda_g_bias"])
    o = _rms_norm(o, bp["kda_o_norm"], cfg["rms_norm_eps"]) * heads_of(gate)
    return mm(o.transpose(0, 2, 1, 3).reshape(b, l, heads * width),
              bp["kda_o_kernel"])


def _latent(qs, cfg, bp, u):
    """Latent attention of the normed state ``u``, without positions."""
    q, qw = qs
    b, l, _ = u.shape
    n_head, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    rank, nope, rope, vd = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"], cfg["v_head_dim"]

    def mm(a, w):
        return q(jnp.matmul(q(a), qw(w), precision=HIGHEST))

    def heads(x):
        return x.reshape(b, l, n_head, -1).transpose(0, 2, 1, 3)

    qh = heads(mm(u, bp["q_kernel"]))
    ckr = mm(u, bp["kv_a_kernel"])
    c, k_r = ckr[..., :rank], ckr[..., rank:]
    kv = heads(mm(q(_rms_norm(c, bp["kv_a_norm"], eps)), bp["kv_b_kernel"]))
    kh = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_r[:, None], (b, n_head, l, rope))], axis=-1)
    group = math.gcd(n_head, HEAD_GROUP)
    grouped = [t.reshape(b * n_head // group, group, l, t.shape[-1])
               for t in (qh, kh, kv[..., nope:])]
    ctx = lax.map(lambda t: jax.checkpoint(functools.partial(
        _attention, q))(*t), grouped)
    ctx = ctx.reshape(b, n_head, l, vd).transpose(0, 2, 1, 3) \
        .reshape(b, l, n_head * vd)
    return mm(ctx, bp["o_kernel"])


def _layer(qs, cfg, bp, h):
    q, _ = qs
    b, l, d = h.shape
    eps = cfg["rms_norm_eps"]
    u = q(_rms_norm(h, bp["ln1_gamma"], eps))
    mixer = _kda if "kda_q_kernel" in bp else _latent
    h = q(h + mixer(qs, cfg, bp, u))
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
