"""Plain reference of the ``nemotron-3-nano-30b-a3b`` configuration: a
decoder (``model_type`` ``nemotron_h``) whose layers are each one residual
branch of one of three kinds, in ``jax.numpy``, float32, every matrix
product at ``highest`` precision, no kernel, no chunk, no sort, no groups of
rows: Mamba-2 as its recurrence, token by token (``lax.scan``), attention by
its definition (the two key-value heads repeated to the 32 query heads,
scores over (L, L), causal mask, softmax), the routed layer as a masked sum
over the experts held, the loss and its gradient by ``jax.grad``, Adam
written out.

    h = E[x]; for layer l = 0..N-1, f_l by hybrid_override_pattern[l]:
      h = h + f_l(RMSNorm(h; g_l))
      M: [z, xBC, dt~] = u W_in
         xBC = SiLU(conv4(xBC) + b_conv)
           conv4: y_t = sum_{i=0..3} w_i z_{t-3+i} a channel, zeros before
           the start
         [x, B, C] = xBC: x in 64 heads of 64, B and C in 8 groups of 128
         dt = softplus(dt~ + dt_bias), A = -exp(A_log), a head
         S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, S_0 = 0 (head h reads
           group h // 8);  y_t = S_t C_t + D x_t
         f = RMSNorm_8 groups(y o SiLU(z); g_norm) W_out
      *: q = u Wq (32 heads of 128), k = u Wk, v = u Wv (2 heads of 128,
         query head h reading head h // 16), no positions;
         f = softmax(q k^T / sqrt(128) + causal) v Wo
      E: s = sigmoid(u Wr) in float32 over all router_width experts; the
         token's experts: top-k of s + b; their weights: s at those k, over
         their sum + 1e-20, times routed_scaling_factor;
         f = sum_{e held} w_e relu(u Wup_e)^2 Wdown_e + relu(u Wsu)^2 Wsd
    logits = RMSNorm(h; g_final) W_head; loss = mean CE(logits, y)

It imports nothing of the program (``benchmark.narrow`` is the control's
rounding, the benchmark's own).  The weights are made here from the seed;
the harness hands the same tree to the program, whose layer names the tree
follows so that the two can be compared leaf by leaf: two layers in a row,
a mixer and the "E" after it, are one block of the tree (``blocks``), with
``ln1_gamma`` before the mixer and ``ln2_gamma`` before the feed-forward.
The routed layer and the head's blocks are ``kimi-linear-48b-a3b``'s
reference's with relu² experts without a gate, copied: a configuration
carries its own reference.

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``config.json``:
- the published layers 0-6 (M E M E M * E) of 52; of the 128 routed experts
  the 8 that this worker holds (``experts_held_from`` .. + 7): what the
  other 120 would add to a token is left out, here as in the program, and
  the partial sum goes on to the next layer; the vocabulary's slice of
  16,384 rows, ids and targets drawn inside it;
- the draws the published file does not state (``A_log``, ``dt_bias``, D,
  the convolution's taps and bias, W_out unscaled) are this file's
  ``init_params``; b (``router_bias``) as ``kanana-2-30b-a3b`` draws it;
- the scan over a sequence's tokens is nested, ``SCAN_INNER`` tokens
  inside one ``jax.checkpoint``, because a token's states are 2.1 MB a
  sequence and a flat scan over the batch of two keeps 17 GB a layer for
  its gradient; the arithmetic is the recurrence's.

So that it fits one chip beside 16 bytes a parameter of float32 state: one
``jax.checkpoint`` a layer, attention four heads of a sequence at a time,
the head's cross-entropy in blocks of 1,024 positions, each recomputed in
the backward pass; the held experts are walked in ``lax.scan``.

``round_to``: as in the other references, the same mathematics in a
narrower type as the program computes in bfloat16: the operands of every
matrix product, every tensor a layer hands on and every cotangent a layer
hands back rounded to it (``benchmark/narrow.py``): the lower-precision
control.  The router's product, its sigmoid, the norms, dt, the decay and
the recurrence's state stay float32 there as in the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.narrow import rounders

HIGHEST = lax.Precision.HIGHEST
CORE = "nemotron"
#: heads of one sequence whose (L, L) scores are live at a time, and
#: positions whose logits are
HEAD_GROUP, LOSS_BLOCK = 4, 1024
#: the scale b is drawn at
BIAS_SCALE = 0.01
#: tokens of the recurrence inside one ``jax.checkpoint``
SCAN_INNER = 64
#: A = exp(A_log) a head is drawn uniform in this range
A_RANGE = (1.0, 16.0)


def blocks(cfg):
    """The pattern's layers as blocks of (mixer, whether an "E" follows):
    "M" or "*", then the "E" after it where there is one."""
    pattern, out, i = cfg["hybrid_override_pattern"], [], 0
    while i < len(pattern):
        if pattern[i] not in "M*":
            raise ValueError(f"{pattern!r}: a block begins at layer {i} "
                             f"with {pattern[i]!r}")
        routed = pattern[i + 1:i + 2] == "E"
        out.append((pattern[i], routed))
        i += 2 if routed else 1
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(pattern)} layers in the pattern for "
                         f"num_hidden_layers {cfg['num_hidden_layers']}")
    return out


def widths(cfg):
    """(I, the convolution's channels, G N) of a Mamba-2 mixer."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    groups = cfg["n_groups"] * cfg["ssm_state_size"]
    return inner, inner + 2 * groups, groups


def init_params(key, cfg):
    """The whole parameter tree from one key, float32."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, hd = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    f, held, width = cfg["moe_intermediate_size"], cfg["n_routed_experts"], \
        cfg["router_width"]
    shared = cfg["moe_shared_expert_intermediate_size"] \
        * cfg["n_shared_experts"]
    mh, taps = cfg["mamba_num_heads"], cfg["conv_kernel"]
    inner, conv, _ = widths(cfg)
    std = cfg["initializer_range"]

    def normal(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    keys = iter(jax.random.split(key, 2 + 16 * cfg["num_hidden_layers"]))
    tree = []
    for mixer, routed in blocks(cfg):
        bp = {"ln1_gamma": jnp.ones((d,), jnp.float32)}
        if mixer == "M":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                next(keys), (mh,), jnp.float32,
                math.log(cfg["time_step_min"]),
                math.log(cfg["time_step_max"]))), cfg["time_step_floor"])
            bp.update({
                "ssd_in_kernel": normal(next(keys), (d, inner + conv + mh)),
                "ssd_conv": jax.random.uniform(
                    next(keys), (taps, conv), jnp.float32, -1.0, 1.0)
                / math.sqrt(taps),
                "ssd_conv_bias": jnp.zeros((conv,), jnp.float32),
                "ssd_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "ssd_a_log": jnp.log(jax.random.uniform(
                    next(keys), (mh,), jnp.float32, *A_RANGE)),
                "ssd_d": jnp.ones((mh,), jnp.float32),
                "ssd_norm": jnp.ones((inner,), jnp.float32),
                "ssd_out_kernel": normal(next(keys), (inner, d)),
            })
        else:
            bp.update({
                "attn_q_kernel": normal(next(keys), (d, heads * hd)),
                "attn_k_kernel": normal(next(keys), (d, kv * hd)),
                "attn_v_kernel": normal(next(keys), (d, kv * hd)),
                "attn_o_kernel": normal(next(keys), (heads * hd, d)),
            })
        if routed:
            bias = BIAS_SCALE * jax.random.normal(next(keys), (width,),
                                                  jnp.float32)
            bp.update({
                "ln2_gamma": jnp.ones((d,), jnp.float32),
                "router_kernel": normal(next(keys), (d, width)),
                "router_bias": bias.astype(jnp.bfloat16).astype(jnp.float32),
                "experts_up": normal(next(keys), (held, d, f)),
                "experts_down": normal(next(keys), (held, f, d)),
                "shared_fc_kernel": normal(next(keys), (d, shared)),
                "shared_out_kernel": normal(next(keys), (shared, d)),
            })
        tree.append(bp)
    return {CORE: {
        "tok_embed": normal(next(keys), (v, d)),
        "blocks": tree,
        "final_gamma": jnp.ones((d,), jnp.float32),
        "head_kernel": normal(next(keys), (d, v)),
    }}


def _rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gamma


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def ssd_rule(x, dt, a, b, c):
    """Mamba-2's rule by its recurrence, a token at a time, for x (B, H,
    L, P), dt (B, H, L), A (H,) and B, C (B, G, L, N), head h reading group
    h // (H / G), from a zero state (float32, no matrix unit: sums of
    products written out), without the skip D x.  The scan is nested,
    ``SCAN_INNER`` tokens inside one ``jax.checkpoint``."""
    bt, h, l, p = x.shape
    share = h // b.shape[1]
    b, c = (jnp.repeat(t, share, axis=1) for t in (b, c))
    inner = math.gcd(l, SCAN_INNER)

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    def by_token(t):    # (B, H, L, ...) -> (L / inner, inner, B, H, ...)
        t = jnp.moveaxis(t, 2, 0)
        return t.reshape((l // inner, inner) + t.shape[1:])

    _, y = lax.scan(tokens, jnp.zeros((bt, h, p, b.shape[-1]), jnp.float32),
                    tuple(by_token(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape((l,) + y.shape[2:]), 0, 2)


def _mamba(qs, cfg, bp, u):
    """One Mamba-2 mixer of the normed state ``u`` (B, L, D)."""
    q, qw = qs
    bt, l, _ = u.shape
    inner, conv, groups = widths(cfg)
    mh, ng = cfg["mamba_num_heads"], cfg["n_groups"]

    def mm(a, w):
        return q(jnp.matmul(q(a), qw(w), precision=HIGHEST))

    def heads_of(t, n):
        return t.reshape(bt, l, n, -1).transpose(0, 2, 1, 3)

    zxbcdt = mm(u, bp["ssd_in_kernel"])
    z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv], \
        zxbcdt[..., inner + conv:]
    taps = bp["ssd_conv"]
    n = taps.shape[0]
    past = jnp.concatenate([jnp.zeros((bt, n - 1, conv)), xbc], 1)
    xbc = q(jax.nn.silu(sum(taps[i] * past[:, i:i + l] for i in range(n))
                        + bp["ssd_conv_bias"]))
    x = heads_of(xbc[..., :inner], mh)
    b = heads_of(xbc[..., inner:inner + groups], ng)
    c = heads_of(xbc[..., inner + groups:], ng)
    dt = jax.nn.softplus(dt + bp["ssd_dt_bias"]).transpose(0, 2, 1)
    y = ssd_rule(x, dt, -jnp.exp(bp["ssd_a_log"]), b, c) \
        + bp["ssd_d"][:, None, None] * x
    y = q(y).transpose(0, 2, 1, 3).reshape(bt, l, inner)
    g = (y * jax.nn.silu(z)).reshape(bt, l, ng, inner // ng)
    g = _rms_norm(g, 1.0, cfg["norm_eps"]).reshape(bt, l, inner) \
        * bp["ssd_norm"]
    return mm(g, bp["ssd_out_kernel"])


def _attention(q, qh, kh, vh):
    """softmax(q k^T / sqrt(width of q) + causal) v by its definition, for
    (G, L, .) heads of one sequence."""
    l, width = qh.shape[-2], qh.shape[-1]
    scores = jnp.einsum("gqd,gkd->gqk", q(qh), q(kh),
                        precision=HIGHEST) / math.sqrt(width)
    causal = jnp.tril(jnp.ones((l, l), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return q(jnp.einsum("gqk,gkd->gqd", q(probs), q(vh), precision=HIGHEST))


def _grouped_query(qs, cfg, bp, u):
    """Grouped-query attention of the normed state ``u``, without
    positions: the key-value heads repeated to the query heads."""
    q, qw = qs
    bt, l, _ = u.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def mm(a, w):
        return q(jnp.matmul(q(a), qw(w), precision=HIGHEST))

    def heads_of(t, n):
        return t.reshape(bt, l, n, -1).transpose(0, 2, 1, 3)

    qh = heads_of(mm(u, bp["attn_q_kernel"]), heads)
    kh, vh = (jnp.repeat(heads_of(mm(u, bp[f"attn_{x}_kernel"]), kv),
                         heads // kv, axis=1) for x in "kv")
    group = math.gcd(heads, HEAD_GROUP)
    grouped = [t.reshape(bt * heads // group, group, l, t.shape[-1])
               for t in (qh, kh, vh)]
    ctx = lax.map(lambda t: jax.checkpoint(functools.partial(
        _attention, q))(*t), grouped)
    ctx = ctx.reshape(bt, heads, l, -1).transpose(0, 2, 1, 3) \
        .reshape(bt, l, -1)
    return mm(ctx, bp["attn_o_kernel"])


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


def _routed(qs, cfg, bp, u):
    """The part that the experts in ``bp`` give of (T, d) tokens (those
    from ``cfg["experts_held_from"]`` on), with the shared expert."""
    q, qw = qs
    u = q(u)

    def mm(a, w):
        return q(jnp.matmul(a, qw(w), precision=HIGHEST))

    def ungated(u, wu, wd):
        return mm(q(_relu2(mm(u, wu))), wd)

    first = cfg["experts_held_from"]
    held = bp["experts_up"].shape[0]
    weights = lax.dynamic_slice_in_dim(route(cfg, bp, u), first, held, 1)
    # an expert at a time, its hidden activations made again in the
    # backward pass
    expert = jax.checkpoint(
        lambda u, w_e, wu, wd: q(w_e[:, None] * ungated(u, wu, wd)))

    def add_expert(total, xs):
        return total + expert(u, *xs), None

    routed, _ = lax.scan(add_expert, jnp.zeros_like(u),
                         (weights.T, bp["experts_up"], bp["experts_down"]))
    return q(routed + ungated(u, bp["shared_fc_kernel"],
                              bp["shared_out_kernel"]))


def _block(qs, cfg, bp, h):
    q, _ = qs
    b, l, d = h.shape
    eps = cfg["norm_eps"]
    u = q(_rms_norm(h, bp["ln1_gamma"], eps))
    mixer = _mamba if "ssd_in_kernel" in bp else _grouped_query
    h = q(h + mixer(qs, cfg, bp, u))
    if "router_kernel" not in bp:
        return h
    u = q(_rms_norm(h, bp["ln2_gamma"], eps))
    return q(h + _routed(qs, cfg, bp, u.reshape(b * l, d)).reshape(b, l, d))


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
    block = jax.checkpoint(functools.partial(_block, qs, cfg))
    h = q(core["tok_embed"][tokens])
    for bp in core["blocks"]:
        h = block(bp, h)
    return q(_rms_norm(h, core["final_gamma"], cfg["norm_eps"]))


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
