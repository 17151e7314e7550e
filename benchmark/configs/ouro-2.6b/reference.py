"""Plain reference of the ``ouro-2.6b`` configuration: the looped decoder
in ``jax.numpy``, float32, every matrix product at ``highest`` precision,
attention by its definition (full score matrix, causal mask, softmax),
rotary positions written out, the loss and its gradient by ``jax.grad``,
Adam written out.

    h = E[x]; for pass t = 1..T, for layer l = 1..N (the same N layers in
    every pass):
        u = RMSNorm(h; g1); q, k, v = u Wqkv split in heads; rotary on q, k
        a = softmax(q k^T / sqrt(hd) + causal) v
        h = h + RMSNorm(a Wo; g2)
        u = RMSNorm(h; g3); h = h + RMSNorm((silu(u Wgate) * (u Wup)) Wdown; g4)
    after the layers of pass t: h = s_t = RMSNorm(h; g_final);
        logits_t = s_t W_head; lambda_t = sigmoid(s_t . w_exit + b_exit)
    p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < T, p_T = prod_{j<T} (1 - lambda_j)
    loss = mean over tokens of sum_t p_t CE(logits_t, y) - beta H(p)

It imports nothing of the program (``benchmark.narrow`` is the control's
rounding, the benchmark's own).  The weights are made here from the seed;
the harness hands the same tree to the program, whose layer names the tree
follows so that the two can be compared leaf by leaf.

Departures from the published model, each also under ``assumed`` in
``config.json``:
- 6 of the 48 layers (the configuration's one cut);
- the sandwich placement of the four RMSNorms and the final norm between
  passes are as recalled from the published ``modeling_ouro.py``;
- q, k and v come from one (d, 3d) kernel, the three matrices side by side;
- beta 0.05, ``initializer_range`` 0.02, the gate trained by this loss
  alone (the paper's second stage is left out).

So that it fits one chip beside 16 bytes a parameter of float32 state, and
nothing else: one ``jax.checkpoint`` a layer application, attention four
heads of a sequence at a time, a pass's head and cross-entropy in blocks of
1024 positions, each recomputed in the backward pass.

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
CORE = "ouro"
#: heads of one sequence whose (L, L) scores are live at a time, and
#: positions whose logits are
HEAD_GROUP, LOSS_BLOCK = 4, 1024


def init_params(key, cfg):
    """The whole parameter tree from one key, float32."""
    d, m, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    std = cfg["initializer_range"]

    def normal(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    keys = iter(jax.random.split(key, 3 + 5 * cfg["num_hidden_layers"]))
    blocks = []
    for _ in range(cfg["num_hidden_layers"]):
        blocks.append({
            "qkv_kernel": normal(next(keys), (d, 3 * d)),
            "proj_kernel": normal(next(keys), (d, d)),
            "gate_kernel": normal(next(keys), (d, m)),
            "fc_kernel": normal(next(keys), (d, m)),
            "out_kernel": normal(next(keys), (m, d)),
            **{f"ln{i}_gamma": jnp.ones((d,), jnp.float32)
               for i in (1, 2, 3, 4)},
        })
    return {CORE: {
        "tok_embed": normal(next(keys), (v, d)),
        "blocks": blocks,
        "final_gamma": jnp.ones((d,), jnp.float32),
        "head_kernel": normal(next(keys), (d, v)),
        "exit_kernel": normal(next(keys), (d, 1)),
        "exit_bias": jnp.zeros((1,), jnp.float32),
    }}


def _rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gamma


def _rotary(x, theta):
    """(B, H, L, hd): pair i of a head is (x[i], x[i + hd/2]), turned at
    position p by the angle p * theta^(-2i/hd)."""
    l, hd = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(q, qh, kh, vh):
    """softmax(q k^T / sqrt(hd) + causal) v by its definition, for
    (G, L, hd) heads of one sequence."""
    l, hd = qh.shape[-2], qh.shape[-1]
    scores = jnp.einsum("gqd,gkd->gqk", q(qh), q(kh),
                        precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((l, l), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return q(jnp.einsum("gqk,gkd->gqd", q(probs), q(vh), precision=HIGHEST))


def _layer(qs, cfg, bp, h):
    q, qw = qs
    b, l, d = h.shape
    n_head, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    hd = d // n_head

    def mm(a, w):
        return q(jnp.matmul(q(a), qw(w), precision=HIGHEST))

    u = q(_rms_norm(h, bp["ln1_gamma"], eps))
    heads = [t.reshape(b, l, n_head, hd).transpose(0, 2, 1, 3)
             for t in jnp.split(mm(u, bp["qkv_kernel"]), 3, axis=-1)]
    qh = _rotary(heads[0], cfg["rope_theta"])
    kh = _rotary(heads[1], cfg["rope_theta"])
    group = math.gcd(n_head, HEAD_GROUP)
    grouped = [t.reshape(b * n_head // group, group, l, hd)
               for t in (qh, kh, heads[2])]
    ctx = lax.map(lambda t: jax.checkpoint(functools.partial(
        _attention, q))(*t), grouped)
    ctx = ctx.reshape(b, n_head, l, hd).transpose(0, 2, 1, 3).reshape(b, l, d)
    a = mm(ctx, bp["proj_kernel"])
    h = q(h + q(_rms_norm(a, bp["ln2_gamma"], eps)))
    u = q(_rms_norm(h, bp["ln3_gamma"], eps))
    f = q(jax.nn.silu(mm(u, bp["gate_kernel"])) * mm(u, bp["fc_kernel"]))
    f = mm(f, bp["out_kernel"])
    return q(h + q(_rms_norm(f, bp["ln4_gamma"], eps)))


def _token_ce(qs, kernel, s, targets):
    """CE(s W_head, y), a token each, for one block of positions."""
    q, qw = qs
    logits = q(jnp.matmul(s, qw(kernel), precision=HIGHEST))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def passes(params, tokens, targets, cfg, round_to=None, stacks=None):
    """Every pass's cross-entropy and exit probability, a token each:
    ``(ce, lam)``, both (T, B, L); and the last pass's state s_T.
    ``stacks``: the layers' tree with a leading axis of T, a stack a pass,
    to run in place of the one shared stack (the tie's test: untied
    copies).  The passes and a pass's blocks of positions are walked in
    ``lax.scan``: unrolled, every use of a weight keeps a gradient of the
    weight's size until all are summed, 16 of the head's alone."""
    qs = q, qw = rounders(round_to)
    core = params[CORE]
    b, l = tokens.shape
    n = l // math.gcd(l, LOSS_BLOCK)
    # one layer application's activations at a time in the backward pass,
    # and one block's logits
    layer = jax.checkpoint(functools.partial(_layer, qs, cfg))
    token_ce = jax.checkpoint(functools.partial(_token_ce, qs,
                                                core["head_kernel"]))

    def blocked(x):     # (B, L, ...) -> (n, B, L / n, ...)
        return jnp.moveaxis(x.reshape((b, n, l // n) + x.shape[2:]), 1, 0)

    y_blocks = blocked(targets.astype(jnp.int32))

    def one_pass(h, stack):
        for bp in (core["blocks"] if stack is None else stack):
            h = layer(bp, h)
        h = q(_rms_norm(h, core["final_gamma"], cfg["rms_norm_eps"]))
        ce = lax.map(lambda sy: token_ce(*sy), (blocked(h), y_blocks))
        gate = jnp.matmul(h, qw(core["exit_kernel"]),
                          precision=HIGHEST)[..., 0] + core["exit_bias"]
        return h, (jnp.moveaxis(ce, 0, 1).reshape(b, l),
                   jax.nn.sigmoid(gate))

    h, (ce, lam) = lax.scan(one_pass, q(core["tok_embed"][tokens]), stacks,
                            length=cfg["total_ut_steps"])
    return ce, lam, h


def exit_distribution(lam):
    """p (T, ...) from the gates' lambda (T, ...): p_t = lambda_t
    prod_{j<t} (1 - lambda_j), the last pass taking what is left."""
    survive = jnp.cumprod(1.0 - lam[:-1], axis=0)
    survive = jnp.concatenate([jnp.ones_like(lam[:1]), survive])
    return jnp.concatenate([lam[:-1] * survive[:-1], survive[-1:]])


def loss_fn(params, tokens, targets, cfg, round_to=None, stacks=None):
    """Mean over tokens of sum_t p_t CE_t - beta H(p)."""
    ce, lam, _ = passes(params, tokens, targets, cfg, round_to, stacks)
    p = exit_distribution(lam)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-37)),
                                 0.0), axis=0)
    return jnp.mean(jnp.sum(p * ce, axis=0) - cfg["exit_beta"] * entropy)


def logits(params, tokens, cfg):
    """What inference reads at ``early_exit_threshold`` 1.0: logits_T."""
    _, _, s = passes(params, tokens, jnp.zeros_like(tokens), cfg)
    return jnp.matmul(s, params[CORE]["head_kernel"], precision=HIGHEST)


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
