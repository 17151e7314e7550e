"""Routed mixture-of-experts feed-forward for the Keras model surface.

The reference framework has no MoE at all (its TransformerLayer.scala:137
feed-forward is a dense 4x MLP); SURVEY.md §2.4 makes expert parallelism a
first-class axis of this framework, and round 4 landed the *strategies*
level (``parallel.strategies.moe_mlp_topk``: shard_map + ``all_to_all``
dispatch).  This module is the model-surface counterpart: the same
GShard/Switch top-k + capacity semantics expressed as **dense one-hot
dispatch einsums**, so it composes with the estimator's single GSPMD
``jit`` train step (no ``shard_map`` axis context needed — XLA partitions
the expert dimension and inserts the all_to_all from the sharding
constraint below).

Capacity semantics (GShard): every token proposes its top-k experts; the
assignment stream is priority-ordered (all 1st choices outrank any 2nd
choice) and each expert accepts at most ``C = ceil(cf * k * S / E)``
tokens per group (group = one batch row).  Over-capacity assignments
contribute ZERO to the expert output — callers MUST place this op behind
a residual connection (as ``_TransformerCore._block_forward_aux`` does)
so a dropped token degrades to identity, never to a zeroed activation.
``tests/test_moe_layer.py::test_skewed_routing_*`` pins exactly that.

The auxiliary load-balancing loss is the GShard/Switch one:
``E * sum_e mean_prob_e * frac_first_choice_e`` — ~1.0 when balanced,
up to ~E when collapsed onto one expert.  Under the GSPMD step the batch
means are global (jit sees global shapes), so no pmean is needed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


#: state leaves that join the training loss: an MoE stack's pre-weighted
#: load-balancing loss, a looped decoder's exit-gate loss, a decoder's own
#: next-token cross-entropy
COST_LEAVES = ("moe_aux_cost", "loop_exit_cost", "lm_loss_cost")


def collect_aux_cost(state):
    """Sum every ``COST_LEAVES`` leaf in a model state tree: the costs
    layers report through the layer state channel (keras/layers/
    self_attention.py: ``_moe_state``'s pre-weighted auxiliary loss, a
    ``LoopedDecoder``'s own training loss).  Every train-step builder that
    computes a loss from ``model.forward`` must add this to the task loss,
    or a collapsed router trains unpenalized."""
    total = jnp.zeros((), jnp.float32)
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        last = path[-1]
        key = getattr(last, "key", getattr(last, "name", None))
        if key in COST_LEAVES:
            total = total + leaf.astype(jnp.float32)
    return total


def _constrain_expert_axis(x):
    """Pin the leading (expert) dim of ``x`` to the mesh ``expert`` axis
    when the active context mesh has one — this is what turns the dispatch
    einsum into an all_to_all + per-shard expert MLP under GSPMD."""
    try:
        from analytics_zoo_tpu.common.engine import (
            EXPERT_AXIS,
            get_zoo_context,
        )

        mesh = get_zoo_context().mesh
    except Exception:
        return x
    if dict(mesh.shape).get(EXPERT_AXIS, 1) <= 1:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    # inside a shard_map body (manual axes) constraints over mesh axes
    # are rejected at lowering — there the caller's own specs govern
    # layout and the expert compute runs shard-local; the constraint is
    # only for the GSPMD (estimator) path
    if EXPERT_AXIS in getattr(jax.sharding.get_abstract_mesh(),
                              "manual_axes", ()):
        return x
    spec = P(EXPERT_AXIS, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def routed_ffn(h, gate_w, w1, b1, w2, b2, *, top_k=2, capacity_factor=1.25,
               activation=jax.nn.gelu, renormalize=False):
    """Top-k routed MoE feed-forward on ``(B, S, D)`` activations.

    Args:
      h: (B, S, D) tokens.
      gate_w: (D, E) router.
      w1: (E, D, F), b1: (E, F), w2: (E, F, D), b2: (D,).
      top_k: experts per token.
      capacity_factor: per-expert capacity multiplier (C = ceil(cf*k*S/E)).
      renormalize: rescale the k gate values to sum to 1 (GShard top-2
        convention); default False (Switch: raw softmax probs).

    Returns ``(y, aux, drop_fraction)``: y (B, S, D) — ZERO rows for
    fully-dropped tokens (use behind a residual); aux — the f32 scalar
    load-balancing loss; drop_fraction — f32 scalar fraction of the k*B*S
    assignments that exceeded capacity.
    """
    b, s, d = h.shape
    e = gate_w.shape[-1]
    if top_k > e:
        raise ValueError(f"top_k={top_k} > n_experts={e}")
    cap = int(math.ceil(capacity_factor * top_k * s / e))
    cap = max(1, min(cap, s))

    # routing in f32 regardless of compute dtype (tiny, precision-critical)
    probs = jax.nn.softmax(
        h.astype(jnp.float32) @ gate_w.astype(jnp.float32),
        axis=-1)                                          # (B, S, E)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)       # (B, S, k)
    if renormalize:
        top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)

    # priority-ordered capacity race: choice j's position within an expert
    # counts every earlier token's j-th choice AND all previous choices
    counts = jnp.zeros((b, 1, e), jnp.float32)
    dispatch = jnp.zeros((b, s, e, cap), h.dtype)
    combine = jnp.zeros((b, s, e, cap), h.dtype)
    kept = jnp.zeros((), jnp.float32)
    for j in range(top_k):
        m = jax.nn.one_hot(top_idx[..., j], e, dtype=jnp.float32)
        pos = jnp.cumsum(m, axis=1) - 1.0 + counts        # (B, S, E)
        keep = m * (pos < cap)
        slot = jax.nn.one_hot(jnp.clip(pos, 0, cap - 1).astype(jnp.int32),
                              cap, dtype=jnp.float32)     # (B, S, E, C)
        dc = (keep[..., None] * slot).astype(h.dtype)
        dispatch = dispatch + dc
        combine = combine + dc * top_vals[..., j, None, None].astype(h.dtype)
        counts = counts + jnp.sum(m, axis=1, keepdims=True)
        kept = kept + jnp.sum(keep)

    # gather each expert's C tokens per group: (E, B, C, D) -> (E, B*C, D)
    xin = jnp.einsum("bsec,bsd->ebcd", dispatch, h)
    xin = _constrain_expert_axis(xin.reshape(e, b * cap, d))
    h1 = activation(jnp.einsum("etd,edf->etf", xin, w1) + b1[:, None, :])
    # b2 joins INSIDE the expert output (before the gate-weighted
    # combine): a fully-dropped token's row stays exactly zero even after
    # b2 trains away from zero — the residual-passthrough contract.  For
    # kept tokens the bias arrives scaled by the gate sum, and with
    # top_k=E full dispatch this reduces to +b2 (probs sum to 1), so the
    # dense-mixture oracle is unchanged.
    ye = (jnp.einsum("etf,efd->etd", h1, w2)
          + b2[None, None, :]).reshape(e, b, cap, d)
    y = jnp.einsum("bsec,ebcd->bsd", combine, ye)

    # GShard load balance: mean router prob x fraction-of-first-choices
    me = jnp.mean(probs, axis=(0, 1))                           # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_idx[..., 0], e,
                                 dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(me * ce)
    drop_fraction = 1.0 - kept / float(top_k * b * s)
    return y, aux, drop_fraction


# ---------------------------------------------------------------------------
# Routed experts without dropped tokens, for a worker that holds some of them
# ---------------------------------------------------------------------------

#: the ``checkpoint_name`` of what the route decides by integers alone: the
#: picked experts, the sort's permutation and its inverse, the group sizes.
#: The ``"attn"`` recomputation policy keeps them (``parallel/plan.py``): a
#: few hundred KB that cost a top-k and two sorts to make again.
ROUTE_NAME = "moe_route"


# The two below are each other's transpose, and both are gathers: the
# assignments are a permutation of (token, choice) pairs, so the scatter-add
# that autodiff would make of either gather is the other gather through the
# inverse permutation.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def dispatch(k, x, perm, inv):
    """``x`` (T, D) -> (k T, D): row a is the token of the a-th assignment
    in sorted order (``perm`` (k T,) of indices into the (T, k) assignments
    laid out row by row, ``inv`` its inverse)."""
    return jnp.take(x, perm // k, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def combine(k, y, perm, inv):
    """``y`` (k T, D) in sorted order -> (T, D): the sum of a token's k
    rows, in float32."""
    rows = jnp.take(y, inv, axis=0).astype(jnp.float32)
    return jnp.sum(rows.reshape(-1, k, y.shape[-1]), axis=1).astype(y.dtype)


dispatch.defvjp(
    lambda k, x, perm, inv: (dispatch(k, x, perm, inv), (perm, inv)),
    lambda k, kept, g: (combine(k, g, *kept), None, None))
combine.defvjp(
    lambda k, y, perm, inv: (combine(k, y, perm, inv), (perm, inv)),
    lambda k, kept, g: (dispatch(k, g, *kept), None, None))


def sigmoid_route(u, router, score_bias, *, top_k, routed_scale):
    """DeepSeek-V3's router without groups (``n_group`` 1): scores
    s = sigmoid(u router) in float32 over ALL the router's experts; a
    token's experts are the top-k of s + ``score_bias`` (the
    ``e_score_correction_bias``, which picks and takes no gradient); their
    weights are s itself at those k, over their sum + 1e-20, times
    ``routed_scale``.  Returns (experts (T, k) int32, weights (T, k)
    float32)."""
    s = jax.nn.sigmoid(u.astype(jnp.float32) @ router.astype(jnp.float32))
    _, experts = jax.lax.top_k(
        jax.lax.stop_gradient(s + score_bias.astype(jnp.float32)), top_k)
    picked = jnp.take_along_axis(s, experts, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * routed_scale


def held_experts_ffn(u, router, score_bias, w_gate, w_up, w_down, *,
                     first_held, top_k, routed_scale,
                     activation=jax.nn.silu):
    """The part that the experts held here give of a routed feed-forward,
    for (T, D) tokens: sum over a token's top-k experts e that are held of
    w_e (act(u Wgate_e) * (u Wup_e)) Wdown_e.

    One expert-parallel worker's share: ``router`` (D, E) and
    ``score_bias`` (E,) are the whole router's, ``w_gate``/``w_up``
    (H, D, F) and ``w_down`` (H, F, D) the H experts held, which are
    ``first_held`` .. ``first_held + H - 1``.  Every token is routed over
    all E experts (``sigmoid_route``); the k T assignments are sorted so
    that the held experts' rows come first, expert by expert, and only
    those rows are multiplied, in groups, by their expert
    (``ops/pallas/grouped_matmul.py``).  There is no capacity: the buffer
    has all k T rows, so no assignment is dropped at any skew.  What the
    experts held elsewhere would add is left out, and no code stands in
    for the exchange that would bring it.

    Returns ``(y (T, D), stats)`` with ``stats`` float32 scalars:
    ``held_assignments`` (rows multiplied), ``load_max_over_mean`` (the
    fullest held expert's rows over the mean) and ``dropped_assignments``
    (held assignments that were not multiplied: 0 by construction)."""
    from jax.ad_checkpoint import checkpoint_name

    from analytics_zoo_tpu.ops.pallas.grouped_matmul import grouped_matmul

    t, held = u.shape[0], w_gate.shape[0]
    experts, weights = sigmoid_route(u, router, score_bias, top_k=top_k,
                                     routed_scale=routed_scale)
    # sorted by (held expert, then the rest), stably: rows of one expert
    # keep the tokens' order
    local = experts.reshape(-1) - first_held
    key = jnp.where((local >= 0) & (local < held), local, held)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.argsort(perm).astype(jnp.int32)
    group_sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                          axis=0, dtype=jnp.int32)
    perm, inv, group_sizes = (checkpoint_name(x, ROUTE_NAME)
                              for x in (perm, inv, group_sizes))
    n_held = jnp.sum(group_sizes)
    live = (jnp.arange(top_k * t) < n_held)[:, None]

    # rows past the held ones are never multiplied and never written: the
    # select on the way in zeroes what the kernel leaves in their gradient,
    # the one on the way out what it leaves in the result
    x = jnp.where(live, dispatch(top_k, u, perm, inv), 0)
    f = activation(grouped_matmul(x, w_gate, group_sizes)) \
        * grouped_matmul(x, w_up, group_sizes)
    y = grouped_matmul(f.astype(u.dtype), w_down, group_sizes)
    w_sorted = jnp.take(weights.reshape(-1), perm)[:, None]
    # masked before it meets its weight: the weight's gradient reads y
    y = jnp.where(live, y, 0) * w_sorted.astype(y.dtype)
    multiplied = jnp.minimum(n_held, top_k * t)
    stats = {
        "held_assignments": multiplied.astype(jnp.float32),
        "load_max_over_mean": jnp.max(group_sizes).astype(jnp.float32)
        * held / jnp.maximum(n_held, 1).astype(jnp.float32),
        "dropped_assignments": (n_held - multiplied).astype(jnp.float32)}
    return combine(top_k, y, perm, inv), stats
