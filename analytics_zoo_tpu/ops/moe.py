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
#: sort's permutation and the group sizes.  The ``"attn"`` recomputation
#: policy keeps them (``parallel/plan.py``): a few hundred KB that cost a
#: top-k and a sort to make again.
ROUTE_NAME = "moe_route"


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


def walk_bound(assignments, held, router_width):
    """R, the sorted rows a window of the walk covers: four thirds of the
    even share of the ``assignments`` that ``held`` of ``router_width``
    experts take, and no fewer than an eighth of them all, up to the
    grouped product's row tile, and at most all of them.  From the shapes
    alone; a step whose held rows pass R walks a second window.

    The eighth: a window costs the same whatever it covers (its sort, its
    gathers and zero fills, thirteen kernel launches: what some 5,000 held
    rows cost), and at seeded weights a routed layer holds several times
    its even share through tens of steps.  A worker with a small share (8
    of 256 experts: 2,816 rows by the four thirds) then walked 4.4 to 5.7
    windows a step over four layers where 4 do at 8,192, and its step
    followed the seed by as much (PERF.md, PR 35)."""
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import ROWS

    share = max(-(-4 * assignments * held // (3 * router_width)),
                -(-assignments // 8))
    return min(assignments, -(-share // ROWS) * ROWS)


def _rows(x, at):
    """Rows ``at`` of ``x``.  Every index here is one of a permutation's or
    a token's, in bounds by construction, and says so: checked, the gather
    is followed by a select over all it gathered that costs as much again
    (0.61 ms after 0.31 at (49,152, 2048) bf16; PERF.md, PR 33)."""
    return x.at[at].get(mode="promise_in_bounds")


def _window(bound, top_k, w, u, weights, perm, group_sizes, w_gate, w_up):
    """Window ``w`` of the walk, sorted rows w R .. w R + R - 1, up to the
    hidden rows: which rows of it are held (``live``), its share of each
    held expert's rows (they stay sorted by expert, so the grouped products
    take them as they are), its tokens' rows ``x``, the two products ``a``
    and ``b``, the rows' weights ``weight`` and, for the sum over a token's
    rows, the window's rows in the tokens' order (``by_token``, whose they
    are, and how many fall in each tile of tokens; one sort of R pairs: the
    same order from a running count and a search took 1.49 ms on the chip;
    PERF.md, PR 33).  Rows that are not live are never multiplied, never
    written and never summed: the kernels walk the sizes."""
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul,
        token_tile,
    )

    t = u.shape[0]
    first = w * bound
    ends = jnp.cumsum(group_sizes)
    sizes = jnp.clip(ends, first, first + bound) \
        - jnp.clip(ends - group_sizes, first, first + bound)
    rows = jnp.arange(bound, dtype=jnp.int32)
    live = first + rows < ends[-1]
    picks = jax.lax.dynamic_slice(perm, (first,), (bound,))
    token = picks // top_k
    x = _rows(u, token)
    whose, order = jax.lax.sort((jnp.where(live, token, t), rows),
                                num_keys=1)
    tile_sizes = jnp.sum(
        whose[:, None] // token_tile(t)
        == jnp.arange(t // token_tile(t))[None, :], axis=0, dtype=jnp.int32)
    return {"live": live, "sizes": sizes, "picks": picks, "token": token,
            "x": x, "a": grouped_matmul(x, w_gate, sizes),
            "b": grouped_matmul(x, w_up, sizes),
            "weight": _rows(weights.reshape(-1), picks)[:, None],
            "by_token": (order, whose, tile_sizes)}


def _sum_by_token(rows, by_token, total):
    """``total`` (T, D) float32 + the sum of a token's ``rows`` (R, D)."""
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import grouped_row_sum

    order, token, tile_sizes = by_token
    return grouped_row_sum(_rows(rows, order), token, tile_sizes, total)


def _windows(bound, perm, group_sizes, body, start):
    """``body(w, carry)`` over the windows that the step's held rows fill,
    ceil(held / R) of them, counted on the device: one compiled body.  No
    loop where R is all the rows."""
    if bound == perm.shape[0]:
        return body(0, start)
    return jax.lax.fori_loop(
        0, (jnp.sum(group_sizes) + bound - 1) // bound, body, start)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _walk(bound, top_k, activation, u, weights, perm, group_sizes,
          w_gate, w_up, w_down):
    """The held experts' part of (T, D) tokens ``u``, window by window:
    ``weights`` (T, k) are the assignments' and ``perm`` their sorted
    order, padded to whole windows.  The parts are summed in float32.  A
    loop whose trip count the device reads is not reverse-differentiable,
    so the backward rule is a loop too: it keeps the operands only, makes a
    window's hidden rows again and pulls the cotangent through them (under
    the ``"attn"`` policy the forward products are made again in the
    backward pass anyway), adding a window's share to the running sums:
    the tokens' in float32, the experts' in their own dtype, each summed in
    float32 by the kernel that writes it."""
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import grouped_matmul

    operands = (u, weights, perm, group_sizes, w_gate, w_up, w_down)

    def body(w, y):
        at = _window(bound, top_k, w, *operands[:6])
        f = (activation(at["a"]) * at["b"]).astype(u.dtype)
        part = grouped_matmul(f, w_down, at["sizes"])
        return _sum_by_token(part * at["weight"].astype(part.dtype),
                             at["by_token"], y)

    return _windows(bound, perm, group_sizes, body,
                    jnp.zeros(u.shape, jnp.float32)).astype(u.dtype)


def _walk_bwd(bound, top_k, activation, operands, g):
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul,
        grouped_matmul_transposed,
    )

    u, weights, perm, group_sizes, w_gate, w_up, w_down = operands

    def body(w, sums):
        du, d_weight, d_gate, d_up, d_down = sums
        at = _window(bound, top_k, w, *operands[:6])
        sizes, weight = at["sizes"], at["weight"]
        f, pull = jax.vjp(
            lambda a, b: (activation(a) * b).astype(u.dtype),
            at["a"], at["b"])
        # y = (f Wdown) weight: the cotangent of a row before its weight,
        # through Wdown once, serves f's and the weight's gradient alike
        g_rows = _rows(g, at["token"])
        df = grouped_matmul(g_rows, w_down, sizes, transpose_rhs=True)
        # (rows that are not live add nothing, the padding's to pick 0)
        d_weight = d_weight.at[at["picks"]].add(
            jnp.where(at["live"], jnp.sum(
                f.astype(jnp.float32) * df.astype(jnp.float32), axis=-1), 0),
            mode="promise_in_bounds")
        d_down = grouped_matmul_transposed(
            f * weight.astype(f.dtype), g_rows, sizes, d_down)
        da, db = pull(df * weight.astype(df.dtype))
        d_gate = grouped_matmul_transposed(at["x"], da, sizes, d_gate)
        d_up = grouped_matmul_transposed(at["x"], db, sizes, d_up)
        dx = grouped_matmul(da, w_gate, sizes, transpose_rhs=True) \
            + grouped_matmul(db, w_up, sizes, transpose_rhs=True)
        return (_sum_by_token(dx, at["by_token"], du), d_weight,
                d_gate, d_up, d_down)

    du, d_weight, *d_experts = _windows(bound, perm, group_sizes, body, (
        jnp.zeros(u.shape, jnp.float32),
        jnp.zeros(weights.size, weights.dtype),
        *(jnp.zeros_like(x) for x in (w_gate, w_up, w_down))))
    return (du.astype(u.dtype), d_weight.reshape(weights.shape), None, None,
            *d_experts)


_walk.defvjp(
    lambda bound, top_k, activation, *operands: (
        _walk(bound, top_k, activation, *operands), operands),
    _walk_bwd)


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
    (``ops/pallas/grouped_matmul.py``).  There is no capacity and no
    assignment is dropped at any skew.  What the experts held elsewhere
    would add is left out, and no code stands in for the exchange that
    would bring it.

    The held rows are walked in windows of R = ``walk_bound`` sorted rows
    (from the shapes): the gathers in and out, the elementwise work and the
    sum over a token's rows cost what the held rows cost, not what all k T
    would.  One compiled walk runs ceil(held / R) times, counted on the
    device: once in an ordinary step, again for a step whose held rows pass
    R, not at all where nothing is held.  A worker that holds so many
    experts that R = k T has one window and no loop.

    Returns ``(y (T, D), stats)`` with ``stats`` float32 scalars:
    ``held_assignments`` (rows multiplied), ``load_max_over_mean`` (the
    fullest held expert's rows over the mean), ``dropped_assignments``
    (held assignments that were not multiplied: 0 by construction) and
    ``walk_windows`` (the windows the held rows fill)."""
    from jax.ad_checkpoint import checkpoint_name

    t, held = u.shape[0], w_gate.shape[0]
    experts, weights = sigmoid_route(u, router, score_bias, top_k=top_k,
                                     routed_scale=routed_scale)
    # sorted by (held expert, then the rest), stably: rows of one expert
    # keep the tokens' order
    local = experts.reshape(-1) - first_held
    key = jnp.where((local >= 0) & (local < held), local, held)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    group_sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                          axis=0, dtype=jnp.int32)
    perm, group_sizes = (checkpoint_name(x, ROUTE_NAME)
                         for x in (perm, group_sizes))
    n_held = jnp.sum(group_sizes)

    bound = walk_bound(top_k * t, held, router.shape[-1])
    # whole windows: the rows past k T are no assignment's and never live
    y = _walk(bound, top_k, activation, u, weights,
              jnp.pad(perm, (0, -(top_k * t) % bound)), group_sizes,
              w_gate, w_up, w_down)
    windows = (n_held + bound - 1) // bound
    multiplied = jnp.minimum(n_held, windows * bound)
    stats = {
        "held_assignments": multiplied.astype(jnp.float32),
        "load_max_over_mean": jnp.max(group_sizes).astype(jnp.float32)
        * held / jnp.maximum(n_held, 1).astype(jnp.float32),
        "dropped_assignments": (n_held - multiplied).astype(jnp.float32),
        "walk_windows": windows.astype(jnp.float32)}
    return y, stats
