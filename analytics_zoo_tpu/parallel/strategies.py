"""Explicit shard_map strategies + tensor-parallel building blocks.

The default training path (pipeline/estimator) uses jit + NamedSharding and
lets XLA insert the gradient all-reduce.  This module is the *explicit*
formulation — ``psum`` written out — which (a) documents exactly where the
reference's AllReduceParameter shuffle+broadcast (docs/docs/wp-bigdl.md:
148-164) became one collective, and (b) gives manual control when XLA's
choices need overriding.

Also: Megatron-style column/row-parallel dense ops over the ``model`` axis —
the TP capability the reference never had (SURVEY.md §2.4 "rebuild
requirement: hooks for TP on the same mesh API").
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.common.engine import (
    DATA_AXIS,
    MODEL_AXIS,
    get_zoo_context,
)


def make_shard_map_train_step(model, loss_fn, optimizer, mesh=None,
                              grad_clip=None):
    """A train step as shard_map with explicit pmean — the literal
    TPU translation of the reference's two Spark jobs (local
    forward/backward, then gradient slice aggregation) into one SPMD
    program with a single collective.

    Now a thin wrapper over the unified partitioner: the per-shard body
    is unchanged, but it compiles through
    :func:`~analytics_zoo_tpu.parallel.plan.compile_step` (a
    ``mode="shard_map"`` plan), so the explicit strategy shares the
    persistent compile cache, ``zoo_compile_seconds`` and the HLO
    lint/feature pipe with every jit plan.
    """
    from analytics_zoo_tpu.parallel.plan import ShardingPlan, compile_step
    from analytics_zoo_tpu.pipeline.estimator.estimator import (
        _clip_grads,
        _normalize_grad_clip,
    )

    _refuse_in_model_loss(loss_fn)
    grad_clip = _normalize_grad_clip(grad_clip)
    mesh = mesh or get_zoo_context().mesh

    def local_step(params, opt_state, state, rng, batch):
        # per-shard forward/backward on the local batch slice
        # (= reference Spark job 1, Topology.scala:1178-1197)
        def loss_of(p):
            preds, new_state = model.forward(
                p, batch["x"], state=state, training=True, rng=rng
            )
            from analytics_zoo_tpu.ops.moe import collect_aux_cost

            l = loss_fn.mean(batch.get("y"), preds)
            # MoE stacks report their pre-weighted load-balancing cost
            # through the state channel; it must join every training loss
            return l + collect_aux_cost(new_state), new_state

        (l, new_state), grads = jax.value_and_grad(
            loss_of, has_aux=True
        )(params)
        # gradient all-reduce over ICI (= reference Spark job 2: gradient
        # shuffle to parameter slices + task-side broadcast)
        grads = jax.lax.pmean(grads, DATA_AXIS)
        l = jax.lax.pmean(l, DATA_AXIS)
        new_state = jax.lax.pmean(new_state, DATA_AXIS)
        grads = _clip_grads(grads, grad_clip)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, new_state, l

    repl = P()
    batch_spec = P(DATA_AXIS)
    plan = ShardingPlan(name="shard_map_dp", mode="shard_map",
                        description="explicit-psum data parallelism")
    return compile_step(
        local_step, plan, mesh,
        in_specs=(repl, repl, repl, repl, batch_spec),
        out_specs=(repl, repl, repl, repl),
        donate_argnums=(0, 1, 2), label="shard_map_step")


def _refuse_in_model_loss(loss_fn) -> None:
    if getattr(loss_fn, "in_model", False):
        raise NotImplementedError(
            f"loss {loss_fn.name!r} is taken inside the model from the "
            "step's targets, which only the estimator's GSPMD train step "
            "hands over")


def _ring_reduce_scatter(flat, n, axis_name=DATA_AXIS):
    """Reduce-scatter spelled as an explicit ``ppermute`` ring: the flat
    vector (size divisible by ``n``) is viewed as ``n`` blocks, partial
    sums circulate the ring for ``n-1`` hops, and chip ``i`` ends holding
    block ``i`` fully summed.  The summation is LEFT-ASSOCIATIVE and
    sequential — a different reduction grouping from ``psum_scatter``'s
    tree, so trajectories are ulp-recorded, not bitwise (same caveat as
    zero1 vs dp)."""
    m = flat.size // n
    blocks = flat.reshape(n, m)
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j - 1) % n) for j in range(n)]
    acc = jax.lax.dynamic_index_in_dim(blocks, (idx + 1) % n, 0,
                                       keepdims=False)
    for r in range(1, n):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + jax.lax.dynamic_index_in_dim(
            blocks, (idx + 1 + r) % n, 0, keepdims=False)
    return acc


def make_zero1_train_step(model, loss_fn, optimizer, mesh=None,
                          grad_clip=None, bucket_bytes=None, ring=False):
    """Data-parallel step with a SHARDED optimizer (ZeRO-1 spelled out):
    gradients are ``psum_scatter`` (reduce-scatter) onto each chip's 1/n
    slice of the flattened parameter vector, the optimizer update runs on
    that slice only (opt state lives at 1/n per chip — the memory win; an
    Adam state is 2× params), and one tiled ``all_gather`` restores the
    full parameters.  Communication volume equals the plain all-reduce
    (all-reduce ≡ reduce-scatter + all-gather); memory and update compute
    drop by the data-axis size.

    ``bucket_bytes`` turns the single whole-vector reduce-scatter into
    CHUNKED reduce-scatters: gradient leaves are grouped into
    ~bucket-sized contiguous flat-vector slices in backward-completion
    (reverse-traversal) order, and each bucket's collective is issued as
    its own op, chained by ``optimization_barrier`` tokens so the
    scheduler can overlap bucket k+1's backward segment with bucket k's
    scatter.  Per-element reduction grouping is unchanged, but the
    per-chunk padding changes each chip's slice composition — a
    different compiled program, so XLA fusion (fma contraction) may
    drift the trajectory by ~1 ulp vs the unbucketed step; record it
    like zero1 vs dp.  (The GSPMD spelling,
    ``plan.zero1(overlap=True)`` through the estimator, keeps the exact
    program and IS bitwise-pinned.)  ``ring=True``
    replaces ``psum_scatter`` with the explicit
    :func:`_ring_reduce_scatter` ``ppermute`` ring, whose left-assoc
    summation is ulp-recorded like zero1 vs dp.

    Returns ``(step, init_opt_state)``: the optimizer state is a
    per-shard pytree, so it must be created by ``init_opt_state(params)``
    (and checkpointed as-is — it is a different layout from the plain
    step's, and the bucketed layout differs again: per-chunk padding
    changes each chip's slice composition, which is why the bucketed
    variants compile/init under their own labels).

    Like :func:`make_shard_map_train_step`, this is now a thin wrapper
    over the partitioner's choke point: both the step AND
    ``init_opt_state`` compile through
    :func:`~analytics_zoo_tpu.parallel.plan.compile_step`.  (The GSPMD
    spelling of the same idea — and of full FSDP — is
    ``plan.zero1()`` / ``plan.fsdp()`` through the estimator; its
    bucketed spelling is ``plan.zero1(overlap=True)``.)
    """
    from jax.flatten_util import ravel_pytree

    from analytics_zoo_tpu.parallel.plan import (
        ShardingPlan,
        compile_step,
        grad_bucket_indices,
    )
    from analytics_zoo_tpu.pipeline.estimator.estimator import (
        _normalize_grad_clip,
    )

    # same grad_clip contract as make_shard_map_train_step / the Estimator
    _refuse_in_model_loss(loss_fn)
    _clip = _normalize_grad_clip(grad_clip)
    mesh = mesh or get_zoo_context().mesh
    n = mesh.shape[DATA_AXIS]
    if bucket_bytes is not None:
        bucket_bytes = int(bucket_bytes)
        if bucket_bytes < 1:
            raise ValueError(
                f"bucket_bytes must be a positive byte count, "
                f"got {bucket_bytes!r}")

    def _bucket_slices(tree):
        """Contiguous ``(lo, hi)`` flat-vector slices, one per gradient
        bucket, in backward-completion (tail-first) order; a single
        whole-vector slice when unbucketed."""
        leaves = jax.tree_util.tree_leaves(tree)
        sizes = [int(leaf.size) for leaf in leaves]
        offs = [0]
        for s in sizes:
            offs.append(offs[-1] + s)
        if bucket_bytes is None:
            return [(0, offs[-1])]
        buckets = grad_bucket_indices(leaves, bucket_bytes)
        # each bucket is a descending contiguous index run → one slice
        return [(offs[b[-1]], offs[b[0]] + sizes[b[0]]) for b in buckets]

    def _shard_of(flat, slices):
        """This chip's slice of each (padded) chunk, concatenated in
        bucket order — the unbucketed layout when ``slices`` is the
        single whole-vector slice."""
        idx = jax.lax.axis_index(DATA_AXIS)
        parts = []
        for lo, hi in slices:
            c = jnp.pad(flat[lo:hi], (0, (-(hi - lo)) % n))
            m = c.size // n
            parts.append(jax.lax.dynamic_slice(c, (idx * m,), (m,)))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _local_init(params):
        flat, _ = ravel_pytree(params)
        return optimizer.init(_shard_of(flat, _bucket_slices(params)))

    repl = P()
    # optimizer-state layout: 1-D leaves mirror the flat param shard
    # (sharded over data); 0-D leaves (e.g. Adam's step count) replicate.
    # The structure is m-independent, so probe it with a dummy shard.
    proto = jax.eval_shape(optimizer.init,
                           jax.ShapeDtypeStruct((8,), jnp.float32))
    opt_specs = jax.tree_util.tree_map(
        lambda leaf: P(DATA_AXIS) if getattr(leaf, "ndim", 0) >= 1
        else repl, proto)

    variant = ("_bucketed" if bucket_bytes is not None else "") + \
              ("_ring" if ring else "")
    plan = ShardingPlan(name="zero1_explicit", mode="shard_map",
                        bucket_bytes=bucket_bytes,
                        description="explicit reduce-scatter/all-gather "
                                    "ZeRO-1 on the padded flat vector")

    def init_opt_state(params):
        fn = compile_step(_local_init, plan, mesh, in_specs=(repl,),
                          out_specs=opt_specs,
                          label=f"zero1{variant}_init_opt_state")
        return fn(params)

    def local_step(params, opt_state, state, rng, batch):
        def loss_of(p):
            preds, new_state = model.forward(
                p, batch["x"], state=state, training=True, rng=rng
            )
            from analytics_zoo_tpu.ops.moe import collect_aux_cost

            l = loss_fn.mean(batch.get("y"), preds)
            # MoE stacks report their pre-weighted load-balancing cost
            # through the state channel; it must join every training loss
            return l + collect_aux_cost(new_state), new_state

        (l, new_state), grads = jax.value_and_grad(
            loss_of, has_aux=True
        )(params)
        l = jax.lax.pmean(l, DATA_AXIS)
        new_state = jax.lax.pmean(new_state, DATA_AXIS)

        flat_g, _ = ravel_pytree(grads)
        slices = _bucket_slices(grads)
        # reduce-scatter: each chip ends with the MEAN of its own slice
        # (of each bucket's chunk, when bucketed — issued tail-first in
        # backward-completion order, barrier-chained to pin the schedule)
        shard_parts = []
        token = None
        for lo, hi in slices:
            c = jnp.pad(flat_g[lo:hi], (0, (-(hi - lo)) % n))
            if token is not None:
                c, token = jax.lax.optimization_barrier((c, token))
            red = (_ring_reduce_scatter(c, n) if ring else
                   jax.lax.psum_scatter(
                       c, DATA_AXIS, scatter_dimension=0, tiled=True)) / n
            token = red
            shard_parts.append(red)
        g_shard = (shard_parts[0] if len(shard_parts) == 1
                   else jnp.concatenate(shard_parts))
        if _clip is not None:
            if _clip[0] == "const":
                g_shard = jnp.clip(g_shard, _clip[1], _clip[2])
            else:  # l2norm: global norm from shard norms, one scalar psum
                gn = jnp.sqrt(jax.lax.psum(jnp.sum(g_shard ** 2), DATA_AXIS))
                scale = jnp.minimum(1.0, _clip[1] / jnp.maximum(gn, 1e-12))
                g_shard = g_shard * scale
        flat_p, unravel = ravel_pytree(params)
        p_shard = _shard_of(flat_p, slices)
        updates, opt_state = optimizer.update(g_shard, opt_state, p_shard)
        p_shard = optax.apply_updates(p_shard, updates)
        # all-gather the updated slices back into the full vector —
        # per chunk when bucketed, reassembled in forward (offset) order
        fulls = []
        off = 0
        for lo, hi in slices:
            m = ((hi - lo) + (-(hi - lo)) % n) // n
            part = p_shard[off:off + m] if len(slices) > 1 else p_shard
            off += m
            fulls.append((lo, jax.lax.all_gather(
                part, DATA_AXIS, tiled=True)[:hi - lo]))
        full = (fulls[0][1] if len(fulls) == 1 else jnp.concatenate(
            [f for _, f in sorted(fulls, key=lambda t: t[0])]))
        return unravel(full), opt_state, new_state, l

    batch_spec = P(DATA_AXIS)
    step = compile_step(
        local_step, plan, mesh,
        in_specs=(repl, opt_specs, repl, repl, batch_spec),
        out_specs=(repl, opt_specs, repl, repl),
        donate_argnums=(0, 1, 2), label=f"zero1{variant}_step")
    return step, init_opt_state


def reshard_zero1_opt_state(opt_state, params, mesh=None,
                            n_old: int | None = None,
                            dtype_policy: str | None = None):
    """Re-lay an explicit-ZeRO-1 optimizer state (the
    :func:`make_zero1_train_step` layout) for a DIFFERENT data-axis size —
    the elastic slice-down/up restart (SURVEY §5): save on ``{data: 8}``,
    resume on ``{data: 4}`` or vice versa.

    The layout's only mesh-shape dependence is the flat vector's zero-pad
    to a multiple of the data-axis size n: every 1-D leaf is (a moment
    mirror of) the padded flat param vector, so resharding = strip the old
    pad, re-pad for the new n, and place sharded over ``data`` on the new
    mesh.  0-D leaves (step counts) replicate unchanged.  Works on host
    numpy trees (a loaded checkpoint) or live jax.Arrays.

    The estimator's GSPMD ZeRO-1 path needs none of this: its checkpoint
    stores global logical arrays, so restoring onto a different mesh is
    just a device_put (tests/test_elastic_resume.py proves both paths).

    Flat-vector leaves are matched by EXACT padded length (ADVICE r05
    low), not by ``size >= param_size``: pass ``n_old`` (the data-axis
    size the state was saved under) for the exact expected length
    ``size + (-size) % n_old``; without it, the length is inferred as
    the smallest 1-D leaf length >= the param count that is SHARED by at
    least two leaves (the moment mirrors always agree on one padded
    length; a coincidental unrelated 1-D leaf is almost surely unique),
    falling back to the smallest overall for single-mirror states.
    Pass ``n_old`` when the state shape is unusual.  Leaves that do NOT
    match the flat-vector layout are left value-untouched and REPLICATED
    (the plan's rules name exactly the matched flat vectors by tree
    path) — never truncated, never force-sharded onto a dimension the
    new mesh cannot divide.

    Placement goes through :meth:`ShardingPlan.place_opt_state` — the
    same rule→spec→clamp path every canned plan uses — so the explicit
    layout shares one placement code path with the GSPMD plans.

    ``dtype_policy`` (a ``ShardingPlan.dtype_policy_str()`` rule string)
    is carried onto the explicit plan's ``dtype_rules`` so the resharded
    state's placement record keeps the precision contract it was trained
    under — resuming it under a different policy fails loudly at the
    estimator's resume guard instead of silently mixing master widths
    (docs/parallelism.md "Precision plane").
    """
    import re

    from jax.flatten_util import ravel_pytree

    import numpy as np

    from .partition import leaf_path_name
    from .plan import ShardingPlan, resolve_dtype_rules

    mesh = mesh or get_zoo_context().mesh
    n_new = dict(mesh.shape)[DATA_AXIS]
    size = ravel_pytree(params)[0].size
    pad_new = (-size) % n_new

    if n_old is not None:
        expected = size + ((-size) % int(n_old))
    else:
        cands = [np.size(l) for l in jax.tree_util.tree_leaves(opt_state)
                 if np.ndim(l) == 1 and np.size(l) >= size]
        # prefer a length SHARED by >=2 leaves: the moment mirrors (mu,
        # nu) always agree on the padded length, while a coincidental
        # unrelated 1-D leaf in [size, size+pad) is almost surely unique
        # — picking it would truncate it AND leave the real flat vectors
        # un-resharded
        shared = [c for c in cands if cands.count(c) >= 2]
        expected = min(shared) if shared else (
            min(cands) if cands else None)

    def is_flat_vec(leaf) -> bool:
        return np.ndim(leaf) == 1 and np.size(leaf) == expected

    def fix(leaf):
        # stay on the HOST until the final sharded device_put: jnp ops
        # here would transiently materialize every params-sized moment on
        # one device — the allocation ZeRO-1 exists to avoid
        leaf = np.asarray(leaf)
        if is_flat_vec(leaf):
            return np.pad(leaf[:size], (0, pad_new))
        return leaf

    out = jax.tree_util.tree_map(fix, opt_state)
    # placement through the partitioner: the rules name EXACTLY the
    # flat vectors is_flat_vec matched (by rendered tree path), so the
    # plan shards those over data and replicates every other leaf —
    # including a coincidental 1-D leaf whose length happens to divide
    # n_new, which a blanket catch-all rule would wrongly shard
    matched = {
        leaf_path_name(path)
        for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]
        if is_flat_vec(leaf)
    }
    plan = ShardingPlan(
        name="zero1_explicit",
        opt_rules=tuple((rf"^{re.escape(name)}$", P(DATA_AXIS))
                        for name in sorted(matched))
        + ((r".*", P()),),
        dtype_rules=resolve_dtype_rules(dtype_policy))
    return plan.place_opt_state(out, mesh)


# ---------------------------------------------------------------------------
# Tensor-parallel dense blocks (model axis)
# ---------------------------------------------------------------------------


def column_parallel_dense(x, kernel, bias=None, axis_name=MODEL_AXIS):
    """Y_local = x @ W_local where W is column-sharded: no collective on the
    forward (outputs stay sharded on the feature dim)."""
    y = x @ kernel
    if bias is not None:
        y = y + bias
    return y


def row_parallel_dense(x_local, kernel, bias=None, axis_name=MODEL_AXIS):
    """Y = psum_over_model(x_local @ W_local): input feature dim is sharded,
    one psum restores the full output (Megatron row-parallel)."""
    y = jax.lax.psum(x_local @ kernel, axis_name)
    if bias is not None:
        y = y + bias
    return y


def tp_mlp(x, w1, b1, w2, b2, axis_name=MODEL_AXIS, activation=jax.nn.gelu):
    """Column-parallel up-projection + row-parallel down-projection: ONE
    psum per MLP block — the canonical TP transformer feed-forward."""
    h = activation(column_parallel_dense(x, w1, b1))
    return row_parallel_dense(h, w2, b2, axis_name=axis_name)


def moe_mlp_topk(x, gate_w, w1, b1, w2, b2, top_k=2, capacity_factor=1.25,
                 axis_name=None, renormalize=False, return_aux=False):
    """GShard/Switch-style **routed** MoE feed-forward: top-k routing with
    expert capacity and ``all_to_all`` dispatch over the ``expert`` mesh
    axis.  This is the scalable counterpart of :func:`ep_moe_mlp` (dense
    dispatch, kept as the correctness oracle: with ``top_k=E`` and
    ``capacity_factor`` >= 1 the two are numerically equal).

    Per shard: tokens pick their top-k experts from the full router; the
    assignment stream is priority-ordered (all 1st choices first, then 2nd
    choices, token order within a choice) and each expert accepts at most
    ``C = ceil(capacity_factor * top_k * T / E)`` assignments — the rest
    are dropped (output contribution zero, the standard Switch semantics).
    Kept tokens are scattered into a per-expert ``(E, C, D)`` buffer, an
    ``all_to_all`` ships each expert's buffer to its owning shard, the
    owner runs its experts' MLP on ``(E_local, n_shards*C, D)``, and the
    reverse ``all_to_all`` + gather + gate-weighted scatter-add rebuilds
    the token outputs.  EP FLOPs are O(top_k/E) of dense dispatch.

    Args (inside shard_map, all local views):
      x: (T, D) this shard's tokens (shard tokens over the expert axis; a
        replicated x is also correct, just redundant compute).
      gate_w: (D, E) the FULL router, replicated over the expert axis.
      w1: (E_local, D, F), b1: (E_local, F), w2: (E_local, F, D): this
        shard's experts.  b2: (D,) replicated.
      renormalize: rescale the k gate values to sum to 1 (GShard top-2
        convention); default False (Switch: raw softmax probs).
      return_aux: also return the load-balancing auxiliary loss
        (E * sum_e mean_prob_e * frac_first_choice_e, pmean'd over the
        expert axis — ~1.0 when perfectly balanced).
    Returns: (T, D) [, aux scalar].
    """
    import math

    from analytics_zoo_tpu.common.engine import EXPERT_AXIS

    axis_name = axis_name or EXPERT_AXIS
    t, d = x.shape
    e_local = w1.shape[0]
    e = gate_w.shape[1]
    if e % e_local:
        raise ValueError(
            f"router width E={e} must be a multiple of the local expert "
            f"count E_local={e_local} (w1 leading dim)")
    cap = int(math.ceil(capacity_factor * top_k * t / e))
    cap = max(1, min(cap, t))

    probs = jax.nn.softmax((x @ gate_w).astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)  # (T, k)
    if renormalize:
        top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)
    # assignment stream, priority-ordered: k-major so every token's 1st
    # choice outranks any 2nd choice in the capacity race
    expert = top_idx.T.reshape(-1)                      # (kT,)
    gatev = top_vals.T.reshape(-1).astype(x.dtype)      # (kT,)
    tok = jnp.tile(jnp.arange(t), top_k)                # (kT,)
    oh = jax.nn.one_hot(expert, e, dtype=jnp.int32)     # (kT, E)
    slot = jnp.sum((jnp.cumsum(oh, 0) - 1) * oh, 1)     # slot within expert
    keep = slot < cap
    slot_c = jnp.where(keep, slot, 0)
    # scatter kept tokens into per-expert buffers; dropped assignments
    # scatter-add zeros (slot collisions impossible for kept: cumsum slots
    # are unique per expert)
    contrib = jnp.where(keep[:, None], x[tok], 0.0)
    buf = jnp.zeros((e, cap, d), x.dtype).at[expert, slot_c].add(contrib)
    # ship each expert's buffer to its owner shard; receive every shard's
    # buffer for OUR experts: (E, C, D) -> (E_local, n_sh*C, D)
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=1,
                              tiled=True)
    h = jax.nn.gelu(jnp.einsum("etd,edf->etf", recv, w1) + b1[:, None, :])
    y = jnp.einsum("etf,efd->etd", h, w2)  # (E_local, n_sh*C, D)
    # reverse path: give every shard back its slots
    back = jax.lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0,
                              tiled=True)  # (E, C, D)
    got = back[expert, slot_c] * jnp.where(keep, gatev, 0.0)[:, None]
    out = jnp.zeros((t, d), x.dtype).at[tok].add(got) + b2
    if not return_aux:
        return out
    # GShard load-balance loss on global statistics (tokens are sharded
    # over the expert axis, so pmean the per-shard means)
    me = jax.lax.pmean(jnp.mean(probs, 0), axis_name)
    ce = jax.lax.pmean(
        jnp.mean(jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32), 0),
        axis_name)
    aux = e * jnp.sum(me * ce)
    return out, aux


def ep_moe_mlp(x, gate_w, w1, b1, w2, b2, axis_name=None):
    """Expert-parallel dense-dispatch MoE feed-forward.

    Experts are SHARDED over the mesh ``expert`` axis: each shard holds
    ``E_local`` experts' weights and computes the gated contribution of its
    experts for EVERY token; one ``psum`` over the expert axis sums the
    contributions (and the gate's softmax denominator).  No all_to_all /
    token routing: tokens stay data/seq-local, weights stay expert-local —
    the EP capability hook the reference never had (SURVEY.md §2.4).

    Args (inside shard_map, all local views):
      x: (..., D) tokens (replicated over the expert axis).
      gate_w: (D, E_local) this shard's columns of the global gate.
      w1: (E_local, D, F), b1: (E_local, F)
      w2: (E_local, F, D), b2: (D,) replicated.
    Returns: (..., D), replicated over the expert axis.
    """
    from analytics_zoo_tpu.common.engine import EXPERT_AXIS

    axis_name = axis_name or EXPERT_AXIS
    # numerically-stable global softmax over experts, computed shard-wise:
    logits = x @ gate_w  # (..., E_local)
    # max-subtraction is gradient-neutral; stop_gradient keeps autodiff out
    # of pmax (which has no differentiation rule)
    local_max = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    global_max = jax.lax.pmax(local_max, axis_name)
    expg = jnp.exp(logits - global_max[..., None])
    denom = jax.lax.psum(jnp.sum(expg, axis=-1), axis_name)
    gates = expg / denom[..., None]  # (..., E_local), sums to 1 globally
    # per-expert MLP, gated and summed over the local experts
    h = jax.nn.gelu(jnp.einsum("...d,edf->...ef", x, w1) + b1)
    y_e = jnp.einsum("...ef,efd->...ed", h, w2)
    local = jnp.einsum("...ed,...e->...d", y_e, gates)
    return jax.lax.psum(local, axis_name) + b2
