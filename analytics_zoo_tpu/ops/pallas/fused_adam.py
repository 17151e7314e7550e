# zoolint: disable-file=raw-pallas-call -- ops/pallas/ is the one home
# for raw pl.pallas_call; everything here ships a jnp fallback oracle and
# lowers under a kernel_* label through the compile choke point.
"""Fused Adam — one Pallas kernel per param block instead of optax's
unfused elementwise chain.

``optax.adam`` lowers to ~10 separate elementwise HLO ops per leaf
(two moment EMAs, two bias corrections, rsqrt, scale) and XLA's fusion
usually — but not contractually — merges them.  This kernel does the
whole update (moment update + bias correction + param delta) in a
single HBM round-trip per block: read (g, mu, nu), write (upd, mu',
nu').  Bytes accessed per step is exactly ``24·N`` (6 f32 arrays of N
params) plus the scalar block, which is what
:func:`analytics_zoo_tpu.analysis.costmodel.kernel_bytes` predicts and
the bench's cross-lowered HLO measurement checks against.

Exposed as an optax-compatible ``GradientTransformation`` so the
estimator swaps it in transparently under a plan whose ``kernel_rules``
map ``optimizer.adam`` to ``fused_adam``:

* ``init`` delegates to the inner ``optax.adam`` — the optimizer state
  STRUCTURE (``ScaleByAdamState`` + lr-scaling state) is identical, so
  checkpoints, ZeRO sharding rules and ``opt_rules`` regexes all apply
  unchanged.
* On the fallback path ``update`` delegates to the inner optax chain
  verbatim — BITWISE identical to ``optax.adam`` by construction (the
  "bitwise where achievable" contract; the bench records it).
* On the Pallas path (TPU, or ``ZOO_KERNEL_INTERPRET=1`` interpret
  mode) f32 leaves run the fused kernel; the bias corrections
  ``1 - b**t`` are computed once outside the kernel and passed through
  SMEM with the other scalars.  Tolerance vs optax: ~1e-6 relative
  (same formula, different fma association).

Schedule semantics match ``optax.scale_by_schedule``: a callable
learning rate is evaluated at the PRE-increment count.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import optax

# Trace/dispatch-time routing counters (tests + zoo_kernel_invocations
# read these; jit traces once so the pallas counter counts compilations).
invocation_counts = {"pallas": 0, "fallback": 0}

_LANES = 128
_BLOCK_ROWS = 512


def _env_flag(name: str) -> bool:
    # same convention as engine.py's ZOO_SHARD_OPTIMIZER: "0"/"" are false
    return os.environ.get(name, "") not in ("", "0")


def _interpret_forced() -> bool:
    return _env_flag("ZOO_KERNEL_INTERPRET")


def _pallas_available() -> bool:
    # ZOO_KERNEL_FORCE_PALLAS routes to the REAL (non-interpret) kernel on
    # any backend — lowering-only CI: trace + lower(platforms=("tpu",))
    # goes through genuine Mosaic lowering with no chip.  Executing under
    # this knob off-TPU will fail — lower, don't run.
    return (jax.default_backend() == "tpu" or _interpret_forced()
            or _env_flag("ZOO_KERNEL_FORCE_PALLAS"))


def _adam_kernel(scal_ref, g_ref, mu_ref, nu_ref,
                 upd_ref, mu_out_ref, nu_out_ref):
    """One block: read (g, mu, nu), write (upd, mu', nu').

    scal_ref (SMEM, (6,) f32): lr, b1, b2, eps, bc1, bc2 where
    bc* = 1 - beta***count_inc (computed outside — scalar transcendental
    on a traced int has no business on the VPU's hot path).
    """
    lr = scal_ref[0]
    b1 = scal_ref[1]
    b2 = scal_ref[2]
    eps = scal_ref[3]
    bc1 = scal_ref[4]
    bc2 = scal_ref[5]
    g = g_ref[...]
    mu = b1 * mu_ref[...] + (1.0 - b1) * g
    nu = b2 * nu_ref[...] + (1.0 - b2) * g * g
    # optax order: mu_hat/(sqrt(nu_hat + eps_root=0) + eps), scaled -lr.
    # zero padding is benign: 0 / (sqrt(0) + eps) = 0.
    upd_ref[...] = -lr * (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
    mu_out_ref[...] = mu
    nu_out_ref[...] = nu


def _adam_leaf_pallas(g, mu, nu, scalars, interpret):
    """Run the fused kernel on one flattened f32 leaf.

    The leaf is padded to a (rows, 128) layout with rows a multiple of
    the block size — min f32 tile is (8, 128) and _BLOCK_ROWS is
    8-aligned, so padding once covers both constraints.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = g.size
    rows = max(-(-n // _LANES), 1)
    block_rows = min(_BLOCK_ROWS, -(-rows // 8) * 8)
    n_blocks = -(-rows // block_rows)
    total = n_blocks * block_rows * _LANES

    def prep(a):
        flat = a.astype(jnp.float32).reshape(-1)
        return jnp.pad(flat, (0, total - n)).reshape(-1, _LANES)

    block = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((total // _LANES, _LANES), jnp.float32)
    upd, mu2, nu2 = pl.pallas_call(
        _adam_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((6,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
            block, block, block,
        ],
        out_specs=[block, block, block],
        out_shape=[shape, shape, shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(scalars, prep(g), prep(mu), prep(nu))

    def unprep(a):
        return a.reshape(-1)[:n].reshape(g.shape)

    return unprep(upd), unprep(mu2), unprep(nu2)


def _adam_leaf_reference(g, mu, nu, scalars):
    """jnp oracle with the kernel's exact formula (per-leaf tests)."""
    lr, b1, b2, eps, bc1, bc2 = [scalars[i] for i in range(6)]
    g = g.astype(jnp.float32)
    mu2 = b1 * mu + (1.0 - b1) * g
    nu2 = b2 * nu + (1.0 - b2) * g * g
    upd = -lr * (mu2 / bc1) / (jnp.sqrt(nu2 / bc2) + eps)
    return upd, mu2, nu2


def _fused_update(updates, state, b1, b2, eps, lr_fn):
    """The fused tree update: pallas for f32 leaves, the reference
    formula (identical math) for everything else."""
    adam_state, *rest = state
    count_inc = optax.safe_int32_increment(adam_state.count)
    # scale_by_schedule evaluates at the PRE-increment count
    lr = jnp.asarray(lr_fn(adam_state.count), jnp.float32)
    bc1 = 1.0 - jnp.asarray(b1, jnp.float32) ** count_inc
    bc2 = 1.0 - jnp.asarray(b2, jnp.float32) ** count_inc
    scalars = jnp.stack([
        lr, jnp.float32(b1), jnp.float32(b2), jnp.float32(eps), bc1, bc2])
    interpret = _interpret_forced()

    def leaf(g, mu, nu):
        if g.dtype == jnp.float32 and g.size >= _LANES:
            return _adam_leaf_pallas(g, mu, nu, scalars, interpret)
        return _adam_leaf_reference(g, mu, nu, scalars)

    g_leaves, treedef = jax.tree_util.tree_flatten(updates)
    triples = [leaf(g, m, n) for g, m, n in zip(
        g_leaves,
        jax.tree_util.tree_leaves(adam_state.mu),
        jax.tree_util.tree_leaves(adam_state.nu))]
    upd = treedef.unflatten([t[0] for t in triples])
    mu2 = treedef.unflatten([t[1] for t in triples])
    nu2 = treedef.unflatten([t[2] for t in triples])
    new_adam = adam_state._replace(count=count_inc, mu=mu2, nu=nu2)
    # the lr-scaling tail state: EmptyState for a constant lr,
    # ScaleByScheduleState(count) for a schedule — keep its count in
    # lockstep so checkpoints resume identically either way
    new_rest = tuple(
        r._replace(count=count_inc)
        if "count" in getattr(r, "_fields", ()) else r
        for r in rest)
    return upd, (new_adam, *new_rest)


def fused_adam(learning_rate=0.001, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-8) -> optax.GradientTransformation:
    """Optax-compatible fused Adam (drop-in for ``optax.adam``).

    ``learning_rate`` may be a float or an optax schedule (callable of
    the step count), exactly like ``optax.adam``.  State structure and
    the fallback trajectory are identical to ``optax.adam`` — the
    kernel only changes HOW the same numbers move through HBM.
    """
    inner = optax.adam(learning_rate, b1=b1, b2=b2, eps=eps)
    lr_fn = learning_rate if callable(learning_rate) \
        else (lambda _count, _lr=learning_rate: _lr)

    def init_fn(params):
        return inner.init(params)

    def update_fn(updates, state, params=None):
        if not _pallas_available():
            invocation_counts["fallback"] += 1
            return inner.update(updates, state, params)
        if not (isinstance(state, tuple) and len(state) >= 1
                and hasattr(state[0], "mu")):
            # unexpected state structure (wrapped/injected) — the inner
            # chain is the contract, never guess
            invocation_counts["fallback"] += 1
            return inner.update(updates, state, params)
        out = _fused_update(updates, state, b1, b2, eps, lr_fn)
        invocation_counts["pallas"] += 1
        return out

    return optax.GradientTransformation(init_fn, update_fn)
