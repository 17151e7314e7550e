# zoolint: disable-file=raw-jit,raw-remat -- this module IS the compile choke point: the jax.jit here is the one every plan routes through (timed_compile telemetry, persistent cache, HLO lint), and apply_remat is the one jax.checkpoint site every remat rule resolves to
"""zooplan — the unified partitioner: sharding plans + ONE compile entry.

Before this module, sharding decisions were scattered per strategy:
``parallel/strategies.py`` hand-wrote shard_map specs, the zero1
resharder re-laid optimizer state ad hoc, and the estimator's
``ZOO_SHARD_OPTIMIZER`` path picked its own NamedShardings.  FSDP/TP
were bespoke programs.  Here they are CONFIGURATIONS:

- A :class:`ShardingPlan` carries ordered regex rules → ``PartitionSpec``
  over the logical parameter / optimizer-state tree paths (T5X-style;
  ``match_partition_rules`` in :mod:`.partition` does the matching) plus
  the compile contract (jit + GSPMD constraints, or explicit shard_map).
  Specs are CLAMPED per leaf to what the mesh can actually divide, so a
  rule table written for one topology stays valid on another.
- Canned plans: :func:`data_parallel` (replicate everything — today's
  default), :func:`zero1` (optimizer state sharded over ``data``, the
  ZeRO-1 memory win), :func:`fsdp` (params AND optimizer state sharded
  over ``data`` — XLA all-gathers params on use and reduce-scatters
  grads, the ZeRO-2/3 direction of arXiv:2004.13336), and
  :func:`tensor_parallel` (user rules over the ``model`` axis).
- :func:`build_mesh` — one mesh builder: a plain ``Mesh`` on a single
  slice, a hybrid ICI×DCN mesh (DCN-crossing axis outermost, riding
  :func:`~analytics_zoo_tpu.parallel.multihost.hybrid_mesh`) for
  multi-pod; ``ZOO_DCN_AXIS`` names the crossing axis.
- :func:`compile_step` — THE compile choke point.  Every strategy's
  step function (plain DP, fsdp, zero1, TP, explicit shard_map) lowers
  through :func:`~analytics_zoo_tpu.common.compile_cache.timed_compile`
  here, so every compiled program shares the persistent compile cache,
  AOT warmup, ``zoo_compile_seconds`` metering, and the HLO graph
  lint / analytic cost features (``zoo_hlo_*``) — none of which the
  explicit strategies saw before.

Loss trajectories are placement-invariant: a plan changes WHERE bytes
live and which collectives XLA inserts, never the math — the fsdp plan
trains bit-identically to replicated DP (pinned by
``tests/test_partitioner.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import re
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.common.engine import (
    ALL_AXES,
    DATA_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    logger,
)
from analytics_zoo_tpu.parallel.partition import (
    match_partition_rules,
    tree_shardings,
)

__all__ = [
    "ShardingPlan", "data_parallel", "fsdp", "zero1", "zero2", "zero3",
    "tensor_parallel", "pipeline_plan", "with_remat",
    "with_dtype", "with_dtype_policy", "mixed_precision", "int8_serving",
    "resolve_dtype_rules", "DTYPE_ROLES", "DTYPE_POLICY_NAMES",
    "with_kernels", "resolve_kernel", "KERNEL_NAMES",
    "DEFAULT_KERNEL_RULES",
    "resolve_plan", "build_mesh", "compile_step", "PlannedStep",
    "apply_remat", "resolve_remat", "REMAT_POLICIES", "REMAT_KEPT_NAMES",
    "per_chip_bytes", "live_bytes", "record_mem_gauges",
    "record_dtype_gauges", "record_kernel_gauges",
    "serialize_specs", "deserialize_specs",
    "PLAN_NAMES", "DEFAULT_BUCKET_BYTES", "default_bucket_bytes",
    "grad_bucket_indices", "fold_world_to_mesh",
]

#: names ``ZOO_SHARDING_PLAN`` / ``resolve_plan`` accept (tensor
#: parallelism needs a rule table, so it is constructed in code, not
#: named from the environment)
PLAN_NAMES = ("dp", "data_parallel", "none", "fsdp", "zero1", "zero2",
              "zero3")

#: remat policy names a plan's ``remat_rules`` may map a path to —
#: ``"full"`` recomputes everything in the matched scope, ``"dots"``
#: keeps contraction outputs (``dots_with_no_batch_dims_saveable``),
#: ``"attn"`` keeps only the arrays named in :data:`REMAT_KEPT_NAMES`;
#: any other string resolves as an attribute of ``jax.checkpoint_policies``
REMAT_POLICIES = ("full", "dots", "attn")

#: what a policy keeps by ``checkpoint_name``.  ``"attn_context"`` is
#: given by the attention ops to what their backward pass reads besides
#: q, k and v: the flash kernels' output and both softmax statistics
#: (inside the ``custom_vjp``'s forward rule, ``ops/pallas/
#: flash_attention.py::_fwd``), the context on the dense path
#: (``ops/attention.py``).  ``"ffn_out"`` is the transformer block's
#: feed-forward output, which the norm after or around that branch reads.
#: ``"moe_route"`` is what a routed feed-forward without dropped tokens
#: decides by integers (``ops/moe.py::held_experts_ffn``: the sort's
#: permutation and the group sizes).  ``"kda_state"`` is the chunks'
#: incoming states of the chunked gated delta rule, which its backward
#: walk reads (``ops/linear_attention.py``, named in the forward rule with
#: the rule's output, which is an ``"attn_context"``).
#: So under ``"attn"`` the backward pass makes the norms, q, k, v and the
#: other products of the scope again, but runs no attention forward, no
#: down-projection, no sort and no walk over a sequence's chunks a second
#: time (measured: ``PERF.md``, PR 29).
REMAT_KEPT_NAMES = {"attn": ("attn_context", "ffn_out", "moe_route",
                             "kda_state")}

#: dtype ROLES a plan's ``dtype_rules`` may map a path to.  A role is
#: not a raw dtype: it names the leaf's job in the precision plane.
#: ``"f32"`` = master/accumulation precision (keep the stored f32 copy —
#: the default for every unmatched leaf); ``"bf16"`` / ``"f16"`` =
#: low-precision COMPUTE copy (the stored master stays f32; the step
#: casts down on use and the f32 cast-up happens before the optimizer
#: update, so optimizer state is bitwise-stable); ``"int8"`` =
#: weight-only quantized serving copy (training computes in bf16, the
#: serving replica routes through ``pipeline/inference/quantize.py``).
DTYPE_ROLES = ("f32", "bf16", "f16", "int8")

#: canned policy names ``ZOO_DTYPE_POLICY`` / :func:`resolve_dtype_rules`
#: accept (besides a ``<regex>=<role>,...`` rule string, and ``auto``
#: which the estimator resolves through the config oracle)
DTYPE_POLICY_NAMES = ("f32", "bf16_mixed", "int8_serving")

#: kernel names a plan's ``kernel_rules`` may map a scope to.  ``"xla"``
#: is the explicit opt-out — the scope runs whatever fusion XLA emits
#: (every kernel's jnp fallback path); the rest name modules under
#: ``ops/pallas/``.  Scopes are logical op names, not leaf paths:
#: ``"attention"``, ``"optimizer.adam"``, ``"loss.softmax_xent"``,
#: ``"serving.int8_matmul"``.
KERNEL_NAMES = ("xla", "flash", "fused_adam", "fused_softmax_xent",
                "int8_matmul")

#: the full kernel table :func:`with_kernels` applies by default — one
#: rule per kernel the plane ships.  ``ZOO_USE_PALLAS=1`` overlays this
#: on the resolved plan (a plan with its OWN kernel_rules wins).
DEFAULT_KERNEL_RULES = (
    (r"^attention$", "flash"),
    (r"^optimizer\.adam$", "fused_adam"),
    (r"^loss\.softmax_xent$", "fused_softmax_xent"),
    (r"^serving\.int8_matmul$", "int8_matmul"),
)

#: the compute dtype each role casts floating leaves to inside the step
#: (``None`` = keep the stored dtype).  The ``"int8"`` role computes in
#: bf16 during TRAINING — int8 is a weight-only serving transform, not
#: a training number format.
_ROLE_COMPUTE_DTYPES = {"f32": None, None: None,
                        "bf16": "bfloat16", "f16": "float16",
                        "int8": "bfloat16"}

#: default gradient-overlap bucket size (bytes) when a canned plan is
#: built with ``overlap=True`` — override per process with
#: ``ZOO_OVERLAP_BUCKET_BYTES`` or per plan with ``overlap=<bytes>``.
#: ~4 MiB groups enough small leaves to amortize a collective's latency
#: without deferring the first reduce behind the whole backward.
DEFAULT_BUCKET_BYTES = 4 << 20

_REPLICATE_ALL = ((r".*", P()),)


def fold_world_to_mesh(world: int, devices: int | None = None) -> int:
    """Largest usable data-axis extent for an elastic cohort of
    ``world`` workers: the biggest power of two <= min(world, devices).

    An elastic generation change can leave ANY world size (lose one of
    four workers -> 3), but mesh extents must divide the device count
    (``_infer_mesh_shape``) and real pod topologies only expose
    power-of-two slices — so the cohort folds down to the largest
    feasible slice and the spare workers stand by as hot spares until
    the next generation.  The checkpoint stores global logical arrays,
    so folding 4 -> 2 -> 4 reshards bit-exactly through the plan's
    placement (tests/test_elastic_resume.py)."""
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    if devices is None:
        devices = len(jax.devices())
    cap = min(int(world), max(int(devices), 1))
    return 1 << (cap.bit_length() - 1)


def default_bucket_bytes() -> int:
    """The overlap bucket size ``overlap=True`` resolves to:
    ``ZOO_OVERLAP_BUCKET_BYTES`` (validated > 0) over
    :data:`DEFAULT_BUCKET_BYTES`."""
    raw = os.environ.get("ZOO_OVERLAP_BUCKET_BYTES")
    if not raw:
        return DEFAULT_BUCKET_BYTES
    try:
        out = int(raw)
    except ValueError:
        raise ValueError(
            f"ZOO_OVERLAP_BUCKET_BYTES must be a positive integer byte "
            f"count, got {raw!r}") from None
    if out < 1:
        raise ValueError(
            f"ZOO_OVERLAP_BUCKET_BYTES must be >= 1, got {out}")
    return out


def grad_bucket_indices(leaves, bucket_bytes: int) -> list:
    """Group leaf INDICES into ~``bucket_bytes`` buckets in REVERSE
    traversal order — the order the backward pass completes gradients
    (last forward layer first), so bucket k's collective can be issued
    while bucket k+1's backward segment is still computing.  Every
    bucket holds at least one leaf (a single leaf larger than the
    bucket is its own bucket)."""
    buckets, cur, size = [], [], 0
    for idx in reversed(range(len(leaves))):
        leaf = leaves[idx]
        nbytes = int(getattr(leaf, "nbytes", 0) or
                     np.size(leaf) * np.dtype(
                         getattr(leaf, "dtype", np.float32)).itemsize)
        cur.append(idx)
        size += nbytes
        if size >= bucket_bytes:
            buckets.append(cur)
            cur, size = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def _chain_buckets(leaves, buckets):
    """Pin the buckets' schedule with an ``optimization_barrier`` chain:
    bucket k+1's values pass through a barrier together with a token
    aliased from bucket k's output, so XLA cannot collapse the bucketed
    collectives back into one post-backward group.  Identity on values
    (bitwise — the trajectory cannot change), and only used OUTSIDE
    differentiated regions (``optimization_barrier`` has no AD rule;
    the differentiable spelling is :func:`_sched_barrier`)."""
    out = list(leaves)
    token = None
    for bucket in buckets:
        vals = tuple(out[i] for i in bucket)
        if token is None:
            vals = jax.lax.optimization_barrier(vals)
        else:
            chained = jax.lax.optimization_barrier(vals + (token,))
            vals = chained[:-1]
        for i, v in zip(bucket, vals):
            out[i] = v
        token = vals[0]
    return out


@jax.custom_vjp
def _sched_barrier(values: tuple):
    """Differentiable schedule barrier: identity on ``values`` with an
    ``optimization_barrier`` in BOTH directions — the forward barrier
    pins the prefetch-gather order, and the transpose barrier pins the
    matching reduce order in the backward pass."""
    return jax.lax.optimization_barrier(values)


def _sched_barrier_fwd(values):
    return jax.lax.optimization_barrier(values), None


def _sched_barrier_bwd(_, cts):
    return (jax.lax.optimization_barrier(tuple(cts)),)


_sched_barrier.defvjp(_sched_barrier_fwd, _sched_barrier_bwd)


def _freeze_rules(rules):
    out = []
    for pat, spec in rules:
        if isinstance(spec, str):
            # P(*"model") would silently splat into per-character axes
            # ('m','o','d','e','l') that all clamp to replicate — the
            # exact quiet failure the partitioner exists to prevent
            raise TypeError(
                f"rule {pat!r}: spec must be a PartitionSpec (or a "
                f"tuple of axis entries), got the bare string {spec!r} "
                f"— write P({spec!r}) to shard dim 0 over that axis")
        out.append((str(pat), spec if isinstance(spec, P) else P(*spec)))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Ordered regex rules → PartitionSpec over logical tree paths, plus
    the compile contract.

    ``param_rules`` / ``opt_rules`` match against
    :func:`~analytics_zoo_tpu.parallel.partition.leaf_path_name` paths
    (``opt_rules=None`` reuses ``param_rules`` — optimizer moments
    mirror the parameter paths under their state prefix, and
    ``re.search`` matching makes the same regexes hit).  ``batch_axes``
    is the mesh axes the leading (batch) dimension shards over.
    ``mode`` picks the compile formulation in :func:`compile_step`:
    ``"jit"`` (GSPMD — XLA inserts collectives from the shardings) or
    ``"shard_map"`` (explicit per-shard program with hand-written
    collectives; requires ``in_specs``/``out_specs`` at compile time).

    ``grad_rules`` extends the rule table to the GRADIENTS inside the
    step (``None`` = unconstrained, today's behavior): zero2/zero3 pin
    grads to per-chip shards so XLA reduce-scatters instead of
    all-reducing — the weight-update sharding of arXiv:2004.13336.
    ``remat_rules`` maps logical scope names (layer names, ``"blocks"``)
    to a :data:`REMAT_POLICIES` entry; :func:`resolve_remat` consults
    the plan active during tracing, so activation checkpointing is plan
    configuration, not a per-layer flag.

    ``dtype_rules`` is the FOURTH rule table — the precision plane:
    ordered ``(regex, role)`` pairs over the same logical leaf paths,
    where the role is a :data:`DTYPE_ROLES` name.  The stored params
    stay the MASTER copy (f32); a ``"bf16"``/``"f16"`` role makes the
    step cast that leaf down on use (:meth:`cast_params_for_compute`),
    and because the cast is in-graph, the vjp's cast-up hands f32
    gradients back to the f32 masters — gradient/collective
    accumulation and the optimizer update stay in f32 (bitwise-stable
    optimizer state, arXiv:2004.13336's sharded-master shape).  The
    ``"int8"`` role marks weight-only serving leaves for
    ``pipeline/inference/quantize.py``.  Scalars and unmatched leaves
    keep their stored dtype.  Participates in :meth:`cache_key`, so
    the persistent compile cache and per-plan labels distinguish
    precision variants.

    ``kernel_rules`` is the FIFTH rule table — the kernel plane:
    ordered ``(regex, kernel)`` pairs over logical OP scopes
    (``"attention"``, ``"optimizer.adam"``, ``"loss.softmax_xent"``,
    ``"serving.int8_matmul"``), where the kernel is a
    :data:`KERNEL_NAMES` entry.  Consumers ask
    :func:`resolve_kernel` during tracing (the plan is active inside
    ``compile_step``, like ``remat_rules``): a named kernel routes the
    scope to its ``ops/pallas/`` module, ``"xla"`` explicitly pins the
    jnp/XLA fallback (a table with every scope at ``"xla"`` is
    trajectory-identical to no table), and no match leaves the
    consumer's own heuristics in charge.  Participates in
    :meth:`cache_key`; :func:`with_kernels` appends the default table
    and the ``+kernels`` name suffix.

    ``bucket_bytes`` turns on bucketed gradient overlap (the latency-
    hiding plane): inside the step, gradients are grouped into
    ~bucket-sized chunks in backward-completion order and each group's
    reduction collective is pinned (via an ``optimization_barrier``
    chain) to issue as soon as that group's backward segment completes,
    instead of all collectives queuing behind the full backward.
    Identity on the traced values — the trajectory is the unbucketed
    plan's to float32 rounding (see :meth:`constrain_grads`).
    ``prefetch`` adds the fsdp gather-on-use
    schedule: sharded params are explicitly gathered bucket-by-bucket
    ahead of use (double-buffered order pin via
    :func:`_sched_barrier`), so layer k+1's all-gather can overlap
    layer k's compute under a latency-hiding scheduler.
    """

    name: str
    param_rules: tuple = _REPLICATE_ALL
    opt_rules: tuple | None = None
    batch_axes: tuple = (DATA_AXIS,)
    mode: str = "jit"
    description: str = ""
    grad_rules: tuple | None = None
    remat_rules: tuple = ()
    bucket_bytes: int | None = None
    prefetch: bool = False
    dtype_rules: tuple = ()
    kernel_rules: tuple = ()

    def __post_init__(self):
        if self.mode not in ("jit", "shard_map"):
            raise ValueError(
                f"plan mode must be 'jit' or 'shard_map', got {self.mode!r}")
        if self.bucket_bytes is not None:
            bb = int(self.bucket_bytes)
            if bb < 1:
                raise ValueError(
                    f"bucket_bytes must be a positive byte count, "
                    f"got {self.bucket_bytes!r}")
            object.__setattr__(self, "bucket_bytes", bb)
        object.__setattr__(self, "param_rules",
                           _freeze_rules(self.param_rules))
        if self.opt_rules is not None:
            object.__setattr__(self, "opt_rules",
                               _freeze_rules(self.opt_rules))
        if self.grad_rules is not None:
            object.__setattr__(self, "grad_rules",
                               _freeze_rules(self.grad_rules))
        remat = []
        for pat, policy in self.remat_rules:
            if policy is not None and not isinstance(policy, str):
                raise TypeError(
                    f"remat rule {pat!r}: policy must be a name from "
                    f"REMAT_POLICIES (or a jax.checkpoint_policies "
                    f"attribute name, or None), got {policy!r}")
            remat.append((str(pat), policy))
        object.__setattr__(self, "remat_rules", tuple(remat))
        dtyped = []
        for pat, role in self.dtype_rules:
            if role is not None and role not in DTYPE_ROLES:
                raise ValueError(
                    f"dtype rule {pat!r}: role must be one of "
                    f"{DTYPE_ROLES} (or None to keep the stored dtype), "
                    f"got {role!r}")
            dtyped.append((str(pat), role))
        object.__setattr__(self, "dtype_rules", tuple(dtyped))
        kerneled = []
        for pat, kernel in self.kernel_rules:
            if kernel is not None and kernel not in KERNEL_NAMES:
                raise ValueError(
                    f"kernel rule {pat!r}: kernel must be one of "
                    f"{KERNEL_NAMES} (or None to defer to later rules), "
                    f"got {kernel!r}")
            kerneled.append((str(pat), kernel))
        object.__setattr__(self, "kernel_rules", tuple(kerneled))
        object.__setattr__(self, "batch_axes", tuple(self.batch_axes))

    # -- identity ------------------------------------------------------
    def cache_key(self) -> tuple:
        """Hashable identity for compiled-step caches: two plans with
        the same rules compile the same program."""
        return (self.name, self.param_rules, self.opt_rules,
                self.batch_axes, self.mode, self.grad_rules,
                self.remat_rules, self.bucket_bytes, self.prefetch,
                self.dtype_rules, self.kernel_rules)

    @property
    def effective_opt_rules(self) -> tuple:
        return self.opt_rules if self.opt_rules is not None \
            else self.param_rules

    def _is_replicated(self, rules) -> bool:
        return all(spec == P() for _, spec in rules)

    @property
    def shards_params(self) -> bool:
        return not self._is_replicated(self.param_rules)

    @property
    def shards_opt(self) -> bool:
        return not self._is_replicated(self.effective_opt_rules)

    # -- spec resolution ----------------------------------------------
    def param_specs(self, params, mesh, *, report_unused: bool = False):
        """Clamped PartitionSpec tree for ``params`` on ``mesh``."""
        return self._specs(self.param_rules, params, mesh,
                           report_unused=report_unused)

    def opt_specs(self, opt_state, mesh):
        """Clamped PartitionSpec tree for an optimizer state on
        ``mesh`` (scalar step counts replicate via the scalar rule in
        ``match_partition_rules``)."""
        return self._specs(self.effective_opt_rules, opt_state, mesh)

    def _specs(self, rules, tree, mesh, *, report_unused: bool = False):
        out = match_partition_rules(rules, tree,
                                    report_unused=report_unused)
        specs, unused = out if report_unused else (out, None)
        # GSPMD computes the same values under any layout, so a leaf
        # whose dim 0 the axis cannot divide may take the shard on a
        # later dim; a shard_map plan's specs are its program's contract
        clamped = jax.tree_util.tree_map(
            lambda leaf, spec: _clamp_spec(spec, np.shape(leaf), mesh,
                                           spill=self.mode == "jit"),
            tree, specs)
        return (clamped, unused) if report_unused else clamped

    def batch_spec(self, ndim: int, stacked: bool = False) -> P:
        """Spec for one batch leaf: batch dim over ``batch_axes``.

        ``stacked=True`` is the fused-dispatch [K, batch, ...] layout —
        axis 0 is the inner-step index (replicated), axis 1 the batch.
        """
        entry = self.batch_axes[0] if len(self.batch_axes) == 1 \
            else tuple(self.batch_axes)
        min_ndim = 2 if stacked else 1
        if ndim < min_ndim:
            return P()
        lead = (None, entry) if stacked else (entry,)
        return P(*lead, *([None] * (ndim - len(lead))))

    # -- precision plane ----------------------------------------------
    def dtype_policy_str(self) -> str:
        """Canonical ``<regex>=<role>,...`` rendering of ``dtype_rules``
        (empty string = no policy) — the form compile meta, checkpoint
        plan records and the hlo dtype-policy lint carry; round-trips
        through :func:`resolve_dtype_rules`."""
        return ",".join(
            f"{pat}={role if role is not None else 'keep'}"
            for pat, role in self.dtype_rules)

    def dtype_roles(self, tree) -> dict:
        """Leaf path → matched dtype role, for every non-scalar leaf a
        rule hits (first ``re.search`` over the same
        :func:`~analytics_zoo_tpu.parallel.partition.leaf_path_name`
        paths the other three tables use).  Unmatched leaves are absent
        — they keep master precision."""
        from analytics_zoo_tpu.parallel.partition import (
            leaf_path_name,
        )

        out = {}

        def visit(path, leaf):
            if np.ndim(leaf) == 0 or np.size(leaf) == 1:
                return leaf
            name = leaf_path_name(path)
            for pat, role in self.dtype_rules:
                if re.search(pat, name):
                    if role is not None:
                        out[name] = role
                    break
            return leaf

        jax.tree_util.tree_map_with_path(visit, tree)
        return out

    # -- kernel plane --------------------------------------------------
    def kernel_policy_str(self) -> str:
        """Canonical ``<regex>=<kernel>,...`` rendering of
        ``kernel_rules`` (empty string = no table) — the form compile
        meta and checkpoint plan records carry."""
        return ",".join(
            f"{pat}={kernel if kernel is not None else 'defer'}"
            for pat, kernel in self.kernel_rules)

    def kernel_for(self, scope: str, default: str | None = None):
        """Kernel name for a logical op scope (``"attention"``,
        ``"optimizer.adam"``, ...): first ``kernel_rules``
        ``re.search`` match wins; ``"xla"`` is the explicit fallback
        pick, no match returns ``default``."""
        for pat, kernel in self.kernel_rules:
            if re.search(pat, scope):
                if kernel is not None:
                    return kernel
        return default

    def compute_cast_dtype(self):
        """The dominant low-precision compute dtype this plan's rules
        declare (``jnp.bfloat16`` / ``jnp.float16``), or ``None`` for a
        pure-f32 plan — what batch inputs cast to so the matmuls lower
        in the compute dtype, not a silent f32 upcast."""
        for _, role in self.dtype_rules:
            name = _ROLE_COMPUTE_DTYPES.get(role)
            if name is not None:
                return jax.numpy.dtype(name)
        return None

    def cast_params_for_compute(self, params):
        """The cast-down half of the accumulation contract: a COMPUTE
        copy of ``params`` with each floating leaf whose dtype role is
        ``bf16``/``f16`` (or ``int8`` — weight-only serving leaves
        train in bf16) cast to its role's compute dtype.  The argument
        tree is untouched: it remains the f32 master copy the
        optimizer updates.  In-graph use means the vjp inserts the
        matching cast-up, so gradients arrive f32 at the masters and
        collectives accumulate in f32."""
        if not self.dtype_rules:
            return params
        from analytics_zoo_tpu.parallel.partition import (
            match_rule_values,
        )

        jnp = jax.numpy
        roles = match_rule_values(self.dtype_rules, params, default="f32")

        def cast(leaf, role):
            name = _ROLE_COMPUTE_DTYPES.get(role)
            if name is None or not hasattr(leaf, "dtype") \
                    or not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            return leaf.astype(jnp.dtype(name))

        return jax.tree_util.tree_map(cast, params, roles)

    # -- placement -----------------------------------------------------
    def param_shardings(self, params, mesh):
        return tree_shardings(mesh, self.param_specs(params, mesh))

    def opt_shardings(self, opt_state, mesh):
        return tree_shardings(mesh, self.opt_specs(opt_state, mesh))

    def place_params(self, params, mesh):
        """device_put ``params`` into this plan's layout."""
        return jax.device_put(params, self.param_shardings(params, mesh))

    def place_opt_state(self, opt_state, mesh):
        """device_put an optimizer state into this plan's layout — the
        ONE resharding path elastic resume uses: a checkpoint stores
        global logical arrays, so restoring onto any mesh size is this
        device_put.  Even the explicit padded-flat-vector layout
        (:func:`~analytics_zoo_tpu.parallel.strategies.
        reshard_zero1_opt_state`) routes its final placement here after
        its host-side pad surgery."""
        return jax.device_put(opt_state,
                              self.opt_shardings(opt_state, mesh))

    # -- in-graph constraints -----------------------------------------
    def constrain_params(self, params, mesh):
        """``with_sharding_constraint`` the updated params to the plan
        layout (inside the jitted step) — pins the OUTPUT layout so
        donation reuses the plan's buffers, XLA cannot 'helpfully'
        replicate an fsdp plan's weights, AND a partially-sharded plan
        cannot leak its sharding into replicated outputs (zero1's
        sharded moments would otherwise propagate onto the updated
        params, silently changing the step's signature).  A fully
        replicated plan (dp) constrains nothing."""
        if not (self.shards_params or self.shards_opt):
            return params
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, params,
            self.param_shardings(params, mesh))

    def constrain_opt(self, opt_state, mesh):
        if not (self.shards_params or self.shards_opt):
            return opt_state
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, opt_state,
            self.opt_shardings(opt_state, mesh))

    def constrain_grads(self, grads, mesh):
        """Pin the gradients inside the step to ``grad_rules`` — the
        zero2/zero3 hook: constraining grads to per-chip shards forces
        XLA to lower the gradient sum as a reduce-scatter (each chip
        keeps only its shard) instead of a full all-reduce, so the
        optimizer update runs on 1/n of every leaf.  ``grad_rules=None``
        (dp/zero1/fsdp) leaves the gradients to GSPMD's own choice.

        With ``bucket_bytes`` set, the constrained gradients are
        additionally grouped into ~bucket-sized chunks in backward-
        completion order and schedule-pinned with an
        ``optimization_barrier`` chain (:func:`_chain_buckets`): each
        bucket's reduce-scatter/all-reduce is issued as its backward
        segment completes instead of queueing behind the full backward.
        The traced values are untouched (the per-leaf reduction grouping
        is unchanged); the two compiled programs may still sum in
        different orders, so the trajectory is the unbucketed plan's to
        float32 rounding (``tests/test_overlap.py``).
        """
        if self.grad_rules is not None:
            specs = self._specs(self.grad_rules, grads, mesh)
            grads = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, grads,
                tree_shardings(mesh, specs))
        if not self.bucket_bytes:
            return grads
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        arrays = [i for i, leaf in enumerate(leaves)
                  if hasattr(leaf, "dtype")]
        if len(arrays) < 2:
            return grads  # nothing to bucket
        buckets = grad_bucket_indices(
            [leaves[i] for i in arrays], self.bucket_bytes)
        chained = _chain_buckets(
            [leaves[i] for i in arrays],
            buckets)
        for pos, val in zip(arrays, chained):
            leaves[pos] = val
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def prefetch_params(self, params, mesh):
        """The fsdp gather-prefetch schedule: explicitly all-gather
        sharded params bucket-by-bucket IN FORWARD ORDER, each bucket's
        gather chained behind the previous one through the
        differentiable :func:`_sched_barrier` — a double-buffered
        gather-on-use order pin, so bucket k+1's all-gather can issue
        while bucket k's layer computes (XLA's latency-hiding scheduler
        does the overlap; the chain keeps it from collapsing the
        gathers into one prologue group).  The transpose of the
        explicit gather is a reduce-scatter of the cotangent, barriered
        in the matching reverse order — so the backward inherits the
        bucketed reduction schedule for free.  No-op unless the plan
        sets ``prefetch`` and shards params."""
        if not (self.prefetch and self.shards_params):
            return params
        leaves, treedef = jax.tree_util.tree_flatten(params)
        arrays = [i for i, leaf in enumerate(leaves)
                  if hasattr(leaf, "dtype")]
        if not arrays:
            return params
        repl = NamedSharding(mesh, P())
        gathered = [jax.lax.with_sharding_constraint(leaves[i], repl)
                    for i in arrays]
        bucket_bytes = self.bucket_bytes or default_bucket_bytes()
        # forward traversal order: gather the buckets the forward
        # consumes first, first
        buckets = [list(reversed(b)) for b in reversed(
            grad_bucket_indices(gathered, bucket_bytes))]
        token = None
        for bucket in buckets:
            vals = tuple(gathered[i] for i in bucket)
            if token is None:
                vals = _sched_barrier(vals)
            else:
                chained = _sched_barrier(vals + (token,))
                vals = chained[:-1]
            for i, v in zip(bucket, vals):
                gathered[i] = v
            token = vals[0]
        for pos, val in zip(arrays, gathered):
            leaves[pos] = val
        return jax.tree_util.tree_unflatten(treedef, leaves)


def _clamp_spec(spec: P, shape: tuple, mesh, spill: bool = False) -> P:
    """Clamp a rule's spec to what ``mesh`` can divide on this leaf:
    axes missing from the mesh drop to None, a dim the axis product does
    not divide evenly drops to None, entries beyond the leaf's rank are
    truncated.  A rule table written for ``{data: 8, model: 4}`` then
    stays valid on ``{data: 2}`` — undividable dims just replicate.

    ``spill=True`` keeps the one-entry spec ``P(axis)`` — the fsdp/ZeRO
    idiom "shard this leaf over ``axis``" — from replicating a leaf only
    because its dim 0 is small: the shard moves to the first later dim
    the axis divides (a (3, 3, Cin, Cout) conv kernel shards Cin).
    Leaves with no such dim, and specs that name more than one dim,
    clamp as above."""
    if spec == P():
        return spec
    if spill and len(spec) == 1 and spec[0] is not None:
        for i in range(len(shape)):
            moved = _clamp_spec(P(*([None] * i), spec[0]), shape, mesh)
            if moved != P():
                return moved
        return P()
    entries = list(spec)[: len(shape)]
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        sizes = [dict(mesh.shape).get(a) for a in axes]
        if any(s is None for s in sizes):
            out.append(None)
            continue
        total = math.prod(sizes)
        if total <= 1 or dim % total != 0:
            out.append(None)
            continue
        out.append(tuple(axes) if len(axes) > 1 else axes[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


# ---------------------------------------------------------------------------
# Remat policy — the ONE jax.checkpoint site (zoolint raw-remat keeps it
# that way), plus the active-plan context resolve_remat consults.
# ---------------------------------------------------------------------------

# plans entered by compile_step for the duration of tracing, innermost
# last — resolve_remat walks it top-down so the plan being compiled wins
_ACTIVE_PLANS: list = []


@contextlib.contextmanager
def _active_plan(plan: "ShardingPlan"):
    _ACTIVE_PLANS.append(plan)
    try:
        yield plan
    finally:
        _ACTIVE_PLANS.pop()


def resolve_remat(path: str, default: str | None = None) -> str | None:
    """Remat policy for a logical scope name (a layer name, ``"blocks"``)
    under the plan currently being compiled: first ``remat_rules`` match
    (``re.search``, innermost active plan first) wins; no active plan or
    no match falls back to ``default`` — so a plan's rules SUBSUME the
    per-layer ``remat=`` flag without breaking it."""
    for plan in reversed(_ACTIVE_PLANS):
        for pat, policy in plan.remat_rules:
            if re.search(pat, path):
                return policy
    return default


def resolve_kernel(scope: str, default: str | None = None) -> str | None:
    """Kernel pick for a logical op scope under the plan currently
    being compiled (the kernel-plane twin of :func:`resolve_remat`):
    first ``kernel_rules`` match on the innermost active plan wins.
    ``"xla"`` is an explicit pick — the consumer must take its jnp/XLA
    fallback path; no active plan or no match returns ``default``
    (``None`` = the consumer's own routing heuristics apply, e.g.
    flash's eligibility check).  Consumers: ``ops/attention.py``
    (``"attention"``), the estimator's optimizer swap
    (``"optimizer.adam"``), ``objectives.py``
    (``"loss.softmax_xent"``), ``pipeline/inference/quantize.py``
    (``"serving.int8_matmul"``)."""
    for plan in reversed(_ACTIVE_PLANS):
        kernel = plan.kernel_for(scope)
        if kernel is not None:
            return kernel
    return default


def apply_remat(fn, policy: str | None, *, static_argnums=()):
    """Wrap ``fn`` in ``jax.checkpoint`` under a named policy — the one
    remat site every layer and pipeline schedule routes through.

    ``None`` returns ``fn`` unchanged; ``"full"`` recomputes the whole
    scope in the backward pass (max memory saving, ~1/3 extra FLOPs);
    ``"dots"`` keeps contraction outputs
    (``dots_with_no_batch_dims_saveable``); ``"attn"`` keeps the arrays
    named in :data:`REMAT_KEPT_NAMES` (the flash kernels' output and
    softmax statistics, which are what their backward kernels read, and
    a transformer block's feed-forward output), so the scope is
    recomputed but for the attention forward and the down-projection;
    any other name resolves as an attribute of
    ``jax.checkpoint_policies``."""
    if policy in (None, "", "none"):
        return fn
    if policy == "full":
        return jax.checkpoint(fn, static_argnums=static_argnums)
    if policy == "dots":
        ckpt_policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif policy in REMAT_KEPT_NAMES:
        ckpt_policy = jax.checkpoint_policies.save_only_these_names(
            *REMAT_KEPT_NAMES[policy])
    else:
        try:
            ckpt_policy = getattr(jax.checkpoint_policies, policy)
        except AttributeError:
            raise ValueError(
                f"unknown remat policy {policy!r}; expected one of "
                f"{REMAT_POLICIES} or a jax.checkpoint_policies "
                "attribute name") from None
    return jax.checkpoint(fn, policy=ckpt_policy,
                          static_argnums=static_argnums)


# ---------------------------------------------------------------------------
# Canned plans — FSDP/TP/ZeRO as rule sets instead of bespoke programs.
# ---------------------------------------------------------------------------


def data_parallel() -> ShardingPlan:
    """Replicated parameters + optimizer state, batch over ``data`` —
    the historical default, now spelled as a plan."""
    return ShardingPlan(
        name="dp",
        description="replicated params/opt state, batch over data")


def _overlap_fields(overlap) -> dict:
    """Resolve a canned plan's ``overlap=`` argument: ``False`` → no
    overlap (today's serial schedule), ``True`` → bucketed gradient
    overlap at :func:`default_bucket_bytes`, an int → that bucket size.
    The plan name gains a ``+overlap`` suffix so compile labels, the
    estimator's step cache and the cost model's exposed-fraction lookup
    all see the bucketed variant as a distinct program."""
    if not overlap:
        return {}
    bb = default_bucket_bytes() if overlap is True else int(overlap)
    return {"bucket_bytes": bb}


def zero1(axis: str = DATA_AXIS, overlap=False) -> ShardingPlan:
    """Params replicated, optimizer state sharded over ``axis``
    (ZeRO-1: 1/n moment memory + update compute per chip).  Subsumes the
    old ``ZOO_SHARD_OPTIMIZER`` GSPMD path.  ``overlap`` turns on
    bucketed gradient overlap (``True`` = default bucket size, an int =
    that many bytes per bucket; the trajectory to float32 rounding)."""
    extra = _overlap_fields(overlap)
    return ShardingPlan(
        name="zero1+overlap" if extra else "zero1",
        param_rules=_REPLICATE_ALL,
        opt_rules=((r".*", P(axis)),),
        description=f"replicated params, opt state sharded over {axis}",
        **extra)


def fsdp(axis: str = DATA_AXIS, overlap=False) -> ShardingPlan:
    """Params AND optimizer state sharded over ``axis``: XLA all-gathers
    weights where the forward uses them and reduce-scatters gradients
    into each chip's shard — per-chip param+opt bytes drop ~1/n at an
    unchanged (bit-identical) loss trajectory.  The whole-weight-update
    sharding of arXiv:2004.13336 as a two-line rule set.  ``overlap``
    adds bucketed gradient overlap AND the double-buffered gather
    prefetch (:meth:`ShardingPlan.prefetch_params` — layer k+1's
    all-gather issues while layer k computes)."""
    rules = ((r".*", P(axis)),)
    extra = _overlap_fields(overlap)
    return ShardingPlan(
        name="fsdp+overlap" if extra else "fsdp",
        param_rules=rules, opt_rules=rules,
        prefetch=bool(extra),
        description=f"params + opt state sharded over {axis} "
                    "(gather-on-use / reduce-scatter)",
        **extra)


def zero2(axis: str = DATA_AXIS, overlap=False) -> ShardingPlan:
    """ZeRO-2 (arXiv:2004.13336): optimizer state sharded AND grads
    reduce-scattered into per-chip shards over ``axis``; params stay
    replicated, so the update all-gathers the new weights once per step
    (grad_rules pin the scatter, constrain_params pins the gather-at-
    update).  Same math as DP — per-chip persistent state matches
    zero1, and the transient gradient buffer drops to 1/n.  ``overlap``
    buckets the reduce-scatters into backward-completion-order groups
    (the trajectory to float32 rounding)."""
    shard = ((r".*", P(axis)),)
    extra = _overlap_fields(overlap)
    return ShardingPlan(
        name="zero2+overlap" if extra else "zero2",
        param_rules=_REPLICATE_ALL,
        opt_rules=shard,
        grad_rules=shard,
        description=f"replicated params, opt state + grads sharded over "
                    f"{axis} (reduce-scatter, gather at update)",
        **extra)


def zero3(axis: str = DATA_AXIS, overlap=False) -> ShardingPlan:
    """ZeRO-3: params, optimizer state AND grads all sharded over
    ``axis`` — XLA all-gathers each weight where the forward uses it
    and reduce-scatters its gradient straight into the owning chip's
    shard, so per-chip param+opt state is ~1/n (the fsdp layout with
    the gradient scatter pinned explicitly).  ``overlap`` buckets the
    gradient reduce-scatters and prefetch-gathers the params
    (the trajectory to float32 rounding)."""
    shard = ((r".*", P(axis)),)
    extra = _overlap_fields(overlap)
    return ShardingPlan(
        name="zero3+overlap" if extra else "zero3",
        param_rules=shard,
        opt_rules=shard,
        grad_rules=shard,
        prefetch=bool(extra),
        description=f"params + opt state + grads sharded over {axis} "
                    "(gather-on-use, reduce-scatter)",
        **extra)


def pipeline_plan(schedule: str, axis: str = PIPE_AXIS,
                  remat: str | None = None) -> ShardingPlan:
    """Stage assignment as a plan: stage-stacked params (leading dim =
    stage index) shard over the ``pipe`` axis, and the schedule lowers
    through :func:`compile_step` in shard_map mode — so gpipe/1F1B
    share the persistent compile cache, per-plan labels and the
    ``zoo_hlo_*`` feature pipe like every other plan.  ``remat`` adds a
    catch-all remat rule for the stage bodies."""
    return ShardingPlan(
        name=f"pipeline_{schedule}",
        param_rules=((r".*", P(axis)),),
        mode="shard_map",
        remat_rules=((r".*", remat),) if remat else (),
        description=f"{schedule} schedule over the {axis} axis")


def with_remat(plan: ShardingPlan, policy: str = "full",
               pattern: str = r".*") -> ShardingPlan:
    """A copy of ``plan`` with a remat rule appended (and the policy in
    the name, so compile labels and cost-model lookups see it):
    ``with_remat(zero3(), "full")`` → ``"zero3+remat_full"``."""
    return dataclasses.replace(
        plan,
        name=f"{plan.name}+remat_{policy}",
        remat_rules=plan.remat_rules + ((str(pattern), policy),))


def with_dtype(plan: ShardingPlan, role: str = "bf16",
               pattern: str = r".*") -> ShardingPlan:
    """A copy of ``plan`` with a dtype rule appended and the role in the
    name — ``with_dtype(fsdp(), "bf16")`` → ``"fsdp+bf16"``, so compile
    labels, the estimator's step cache and the cost model's
    dtype-dependent ceilings all see the precision variant as a
    distinct program (``_plan_key`` strips ``+`` segments, so sharding
    lookups still resolve)."""
    if role not in DTYPE_ROLES:
        raise ValueError(
            f"dtype role must be one of {DTYPE_ROLES}, got {role!r}")
    return dataclasses.replace(
        plan,
        name=f"{plan.name}+{role}",
        dtype_rules=plan.dtype_rules + ((str(pattern), role),))


def with_kernels(plan: ShardingPlan | str | None = None,
                 rules=DEFAULT_KERNEL_RULES) -> ShardingPlan:
    """A copy of ``plan`` with a ``kernel_rules`` table appended and
    ``+kernels`` suffixed to the name — the kernel-plane twin of
    :func:`with_dtype`.  Compile labels, the estimator's step cache and
    the persistent compile cache all see the kernel variant as a
    distinct program (:meth:`ShardingPlan.cache_key` includes the
    table); ``resolve_plan`` strips the suffix, so checkpoint plan
    records round-trip.  Default rules route every op the plane ships a
    kernel for (:data:`DEFAULT_KERNEL_RULES`); pass an explicit table
    to pick per scope (``(("optimizer.adam", "xla"),)`` forces the
    optax chain)."""
    plan = resolve_plan(plan)
    frozen = ShardingPlan(name="_kernel_probe",
                          kernel_rules=tuple(rules)).kernel_rules
    name = plan.name if plan.name.endswith("+kernels") \
        else f"{plan.name}+kernels"
    return dataclasses.replace(
        plan, name=name, kernel_rules=plan.kernel_rules + frozen)


def mixed_precision(plan: ShardingPlan | str | None = None) -> ShardingPlan:
    """The canned bf16 mixed-precision policy over any base plan:
    bf16 compute params + f32 master copies + f32 gradient/collective
    accumulation.  The stored params ARE the f32 masters; the step
    casts a compute copy down on use and the in-graph vjp casts
    gradients back up before the optimizer update, so optimizer state
    is bitwise-stable and elastic resume reshards the f32 masters
    bit-exact across world sizes (the master copies never leave the
    plan's normal placement path)."""
    return with_dtype(resolve_plan(plan), "bf16")


def int8_serving(plan: ShardingPlan | str | None = None) -> ShardingPlan:
    """The weight-only int8 SERVING policy: matmul-sized weights carry
    the ``"int8"`` role, and a serving replica quantizes exactly those
    leaves through :func:`~analytics_zoo_tpu.pipeline.inference.
    quantize.quantize_params_for_plan` (~4× weight bytes).  Training
    under this plan still computes in bf16 — int8 is a serving
    transform, not a training number format."""
    return with_dtype(resolve_plan(plan), "int8")


def resolve_dtype_rules(value) -> tuple:
    """``dtype_rules`` from a policy spec: ``None``/``""``/``"f32"`` →
    no rules, ``"bf16_mixed"`` → catch-all bf16 compute,
    ``"int8_serving"`` → catch-all int8 weight-only, a
    ``<regex>=<role>,...`` rule string → that table (role ``keep`` /
    ``none`` pins a path to its stored dtype, shadowing later rules),
    or an already-built rule sequence (validated).  ``"auto"`` is
    rejected here the way ``resolve_plan`` rejects ``plan="auto"`` —
    the estimator resolves it through the config oracle's dtype
    sweep."""
    if value is None:
        return ()
    if isinstance(value, (tuple, list)):
        return ShardingPlan(name="_dtype_probe",
                            dtype_rules=tuple(value)).dtype_rules
    name = str(value).strip()
    low = name.lower()
    if low in ("", "f32", "none"):
        return ()
    if low == "bf16_mixed":
        return ((r".*", "bf16"),)
    if low == "int8_serving":
        return ((r".*", "int8"),)
    if low == "auto":
        raise ValueError(
            'dtype policy "auto" is resolved by the estimator (the '
            "config oracle sweeps f32 vs bf16 with dtype-dependent "
            "roofline ceilings — analysis/oracle.py); pass a concrete "
            "policy here")
    rules = []
    for part in name.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"dtype policy rule {part!r} must be '<regex>=<role>' "
                f"with role in {DTYPE_ROLES} (or a policy name from "
                f"{DTYPE_POLICY_NAMES})")
        pat, role = part.rsplit("=", 1)
        role = role.strip().lower()
        if role in ("keep", "none"):
            role = None
        elif role not in DTYPE_ROLES:
            raise ValueError(
                f"dtype policy rule {part!r}: role must be one of "
                f"{DTYPE_ROLES} (or 'keep'), got {role!r}")
        rules.append((pat.strip(), role))
    return tuple(rules)


def with_dtype_policy(plan: ShardingPlan, policy) -> ShardingPlan:
    """Apply a dtype policy spec (anything :func:`resolve_dtype_rules`
    accepts) to ``plan`` — no-op for ``None``/``"f32"``; otherwise the
    rules are appended and the first concrete role suffixes the name
    (``"fsdp"`` + ``"bf16_mixed"`` → ``"fsdp+bf16"``)."""
    rules = resolve_dtype_rules(policy)
    if not rules:
        return plan
    roles = [role for _, role in rules if role is not None]
    name = f"{plan.name}+{roles[0]}" if roles else plan.name
    return dataclasses.replace(
        plan, name=name, dtype_rules=plan.dtype_rules + rules)


def tensor_parallel(rules, axis: str = MODEL_AXIS,
                    name: str = "tp") -> ShardingPlan:
    """Megatron-style TP from a user rule table over the ``model`` axis
    (e.g. ``[("kernel", P(None, "model"))]``); anything unmatched
    replicates via an appended catch-all."""
    rules = _freeze_rules(rules)
    if not any(pat in (r".*", ".*") for pat, _ in rules):
        rules = rules + _REPLICATE_ALL
    return ShardingPlan(
        name=name, param_rules=rules,
        description=f"tensor parallel over {axis} by rule table")


def resolve_plan(value=None, config=None) -> ShardingPlan:
    """Resolve a plan argument: a :class:`ShardingPlan` passes through,
    a name string maps to its canned plan, ``None`` falls back to
    ``ZOO_SHARDING_PLAN`` (``config.sharding_plan``), then the legacy
    ``ZOO_SHARD_OPTIMIZER`` flag (→ :func:`zero1`), then
    :func:`data_parallel`."""
    if isinstance(value, ShardingPlan):
        return value
    if value is None and config is not None:
        value = getattr(config, "sharding_plan", None)
        if value is None and getattr(config, "shard_optimizer", False):
            return zero1()
    if value is None:
        return data_parallel()
    name = str(value).strip().lower()
    if name == "auto":
        raise ValueError(
            'plan="auto" is resolved by the estimator (the config '
            "oracle sweeps dp/zero1/zero2/fsdp/zero3 × remat against "
            "predicted per-chip bytes vs the HBM budget — "
            "analysis/oracle.py); pass a concrete plan or name here")
    # +kernels is appended LAST by with_kernels, so it strips first —
    # then the dtype role, then +overlap (mirrors construction order)
    kernels = False
    if name.endswith("+kernels"):
        kernels = True
        name = name[: -len("+kernels")]
    dtype_role = None
    for role in DTYPE_ROLES:
        if name.endswith("+" + role):
            dtype_role = role
            name = name[: -len(role) - 1]
            break
    overlap = False
    if name.endswith("+overlap"):
        overlap = True
        name = name[: -len("+overlap")]

    def _dtyped(plan: ShardingPlan) -> ShardingPlan:
        # "+f32" names the explicit master-precision variant: same
        # rules-free plan, so it resolves to the base plan unchanged
        if dtype_role in (None, "f32"):
            plan = plan
        else:
            plan = with_dtype(plan, dtype_role)
        return with_kernels(plan) if kernels else plan

    if name in ("dp", "data_parallel", "none", ""):
        if overlap:
            raise ValueError(
                "dp has no collectives to overlap; bucket_bytes applies "
                "to zero1/zero2/zero3/fsdp")
        return _dtyped(data_parallel())
    if name == "fsdp":
        return _dtyped(fsdp(overlap=overlap))
    if name == "zero1":
        return _dtyped(zero1(overlap=overlap))
    if name == "zero2":
        return _dtyped(zero2(overlap=overlap))
    if name == "zero3":
        return _dtyped(zero3(overlap=overlap))
    raise ValueError(
        f"unknown sharding plan {value!r}; valid names: "
        f"{', '.join(PLAN_NAMES)}, optionally suffixed +overlap, "
        f"a dtype role and/or +kernels (e.g. 'fsdp+overlap', "
        f"'zero1+bf16', 'dp+kernels') "
        "(tensor_parallel(...) takes a rule "
        "table, so it is built in code, not named)")


# ---------------------------------------------------------------------------
# Mesh builder — plain single-slice, or hybrid ICI×DCN for multi-pod.
# ---------------------------------------------------------------------------


def build_mesh(mesh_shape: Mapping[str, int] | None = None,
               dcn_shape: Mapping[str, int] | int | None = None,
               axes: Sequence[str] | None = None,
               devices=None, slice_groups=None, allow_idle: bool = False,
               dcn_axis: str | None = None) -> Mesh:
    """One mesh builder for every plan.

    Single slice (``dcn_shape`` unset): today's ``Mesh`` — missing axes
    get size 1, leftover devices fold into ``data``.  Multi-pod: the
    DCN-crossing axis goes OUTERMOST and the per-slice (ICI) extents
    come from ``mesh_shape``, via
    :func:`~analytics_zoo_tpu.parallel.multihost.hybrid_mesh` (the
    ``create_hybrid_device_mesh`` layout: inner-axis collectives ride
    ICI, only the outer axis crosses the data-center network).

    ``dcn_shape`` may be a mapping (``{"data": 2}``) or a bare slice
    count — then the crossing axis is ``dcn_axis`` > ``ZOO_DCN_AXIS`` >
    ``"data"``; an axis name not already in ``axes`` (e.g. ``"dcn"``)
    is prepended as a NEW outermost axis, so a plan can shard the batch
    over ``("dcn", "data")`` while keeping model axes ICI-only.
    """
    if dcn_shape is None:
        from analytics_zoo_tpu.common.engine import _infer_mesh_shape

        devices = list(jax.devices()) if devices is None else list(devices)
        axes = tuple(axes) if axes is not None else tuple(
            a for a in ALL_AXES if a in (mesh_shape or {})) or (DATA_AXIS,)
        shape = _infer_mesh_shape(devices, axes, mesh_shape)
        n_used = math.prod(shape.values())
        dev = np.asarray(devices[:n_used]).reshape(
            [shape[a] for a in axes])
        return Mesh(dev, axes)

    from analytics_zoo_tpu.parallel.multihost import hybrid_mesh

    ici = dict(mesh_shape or {})
    if isinstance(dcn_shape, int):
        axis = dcn_axis or os.environ.get("ZOO_DCN_AXIS") or DATA_AXIS
        dcn_shape = {axis: int(dcn_shape)}
    else:
        dcn_shape = dict(dcn_shape)
    if axes is None:
        named = [a for a in ALL_AXES if a in ici or a in dcn_shape]
        extra = [a for a in dcn_shape if a not in named]
        axes = tuple(extra + named)
    else:
        axes = tuple(axes)
        missing = [a for a in dcn_shape if a not in axes]
        axes = tuple(missing) + axes
    return hybrid_mesh(ici, dcn_shape, axes=axes, devices=devices,
                       slice_groups=slice_groups, allow_idle=allow_idle)


# ---------------------------------------------------------------------------
# compile_step — THE choke point.
# ---------------------------------------------------------------------------


def _lower_seconds(label: str):
    from analytics_zoo_tpu.common.compile_cache import COMPILE_BUCKETS
    from analytics_zoo_tpu.metrics import get_registry

    return get_registry().histogram(
        "zoo_lower_seconds",
        "wall time of jit(...).lower(): tracing the step and lowering it, "
        "before zoo_compile_seconds' compile",
        ("label",), buckets=COMPILE_BUCKETS).labels(label=label)


class PlannedStep:
    """A step function compiled through the choke point.

    Call it like the function it wraps: the first call per input
    signature lowers and compiles through
    :func:`~analytics_zoo_tpu.common.compile_cache.timed_compile`
    (persistent-cache hit/miss counters, ``zoo_compile_seconds``, the
    HLO graph lint + ``zoo_hlo_*`` cost features), caches the
    executable, and later calls dispatch it directly — so the in-loop
    cost is one pytree signature probe + the XLA execute.  Signatures
    key on tree structure, leaf shape/dtype/weak-type AND sharding (a
    resharded input is a different program; python scalars key on
    their type).  The probe is a Python-level tree_flatten per call —
    microseconds against a training dispatch, and the fused scan-K
    path (ZOO_STEPS_PER_DISPATCH) amortizes it K-fold; the dispatch
    quick-tier bench guards pin that the trade holds.
    """

    _MAX_EXES = 32  # tail-batch shape churn bound; oldest evicted

    def __init__(self, jitted, label: str, plan: ShardingPlan,
                 meta: dict | None = None):
        self._jitted = jitted
        self.label = label
        self.plan = plan
        # compile context forwarded into the zoo-hlo-report/2 rows
        # (plan name, mesh axis shape, steps_per_dispatch K)
        self.meta = dict(meta) if meta else {"plan": plan.name}
        self._exes: dict = {}

    def _sig(self, args) -> tuple:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = []
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                sig.append((leaf.shape, leaf.dtype,
                            getattr(leaf, "weak_type", False),
                            leaf.sharding))
            elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                sig.append((tuple(leaf.shape), leaf.dtype, False, None))
            else:
                # python scalars: the TYPE is the signature — an int and
                # a float at the same position are different programs
                # (int32 vs f32 weak avals), and the AOT executable
                # rejects a mismatched aval instead of recompiling
                sig.append(type(leaf))
        return treedef, tuple(sig)

    def lower(self, *args):
        """The underlying ``jit(...).lower`` — for callers that need the
        lowered module (HLO inspection); normal use just calls the
        step."""
        return self._jitted.lower(*args)

    def __call__(self, *args):
        from analytics_zoo_tpu.common.compile_cache import timed_compile
        from analytics_zoo_tpu.metrics import span

        key = self._sig(args)
        exe = self._exes.get(key)
        if exe is None:
            # tracing and lowering, apart from the compile that follows:
            # where a model's depth (a loop traced a pass at a time) shows
            with span("zoo.compile.lower",
                      observe=_lower_seconds(self.label).observe):
                lowered = self._jitted.lower(*args)
            exe = timed_compile(lowered, self.label, meta=self.meta)
            while len(self._exes) >= self._MAX_EXES:
                self._exes.pop(next(iter(self._exes)))
            self._exes[key] = exe
        return exe(*args)


def compile_step(step_fn, plan: ShardingPlan | None = None, mesh=None, *,
                 donate_argnums=(), label: str | None = None,
                 in_specs=None, out_specs=None, check_vma: bool = False,
                 meta: dict | None = None) -> PlannedStep:
    """Compile a step function under a plan — the ONE entry every
    strategy uses (SNIPPETS [2] Titanax shape).

    ``mode="jit"`` plans run GSPMD: the caller device_puts inputs into
    the plan layout (:meth:`ShardingPlan.place_params` /
    ``place_opt_state``) and constrains outputs in-graph
    (:meth:`ShardingPlan.constrain_params`); XLA inserts the
    collectives.  ``mode="shard_map"`` plans wrap ``step_fn`` in
    ``jax.shard_map`` with the given ``in_specs``/``out_specs`` — the
    explicit-collectives formulation the legacy strategies use.  Either
    way the result lowers through ``timed_compile``: persistent cache,
    AOT warmup, compile metering and the HLO lint/feature pipe apply to
    EVERY plan.

    ``label`` names the program in ``zoo_compile_seconds{label=}`` /
    ``zoo_hlo_*{label=}`` (default ``<plan.name>_step``); ``meta``
    adds compile context (mesh axis shape, steps_per_dispatch) to the
    plan name in each ``zoo-hlo-report/2`` row.
    """
    # the choke point owns the compile plane end to end: a plan compiled
    # here gets the persistent cache whenever ZOO_COMPILE_CACHE is set,
    # even when no estimator entry point ran first (e.g. the eager
    # pipeline schedules).  Idempotent; no-op without the env knob.
    from analytics_zoo_tpu.common.compile_cache import (
        maybe_enable_persistent_cache,
    )

    maybe_enable_persistent_cache()
    plan = resolve_plan(plan)
    if plan.mode == "shard_map" or in_specs is not None:
        if in_specs is None or out_specs is None:
            raise ValueError(
                "shard_map-mode plans need explicit in_specs/out_specs")
        if mesh is None:
            from analytics_zoo_tpu.common.engine import get_zoo_context

            mesh = get_zoo_context().mesh
        step_fn = jax.shard_map(step_fn, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=check_vma)
    if plan.remat_rules or plan.kernel_rules:
        # enter the plan for the duration of TRACING, so resolve_remat /
        # resolve_kernel inside any layer sees this plan's rule tables
        # (tracing happens under the jit call below, inside this
        # wrapper's with-block)
        inner = step_fn

        def step_fn(*args):
            with _active_plan(plan):
                return inner(*args)
    jitted = jax.jit(step_fn, donate_argnums=donate_argnums)
    full_meta = {"plan": plan.name, **(meta or {})}
    if "mesh_shape" not in full_meta and mesh is not None:
        full_meta["mesh_shape"] = dict(mesh.shape)
    if plan.dtype_rules and "dtype_policy" not in full_meta:
        # ride the compile meta into the zoo-hlo-report/2 rows AND the
        # hlo dtype-policy lint — the lowered program is checked against
        # the precision the plan declared
        full_meta["dtype_policy"] = plan.dtype_policy_str()
    if plan.kernel_rules and "kernel_policy" not in full_meta:
        full_meta["kernel_policy"] = plan.kernel_policy_str()
    return PlannedStep(jitted, label or f"{plan.name}_step", plan,
                       meta=full_meta)


# ---------------------------------------------------------------------------
# Introspection + checkpoint serialization helpers.
# ---------------------------------------------------------------------------


def per_chip_bytes(tree, device=None) -> int:
    """Bytes of ``tree`` resident on ONE device (default: the first
    device of the first leaf's sharding) — the quantity an fsdp/zero1
    plan shrinks.  Replicated leaves count full size; sharded leaves
    count one shard."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array):
            continue
        shards = leaf.addressable_shards
        if not shards:
            continue
        if device is None:
            device = shards[0].device
        total += sum(s.data.nbytes for s in shards if s.device == device)
    return total


def live_bytes(device=None) -> dict:
    """Measured per-chip memory: ``{"live_bytes", "peak_bytes",
    "source"}`` for ONE device (default: the first).

    On accelerators with allocator stats the numbers come straight from
    ``device.memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use``).
    The CPU backend has no allocator stats, so the fallback sums the
    shard bytes of every live ``jax.Array`` resident on the device —
    live == peak there (what is referenced is what exists), which is
    exactly the persistent param+opt state the bench compares against
    :func:`~analytics_zoo_tpu.analysis.costmodel.predict_chip_bytes`."""
    if device is None:
        device = jax.devices()[0]
    try:
        stats = device.memory_stats()
    except Exception:
        stats = None
    if stats and stats.get("bytes_in_use") is not None:
        in_use = int(stats["bytes_in_use"])
        return {"live_bytes": in_use,
                "peak_bytes": int(stats.get("peak_bytes_in_use", in_use)),
                "source": "memory_stats"}
    total = 0
    for arr in jax.live_arrays():
        try:
            for s in arr.addressable_shards:
                if s.device == device:
                    total += s.data.nbytes
        except Exception:  # deleted/donated buffers mid-iteration
            continue
    return {"live_bytes": int(total), "peak_bytes": int(total),
            "source": "live_arrays"}


def record_mem_gauges(label: str, predicted_bytes: int | None = None,
                      measured_bytes: int | None = None,
                      device=None) -> dict:
    """Publish the ``zoo_mem_*`` gauge family for one plan label —
    closing the memory loop the way ``zoo_oracle`` rel_error does for
    steps/sec: ``zoo_mem_live_bytes`` / ``zoo_mem_peak_bytes`` (from
    :func:`live_bytes`, or ``measured_bytes`` when the caller already
    measured, e.g. ``per_chip_bytes`` of the state it placed),
    ``zoo_mem_predicted_bytes`` and ``zoo_mem_rel_error`` when the cost
    model's prediction is given.  Returns the measured dict."""
    from analytics_zoo_tpu.metrics import get_registry

    if measured_bytes is not None:
        meas = {"live_bytes": int(measured_bytes),
                "peak_bytes": int(measured_bytes), "source": "caller"}
    else:
        meas = live_bytes(device)
    reg = get_registry()
    lab = ("label",)
    reg.gauge("zoo_mem_live_bytes",
              "measured per-chip bytes for a plan label",
              lab).labels(label=label).set(meas["live_bytes"])
    reg.gauge("zoo_mem_peak_bytes",
              "peak per-chip bytes for a plan label",
              lab).labels(label=label).set(meas["peak_bytes"])
    if predicted_bytes is not None:
        reg.gauge("zoo_mem_predicted_bytes",
                  "cost-model predicted per-chip bytes",
                  lab).labels(label=label).set(int(predicted_bytes))
        if predicted_bytes > 0:
            rel = abs(meas["live_bytes"] - predicted_bytes) / predicted_bytes
            reg.gauge("zoo_mem_rel_error",
                      "|measured - predicted| / predicted chip bytes",
                      lab).labels(label=label).set(rel)
    return meas


def record_dtype_gauges(label: str, plan: ShardingPlan, params) -> dict:
    """Publish the ``zoo_dtype_*`` gauge family for one plan label —
    the precision plane's observable: per-role leaf counts and COMPUTE
    bytes (what the role's compute dtype makes the leaf weigh in the
    step — bf16 halves, int8 quarters; role ``f32`` counts every
    unmatched/kept leaf at its stored size).  Returns
    ``{"roles": {role: {"leaves", "compute_bytes"}}, "master_bytes",
    "compute_bytes"}`` so benches can pin the bytes ratio."""
    from analytics_zoo_tpu.metrics import get_registry

    role_bytes = {"f32": 4, "bf16": 2, "f16": 2, "int8": 1}
    roles = plan.dtype_roles(params)
    per_role: dict = {}
    master_bytes = compute_bytes = 0
    from analytics_zoo_tpu.parallel.partition import leaf_path_name

    def visit(path, leaf):
        nonlocal master_bytes, compute_bytes
        if not hasattr(leaf, "dtype"):
            return leaf
        role = roles.get(leaf_path_name(path), "f32")
        size = int(np.size(leaf))
        stored = size * np.dtype(leaf.dtype).itemsize
        comp = size * role_bytes.get(role, 4) if role != "f32" else stored
        slot = per_role.setdefault(role,
                                   {"leaves": 0, "compute_bytes": 0})
        slot["leaves"] += 1
        slot["compute_bytes"] += comp
        master_bytes += stored
        compute_bytes += comp
        return leaf

    jax.tree_util.tree_map_with_path(visit, params)
    reg = get_registry()
    for role, slot in per_role.items():
        lab = ("label", "role")
        reg.gauge("zoo_dtype_leaves",
                  "param leaves per dtype role under a plan's "
                  "dtype_rules", lab).labels(
            label=label, role=role).set(slot["leaves"])
        reg.gauge("zoo_dtype_compute_bytes",
                  "compute-copy bytes per dtype role (master stays f32)",
                  lab).labels(
            label=label, role=role).set(slot["compute_bytes"])
    reg.gauge("zoo_dtype_bytes_ratio",
              "compute-copy bytes / master bytes for a plan label",
              ("label",)).labels(label=label).set(
        compute_bytes / master_bytes if master_bytes else 1.0)
    return {"roles": per_role, "master_bytes": int(master_bytes),
            "compute_bytes": int(compute_bytes)}


#: the logical op scopes the kernel plane routes (consumers listed in
#: :func:`resolve_kernel`) — what record_kernel_gauges resolves a plan's
#: table against
KERNEL_SCOPES = ("attention", "optimizer.adam", "loss.softmax_xent",
                 "serving.int8_matmul")


def record_kernel_gauges(label: str, plan: ShardingPlan) -> dict:
    """Publish the ``zoo_kernel_*`` selection/routing gauges for one
    plan label — the kernel plane's observable (the twin of
    :func:`record_dtype_gauges` for the fifth rule table):
    ``zoo_kernel_selections{label, scope, kernel}`` is what the plan's
    ``kernel_rules`` resolve to per known scope (kernel ``"xla"``
    included — a declined kernel is a decision, not an absence), and
    ``zoo_kernel_invocations{kernel, backend}`` re-exports each kernel
    module's pallas/fallback routing counters.  Returns
    ``{"selections": {scope: kernel}, "invocations": {...}}``."""
    from analytics_zoo_tpu.metrics import get_registry
    from analytics_zoo_tpu.ops.pallas import kernel_invocation_counts

    reg = get_registry()
    selections = {}
    for scope in KERNEL_SCOPES:
        kernel = plan.kernel_for(scope)
        if kernel is None:
            continue
        selections[scope] = kernel
        reg.gauge("zoo_kernel_selections",
                  "kernel a plan's kernel_rules resolve for an op scope "
                  "(1 = selected; 'xla' is the explicit fallback pick)",
                  ("label", "scope", "kernel")).labels(
            label=label, scope=scope, kernel=kernel).set(1)
    invocations = kernel_invocation_counts()
    for kernel, counts in invocations.items():
        for backend, n in counts.items():
            reg.gauge("zoo_kernel_invocations",
                      "per-kernel routing counter: compiles that took "
                      "the pallas path vs the jnp fallback",
                      ("kernel", "backend")).labels(
                kernel=kernel, backend=backend).set(n)
    return {"selections": selections, "invocations": invocations}


def serialize_specs(spec_tree) -> list:
    """PartitionSpec tree → plain-builtin leaves list (tree_leaves
    order) for checkpoint payloads: each spec becomes a list whose
    entries are None / axis name / list of axis names — survives
    ``safe_load`` without any custom-type allowlisting."""
    flat = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda s: isinstance(s, P))
    return [[list(e) if isinstance(e, (tuple, list)) else e
             for e in spec] for spec in flat]


def deserialize_specs(serialized: list) -> list:
    """Inverse of :func:`serialize_specs` (a flat list of
    PartitionSpecs, paired by position with the tree's leaves)."""
    return [P(*[tuple(e) if isinstance(e, list) else e for e in entries])
            for entries in serialized]
