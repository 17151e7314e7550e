"""Grouped matrix product: rows of ``lhs`` sorted by group, each group's
rows multiplied by that group's matrix.  The product of routed experts
that drop no token (``ops/moe.py::held_experts_ffn``): the rows are the
assignments sorted by expert, the groups the experts held here.

    out[start_g : start_g + group_sizes[g]] = lhs[the same rows] @ rhs[g]

On a TPU this is the Pallas kernel JAX ships as ``jax.experimental.pallas.
ops.tpu.megablox`` (``gmm``, and ``tgmm`` for the weights' gradient): its
grid runs over the row tiles that the group sizes fill, so rows past
``sum(group_sizes)`` cost nothing and are NOT WRITTEN: the caller masks
them.  Elsewhere ``jax.lax.ragged_dot`` is the plain fallback and the
oracle (it writes zeros there).  ``ZOO_KERNEL_INTERPRET=1`` runs the kernel
in interpret mode, ``ZOO_KERNEL_FORCE_PALLAS=1`` routes to the real kernel
on any backend for lowering-only checks, as for the other kernels of this
package.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

# Trace-time routing counters, as ``flash_attention.invocation_counts``.
invocation_counts = {"pallas": 0, "fallback": 0}

#: The most rows, contraction and columns a grid step multiplies.  Row tiles
#: of 256 keep the tiles that straddle two experts few against 384 rows an
#: expert (8,192 tokens x 6 / 128); at (256, 2048, 1024) a step holds a
#: 1 MB and a 4 MB bf16 tile twice over and a 1 MB accumulator, under the
#: v5e's 16 MB of scoped VMEM.  The weights' gradient accumulates a
#: (contraction, columns) tile in float32, so its contraction tile is half.
ROWS, CONTRACTION, COLUMNS = 256, 2048, 1024


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "") not in ("", "0")


def _interpret_forced() -> bool:
    return _env_flag("ZOO_KERNEL_INTERPRET")


def _pallas_available() -> bool:
    return (jax.default_backend() == "tpu" or _interpret_forced()
            or _env_flag("ZOO_KERNEL_FORCE_PALLAS"))


def _tile(x: int, cap: int, unit: int = 128) -> int:
    """The largest multiple of ``unit`` that divides ``x`` and is at most
    ``cap``; all of ``x`` where it is that small or has no such divisor."""
    if x <= cap:
        return x
    return next((t for t in range(cap - cap % unit, 0, -unit)
                 if x % t == 0), x)


def _rows_tile(m: int) -> int:
    return _tile(m, ROWS, 8)


def _megablox():
    """The module of the two kernels (the package's ``gmm`` attribute is
    its own differentiable wrapper, which takes one tiling for all three
    products)."""
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, interpret):
    return _gmm_fwd(lhs, rhs, group_sizes, interpret)[0]


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    megablox = _megablox()
    (m, k), n = lhs.shape, rhs.shape[2]
    out = megablox.gmm(
        lhs, rhs, group_sizes, lhs.dtype,
        (_rows_tile(m), _tile(k, CONTRACTION), _tile(n, COLUMNS)),
        interpret=interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(interpret, kept, g):
    """d lhs = g @ rhs[group]^T, the same kernel with the transposed
    matrices; d rhs[group] = lhs[rows]^T @ g[rows], its transposed form."""
    megablox = _megablox()
    lhs, rhs, group_sizes = kept
    (m, k), n = lhs.shape, rhs.shape[2]
    d_lhs = megablox.gmm(
        g, rhs, group_sizes, lhs.dtype,
        (_rows_tile(m), _tile(n, CONTRACTION), _tile(k, COLUMNS)),
        transpose_rhs=True, interpret=interpret)
    d_rhs = megablox.tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
        (_rows_tile(m), _tile(k, CONTRACTION // 2), _tile(n, COLUMNS)),
        num_actual_groups=rhs.shape[0], interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (rows, k) sorted by group, ``rhs`` (groups, k, n),
    ``group_sizes`` (groups,) int32 -> (rows, n) in ``lhs``'s dtype.
    Rows past ``sum(group_sizes)`` are unspecified (the kernel leaves them
    unwritten, in the result and in ``lhs``'s gradient alike).
    Differentiable in ``lhs`` and ``rhs``."""
    group_sizes = group_sizes.astype(jnp.int32)
    if _pallas_available():
        invocation_counts["pallas"] += 1
        return _gmm(lhs, rhs, group_sizes, _interpret_forced())
    invocation_counts["fallback"] += 1
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)
