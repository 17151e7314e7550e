"""Grouped matrix product: rows of ``lhs`` sorted by group, each group's
rows multiplied by that group's matrix.  The product of routed experts
that drop no token (``ops/moe.py::held_experts_ffn``): the rows are a
window of the assignments sorted by expert, the groups the experts held
here.

    out[start_g : start_g + group_sizes[g]] = lhs[the same rows] @ rhs[g]

On a TPU this is the Pallas kernel JAX ships as ``jax.experimental.pallas.
ops.tpu.megablox`` (``gmm``), with its transposed form (``tgmm``) for the
groups' own gradient and for the sum of a token's rows: their grids run
over the row tiles that the group sizes fill, so rows past
``sum(group_sizes)`` cost nothing; ``gmm`` does NOT WRITE them and
``tgmm`` does not read them.  Elsewhere ``jax.lax.ragged_dot`` and
``ragged_dot_general`` are the plain fallback and the oracle (zeros
there).  None of the three is differentiable: ``held_experts_ffn`` has the
rule, whose sums over windows the transposed kernels add to in place
(``existing_out``).  ``ZOO_KERNEL_INTERPRET=1`` runs the kernel
in interpret mode, ``ZOO_KERNEL_FORCE_PALLAS=1`` routes to the real kernel
on any backend for lowering-only checks, as for the other kernels of this
package.
"""

from __future__ import annotations

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
#: The tokens a group of ``grouped_row_sum`` covers: one pass of the MXU wide.
TOKENS = 128
#: Bytes of the float32 (contraction, columns) accumulator of the weights'
#: gradient: what (CONTRACTION / 2, COLUMNS) holds.  A width that no
#: multiple of 128 divides is a tile whole (an expert of 1,856), and the
#: other side's tile is then cut to keep the sum at this size: at (896,
#: 1856) the kernel asked for 20.6 MB of the 16 MB of scoped VMEM.
ACCUMULATOR = 4 * (CONTRACTION // 2) * COLUMNS


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


def _count(kernel: bool) -> bool:
    invocation_counts["pallas" if kernel else "fallback"] += 1
    return kernel


def grouped_matmul(lhs, rhs, group_sizes, transpose_rhs=False):
    """``lhs`` (rows, k) sorted by group, ``rhs`` (groups, k, n), or
    (groups, n, k) with ``transpose_rhs``, ``group_sizes`` (groups,) int32
    -> (rows, n) in ``lhs``'s dtype.  Rows past ``sum(group_sizes)`` are
    unspecified (the kernel leaves them unwritten)."""
    group_sizes = group_sizes.astype(jnp.int32)
    if not _count(_pallas_available()):
        return jax.lax.ragged_dot(
            lhs, rhs.swapaxes(1, 2) if transpose_rhs else rhs, group_sizes)
    (m, k), n = lhs.shape, rhs.shape[1 if transpose_rhs else 2]
    return _megablox().gmm(
        lhs, rhs, group_sizes, lhs.dtype,
        (_rows_tile(m), _tile(k, CONTRACTION), _tile(n, COLUMNS)),
        transpose_rhs=transpose_rhs, interpret=_interpret_forced())


def grouped_matmul_transposed(lhs, g, group_sizes, existing_out):
    """``existing_out[group] + lhs[rows]^T @ g[rows]`` over a group's rows:
    ``lhs`` (rows, k), ``g`` (rows, n), both sorted by group ->
    (groups, k, n) in ``existing_out``'s dtype, the sum made in float32.
    The groups' own gradient of ``grouped_matmul``, added to a running
    sum; every group is written, rows past ``sum(group_sizes)`` are not
    read."""
    group_sizes = group_sizes.astype(jnp.int32)
    if not _count(_pallas_available()):
        return (existing_out.astype(jnp.float32) + jax.lax.ragged_dot_general(
            lhs, g, group_sizes, jax.lax.RaggedDotDimensionNumbers(
                (((0,), (0,)), ((), ())), [0], []),
            preferred_element_type=jnp.float32)).astype(existing_out.dtype)
    (m, k), n = lhs.shape, g.shape[1]
    # the float32 accumulator's (contraction, columns) tile is the large one
    return _megablox().tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, existing_out.dtype,
        (_rows_tile(m), *_accumulator_tiles(k, n)),
        existing_out=existing_out, interpret=_interpret_forced())


def _accumulator_tiles(k: int, n: int) -> tuple[int, int]:
    """(contraction, columns) tiles of the weights' gradient whose float32
    sum stays within ``ACCUMULATOR``: the contraction's cut first, then
    the columns'."""
    tk, tn = _tile(k, CONTRACTION // 2), _tile(n, COLUMNS)
    if 4 * tk * tn > ACCUMULATOR:
        tk = _tile(k, max(ACCUMULATOR // (4 * tn), 128))
    if 4 * tk * tn > ACCUMULATOR:
        tn = _tile(n, max(ACCUMULATOR // (4 * tk), 128))
    return tk, tn


def token_tile(tokens: int) -> int:
    """The tokens a group of ``grouped_row_sum`` covers, of ``tokens``."""
    return _tile(tokens, TOKENS, 8)


def grouped_row_sum(rows, token, tile_sizes, existing_out):
    """``existing_out[t] + the sum of the rows whose token is t``:
    ``rows`` (m, n) in the order of their ``token`` (m,) int32,
    ``tile_sizes`` (tokens / token_tile(tokens),) int32 how many of them
    fall to each tile of tokens, ``existing_out`` (tokens, n) float32.
    Rows past ``sum(tile_sizes)`` are not read; their ``token`` is
    ``tokens`` or more.  On a TPU the transposed grouped product of a row's
    place in its tile, one-hot, with the rows: it walks the row tiles that
    the sizes fill.  Elsewhere a ``segment_sum``."""
    tokens = existing_out.shape[0]
    if not _count(_pallas_available()):
        return existing_out + jax.ops.segment_sum(
            rows.astype(jnp.float32), token, num_segments=tokens,
            indices_are_sorted=True)
    tile = token_tile(tokens)
    place = token[:, None] % tile == jnp.arange(tile)[None, :]
    return _megablox().tgmm(
        place.astype(rows.dtype).swapaxes(0, 1), rows,
        tile_sizes.astype(jnp.int32), existing_out.dtype,
        (_rows_tile(rows.shape[0]), tile, _tile(rows.shape[1], COLUMNS)),
        existing_out=existing_out.reshape(-1, tile, rows.shape[1]),
        interpret=_interpret_forced()).reshape(existing_out.shape)
