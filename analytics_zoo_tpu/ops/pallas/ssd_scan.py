# zoolint: disable-file=raw-pallas-call -- ops/pallas/ is the one home
# for raw pl.pallas_call; the kernels here have their fallback and oracle in
# ops/ssd.py and are inlined into the caller's step program.
"""Mamba-2's chunked rule (``ops/ssd.py``) as two Pallas kernels, forward
and transposed: the walk of ``kda_scan.py`` with another chunk-local
function, in a file of its own so that the KDA kernels stay as they are.

Both run a grid of (groups of heads, chunks), the chunks of a sequence in
order (the backward one from the last), with the states, or their
cotangents, of the group's heads in VMEM scratch from one grid step to the
next.  A grid step loads its group's B and C once for all the group's heads
(a block of the (groups, ...) array: B and C are never copied to every
head), makes their C B^T once for them, walks the heads one after another
through ``ssd.chunk_step`` (or ``chunk_step_transposed``), and in the
backward kernel sums the heads' dB and dC in float32 before it writes the
group's once.  The heads are
independent, which gives the scheduler products to overlap.

``walk_forward`` also hands back every chunk's incoming state, which is
what the backward kernel reads in place of a second forward walk.  The
oracles are ``ssd.walk_forward_scan`` and ``walk_backward_scan``;
``ZOO_KERNEL_INTERPRET=1`` runs the kernels in interpret mode, as for the
other kernels of this package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops.pallas.kda_scan import _PARAMS, _interpret
from analytics_zoo_tpu.ops.ssd import (
    chunk_step,
    chunk_step_transposed,
    pair_products,
)

_F32 = jnp.float32


def _ssd_walk_forward_kernel(xdt_ref, a_ref, b_ref, c_ref, y_ref, states_ref,
                             final_ref, state):
    at = pl.program_id(1)

    @pl.when(at == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    b, c = b_ref[0, 0], c_ref[0, 0]
    gram = pair_products(b, c)
    for h in range(state.shape[0]):
        states_ref[0, h, 0] = state[h]
        state[h], y = chunk_step(state[h], xdt_ref[0, h, 0], a_ref[0, h, 0],
                                 b, c, gram)
        y_ref[0, h, 0] = y.astype(y_ref.dtype)

    @pl.when(at == pl.num_programs(1) - 1)
    def _():
        final_ref[0] = state[...]


def _ssd_walk_backward_kernel(xdt_ref, a_ref, b_ref, c_ref, states_ref,
                              dy_ref, dxdt_ref, da_ref, db_ref, dc_ref,
                              dstate):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    b, c = b_ref[0, 0], c_ref[0, 0]
    gram = pair_products(b, c)
    db = dc = jnp.zeros(b.shape, _F32)
    for h in range(dstate.shape[0]):
        dstate[h], (dx, da, db_h, dc_h) = chunk_step_transposed(
            dstate[h], states_ref[0, h, 0], dy_ref[0, h, 0],
            xdt_ref[0, h, 0], a_ref[0, h, 0], b, c, gram)
        dxdt_ref[0, h, 0] = dx.astype(dxdt_ref.dtype)
        da_ref[0, h, 0] = da
        db, dc = db + db_h, dc + dc_h
    db_ref[0, 0] = db.astype(db_ref.dtype)
    dc_ref[0, 0] = dc.astype(dc_ref.dtype)


def _head_spec(shape, chunk_at):
    """A group's heads, one chunk of each, of a (G, R, n, rows, columns)
    array."""
    return pl.BlockSpec((1, shape[1], 1) + tuple(shape[3:]),
                        lambda g, n: (g, 0, chunk_at(n), 0, 0),
                        memory_space=pltpu.VMEM)


def _group_spec(shape, chunk_at):
    """A group's one chunk of a (G, n, rows, columns) array."""
    return pl.BlockSpec((1, 1) + tuple(shape[2:]),
                        lambda g, n: (g, chunk_at(n), 0, 0),
                        memory_space=pltpu.VMEM)


def walk_forward(xdt, a, b, c):
    """``ssd.walk_forward_scan`` through the kernel."""
    return _ssd_walk_forward(xdt, a, b, c, interpret=_interpret())


def walk_backward(xdt, a, b, c, states, dy):
    """``ssd.walk_backward_scan`` through the kernel."""
    return tuple(_ssd_walk_backward(xdt, a, b, c, states, dy,
                                    interpret=_interpret()))


def _same(n):
    return n


# jitted on their own, so that a model's layers trace each kernel once
# (a bare pallas_call traces its body at every call site); the
# benchmark's reader tells the two kernels in a device trace by "ssd_walk"
# in their instruction's name and by the arrays each returns
# zoolint: disable=raw-jit -- an inner jit, inlined into the caller's program (the step that compile_step compiles): it is there for JAX's trace cache, not as a compile site
@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_walk_forward(xdt, a, b, c, *, interpret=False):
    g, r, n, _, p = xdt.shape
    states = jax.ShapeDtypeStruct((g, r, n, p, b.shape[-1]), _F32)
    return pl.pallas_call(
        _ssd_walk_forward_kernel,
        grid=(g, n),
        in_specs=[_head_spec(xdt.shape, _same), _head_spec(a.shape, _same),
                  _group_spec(b.shape, _same), _group_spec(c.shape, _same)],
        out_specs=[_head_spec(xdt.shape, _same),
                   _head_spec(states.shape, _same),
                   pl.BlockSpec((1, r, p, b.shape[-1]),
                                lambda g, n: (g, 0, 0, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct(xdt.shape, xdt.dtype), states,
                   jax.ShapeDtypeStruct((g, r, p, b.shape[-1]), _F32)],
        scratch_shapes=[pltpu.VMEM((r, p, b.shape[-1]), _F32)],
        compiler_params=_PARAMS, interpret=interpret)(xdt, a, b, c)


# zoolint: disable=raw-jit -- an inner jit, inlined into the caller's program (the step that compile_step compiles): it is there for JAX's trace cache, not as a compile site
@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_walk_backward(xdt, a, b, c, states, dy, *, interpret=False):
    g, r, n, _, p = xdt.shape

    def from_the_last(at):
        return n - 1 - at

    return pl.pallas_call(
        _ssd_walk_backward_kernel,
        grid=(g, n),
        in_specs=[_head_spec(xdt.shape, from_the_last),
                  _head_spec(a.shape, from_the_last),
                  _group_spec(b.shape, from_the_last),
                  _group_spec(c.shape, from_the_last),
                  _head_spec(states.shape, from_the_last),
                  _head_spec(dy.shape, from_the_last)],
        out_specs=[_head_spec(xdt.shape, from_the_last),
                   _head_spec(a.shape, from_the_last),
                   _group_spec(b.shape, from_the_last),
                   _group_spec(c.shape, from_the_last)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (xdt, a, b, c)],
        scratch_shapes=[pltpu.VMEM((r, p, b.shape[-1]), _F32)],
        compiler_params=_PARAMS, interpret=interpret)(xdt, a, b, c, states,
                                                      dy)
