# zoolint: disable-file=raw-pallas-call -- ops/pallas/ is the one home
# for raw pl.pallas_call; the kernels here have their fallback and oracle in
# ops/ssd.py and are inlined into the caller's step program.
"""Mamba-2's chunked rule (``ops/ssd.py``) as two Pallas kernels, forward
and transposed: the walk of ``kda_scan.py`` with another chunk-local
function, in a file of its own so that the KDA kernels stay as they are.

The kernels read and write the projections' own token-major layout: X~,
Y and their cotangents as (B, L, heads x P), B and C as (B, L, groups x N),
so that a group's R heads of one chunk are one lane-dense (Q, R P) block
and its B and C one (Q, N) block each, and nobody has to split the heads
out before or merge them after.  The cumulative decay is the small
(B, groups, chunks, R, Q) float32 array, one (R, Q) tile a group's chunk;
the states are (B groups, R, chunks, P, N) float32, the oracle's.

Both run a grid of (sequences x groups of heads, chunks), the chunks of a
sequence in order (the backward one from the last), with the states, or
their cotangents, of the group's heads in VMEM scratch from one grid step
to the next.  A grid step loads its group's B and C once for all the
group's heads (B and C are never copied to every head), makes their C B^T
once for them, and walks the heads one after another through
``ssd.chunk_step`` (or ``chunk_step_transposed``), unchanged.  Heads
narrower than the 128 lanes of a register share a lane tile (two heads of
64 at the published widths): a head's step runs on the whole tile with the
other heads' lanes zeroed, its state as the tile's heads' states stacked
(128, N) with the other heads' rows zeroed, so every load and store is
lane-aligned and the heads' results, each zero outside its own lanes and
rows, add up exactly.  (Slicing a head's 64 lanes out of the tile instead
made Mosaic realign them at every use: the backward kernel took 2.57 ms a
call against 1.21 at the cell's size on a v5e.)  The backward kernel sums
the heads' dB and dC in float32 before it writes the group's once.  The
heads are independent, which gives the scheduler products to overlap.

``walk_forward`` also hands back every chunk's incoming state, which is
what the backward kernel reads in place of a second forward walk.  The
oracles are ``ssd.walk_forward_scan`` and ``walk_backward_scan`` (head-major:
``ssd.heads_of`` and ``ssd.tokens_of`` convert); ``ZOO_KERNEL_INTERPRET=1``
runs the kernels in interpret mode, as for the other kernels of this
package.
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


#: lanes of a vector register: heads of P < 128 share a lane tile
LANES = 128


def _heads_a_tile(r, p):
    """Heads whose P lanes a kernel takes as one lane tile: as many as
    fill 128 lanes where P divides 128 (no more than the group's R), else
    one."""
    return max(1, min(r, LANES // p)) if LANES % p == 0 else 1


def _only(t, j, p, axis):
    """``t`` with all but the ``j``-th run of ``p`` along ``axis`` zeroed
    (``t`` itself where that run is all of it)."""
    if t.shape[axis] == p:
        return t
    at = jax.lax.broadcasted_iota(jnp.int32, t.shape, axis) // p
    return jnp.where(at == j, t, jnp.zeros_like(t))


def _ssd_walk_forward_kernel(xdt_ref, a_ref, b_ref, c_ref, y_ref, states_ref,
                             final_ref, state):
    at = pl.program_id(1)

    @pl.when(at == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    b, c = b_ref[0], c_ref[0]
    gram = pair_products(b, c)
    p = states_ref.shape[3]
    per = state.shape[1] // p
    for k in range(state.shape[0]):
        lanes = pl.ds(k * per * p, per * p)
        tile, stacked = xdt_ref[0, :, lanes], state[k]
        y = out = 0.0
        for j in range(per):
            h = k * per + j
            states_ref[0, h, 0] = stacked[j * p:(j + 1) * p]
            out_j, y_j = chunk_step(
                _only(stacked, j, p, 0), _only(tile, j, p, 1),
                a_ref[0, 0, 0, pl.ds(h, 1)], b, c, gram)
            # zero outside head j's lanes and rows: the sums are exact
            y, out = y + y_j, out + out_j
        state[k] = out
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)

    @pl.when(at == pl.num_programs(1) - 1)
    def _():
        for k in range(state.shape[0]):
            for j in range(per):
                final_ref[0, k * per + j] = state[k][j * p:(j + 1) * p]


def _ssd_walk_backward_kernel(xdt_ref, a_ref, b_ref, c_ref, states_ref,
                              dy_ref, dxdt_ref, da_ref, db_ref, dc_ref,
                              dstate):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    b, c = b_ref[0], c_ref[0]
    gram = pair_products(b, c)
    db = dc = jnp.zeros(b.shape, _F32)
    p = states_ref.shape[3]
    per = dstate.shape[1] // p
    for k in range(dstate.shape[0]):
        lanes = pl.ds(k * per * p, per * p)
        xt, dyt, dstacked = xdt_ref[0, :, lanes], dy_ref[0, :, lanes], \
            dstate[k]
        stacked = jnp.concatenate(
            [states_ref[0, k * per + j, 0] for j in range(per)], 0)
        dx = before = 0.0
        for j in range(per):
            h = k * per + j
            d_in, (dx_j, da, db_h, dc_h) = chunk_step_transposed(
                _only(dstacked, j, p, 0), _only(stacked, j, p, 0),
                _only(dyt, j, p, 1), _only(xt, j, p, 1),
                a_ref[0, 0, 0, pl.ds(h, 1)], b, c, gram)
            da_ref[0, 0, 0, pl.ds(h, 1)] = da
            db, dc = db + db_h, dc + dc_h
            dx, before = dx + dx_j.astype(_F32), before + d_in
        dstate[k] = before
        dxdt_ref[0, :, lanes] = dx.astype(dxdt_ref.dtype)
    db_ref[0] = db.astype(db_ref.dtype)
    dc_ref[0] = dc.astype(dc_ref.dtype)


class _Specs:
    """The blocks of one grid step (sequence x group ``i``, chunk ``at``)
    of each layout, the chunk taken where ``chunk_at`` says."""

    def __init__(self, a_shape, chunk_at):
        self.groups, _, self.r, self.q = a_shape[1:]
        self.chunk_at = chunk_at

    def _seq_group(self, i):
        return i // self.groups, i % self.groups

    def tokens(self, width):
        """A group's ``width`` channels of one chunk of a (B, L, groups x
        width) array."""
        def index(i, at):
            s, g = self._seq_group(i)
            return s, self.chunk_at(at), g

        return pl.BlockSpec((1, self.q, width), index,
                            memory_space=pltpu.VMEM)

    def decay(self):
        """A group's (R, Q) tile of one chunk of the (B, groups, chunks, R,
        Q) decay."""
        def index(i, at):
            s, g = self._seq_group(i)
            return s, g, self.chunk_at(at), 0, 0

        return pl.BlockSpec((1, 1, 1, self.r, self.q), index,
                            memory_space=pltpu.VMEM)

    def states(self, p, n):
        """A group's heads' incoming states of one chunk of the (B groups,
        R, chunks, P, N) states."""
        return pl.BlockSpec((1, self.r, 1, p, n),
                            lambda i, at: (i, 0, self.chunk_at(at), 0, 0),
                            memory_space=pltpu.VMEM)


def walk_forward(xdt, a, b, c):
    """``ssd.walk_forward_scan`` through the kernel, token-major: ``xdt``
    (B, L, H P), ``a`` (B, G, n, R, Q), ``b`` and ``c`` (B, L, G N) ->
    ``(Y (B, L, H P), the chunks' incoming states (B G, R, n, P, N)
    float32, the last chunk's outgoing state (B G, R, P, N))``."""
    return _ssd_walk_forward(xdt, a, b, c, interpret=_interpret())


def walk_backward(xdt, a, b, c, states, dy):
    """``ssd.walk_backward_scan`` through the kernel, in
    ``walk_forward``'s layouts: ``(dX~, da, dB, dC)``."""
    return tuple(_ssd_walk_backward(xdt, a, b, c, states, dy,
                                    interpret=_interpret()))


def _same(n):
    return n


def _stacked(r, p, n_state):
    """The VMEM states of a group's heads: (lane tiles, the tile's heads'
    states stacked, N)."""
    per = _heads_a_tile(r, p)
    return r // per, per * p, n_state


def _sizes(xdt, a, b):
    """(B x groups, chunks, R, P, N)."""
    bt, g, n, r, _ = a.shape
    return bt * g, n, r, xdt.shape[-1] // (g * r), b.shape[-1] // g


# jitted on their own, so that a model's layers trace each kernel once
# (a bare pallas_call traces its body at every call site); the
# benchmark's reader tells the two kernels in a device trace by "ssd_walk"
# in their instruction's name and by the arrays each returns
# zoolint: disable=raw-jit -- an inner jit, inlined into the caller's program (the step that compile_step compiles): it is there for JAX's trace cache, not as a compile site
@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_walk_forward(xdt, a, b, c, *, interpret=False):
    walks, n, r, p, n_state = _sizes(xdt, a, b)
    specs = _Specs(a.shape, _same)
    states = jax.ShapeDtypeStruct((walks, r, n, p, n_state), _F32)
    return pl.pallas_call(
        _ssd_walk_forward_kernel,
        grid=(walks, n),
        in_specs=[specs.tokens(r * p), specs.decay(),
                  specs.tokens(n_state), specs.tokens(n_state)],
        out_specs=[specs.tokens(r * p), specs.states(p, n_state),
                   pl.BlockSpec((1, r, p, n_state),
                                lambda i, at: (i, 0, 0, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct(xdt.shape, xdt.dtype), states,
                   jax.ShapeDtypeStruct((walks, r, p, n_state), _F32)],
        scratch_shapes=[pltpu.VMEM(_stacked(r, p, n_state), _F32)],
        compiler_params=_PARAMS, interpret=interpret)(xdt, a, b, c)


# zoolint: disable=raw-jit -- an inner jit, inlined into the caller's program (the step that compile_step compiles): it is there for JAX's trace cache, not as a compile site
@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_walk_backward(xdt, a, b, c, states, dy, *, interpret=False):
    walks, n, r, p, n_state = _sizes(xdt, a, b)

    def from_the_last(at):
        return n - 1 - at

    specs = _Specs(a.shape, from_the_last)
    heads, groups = specs.tokens(r * p), specs.tokens(n_state)
    return pl.pallas_call(
        _ssd_walk_backward_kernel,
        grid=(walks, n),
        in_specs=[heads, specs.decay(), groups, groups,
                  specs.states(p, n_state), heads],
        out_specs=[heads, specs.decay(), groups, groups],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (xdt, a, b, c)],
        scratch_shapes=[pltpu.VMEM(_stacked(r, p, n_state), _F32)],
        compiler_params=_PARAMS, interpret=interpret)(xdt, a, b, c, states,
                                                      dy)
