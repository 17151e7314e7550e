# zoolint: disable-file=raw-pallas-call -- ops/pallas/ is the one home
# for raw pl.pallas_call; the kernels here have their fallback and oracle in
# ops/linear_attention.py and are inlined into the caller's step program.
"""The chunked gated delta rule (``ops/linear_attention.py``: Kimi Delta
Attention's recurrence) as two Pallas kernels, forward and transposed.

Both run a grid of (sequences x heads / ``HEADS``, chunks), the chunks of a
sequence in order (the backward one from the last), with the state, or its
cotangent, of ``HEADS`` heads in VMEM scratch from one grid step to the
next: the part that is sequential in the sequence.  A grid step makes its
chunk's local arrays where it uses them, so none of them (the pair
products, the triangular inverse, Wk, Uv, ...) is ever written to HBM: the
arithmetic is ``linear_attention.chunk_local`` and ``walk_step``, called
here on the tiles in VMEM, and in the backward kernel ``jax.vjp`` of the
first around ``walk_step_transposed``.  A step's heads are independent,
which gives the scheduler products to overlap.  The state is kept
transposed, (d_v, d_k) float32, so that the decay a key channel multiplies
along lanes; with d_k = d_v = 128 every product with it fills the MXU's
width.

``walk_forward`` also hands back every chunk's incoming state, which is
what the backward kernel reads in place of a second forward walk.  The
oracles are ``linear_attention.walk_forward_scan`` and
``walk_backward_scan``; ``ZOO_KERNEL_INTERPRET=1`` runs the kernels in
interpret mode, as for the other kernels of this package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops.linear_attention import (
    chunk_local,
    walk_step,
    walk_step_transposed,
)

#: heads a grid step walks (a grid step costs about 0.35 us whatever it
#: does: PERF.md, PR 26; at chunks of 128 one head a step took 16.5 ms a
#: call forward and backward, two 16.2: PERF.md, PR 35)
HEADS = 2

_F32 = jnp.float32


def _heads(x: int) -> int:
    return next(h for h in range(min(HEADS, x), 0, -1) if x % h == 0)


def _sub_blocks(ref, h, sub):
    """The chunk of head ``h`` in ``ref`` as a tuple of its sub-blocks."""
    return tuple(ref[h, 0, pl.ds(i, sub), :]
                 for i in range(0, ref.shape[2], sub))


def _kda_walk_forward_kernel(sub, scale, q_ref, k_ref, kb_ref, vb_ref, g_ref,
                             o_ref, states_ref, final_ref, state):
    at = pl.program_id(1)

    @pl.when(at == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(state.shape[0]):
        local = chunk_local(sub, scale, *(
            _sub_blocks(ref, h, sub)
            for ref in (q_ref, k_ref, kb_ref, vb_ref, g_ref)))
        states_ref[h, 0] = state[h]
        state[h], o = walk_step(state[h], *local)
        o_ref[h, 0] = o.astype(o_ref.dtype)

    @pl.when(at == pl.num_programs(1) - 1)
    def _():
        final_ref[...] = state[...]


def _kda_walk_backward_kernel(sub, scale, q_ref, k_ref, kb_ref, vb_ref, g_ref,
                              states_ref, do_ref, dq_ref, dk_ref, dkb_ref,
                              dvb_ref, dg_ref, dstate):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    for h in range(dstate.shape[0]):
        local, pull = jax.vjp(
            functools.partial(chunk_local, sub, scale),
            *(_sub_blocks(ref, h, sub)
              for ref in (q_ref, k_ref, kb_ref, vb_ref, g_ref)))
        dstate[h], grads = walk_step_transposed(
            dstate[h], states_ref[h, 0], do_ref[h, 0], *local)
        for ref, blocks in zip((dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref),
                               pull(grads)):
            for i, block in enumerate(blocks):
                ref[h, 0, pl.ds(i * sub, sub), :] = block.astype(ref.dtype)


def _specs(arrays, heads, chunk_at):
    """A block of ``heads`` sequence-heads and one chunk of each of
    ``arrays`` (X, N, rows, columns), the chunk at ``chunk_at(n)``."""
    return [pl.BlockSpec((heads, 1) + a.shape[2:],
                         lambda x, n: (x, chunk_at(n), 0, 0),
                         memory_space=pltpu.VMEM) for a in arrays]


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _interpret() -> bool:
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import _interpret_forced

    return _interpret_forced()


def walk_forward(sub, scale, q, k, kb, vb, gsum):
    """``linear_attention.walk_forward_scan`` through the kernel."""
    return _kda_walk_forward(q, k, kb, vb, gsum, sub=sub, scale=scale,
                             interpret=_interpret())


def walk_backward(sub, scale, q, k, kb, vb, gsum, states, do):
    """``linear_attention.walk_backward_scan`` through the kernel."""
    return tuple(_kda_walk_backward(q, k, kb, vb, gsum, states, do, sub=sub,
                                    scale=scale, interpret=_interpret()))


# jitted on their own, so that a model's layers trace each kernel once
# (a bare pallas_call traces its body at every call site: PR 26); the
# benchmark's reader tells the two kernels in a device trace by "kda_walk"
# in their instruction's name and by the arrays each returns
# zoolint: disable=raw-jit -- an inner jit, inlined into the caller's program (the step that compile_step compiles): it is there for JAX's trace cache, not as a compile site
@functools.partial(jax.jit, static_argnames=("sub", "scale", "interpret"))
def _kda_walk_forward(q, k, kb, vb, gsum, *, sub, scale, interpret=False):
    x, n, _, dk = q.shape
    dv = vb.shape[-1]
    heads = _heads(x)
    inputs = (q, k, kb, vb, gsum)
    states = jax.ShapeDtypeStruct((x, n, dv, dk), _F32)
    return pl.pallas_call(
        functools.partial(_kda_walk_forward_kernel, sub, scale),
        grid=(x // heads, n),
        in_specs=_specs(inputs, heads, lambda n: n),
        out_specs=_specs((vb, states), heads, lambda n: n) + [
            pl.BlockSpec((heads, dv, dk), lambda x, n: (x, 0, 0),
                         memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct(vb.shape, vb.dtype), states,
                   jax.ShapeDtypeStruct((x, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_PARAMS, interpret=interpret)(*inputs)


# zoolint: disable=raw-jit -- an inner jit, inlined into the caller's program (the step that compile_step compiles): it is there for JAX's trace cache, not as a compile site
@functools.partial(jax.jit, static_argnames=("sub", "scale", "interpret"))
def _kda_walk_backward(q, k, kb, vb, gsum, states, do, *, sub, scale,
                       interpret=False):
    x, n, _, dk = q.shape
    heads = _heads(x)
    inputs = (q, k, kb, vb, gsum, states, do)
    outputs = inputs[:5]

    def from_the_last(at):
        return n - 1 - at

    return pl.pallas_call(
        functools.partial(_kda_walk_backward_kernel, sub, scale),
        grid=(x // heads, n),
        in_specs=_specs(inputs, heads, from_the_last),
        out_specs=_specs(outputs, heads, from_the_last),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in outputs],
        scratch_shapes=[pltpu.VMEM((heads, states.shape[2], dk), _F32)],
        compiler_params=_PARAMS, interpret=interpret)(*inputs)
