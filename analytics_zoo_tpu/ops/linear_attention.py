"""Linear attention by a recurrence over the sequence: the gated delta rule
with a decay a key channel (Kimi Delta Attention, arXiv:2510.26692; Gated
DeltaNet with the gate a channel), in its chunk-parallel form, forward and
backward.

A head keeps a state S (d_k x d_v), zero at a sequence's start; for token t
with query q_t, key k_t (both of unit length), value v_t, log-decay g_t <= 0
a key channel and step size beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

``chunked_kda`` computes that in chunks of C tokens.  With G_i the summed
log-decay from the chunk's start through token i, k+_i = k_i exp(G_i),
q+_i = q_i exp(G_i), and from the chunk's incoming state S_0:

    U = (I + tril(Diag(beta) [k+_i . k_j exp(-G_j)], -1))^-1
        Diag(beta) (V - K+ S_0)
    O = scale (Q+ S_0 + tril([q+_i . k_j exp(-G_j)]) U)
    S_C = Diag(exp(G_C)) S_0 + [k_j exp(G_C - G_j)]^T U

in two parts, each written once for one chunk of one head.
``chunk_local`` is everything a chunk can make without its incoming state:
the pair products [x_i . k_j exp(G_i - G_j)], the triangular inverse T,
``Wk = T beta K+`` and ``Uv = T beta V`` (so U = Uv - Wk S_0).
``walk_step`` takes a chunk from its incoming state to its output and its
outgoing state: three products with the state and one with the pair
matrix; ``walk_step_transposed`` is its transpose, written out.  On a TPU
both run inside the two Pallas kernels of ``ops/pallas/kda_scan.py``, which
walk a sequence's chunks in order with the state in VMEM and make a chunk's
local arrays where they use them; elsewhere the same functions run under
``jax.vmap`` and ``lax.scan``, which is also the kernels' oracle.

``exp(-G_j)`` overflows where a channel decays hard inside a chunk, so the
pair products are taken a sub-block of ``SUB`` rows at a time against a
local reference point, the sub-block's first row: the rows carry
exp(G_i - G_first), at most 1; the columns carry exp(G_first - G_j), which
is at most 1 for every earlier sub-block whatever the decay, and inside the
rows' own sub-block grows with the decay of at most ``SUB`` tokens.  That
factor is held at exp(``CLAMP``): the form is exact while no channel's
log-decay summed over one sub-block passes -``CLAMP`` (-80 over 16 tokens:
a channel that forgets by e^-5 a token), and a chunk's sum may be as many
times that as it has sub-blocks.  ``stats["sub_block_log_decay_min"]``
reports the exponent that is held, whose bound is -``CLAMP``, and
``stats["chunk_log_decay_min"]`` what a step's chunks summed to.
Cumulative gates, the inverse and the state are float32; the products'
operands are in the values' dtype (bfloat16 on the chip) with float32
sums, the inverse's in two bfloat16 halves (three passes).

The backward rule (``jax.custom_vjp``) keeps the inputs and the chunks'
incoming states, so the forward walk is not run again for it: a chunk's
local arrays are made again where the reverse walk needs them, their
cotangents come from ``walk_step_transposed`` with the state's cotangent
as the carry, and go back through ``jax.vjp`` of ``chunk_local`` (the
inverse's by its own rule, dA = -T^T dT T^T).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# Trace-time routing counters, as ``flash_attention.invocation_counts``.
invocation_counts = {"pallas": 0, "fallback": 0}

#: Trace-time record of each chunked call traced, newest last: ``shape``
#: (B, H, L, d_k, d_v), ``chunk`` C, ``chunks`` a sequence, ``sub_blocks`` a
#: chunk, ``kernel`` (the walk through the Pallas kernels, or the scan).
chunk_schedules: collections.deque = collections.deque(maxlen=64)

#: tokens a chunk (timed at (2, 32, 4096, 128) on a v5e: forward and
#: backward of one call 16.2 ms in chunks of 128, 19.2-20.1 in 64, and the
#: kept states halve with every doubling; PERF.md, PR 35), tokens a
#: sub-block, and the largest exponent of a column factor inside one
#: sub-block
CHUNK, SUB, CLAMP = 128, 16, 80.0

#: the ``checkpoint_name`` of the chunks' incoming states, which the
#: backward walk reads; the ``"attn"`` recomputation policy keeps them with
#: the output (``parallel/plan.py``), so a checkpointed layer's backward
#: pass runs no forward walk
STATE_NAME = "kda_state"

_F32 = jnp.float32


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _row(x, at):
    """Row ``at`` of (rows, d) ``x`` as (1, d), by a masked sum: a slice's
    transpose is a pad, which the kernels' compiler does not take."""
    return jnp.sum(jnp.where(_iota(x.shape, 0) == at, x, 0.0), axis=0,
                   keepdims=True)


def _exact_matmul(a, b, narrow):
    """``a @ b`` of float32 square matrices to about 16 bits: each operand
    as the sum of two ``narrow`` halves, three passes of the matrix unit
    (the product of the two low halves is under the result's rounding).
    Where ``narrow`` is float32 itself, one float32 product."""
    if narrow == _F32:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    a_hi, b_hi = a.astype(narrow), b.astype(narrow)
    a_lo = (a - a_hi.astype(_F32)).astype(narrow)
    b_lo = (b - b_hi.astype(_F32)).astype(narrow)
    return _dot(a_hi, b_hi, (1, 0)) + _dot(a_hi, b_lo, (1, 0)) \
        + _dot(a_lo, b_hi, (1, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _unit_lower_inverse(narrow, a):
    """(I + a)^-1 for strictly lower triangular ``a`` (C, C), float32, by
    block forward substitution on whole matrices: with T_s the inverse of
    (I + a)'s diagonal blocks of s rows (T_2 = I - a inside blocks of two),
    and X the part of ``a`` that lies inside the blocks of 2 s and outside
    those of s,  T_2s = T_s - T_s X T_s  (for a block [[L1, 0], [X, L2]]
    the inverse is [[L1^-1, 0], [-L2^-1 X L1^-1, L2^-1]]).  log2(C) - 1
    levels of two products on the MXU where a substitution by rows takes C
    steps, and as stable as that one: the product of the powers of -a,
    which needs as many products, loses every digit where a chunk's keys
    resemble each other (the powers' entries grow like binomials before
    they cancel: PERF.md, PR 35)."""
    c = a.shape[-1]
    row, column = _iota(a.shape, 0), _iota(a.shape, 1)

    def blocks(size):       # whether an entry lies inside a diagonal block
        return (row // size) == (column // size)

    t = jnp.where(row == column, 1.0, 0.0) - jnp.where(blocks(2), a, 0.0)
    size = 2
    while size < c:
        x = jnp.where(blocks(2 * size) & ~blocks(size), a, 0.0)
        t = t - _exact_matmul(_exact_matmul(t, x, narrow), t, narrow)
        size *= 2
    return t


def _unit_lower_inverse_bwd(narrow, t, dt):
    # T = (I + a)^-1: dT = -T da T, so da = -T^T dT T^T
    return (-_exact_matmul(_exact_matmul(t.T, dt, narrow), t.T, narrow),)


_unit_lower_inverse.defvjp(
    lambda narrow, a: (lambda t: (t, t))(_unit_lower_inverse(narrow, a)),
    _unit_lower_inverse_bwd)


def chunk_local(sub, scale, q, k, kb, vb, gsum):
    """What ONE chunk of ONE head makes without its incoming state.  Each
    argument is the chunk's (C, d) array as a tuple of its sub-blocks of
    ``sub`` rows: ``q``, ``k``, ``kb`` = beta k, ``vb`` = beta v, and
    ``gsum`` the log-decay summed from the chunk's start, float32.
    Returns ``(Wk, Uv, Qp, Kd, Aqk, decay)`` = (T beta K+, T beta V,
    scale Q+, [k_j exp(G_C - G_j)], scale tril([q_i . k_j exp(G_i - G_j)]),
    exp(G_C) as (1, d_k) float32), whole (C, .) arrays.  Plain two-
    dimensional arithmetic that a Pallas kernel's compiler takes, its
    transpose too: the kernels call it, and ``jax.vjp`` of it."""
    cd = vb[0].dtype
    c = len(q) * sub
    k_all = jnp.concatenate(k).astype(_F32)
    g_all = jnp.concatenate(gsum)
    q_pairs, k_pairs = [], []
    for i in range(len(q)):
        first = _row(gsum[i], 0)
        up = jnp.exp(gsum[i] - first)
        # columns against the same row: at most 1 in earlier sub-blocks,
        # the one factor above 1 inside this one, masked in later ones
        columns = (k_all * jnp.exp(jnp.minimum(first - g_all, CLAMP))
                   ).astype(cd)
        q_pairs.append(_dot((q[i].astype(_F32) * up).astype(cd), columns,
                            (1, 1)))
        k_pairs.append(_dot((kb[i].astype(_F32) * up).astype(cd), columns,
                            (1, 1)))
    q_pairs, k_pairs = jnp.concatenate(q_pairs), jnp.concatenate(k_pairs)
    row, column = _iota((c, c), 0), _iota((c, c), 1)
    t = _unit_lower_inverse(
        cd, jnp.where(row > column, k_pairs, 0.0)).astype(cd)
    grow = jnp.exp(g_all)
    whole = _row(g_all, c - 1)
    return (_dot(t, (jnp.concatenate(kb).astype(_F32) * grow).astype(cd),
                 (1, 0)).astype(cd),
            _dot(t, jnp.concatenate(vb), (1, 0)).astype(cd),
            (jnp.concatenate(q).astype(_F32) * grow * scale).astype(cd),
            (k_all * jnp.exp(whole - g_all)).astype(cd),
            (jnp.where(row >= column, q_pairs, 0.0) * scale).astype(cd),
            jnp.exp(whole))


def walk_step(state, wk, uv, qp, kd, aqk, decay):
    """One chunk of one head from its incoming ``state`` (d_v, d_k)
    float32 (transposed, values x keys, so that the decay a key channel
    runs along its minor dimension): ``(outgoing state, O (C, d_v))``."""
    cd = uv.dtype
    sb = state.astype(cd)
    u = (uv.astype(_F32) - _dot(wk, sb, (1, 1))).astype(cd)
    o = _dot(qp, sb, (1, 1)) + _dot(aqk, u, (1, 0))
    return state * decay + _dot(u, kd, (0, 0)), o.astype(cd)


def walk_step_transposed(dstate, state, do, wk, uv, qp, kd, aqk, decay):
    """The transpose of ``walk_step``: from the outgoing state's cotangent
    ``dstate``, the incoming ``state`` and the output's cotangent ``do``
    (U is made again) to ``(the incoming state's cotangent, the cotangents
    of (wk, uv, qp, kd, aqk, decay))``."""
    cd = uv.dtype
    sb, db = state.astype(cd), dstate.astype(cd)
    u = (uv.astype(_F32) - _dot(wk, sb, (1, 1))).astype(cd)
    du = (_dot(aqk, do, (0, 0)) + _dot(kd, db, (1, 1))).astype(cd)
    grads = (-_dot(du, sb, (1, 0)), du, _dot(do, sb, (1, 0)),
             _dot(u, db, (1, 0)), _dot(do, u, (1, 1)),
             jnp.sum(dstate * state, axis=0, keepdims=True))
    before = _dot(do, qp, (0, 0)) + dstate * decay - _dot(du, wk, (0, 0))
    return before, tuple(g.astype(like.dtype) for g, like in
                         zip(grads, (wk, uv, qp, kd, aqk, decay)))


# -- off the chip, and the kernels' oracle: vmap and scan -------------------

def _sub_blocks(sub, a):
    return tuple(a[:, :, i:i + sub] for i in range(0, a.shape[2], sub))


def _local(sub, scale, *chunks):
    """``chunk_local`` of every chunk of (X, N, C, d) arrays."""
    return jax.vmap(jax.vmap(functools.partial(chunk_local, sub, scale)))(
        *(_sub_blocks(sub, a) for a in chunks))


def _chunk_major(arrays):
    return tuple(jnp.moveaxis(a, 1, 0) for a in arrays)


def walk_forward_scan(sub, scale, q, k, kb, vb, gsum):
    """Every sequence's chunks in order from a zero state: ``(O (X, N, C,
    d_v), the chunks' incoming states (X, N, d_v, d_k) float32, the last
    chunk's outgoing state)``."""
    local = _local(sub, scale, q, k, kb, vb, gsum)

    def step(state, chunk):
        out, o = jax.vmap(walk_step)(state, *chunk)
        return out, (o, state)

    final, (o, states) = jax.lax.scan(
        step, jnp.zeros((q.shape[0], vb.shape[-1], q.shape[-1]), _F32),
        _chunk_major(local))
    return jnp.moveaxis(o, 0, 1), jnp.moveaxis(states, 0, 1), final


def walk_backward_scan(sub, scale, q, k, kb, vb, gsum, states, do):
    """The transpose of ``walk_forward_scan``'s output in its five inputs:
    the chunks in reverse with the state's cotangent as the carry."""
    local, pull = jax.vjp(functools.partial(_local, sub, scale),
                          q, k, kb, vb, gsum)

    def step(dstate, chunk):
        state, do, *local = chunk
        return jax.vmap(walk_step_transposed)(dstate, state, do, *local)

    _, grads = jax.lax.scan(
        step, jnp.zeros(states.shape[:1] + states.shape[2:], _F32),
        _chunk_major((states, do) + local), reverse=True)
    return pull(tuple(jnp.moveaxis(a, 0, 1) for a in grads))


def _kernels(count: bool = True) -> bool:
    """Whether a walk goes through its Pallas kernel (on a TPU, or where
    the package's switches say so); counted at trace time."""
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import _pallas_available

    kernel = _pallas_available()
    if count:
        invocation_counts["pallas" if kernel else "fallback"] += 1
    return kernel


def _walk_forward(sub, scale, *chunks):
    if _kernels():
        from analytics_zoo_tpu.ops.pallas import kda_scan

        return kda_scan.walk_forward(sub, scale, *chunks)
    return walk_forward_scan(sub, scale, *chunks)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunked(sub, scale, q, k, kb, vb, gsum):
    """(O, the last chunk's outgoing state) from (X, N, C, d) chunks."""
    o, _, final = _walk_forward(sub, scale, q, k, kb, vb, gsum)
    return o, final


def _chunked_fwd(sub, scale, *chunks):
    o, states, final = _walk_forward(sub, scale, *chunks)
    # named HERE, on what leaves the rule: the output, which the layer goes
    # on from, and the states, which ``_chunked_bwd`` reads (PR 29: a name
    # keeps only the array the backward pass reads)
    o = checkpoint_name(o, "attn_context")
    states = checkpoint_name(states, STATE_NAME)
    return (o, final), chunks + (states,)


def _chunked_bwd(sub, scale, kept, cotangents):
    *chunks, states = kept
    do = cotangents[0]      # the outgoing state is read by a gauge alone
    if _kernels():
        from analytics_zoo_tpu.ops.pallas import kda_scan

        return kda_scan.walk_backward(sub, scale, *chunks, states, do)
    return walk_backward_scan(sub, scale, *chunks, states, do)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def chunk_of(length: int, chunk: int | None = None) -> tuple[int, int]:
    """(tokens a chunk, tokens a sub-block) for sequences of ``length``:
    ``chunk`` (``CHUNK`` by default) in whole sub-blocks, and no more than
    the sequence in whole sub-blocks."""
    sub = min(SUB, chunk or CHUNK)
    return min(-(-(chunk or CHUNK) // sub), -(-length // sub)) * sub, sub


def chunked_kda(q, k, v, g, beta, *, scale, chunk=None):
    """The gated delta rule with a decay a key channel over whole
    sequences from a zero state: ``q``, ``k`` (B, H, L, d_k) of unit
    length, ``v`` (B, H, L, d_v), ``g`` (B, H, L, d_k) log-decays <= 0,
    ``beta`` (B, H, L) -> ``(o (B, H, L, d_v) in v's dtype, stats)`` with
    ``stats`` float32 scalars that take no gradient:
    ``chunk_log_decay_min`` (the most negative log-decay that any channel
    summed to over any chunk), ``sub_block_log_decay_min`` (the same from
    the first row of any sub-block to its last: under -``CLAMP`` the form
    is no longer exact) and ``state_rms`` (the root mean square of the
    states after the last token).  A length that is no multiple of the
    chunk is padded with tokens that decay nothing and write nothing."""
    b, h, l, dk = q.shape
    dv = v.shape[-1]
    c, sub = chunk_of(l, chunk)
    n = -(-l // c)

    def chunks(a):
        a = jnp.pad(a, ((0, 0), (0, 0), (0, n * c - l))
                    + ((0, 0),) * (a.ndim - 3))
        return a.reshape((b * h, n, c) + a.shape[3:])

    cd = v.dtype
    bf = beta.astype(_F32)[..., None]
    gsum = jnp.cumsum(chunks(g.astype(_F32)), axis=2)
    # beta folded into the rows it multiplies, here, so that its gradient
    # and the sum's are XLA's; the rule above takes chunks
    o, final = _chunked(
        sub, float(scale), chunks(q.astype(cd)), chunks(k.astype(cd)),
        chunks((bf * k.astype(_F32)).astype(cd)),
        chunks((bf * v.astype(_F32)).astype(cd)), gsum)
    chunk_schedules.append({
        "shape": (b, h, l, dk, dv), "chunk": c, "chunks": n,
        "sub_blocks": c // sub, "kernel": _kernels(count=False)})
    edges = gsum.reshape(b * h, n, c // sub, sub, dk)
    stats = jax.lax.stop_gradient({
        "chunk_log_decay_min": jnp.min(gsum[:, :, -1]),
        "sub_block_log_decay_min": jnp.min(edges[:, :, :, -1]
                                           - edges[:, :, :, 0]),
        "state_rms": jnp.sqrt(jnp.mean(jnp.square(final)))})
    return o.reshape(b, h, n * c, dv)[:, :, :l], stats
