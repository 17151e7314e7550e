"""Mamba-2's state-space rule (SSD, arXiv:2405.21060) in its chunk-parallel
form, forward and backward.

A head keeps a state S (P x N), zero at a sequence's start; for token t with
the head's input x_t (P), step dt_t > 0, the head's A < 0, and its group's
B_t and C_t (N):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

(the skip D x_t is the caller's).  ``chunked_ssd`` computes that in chunks
of Q tokens.  With a_i = sum_{j<=i} dt_j A from the chunk's start (<= 0 and
falling), X~ = dt o X and the chunk's incoming state S_0:

    Y   = (L o C B^T) X~ + diag(exp(a)) C S_0^T,  L_ij = exp(a_i - a_j), j<=i
    S_Q = exp(a_Q) S_0 + (w o X~)^T B,            w_j = exp(a_Q - a_j)

The decay is one number a head, so every factor is exp of a difference that
is <= 0: nothing to clamp, unlike ``linear_attention``'s decay a channel.
``chunk_step`` is that for ONE chunk of ONE head and ``chunk_step_transposed``
its transpose, written out, each given the group's C B^T
(``pair_products``, made once for the group's heads): plain
two-dimensional arithmetic that the two Pallas kernels of
``ops/pallas/ssd_scan.py`` run on the tiles in VMEM, a sequence's chunks in
order with the state there, and that ``jax.vmap`` and ``lax.scan`` run
elsewhere, which is also the kernels' oracle.  The
cumulative decay and the state are float32; the products' operands are in
x's dtype (bfloat16 on the chip) with float32 sums.

B and C belong to a group of heads: a group's B and C are read once for
all its heads, never copied to every head.  ``chunked_ssd`` and the kernels
take every array in the projections' token-major layout, (B, L, heads x P)
and (B, L, groups x N), so that the mixer hands its channels over as they
come out of the convolution and takes y back where its gate reads it; the
scans, the kernels' oracle, walk the heads as (groups, heads a group, ...)
and B and C as (groups, ...), and ``heads_of`` / ``tokens_of`` convert
around them.

The backward rule (``jax.custom_vjp``) keeps the inputs and the chunks'
incoming states, so the forward walk is not run again for it.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# Trace-time routing counters, as ``flash_attention.invocation_counts``.
invocation_counts = {"pallas": 0, "fallback": 0}

#: Trace-time record of each chunked call traced, newest last: ``rule``
#: ("ssd"), ``shape`` (B, heads, L, P, N: the rule's sizes), ``groups``,
#: ``chunk`` Q, ``chunks`` a sequence, ``kernel`` (the walk through the
#: Pallas kernels, or the scan) and ``layout`` ("tokens": the walk read and
#: wrote the projections' token-major arrays, no heads split out).
chunk_schedules: collections.deque = collections.deque(maxlen=64)

#: tokens a chunk (the published ``chunk_size``)
CHUNK = 128

#: the ``checkpoint_name`` of the chunks' incoming states, which the
#: backward walk reads; the ``"attn"`` recomputation policy keeps them with
#: the output (``parallel/plan.py``), so a checkpointed layer's backward
#: pass runs no forward walk
STATE_NAME = "ssd_state"

_F32 = jnp.float32


def _runs(k, width):
    """(k, k x width) float32: row i is 1 over the i-th run of ``width``."""
    return (jnp.arange(k * width) // width
            == jnp.arange(k)[:, None]).astype(_F32)


def spread(t, width):
    """(..., k) -> (..., k x width) float32: each of ``t``'s k values over
    ``width`` channels in a row, as a product with a 0/1 matrix.  On the TPU
    a reshape that splits or merges the lane dimension of a (B, L, k x
    width) array is a copy of it, and XLA writes such a broadcast out in
    float32 before it copies it; a product is one fused pass of the matrix
    unit.  At ``HIGH`` precision t's bfloat16 high and low parts each meet
    an exact 0 or 1: 16 bits of t's mantissa reach the result (all 24 off
    the TPU)."""
    return jnp.matmul(t.astype(_F32), _runs(t.shape[-1], width),
                      precision=jax.lax.Precision.HIGH)


def run_sums(t, width):
    """(..., k x width) -> (..., k) float32: the sum of each run of
    ``width`` channels, ``spread``'s transpose, by the same product."""
    k = t.shape[-1] // width
    return jnp.matmul(t.astype(_F32), _runs(k, width).T,
                      precision=jax.lax.Precision.HIGH)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _decays(a):
    """From ``a`` (1, Q), float32: the (Q, Q) factors L (0 above the
    diagonal), a as a column (Q, 1), exp(a) (Q, 1), w = exp(a_Q - a) (Q, 1)
    and exp(a_Q) (1, 1).  A column is taken from the row by a masked sum:
    the kernels' compiler takes no transpose of a vector."""
    q = a.shape[-1]
    row, column = _iota((q, q), 0), _iota((q, q), 1)
    across = jnp.broadcast_to(a, (q, q))                # [i, j] = a_j
    a_col = jnp.sum(jnp.where(row == column, across, 0.0), axis=1,
                    keepdims=True)
    pairs = jnp.exp(jnp.where(row >= column, a_col - across, -jnp.inf))
    last = jnp.sum(jnp.where(_iota((1, q), 1) == q - 1, a, 0.0), axis=1,
                   keepdims=True)
    return pairs, a_col, jnp.exp(a_col), jnp.exp(last - a_col), \
        jnp.exp(last)


def pair_products(b, c):
    """C B^T (Q, Q) float32 of a group's chunk: what its heads share."""
    return _dot(c, b, (1, 1))


def chunk_step(state, xdt, a, b, c, gram):
    """One chunk of one head from its incoming ``state`` (P, N) float32:
    ``xdt`` = dt o x (Q, P), ``a`` (1, Q) float32, ``b`` and ``c`` (Q, N)
    the head's group's and ``gram`` their ``pair_products``.  Returns
    ``(outgoing state, Y (Q, P) float32)``."""
    cd = xdt.dtype
    pairs, _, grow, w, whole = _decays(a)
    scores = (gram * pairs).astype(cd)
    y = _dot(scores, xdt, (1, 0)) \
        + grow * _dot(c, state.astype(cd), (1, 1))
    out = state * whole \
        + _dot((xdt.astype(_F32) * w).astype(cd), b, (0, 0))
    return out, y


def _column_to_row(x):
    """(Q, 1) -> (1, Q) by a masked sum."""
    q = x.shape[0]
    return jnp.sum(jnp.where(_iota((q, q), 0) == _iota((q, q), 1),
                             jnp.broadcast_to(x, (q, q)), 0.0), axis=0,
                   keepdims=True)


def chunk_step_transposed(dstate, state, dy, xdt, a, b, c, gram):
    """The transpose of ``chunk_step``: from the outgoing state's cotangent
    ``dstate`` (P, N) float32, the incoming ``state`` and the output's
    cotangent ``dy`` (Q, P) to ``(the incoming state's cotangent, (dxdt,
    da (1, Q) float32, db, dc (Q, N) float32))``; dxdt in xdt's dtype."""
    cd = xdt.dtype
    q = xdt.shape[0]
    pairs, _, grow, w, whole = _decays(a)
    sb, db_out, dyb = state.astype(cd), dstate.astype(cd), dy.astype(cd)
    scores = (gram * pairs).astype(cd)
    # Y = scores X~ + grow o (C S^T)
    d_scores = _dot(dyb, xdt, (1, 1))                   # dY X~^T
    seen = _dot(c, sb, (1, 1))                          # C S^T
    d_grow = jnp.sum(dy.astype(_F32) * seen, axis=1, keepdims=True)
    d_seen = (grow * dy.astype(_F32)).astype(cd)
    d_gram = (d_scores * pairs).astype(cd)
    dc = _dot(d_gram, b, (1, 0)) + _dot(d_seen, sb, (1, 0))
    db = _dot(d_gram, c, (0, 0))
    dxdt = _dot(scores, dyb, (0, 0))
    before = _dot(d_seen, c, (0, 0)) + dstate * whole
    # S_Q = whole S + (w o X~)^T B
    wx = (xdt.astype(_F32) * w).astype(cd)
    d_wx = _dot(b, db_out, (1, 1))                      # B dS_Q^T
    db = db + _dot(wx, db_out, (1, 0))
    dxdt = dxdt + w * d_wx
    d_w = jnp.sum(xdt.astype(_F32) * d_wx, axis=1, keepdims=True)
    d_whole = jnp.sum(jnp.sum(dstate * state, axis=1, keepdims=True),
                      axis=0, keepdims=True)
    # into a: pairs_ij = exp(a_i - a_j), grow_i = exp(a_i),
    # w_j = exp(a_Q - a_j), whole = exp(a_Q)
    k = d_scores * gram * pairs
    at_i = jnp.sum(k, axis=1, keepdims=True) + d_grow * grow - d_w * w
    at_q = jnp.sum(d_w * w, axis=0, keepdims=True) + d_whole * whole
    da = _column_to_row(at_i) - jnp.sum(k, axis=0, keepdims=True) \
        + jnp.where(_iota((1, q), 1) == q - 1, at_q, 0.0)
    return before, (dxdt.astype(cd), da, db, dc)


# -- off the chip, and the kernels' oracle: vmap and scan -------------------

def _each_head(fn, head_args):
    """``fn`` over (groups, heads a group) of the arguments at
    ``head_args`` (True: a head's own, False: its group's)."""
    inner = jax.vmap(fn, in_axes=tuple(0 if h else None for h in head_args))
    return jax.vmap(inner)


def _grams(b, c):
    """``pair_products`` of every group's every chunk, chunk-major."""
    return jnp.moveaxis(jax.vmap(jax.vmap(pair_products))(b, c), 1, 0)


def walk_forward_scan(xdt, a, b, c):
    """Every sequence's chunks in order from a zero state, for ``xdt``
    (G, R, n, Q, P), ``a`` (G, R, n, 1, Q), ``b`` and ``c`` (G, n, Q, N):
    ``(Y (G, R, n, Q, P) in xdt's dtype, the chunks' incoming states (G,
    R, n, P, N) float32, the last chunk's outgoing state)``."""
    g, r, _, _, p = xdt.shape
    step = _each_head(chunk_step, (True, True, True, False, False, False))

    def body(state, chunk):
        out, y = step(state, *chunk)
        return out, (y.astype(xdt.dtype), state)

    final, (y, states) = jax.lax.scan(
        body, jnp.zeros((g, r, p, b.shape[-1]), _F32),
        (jnp.moveaxis(xdt, 2, 0), jnp.moveaxis(a, 2, 0),
         jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0), _grams(b, c)))
    return jnp.moveaxis(y, 0, 2), jnp.moveaxis(states, 0, 2), final


def walk_backward_scan(xdt, a, b, c, states, dy):
    """The transpose of ``walk_forward_scan``'s output in its four inputs:
    the chunks in reverse with the state's cotangent as the carry; a
    group's dB and dC summed over its heads in float32."""
    step = _each_head(chunk_step_transposed,
                      (True, True, True, True, True, False, False, False))

    def body(dstate, chunk):
        before, (dx, da, db, dc) = step(dstate, *chunk)
        return before, (dx, da, jnp.sum(db, axis=1), jnp.sum(dc, axis=1))

    _, (dx, da, db, dc) = jax.lax.scan(
        body, jnp.zeros(states.shape[:2] + states.shape[3:], _F32),
        (jnp.moveaxis(states, 2, 0), jnp.moveaxis(dy, 2, 0),
         jnp.moveaxis(xdt, 2, 0), jnp.moveaxis(a, 2, 0),
         jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0), _grams(b, c)),
        reverse=True)
    return (jnp.moveaxis(dx, 0, 2), jnp.moveaxis(da, 0, 2),
            jnp.moveaxis(db, 0, 1).astype(b.dtype),
            jnp.moveaxis(dc, 0, 1).astype(c.dtype))


# -- the walks' token-major layout, and the scans' head-major one ----------

def heads_of(t, a, heads=True):
    """A token-major array of the walks, (B, L, G x R x W) (``heads``) or
    (B, L, G x W), in the scans' layout: (B G, R, n, Q, W) or (B G, n, Q,
    W); the walks' decay ``a`` (B, G, n, R, Q) gives the sizes."""
    bt, g, n, r, q = a.shape
    r = r if heads else 1
    t = t.reshape(bt, n, q, g, r, -1).transpose(0, 3, 4, 1, 2, 5)
    t = t.reshape(bt * g, r, n, q, t.shape[-1])
    return t if heads else t[:, 0]


def tokens_of(t, sequences):
    """The inverse of ``heads_of``, for ``sequences`` sequences."""
    t = t if t.ndim == 5 else t[:, None]
    bg, r, n, q, w = t.shape
    t = t.reshape(sequences, bg // sequences, r, n, q, w)
    return t.transpose(0, 3, 4, 1, 2, 5).reshape(sequences, n * q, -1)


def _decay_heads(a):
    """The walks' decay (B, G, n, R, Q) as the scans' (B G, R, n, 1, Q)."""
    bt, g, n, r, q = a.shape
    return a.transpose(0, 1, 3, 2, 4).reshape(bt * g, r, n, 1, q)


def walk_forward_by_scan(xdt, a, b, c):
    """``walk_forward_scan`` in the kernels' layout (``ssd_scan.walk_forward``
    takes and gives the same): ``xdt`` (B, L, H P), ``a`` (B, G, n, R, Q),
    ``b`` and ``c`` (B, L, G N) -> (Y (B, L, H P), the states (B G, R, n,
    P, N), the last chunk's outgoing state (B G, R, P, N))."""
    y, states, final = walk_forward_scan(
        heads_of(xdt, a), _decay_heads(a), heads_of(b, a, False),
        heads_of(c, a, False))
    return tokens_of(y, xdt.shape[0]), states, final


def walk_backward_by_scan(xdt, a, b, c, states, dy):
    """``walk_backward_scan`` in the kernels' layout: (dX~, da, dB, dC) as
    ``xdt``, ``a``, ``b`` and ``c``."""
    dx, da, db, dc = walk_backward_scan(
        heads_of(xdt, a), _decay_heads(a), heads_of(b, a, False),
        heads_of(c, a, False), states, heads_of(dy, a))
    bt, g, n, r, q = a.shape
    return (tokens_of(dx, bt),
            da.reshape(bt, g, r, n, q).transpose(0, 1, 3, 2, 4),
            tokens_of(db, bt), tokens_of(dc, bt))


def _kernels(count: bool = True) -> bool:
    """Whether a walk goes through its Pallas kernel (on a TPU, or where
    the package's switches say so); counted at trace time."""
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import _pallas_available

    kernel = _pallas_available()
    if count:
        invocation_counts["pallas" if kernel else "fallback"] += 1
    return kernel


def _walk_forward(*chunks):
    if _kernels():
        from analytics_zoo_tpu.ops.pallas import ssd_scan

        return ssd_scan.walk_forward(*chunks)
    return walk_forward_by_scan(*chunks)


@jax.custom_vjp
def _chunked(xdt, a, b, c):
    """(Y, the last chunk's outgoing state) from the walks' layout."""
    y, _, final = _walk_forward(xdt, a, b, c)
    return y, final


def _chunked_fwd(*chunks):
    y, states, final = _walk_forward(*chunks)
    # named HERE, on what leaves the rule (a name keeps only the array the
    # backward pass reads): the output, which the layer goes on
    # from, and the states, which ``_chunked_bwd`` reads
    y = checkpoint_name(y, "attn_context")
    states = checkpoint_name(states, STATE_NAME)
    return (y, final), chunks + (states,)


def _chunked_bwd(kept, cotangents):
    *chunks, states = kept
    dy = cotangents[0]      # the outgoing state is read by a gauge alone
    if _kernels():
        from analytics_zoo_tpu.ops.pallas import ssd_scan

        return ssd_scan.walk_backward(*chunks, states, dy)
    return walk_backward_by_scan(*chunks, states, dy)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def chunk_of(length: int, chunk: int | None = None) -> int:
    """Tokens a chunk for sequences of ``length``: ``chunk`` (``CHUNK`` by
    default), and no more than the sequence in whole rows of eight."""
    return min(chunk or CHUNK, -(-length // 8) * 8)


def chunked_ssd(x, dt, a, b, c, *, chunk=None):
    """Mamba-2's rule over whole sequences from a zero state, token-major:
    ``x`` (B, L, H, P), ``dt`` (B, L, H) steps > 0, ``a`` (H,) the heads'
    A < 0, ``b`` and ``c`` (B, L, G, N) the groups', head h reading group
    h // (H / G) -> ``(y (B, L, H, P) in x's dtype, without the skip D x,
    stats)`` with ``stats`` float32 scalars that take no gradient:
    ``chunk_log_decay_min`` (the most negative sum of dt A that any head
    reached over any chunk: how far a head forgets inside one),
    ``dt_max`` and ``state_rms`` (the root mean square of the states after
    the last token).  A length that is no multiple of the chunk is padded
    with tokens that decay nothing and write nothing.  The heads are never
    split out: X~, B, C and y reach the walk as (B, L, channels)."""
    bt, l, h, p = x.shape
    g, n_state = b.shape[2:]
    if h % g:
        raise ValueError(f"{h} heads do not part into {g} groups")
    r = h // g
    q = chunk_of(l, chunk)
    n = -(-l // q)
    cd = x.dtype

    def tokens(t):      # (B, L, ...) -> (B, n Q, the rest as one)
        t = t.reshape(bt, l, -1)
        return t if n * q == l else jnp.pad(t, ((0, 0), (0, n * q - l),
                                                (0, 0)))

    dt32 = dt.astype(_F32)
    xdt = tokens((x.reshape(bt, l, h * p).astype(_F32)
                  * spread(dt32, p)).astype(cd))
    # a head's sum of dt A from its chunk's start: (B, G, n, R, Q), as a
    # product with a triangle of ones at float32's precision (a ``cumsum``
    # over this layout is a slow ``reduce_window`` on the TPU)
    steps = tokens(dt32 * a.astype(_F32)).reshape(bt, n, q, g, r)
    upto = (_iota((q, q), 0) <= _iota((q, q), 1)).astype(_F32)
    a_sum = jnp.matmul(steps.transpose(0, 3, 1, 4, 2), upto,
                       precision=jax.lax.Precision.HIGHEST)
    y, final = _chunked(xdt, a_sum, tokens(b.astype(cd)),
                        tokens(c.astype(cd)))
    chunk_schedules.append({
        "rule": "ssd", "shape": (bt, h, l, p, n_state), "groups": g,
        "chunk": q, "chunks": n, "kernel": _kernels(count=False),
        "layout": "tokens"})
    stats = jax.lax.stop_gradient({
        "chunk_log_decay_min": jnp.min(a_sum[..., -1]),
        "dt_max": jnp.max(dt32),
        "state_rms": jnp.sqrt(jnp.mean(jnp.square(final)))})
    return y[:, :l].reshape(bt, l, h, p), stats
