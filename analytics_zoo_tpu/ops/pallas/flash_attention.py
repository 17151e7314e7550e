# zoolint: disable-file=raw-pallas-call -- ops/pallas/ is the one home
# for raw pl.pallas_call; everything here ships a jnp fallback oracle and
# lowers under a kernel_* label through the compile choke point.
"""Flash attention — Pallas TPU kernel with streaming softmax.

The hot op behind TransformerLayer/BERT (reference materializes the full
(L, L) score matrix per head, TransformerLayer.scala:137).  This kernel
tiles Q over the grid and walks K/V tiles through VMEM with the
numerically-stable online-softmax accumulation, so HBM traffic is O(L·D)
per head instead of O(L²), and the score tile lives only in VMEM where the
MXU consumes it.

Training-path features (so real TransformerLayer/BERT training — dropout
on, padded batches — lowers to this kernel instead of the dense path):

* **additive bias/mask**: any shape broadcastable as (B|1, H|1, Lq|1, Lk)
  — covers the BERT (B, 1, 1, L) padding-mask convention (BERT.scala:66)
  and full (B, H, Lq, Lk) biases, streamed blockwise;
* **segment ids**: (B, Lq)/(B, Lk) int arrays; attention is masked where
  q/k segments differ (packed-sequence training);
* **attention dropout**: computed *inside* the kernel from a counter-based
  hash PRNG (`_keep_bits`) keyed on (seed, b, h, q_pos, k_pos).  The same
  pure function runs in the Pallas forward, the jnp fallback forward, and
  the blockwise backward, so the dropout mask is bit-identical across
  forward/backward without ever being materialized in HBM.

Semantics: causal masking is *end-aligned* for lq != lk (query i sees keys
0..(lk-lq)+i), matching the jnp path in ops/attention.py — the decode-style
convention where q is the tail of the key sequence.

Gradient support: ``flash_attention`` is wrapped in jax.custom_vjp.  The
forward saves its softmax stats (m, l), so the backward needs no
stats-recompute pass; on TPU the backward runs as two Pallas kernels
(``_flash_bwd_pallas``: a dq kernel walking K/V tiles past each q tile,
and a dk/dv/dbias kernel walking q tiles past each K/V tile) whose
rematerialized score tiles never leave VMEM.  Which tiles the three
kernels visit, what a visited tile computes and how many independent
tiles one loop body issues (every visit of a call that one grid step
owns, spelled out; on a longer call two or four sub-tiles of the step's
rows against one tile of the other side, which stays in VMEM whole, and
the diagonal in sub-tiles) is the tile schedule below (``tile_schedules``
records it for each traced call).  Elsewhere —
CPU, or a full (Lq, Lk) bias that needs its own O(Lq·Lk) gradient — a
blockwise lax.scan over key blocks serves as fallback and oracle
(O(Lq·block_k) live memory).  Either way long-context training never
materializes the (L, L) matrix.  On CPU (tests) the forward falls back to
the jnp path automatically; set ``ZOO_FLASH_INTERPRET=1`` to force the
actual Pallas kernels in interpret mode on CPU (CI routing + grad-oracle
tests).
"""

from __future__ import annotations

import collections
import functools
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

_NEG = -1e30

# Trace-time routing counters (tests assert the kernel actually fires for
# training-shaped inputs; jit traces once so these count compilations).
invocation_counts = {"pallas": 0, "fallback": 0}

# ---------------------------------------------------------------------------
# Counter-based dropout hash.  splitmix32-style finalizer over a position/
# seed counter: stateless, identical in Pallas and jnp, so fwd/bwd agree.
# ---------------------------------------------------------------------------
_C1 = np.uint32(0x9E3779B9)
_C2 = np.uint32(0x85EBCA6B)
_C3 = np.uint32(0xC2B2AE35)
_C4 = np.uint32(0x27D4EB2F)


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _keep_bits(seed0, seed1, b, h, q_pos, k_pos):
    """uint32 hash tile; shape follows broadcasting of q_pos × k_pos."""
    def u(t):
        return jnp.asarray(t).astype(jnp.uint32)

    x = (u(q_pos) * _C1) ^ (u(k_pos) * _C2)
    x = x ^ (u(b) * _C3) ^ (u(h) * _C4)
    x = x ^ u(seed0) ^ (u(seed1) * _C2)
    return _mix32(x)


def _drop_threshold(dropout_p):
    return np.uint32(min(int(dropout_p * 4294967296.0), 4294967295))


def _normalize_seed(seed):
    """Accept int, PRNG key, or int array; return (2,) int32."""
    if seed is None:
        return None
    if isinstance(seed, int):
        return jnp.asarray([seed, 0], jnp.int32)
    seed = jnp.asarray(seed)
    if jnp.issubdtype(seed.dtype, jax.dtypes.prng_key):
        seed = jax.random.key_data(seed)
    seed = seed.reshape(-1)
    if seed.dtype != jnp.int32:
        seed = jax.lax.bitcast_convert_type(seed.astype(jnp.uint32),
                                            jnp.int32)
    if seed.shape[0] == 1:
        seed = jnp.concatenate([seed, jnp.zeros((1,), jnp.int32)])
    return seed[:2]


# ---------------------------------------------------------------------------
# Dense reference (CPU fallback + test oracle)
# ---------------------------------------------------------------------------


def _per_query_head(x, heads):
    """(B, H_kv, L, d) -> (B, ``heads``, L, d), query head h reading
    key-value head h // (heads / H_kv): the plain form of grouped-query
    heads, for the fallback and the oracle (the kernels read a shared head
    where it lies)."""
    share = heads // x.shape[1]
    return x if share == 1 else jnp.repeat(x, share, axis=1)


def _per_kv_head(dx, kv_heads):
    """The transpose of ``_per_query_head``: a key-value head's cotangent
    is the sum of its query heads', in float32."""
    b, heads = dx.shape[:2]
    if heads == kv_heads:
        return dx
    return jnp.sum(dx.astype(jnp.float32).reshape(
        (b, kv_heads, heads // kv_heads) + dx.shape[2:]), axis=2)


def _attention_reference(q, k, v, causal, scale, bias=None, q_seg=None,
                         kv_seg=None, dropout_p=0.0, seed=None):
    k, v = (_per_query_head(x, q.shape[1]) for x in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk",
                        q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    lq, lk = scores.shape[-2], scores.shape[-1]
    live = None
    if causal:
        live = jnp.tril(jnp.ones((lq, lk), bool), lk - lq)[None, None]
    if q_seg is not None:
        seg_live = (q_seg[:, None, :, None] == kv_seg[:, None, None, :])
        live = seg_live if live is None else live & seg_live
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    if live is not None:
        scores = jnp.where(live, scores, _NEG)
    # softmax with the kernel's exact semantics: the running-max floor at
    # _NEG means rows that are fully masked (by `live` OR by a large
    # negative bias) produce zero output, not softmax's uniform row
    m2 = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), _NEG)
    p = jnp.exp(scores - m2)
    if live is not None:
        p = jnp.where(live, p, 0.0)
    probs = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-20)
    if dropout_p > 0.0:
        b, h = scores.shape[0], scores.shape[1]
        bits = _keep_bits(
            seed[0], seed[1],
            jnp.arange(b, dtype=jnp.int32)[:, None, None, None],
            jnp.arange(h, dtype=jnp.int32)[None, :, None, None],
            jnp.arange(lq, dtype=jnp.int32)[None, None, :, None],
            jnp.arange(lk, dtype=jnp.int32)[None, None, None, :])
        keep = bits >= _drop_threshold(dropout_p)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def _attention_stats_reference(q, k, v, causal, scale, mask=None):
    """(out, m, l) with the kernel's exact streaming semantics — the
    combinable-partial form used by ring attention's inner blocks.
    ``mask``: optional boolean keep-mask broadcastable to the score shape
    (ring attention's per-hop global-position mask); combines with
    ``causal``."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    live = mask
    if causal:
        lq, lk = scores.shape[-2], scores.shape[-1]
        tri = jnp.tril(jnp.ones((lq, lk), bool), lk - lq)[None, None]
        live = tri if live is None else live & tri
    if live is not None:
        scores = jnp.where(live, scores, _NEG)
    m = jnp.maximum(jnp.max(scores, axis=-1), _NEG)
    p = jnp.exp(scores - m[..., None])
    if live is not None:
        p = jnp.where(live, p, 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) \
        / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype), m, l


def attention_stats(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None):
    """Partial attention with running-softmax stats: returns
    ``(out, m, l)`` where ``out * l[..., None]`` is the unnormalized
    accumulator — two partials over disjoint key sets combine exactly via
    the flash update (ring attention's inner kernel).  Pallas on TPU, jnp
    elsewhere.  NOT differentiable on the TPU path — callers (ring
    attention) wrap it in their own custom_vjp."""
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    if _pallas_available() and q.shape[-1] % 64 == 0 \
            and q.shape[2] >= 128 and k.shape[2] >= 128:
        out = _flash_fwd_pallas(q, k, v, causal, scale, block_q,
                                block_k, interpret=_interpret_forced(),
                                return_stats=True)
        invocation_counts["pallas"] += 1
        return out
    invocation_counts["fallback"] += 1
    return _attention_stats_reference(q, k, v, causal, scale)


# ---------------------------------------------------------------------------
# Tile schedule: which (q tile, k tile) pairs a call visits and what a
# visited tile computes, decided at trace time from the call's static
# arguments (lq, lk, causal, bias, segment ids, dropout, input dtype).
#
# A tile is (block_k, block_q) scores, KEYS ON SUBLANES AND QUERIES ON
# LANES (S^T = K Q^T): what the softmax keeps per query (m, l, lse, D) is
# then a lane-dense (1, block_q) row that broadcasts along sublanes, the
# reductions over keys are elementwise across vregs, and the stats travel
# through HBM as (b, h, 1, lq).  With queries on sublanes each of them is
# a (block_q, 1) column: a cross-lane reduction or broadcast for every
# eight queries of every tile, and an array padded 128-fold in HBM.
#
# Above the causal diagonal a tile is SKIPPED.  Wholly below it, in a call
# with no bias, segment ids, dropout or ragged edge, it is PLAIN: no iota,
# compare or select.  Otherwise it is MASKED.  The matrix products take
# their operands in the inputs' dtype (P and dS are rounded to it at the
# product) and accumulate in float32; softmax statistics, exp and D stay
# float32.
#
# One grid step owns up to _RESIDENT_ROWS rows of one side (q rows in the
# forward and dq kernels, key rows in dk/dv) and walks the tiles of the
# other side in loops inside the kernel, bounded by the diagonal, so a
# skipped tile costs neither a grid step (0.35 us, more than a 256 x 256
# tile's products) nor a DMA.  What one loop body, one basic block, issues
# depends on the shape alone:
#
# * UNROLLED: a step that owns the whole sequence in at most
#   _UNROLLED_TILES tiles knows every bound as a Python number and spells
#   the visits out, which lets the scheduler run one tile's products under
#   another's softmax (1,024 tokens: `gpt2-small-fit`).
# * WALK: every longer call.  A tile is a chain (the score product, a
#   column maximum over the whole tile, the exponentials, a product that
#   contracts over the whole tile), so a body that holds one tile leaves
#   the MXU waiting for the vector unit and the vector unit for the MXU:
#   at 4,096 tokens a tile cost its products PLUS its vector arithmetic.
#   The step's rows are therefore cut into up to _BODY_TILES sub-tiles,
#   each with a carry of its own, and one body issues all of them against
#   ONE tile of the other side, loaded once: chains that share nothing
#   else.  The other side stays in VMEM whole up to _RESIDENT_BYTES
#   (K and V of 4,096 x (192 + 128) bf16 are 2.5 MiB), in chunks past
#   that.  The tiles that cross the group's diagonal stand at positions
#   that are static relative to the group (its first row is a multiple of
#   its rows and the offset is static), so that band is spelled out at
#   trace time sub-tile by sub-tile: above the diagonal skipped, on it
#   masked, below it plain (`_band`).
# * STREAMED: bias and segment ids ride one tile a grid step, and a causal
#   call whose explicit blocks do not divide a group keeps the loop of one
#   tile a body (LOOPED).
# ---------------------------------------------------------------------------

_RESIDENT_ROWS = 1024

#: the most independent tiles one loop body of the walk issues
_BODY_TILES = 4

#: bytes of the walked side (one buffer of the two) that a grid step of the
#: walk keeps in VMEM whole
_RESIDENT_BYTES = 4 << 20

#: the walk's tile (block_q, block_k) by kernel where the caller names
#: none, cut to the forward's caps (`_resolve_blocks`) and to the call: the
#: walked side in tiles of 1,024 rows, the step's 1,024 rows in four
#: sub-tiles of 256 (forward, dk/dv), which is then the diagonal's sub-tile
#: too, or in two of 512 (dq, where four of 256 timed 19% slower than two
#: of 512 with the diagonal cut no finer than the tile).  Set by timing the
#: kernels alone at (2, 32, 4096, 192/128) and (2, 16, 4096, 128) on a v5e
#: (PERF.md section 6, PR 36).
_WALK_TILES = {"forward": (256, 1024), "dq": (512, 1024),
               "dkv": (1024, 256)}

#: scoped VMEM for a kernel on the walk: _BODY_TILES chains of float32
#: score tiles beside the resident side pass the default 16 MiB
_WALK_VMEM_BYTES = 64 << 20

#: Trace-time record of each kernel's schedule, newest last: ``kernel``
#: ("forward", "dq", "dkv"), ``shape`` (b, h, lq, lk, d) with d the width
#: of q and k, ``kv_heads`` (the heads of k and v: h, or fewer where query
#: heads share them), ``value_width`` (that of v and of the output, which
#: latent attention makes unlike d), ``blocks`` (block_q, block_k) as
#: resolved, ``operand_dtype`` of the matrix products, ``schedule`` ("unrolled", "walk", "looped" or "streamed"),
#: ``tiles_per_body`` (the independent tiles one basic block issues: the
#: visits of the step on the unrolled path, the sub-tiles of the step's
#: rows on the walk, 1 on a loop of one tile a body), ``resident_rows``
#: (rows of the walked side a grid step holds in VMEM), ``sub_blocks``
#: (the diagonal's sub-tile, (q rows, key rows); the blocks off the walk)
#: and the tiles of one (batch, head) by class, in units of ``sub_blocks``:
#: ``skipped``, ``plain``, ``masked``.  Plain arithmetic on static shapes, like
#: ``invocation_counts``: jit traces once, so it counts compilations.
tile_schedules: collections.deque = collections.deque(maxlen=64)


def _static(*xs) -> bool:
    return all(isinstance(x, (int, np.integer)) for x in xs)


def _k_tile_range(q0, block_q, block_k, offset, n_k, causal, all_masked):
    """For the q tile whose first row is ``q0``: key tiles [0, plain) are
    plain, [plain, need) masked, [need, n_k) skipped.  Python numbers
    where ``q0`` is one (the trace-time record, and a kernel whose grid
    step owns the whole sequence), traced scalars otherwise."""
    xp = np if _static(q0) else jnp
    need = plain = n_k
    if causal:
        need = xp.minimum(
            xp.maximum(q0 + block_q + offset + block_k - 1, 0) // block_k,
            n_k)
        plain = xp.minimum(xp.maximum(q0 + offset + 1, 0) // block_k, n_k)
    return (0 if all_masked else plain), need


def _q_tile_range(k0, block_q, block_k, offset, n_q, causal, all_masked):
    """For the key tile whose first column is ``k0``: q tiles [0, first)
    are skipped, [first, plain) masked, [plain, n_q) plain."""
    xp = np if _static(k0) else jnp
    first = plain = 0
    if causal:
        first = xp.minimum(xp.maximum(k0 - offset, 0) // block_q, n_q)
        plain = xp.minimum(
            xp.maximum(k0 + block_k - 1 - offset + block_q - 1, 0)
            // block_q, n_q)
    return first, (n_q if all_masked else plain)


def _tiles(block_q, block_k, lq, lk):
    """The tile a kernel runs: the resolved blocks cut to the call, the q
    side to whole lanes where the sequence has them."""
    block_q, block_k = min(block_q, lq), min(block_k, lk)
    if block_q > 128:
        block_q -= block_q % 128
    return block_q, block_k


#: the most tiles of a sequence that the one grid step which owns it all
#: spells out one by one
_UNROLLED_TILES = 16


def _grouping(l_outer, t_outer, l_inner, t_inner, streamed):
    """(outer tiles a grid step owns, inner tiles it keeps resident,
    whether one grid step owns them all and few enough to unroll)."""
    if streamed:
        return 1, 1, False
    group = 1
    if l_outer % t_outer == 0:
        n_outer = l_outer // t_outer
        group = max(g for g in range(1, n_outer + 1) if n_outer % g == 0
                    and g * t_outer <= max(_RESIDENT_ROWS, t_outer))
    n_inner = -(-l_inner // t_inner)
    resident = max(1, min(n_inner, _RESIDENT_ROWS // t_inner))
    whole = (group * t_outer == l_outer and resident == n_inner
             and group * resident <= _UNROLLED_TILES)
    return group, resident, whole


def _tile_rows(j, lo, block, resident):
    """The rows that tile ``j`` holds of the resident chunk that starts at
    tile ``lo``."""
    from jax.experimental import pallas as pl

    if resident == 1:
        return pl.ds(0, block)
    start = (j - lo) * block
    return pl.ds(start if _static(start)
                 else pl.multiple_of(start, block), block)


class _Plan(NamedTuple):
    """How one of the three kernels visits its tiles (`_plan`).  The
    GROUPED side is the one whose rows a grid step owns (q rows in the
    forward and dq kernels, key rows in dk/dv), the WALKED side the one it
    loops over."""

    block_q: int
    block_k: int
    group: int       # tiles of the grouped side a grid step owns
    resident: int    # tiles of the walked side it keeps in VMEM
    schedule: str    # "unrolled", "walk", "looped" or "streamed"
    sub: int         # rows of the walked side in a sub-tile of the diagonal
    q_grouped: bool

    @property
    def whole(self):
        return self.schedule == "unrolled"

    @property
    def walk(self):
        return self.schedule == "walk"

    @property
    def tiles(self):
        """(the grouped side's tile, the walked side's)."""
        return (self.block_q, self.block_k) if self.q_grouped \
            else (self.block_k, self.block_q)

    @property
    def sub_blocks(self):
        own = self.tiles[0]
        return (own, self.sub) if self.q_grouped else (self.sub, own)


def _plan(kernel, block_q, block_k, lq, lk, row_bytes, *, causal, streamed,
          full_bias=False, dropout=False) -> _Plan:
    """The schedule of ``kernel`` ("forward", "dq", "dkv") for a call, from
    its static arguments alone: ``block_q`` / ``block_k`` as the caller
    gave them (None: the defaults), ``row_bytes`` of the walked side's
    operands.  A call that one grid step owns in at most _UNROLLED_TILES
    tiles is spelled out, bias and segment ids stream, every other call
    walks (see the schedule comment above)."""
    if kernel == "forward":
        tq, tk = _resolve_blocks(block_q, block_k, lq, lk, causal=causal,
                                 full_bias=full_bias, dropout=dropout)
    else:
        tq, tk = _resolve_bwd_blocks(block_q, block_k, lq, lk, causal=causal,
                                     kernel=kernel)
    tq, tk = _tiles(tq, tk, lq, lk)
    q_grouped = kernel != "dkv"

    def sides(tq, tk):
        return ((lq, tq), (lk, tk)) if q_grouped else ((lk, tk), (lq, tq))

    (l_own, t_own), (l_other, t_other) = sides(tq, tk)
    group, resident, whole = _grouping(l_own, t_own, l_other, t_other,
                                       streamed)
    one_tile = _Plan(tq, tk, group, resident, "unrolled" if whole else
                     "streamed" if streamed else "looped", t_other,
                     q_grouped)
    if whole or streamed:
        return one_tile
    cap_q, cap_k = _resolve_blocks(None, None, lq, lk, causal=causal,
                                   dropout=dropout)
    walk_q, walk_k = _WALK_TILES[kernel]
    tq, tk = _tiles(tq if block_q else min(cap_q, walk_q),
                    tk if block_k else min(cap_k, walk_k), lq, lk)
    (l_own, t_own), (l_other, t_other) = sides(tq, tk)
    n_own = -(-l_own // t_own)
    for n in range(min(_BODY_TILES, n_own), 0, -1):
        rows = n * t_own
        # a group is a whole number of the walked side's tiles, or where
        # the diagonal stands in it would not be static
        if rows <= max(_RESIDENT_ROWS, t_own) \
                and (l_own % t_own or n_own % n == 0) \
                and not (causal and rows % t_other):
            break
    else:
        return one_tile
    sub = t_own if t_own < t_other and t_other % t_own == 0 else t_other
    # the walked side whole where it fits, else in chunks of equal size
    n_other = -(-l_other // t_other)
    fits = max(1, _RESIDENT_BYTES // (t_other * row_bytes))
    resident = -(-n_other // -(-n_other // fits))
    return _Plan(tq, tk, n, resident, "walk", sub, q_grouped)


def _band(plan: _Plan, offset, all_masked):
    """Where a group of the walk meets the diagonal, relative to the
    group: ``(c, classes)``.  The band's tiles of the walked side are
    ``g * per + c + t`` for group ``g``, ``per`` the tiles that a group's
    rows span and ``t`` an index of ``classes``; ``classes[t][u][r]`` says
    what sub-tile ``u`` of that tile is to the group's sub-tile ``r``:
    None (above the diagonal: skipped), True (masked) or False (plain).
    The walked side's tiles before the band (q grouped) or after it (keys
    grouped) are plain for the whole group, those on its other side
    skipped."""
    t_own, t_other = plan.tiles
    rows = plan.group * t_own
    if plan.q_grouped:
        c, end = (offset + 1) // t_other, -(-(rows + offset) // t_other)
    else:
        c, end = -offset // t_other, -(-(rows - 1 - offset) // t_other)
    classes = []
    for t in range(c, end):
        tile = []
        for u in range(t_other // plan.sub):
            first = t * t_other + u * plan.sub
            last = first + plan.sub - 1
            chains = []
            for r in range(plan.group):
                own0, own1 = r * t_own, (r + 1) * t_own - 1
                if plan.q_grouped:   # own rows are queries, the others keys
                    dead = own1 + offset < first
                    clean = own0 + offset >= last
                else:
                    dead = last + offset < own0
                    clean = first + offset >= own1
                chains.append(None if dead else bool(all_masked or not clean))
            tile.append(chains)
        classes.append(tile)
    return c, classes


def _walk_visits(plan: _Plan, lq, lk, causal, all_masked):
    """Every tile and sub-tile that the walk issues for one (batch, head):
    ``(q0, q rows, k0, key rows, masked)``, by the bounds the kernels use
    (`_walk`), in Python numbers."""
    (l_own, l_other) = (lq, lk) if plan.q_grouped else (lk, lq)
    t_own, t_other = plan.tiles
    rows, n_other = plan.group * t_own, -(-l_other // t_other)
    band = _band(plan, lk - lq, all_masked) if causal else None

    def visit(own0, other0, other_rows, masked):
        return (own0, t_own, other0, other_rows, masked) if plan.q_grouped \
            else (other0, other_rows, own0, t_own, masked)

    for g in range(-(-l_own // rows)):
        loop, banded = range(n_other), []
        if band is not None:
            c, classes = band
            start = g * (rows // t_other) + c
            loop = range(min(start, n_other)) if plan.q_grouped \
                else range(max(start + len(classes), 0), n_other)
            banded = [(start + t, tile) for t, tile in enumerate(classes)
                      if 0 <= start + t < n_other]
        for j in loop:
            for r in range(plan.group):
                yield visit(g * rows + r * t_own, j * t_other, t_other,
                            bool(all_masked))
        for j, tile in banded:
            for u, chains in enumerate(tile):
                for r, masked in enumerate(chains):
                    if masked is not None:
                        yield visit(g * rows + r * t_own,
                                    j * t_other + u * plan.sub, plan.sub,
                                    masked)


def _record_schedule(kernel, q, v, plan: _Plan, causal, all_masked):
    b, h, lq, d = q.shape
    lk = v.shape[2]
    block_q, block_k = plan.block_q, plan.block_k
    plain = masked = 0
    if plan.walk:
        t_own, t_other = plan.tiles
        (l_own, l_other) = (lq, lk) if plan.q_grouped else (lk, lq)
        total = -(-l_own // (plan.group * t_own)) * plan.group \
            * -(-l_other // t_other) * (t_other // plan.sub)
        for _, rows_q, _, rows_k, is_masked in _walk_visits(
                plan, lq, lk, causal, all_masked):
            units = (rows_k if plan.q_grouped else rows_q) // plan.sub
            masked += units if is_masked else 0
            plain += 0 if is_masked else units
        per_body = plan.group
    else:
        n_q, n_k = -(-lq // block_q), -(-lk // block_k)
        total = n_q * n_k
        for i in range(n_q):
            p, need = _k_tile_range(i * block_q, block_q, block_k, lk - lq,
                                    n_k, causal, all_masked)
            plain += int(p)
            masked += int(need) - int(p)
        per_body = plain + masked if plan.whole else 1
    tile_schedules.append({
        "kernel": kernel, "shape": (b, h, lq, lk, d),
        "kv_heads": v.shape[1], "value_width": v.shape[3],
        "blocks": (block_q, block_k), "operand_dtype": str(q.dtype),
        "skipped": total - plain - masked, "plain": plain,
        "masked": masked, "schedule": plan.schedule,
        "tiles_per_body": per_body,
        "resident_rows": plan.resident * plan.tiles[1],
        "sub_blocks": plan.sub_blocks})


def _zero_rows(x, live):
    """Zero the rows of a ragged edge: a block read past the array is
    unspecified, and a NaN there would poison a product through 0 * NaN.
    In float32, since a select on packed rows needs a packed mask."""
    return jnp.where(live, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _scaled(x, scale):
    """The score scale folded into a (rows, d) operand tile, (rows, d)
    multiplies where the score tile would take (block_k, block_q); in
    float32, as the v5e's vector unit has no bf16."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _live(q0, k0, block_q, block_k, lq, lk, offset, causal, pad_q, pad_k,
          segs):
    """The (block_k, block_q) mask of a masked tile, None where the call
    masks nothing, and the key rows inside a ragged last tile."""
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
    live = k_rows = None
    if pad_k:
        live = k_rows = k_pos < lk
    if pad_q:
        q_live = q_pos < lq
        live = q_live if live is None else live & q_live
    if causal:
        tri = q_pos + offset >= k_pos
        live = tri if live is None else live & tri
    if segs is not None:
        # q_seg rides as (B, 8, Lq) and kv_seg as (B, Lk, 8): a bare
        # (B, L) operand would need a block (1, block) whose
        # second-to-last dim breaks Mosaic's (8, 128)-or-full-dim rule
        qseg_ref, kseg_ref = segs
        seg = kseg_ref[0][:, :1] == qseg_ref[0][:1, :]
        live = seg if live is None else live & seg
    return live, k_rows, q_pos, k_pos


def _seg_operands(q_seg, kv_seg, b, lq, lk):
    return (jnp.broadcast_to(q_seg.astype(jnp.int32)[:, None, :], (b, 8, lq)),
            jnp.broadcast_to(kv_seg.astype(jnp.int32)[:, :, None],
                             (b, lk, 8)))


def _walk_tiles(lo, resident, masked, plain, tile, carry):
    """Run ``tile(j, carry, masked=...)`` over the share that the resident
    chunk [lo, lo + resident) holds of the ``plain`` and of the ``masked``
    range of tiles, each (first, past the last).  A range that the call's
    static arguments leave empty (no plain tile in a call that masks them
    all, no masked one in a clean call that is not causal) emits no loop."""
    def span(bounds, is_masked, carry):
        if _static(*bounds) and bounds[0] >= bounds[1]:
            return carry
        if _static(lo, *bounds):
            # one grid step owns the whole sequence: every visit spelled
            # out, so that the scheduler can run one tile's products under
            # another's softmax
            for j in range(max(lo, bounds[0]),
                           min(lo + resident, bounds[1])):
                carry = tile(j, carry, masked=is_masked)
            return carry
        return jax.lax.fori_loop(
            jnp.maximum(lo, bounds[0]), jnp.minimum(lo + resident, bounds[1]),
            functools.partial(tile, masked=is_masked), carry)

    return span(masked, True, span(plain, False, carry))


def _walk(plan: _Plan, band, g, ci, lq, lk, carries, load, tile, all_masked):
    """The walk of group ``g`` over the share that the resident chunk
    ``ci`` holds of the other side's tiles, in a call of ``lq`` queries
    and ``lk`` keys.

    ``carries``: one carry for each of the group's sub-tiles.
    ``load(rows, first)``: the walked side's operands on ``rows`` of the
    resident chunk, ``first`` their position in the sequence, loaded once
    for all of the group's sub-tiles.  ``tile(r, operands, first, carry,
    masked)``: sub-tile ``r``'s carry after that tile.  One body of the
    loop issues all ``plan.group`` sub-tiles against one tile: chains that
    share nothing but ``operands``.  ``band`` (`_band`; None in a call that
    is not causal) is spelled out after the loop, a tile of it under a
    ``cond`` only where the shapes cannot say that every group has it in
    its chunk."""
    from jax.experimental import pallas as pl

    t_own, t_other = plan.tiles
    l_own, l_other = (lq, lk) if plan.q_grouped else (lk, lq)
    groups = -(-l_own // (plan.group * t_own))
    n_other = -(-l_other // t_other)
    if plan.resident >= n_other:   # one chunk: every bound but g's static
        lo, hi = 0, n_other
    else:
        lo = ci * plan.resident
        hi = jnp.minimum(lo + plan.resident, n_other)

    def rows(j, u=0, size=t_other):
        start = (j - lo) * t_other + u * plan.sub
        return pl.ds(start if _static(start)
                     else pl.multiple_of(start, size), size)

    def body(j, carries):
        operands = load(rows(j), j * t_other)
        return tuple(tile(r, operands, j * t_other, carry, all_masked)
                     for r, carry in enumerate(carries))

    if band is None:
        return jax.lax.fori_loop(lo, hi, body, tuple(carries))
    c, classes = band
    per = plan.group * t_own // t_other
    start = g * per + c
    carries = jax.lax.fori_loop(
        *((lo, jnp.minimum(hi, start)) if plan.q_grouped
          else (jnp.maximum(lo, start + len(classes)), hi)),
        body, tuple(carries))
    for t, subtiles in enumerate(classes):
        j = start + t

        def visit(carries, j=j, subtiles=subtiles):
            carries = list(carries)
            for u, chains in enumerate(subtiles):
                if all(masked is None for masked in chains):
                    continue
                first = j * t_other + u * plan.sub
                operands = load(rows(j, u, plan.sub), first)
                for r, masked in enumerate(chains):
                    if masked is not None:
                        carries[r] = tile(r, operands, first, carries[r],
                                          masked)
            return tuple(carries)

        # the tile's index in the first group and in the last
        if _static(lo, hi) and lo <= c + t \
                and (groups - 1) * per + c + t < hi:
            carries = visit(carries)
        else:
            carries = jax.lax.cond((j >= lo) & (j < hi), visit,
                                   lambda carries: carries, carries)
    return carries


# ---------------------------------------------------------------------------
# Pallas forward
# ---------------------------------------------------------------------------


# The kernels are jitted on their own: a model's layers call them with the
# same shapes and static arguments, and an inner jit is traced once and
# then found in JAX's cache, where a bare pallas_call traces its kernel
# anew at every call site (half a second a layer's backward at 1024
# tokens, all of it set-up time).
_STATIC = ("causal", "scale", "block_q", "block_k", "interpret",
           "dropout_p")


# zoolint: disable=raw-jit -- an inner jit, inlined into the caller's program (the step that compile_step compiles): it is there for JAX's trace cache, not as a compile site
@functools.partial(jax.jit, static_argnames=_STATIC + ("return_stats",))
def _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                      interpret=False, bias=None, q_seg=None, kv_seg=None,
                      dropout_p=0.0, seed=None, return_stats=False):
    """Forward: grid (b, h, q groups, k chunks), key chunks innermost.

    A grid step owns ``group`` q tiles and sees one chunk of K and V.  On
    the walk (`_plan`: a call that no single grid step owns) the chunk is
    the whole of K and V up to _RESIDENT_BYTES, and one loop body issues
    the step's q sub-tiles, each with its own (m, l, acc), against one key
    tile loaded once; the diagonal's band is spelled out in sub-tiles
    (`_walk`).  Otherwise (the sequence spelled out, or bias and segment
    ids streamed) each q tile walks the chunk's key tiles up to the
    diagonal, one tile a visit, the plain ones before the masked ones
    (`_walk_tiles`).  Softmax running stats (m, l) and the output
    accumulator, (dv, block_q) like the tile, persist across chunks in
    VMEM scratch, so VMEM holds O(group·block_q·d + chunk·d) and the
    sequence length is bounded by HBM, not VMEM.  ``block_q`` /
    ``block_k``: the caller's, None for the defaults.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk, dv = k.shape[2], v.shape[3]   # v and the output have their own width
    share = h // k.shape[1]   # query heads a key-value head serves
    offset = lk - lq  # end-aligned causal diagonal
    has_bias = bias is not None
    has_seg = q_seg is not None
    has_drop = dropout_p > 0.0
    plan = _plan("forward", block_q, block_k, lq, lk,
                 (d + dv) * q.dtype.itemsize, causal=causal,
                 streamed=has_bias or has_seg,
                 full_bias=has_bias and bias.shape[2] > 1, dropout=has_drop)
    block_q, block_k = plan.block_q, plan.block_k
    group, resident, whole = plan.group, plan.resident, plan.whole
    n_k = pl.cdiv(lk, block_k)
    pad_k = lk % block_k != 0
    all_masked = (has_bias or has_seg or has_drop or pad_k
                  or lq % block_q != 0)
    rows_q, chunk = group * block_q, resident * block_k
    n_c = pl.cdiv(n_k, resident)
    band = _band(plan, offset, all_masked) if plan.walk and causal else None
    _record_schedule("forward", q, v, plan, causal, all_masked)

    def kernel(*refs):
        i = 3
        q_ref, k_ref, v_ref = refs[:3]
        bias_ref = segs = None
        if has_bias:
            bias_ref = refs[i]
            i += 1
        if has_seg:
            segs = refs[i:i + 2]
            i += 2
        if has_drop:
            seed_ref = refs[i]
            i += 1
        if return_stats:
            o_ref, m_out_ref, l_out_ref = refs[i:i + 3]
            i += 3
        else:
            o_ref = refs[i]
            i += 1
        m_ref, l_ref, acc_ref = refs[i:i + 3]

        # program ids are read OUTSIDE any loop or pl.when branch (inside
        # one they cannot lower in interpret mode)
        bi = pl.program_id(0)
        hi = pl.program_id(1)
        gi = 0 if whole else pl.program_id(2)
        ci = 0 if whole else pl.program_id(3)

        @pl.when(ci == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def tile(kb, vb, k0, carry, qs, q0, masked):
            """One tile: the k and v rows at ``k0`` against the scaled q
            rows at ``q0``; the tile is as large as they are."""
            m, l, acc = carry  # (1, block_q) twice, (dv, block_q)
            live = None
            if masked:
                # a ragged q tile needs no mask here: its columns past lq
                # are dropped on the way out
                live, k_rows, q_pos, k_pos = _live(
                    q0, k0, qs.shape[0], kb.shape[0], lq, lk, offset,
                    causal, False, pad_k, segs)
                if pad_k:
                    kb, vb = _zero_rows(kb, k_rows), _zero_rows(vb, k_rows)
            s = _dot(kb, qs, (1, 1))  # (block_k, block_q)
            if has_bias:
                s = s + bias_ref[0, 0].astype(jnp.float32)
            if live is not None:
                s = jnp.where(live, s, _NEG)
            new_m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m - new_m)
            p = jnp.exp(s - new_m)
            if live is not None:
                # a query with no live key yet has new_m == _NEG == s
                p = jnp.where(live, p, 0.0)
            # l is the full softmax denominator (pre-dropout), so the final
            # acc / l division reproduces dropout-after-softmax semantics
            l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
            if has_drop:
                bits = _keep_bits(seed_ref[0], seed_ref[1], bi, hi,
                                  q_pos, k_pos)
                p = jnp.where(bits >= _drop_threshold(dropout_p),
                              p * (1.0 / (1.0 - dropout_p)), 0.0)
            acc = acc * alpha + _dot(vb, p.astype(vb.dtype), (0, 0))
            return new_m, l, acc

        def visit(j, carry, qs, q0, masked):
            at = _tile_rows(j, ci * resident, block_k, resident)
            return tile(k_ref[0, 0, at, :], v_ref[0, 0, at, :], j * block_k,
                        carry, qs, q0, masked)

        own = [pl.ds(r * block_q, block_q) for r in range(group)]
        if plan.walk:
            qs = [_scaled(q_ref[0, 0, rows, :], scale) for rows in own]
            carries = _walk(
                plan, band, gi, ci, lq, lk,
                [(m_ref[:, rows], l_ref[:, rows], acc_ref[:, rows])
                 for rows in own],
                lambda at, k0: (k_ref[0, 0, at, :], v_ref[0, 0, at, :]),
                lambda r, kv, k0, carry, masked: tile(
                    *kv, k0, carry, qs[r], gi * rows_q + r * block_q,
                    masked),
                all_masked)
            for rows, carry in zip(own, carries):
                m_ref[:, rows], l_ref[:, rows], acc_ref[:, rows] = carry
        else:
            lo = ci * resident
            for r, rows in enumerate(own):
                q0 = (gi * group + r) * block_q
                qs = _scaled(q_ref[0, 0, rows, :], scale)
                plain, need = _k_tile_range(q0, block_q, block_k, offset, n_k,
                                            causal, all_masked)
                m_ref[:, rows], l_ref[:, rows], acc_ref[:, rows] = _walk_tiles(
                    lo, resident, (plain, need), (0, plain),
                    functools.partial(visit, qs=qs, q0=q0),
                    (m_ref[:, rows], l_ref[:, rows], acc_ref[:, rows]))

        @pl.when(ci == n_c - 1)
        def _emit():
            o_ref[0, 0] = (
                acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
            ).T.astype(o_ref.dtype)
            if return_stats:
                m_out_ref[0, 0] = m_ref[...]
                l_out_ref[0, 0] = l_ref[...]

    def q_side(bi, hi, gi, ci):
        return (bi, hi, gi, 0)

    def k_side(bi, hi, gi, ci):
        return (bi, hi // share if share > 1 else hi, ci, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rows_q, d), q_side, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, chunk, d), k_side, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, chunk, dv), k_side, memory_space=pltpu.VMEM),
    ]
    args = [q, k, v]
    if has_bias:
        # keys on sublanes: a (…, 1, Lk) bias rides as a column, a full
        # (…, Lq, Lk) one transposed
        bb, bh, bq, _ = bias.shape
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k, block_q if bq > 1 else 1),
            lambda bi, hi, gi, ci: (
                bi if bb > 1 else 0, hi if bh > 1 else 0, ci,
                gi if bq > 1 else 0),
            memory_space=pltpu.VMEM))
        args.append(jnp.swapaxes(bias.astype(jnp.float32), 2, 3))
    if has_seg:
        in_specs.append(pl.BlockSpec(
            (1, 8, block_q), lambda bi, hi, gi, ci: (bi, 0, gi),
            memory_space=pltpu.VMEM))
        in_specs.append(pl.BlockSpec(
            (1, block_k, 8), lambda bi, hi, gi, ci: (bi, ci, 0),
            memory_space=pltpu.VMEM))
        args += _seg_operands(q_seg, kv_seg, b, lq, lk)
    if has_drop:
        in_specs.append(pl.BlockSpec(
            (2,), lambda bi, hi, gi, ci: (0,),
            memory_space=pltpu.SMEM))
        args.append(seed.astype(jnp.int32))

    out_specs = pl.BlockSpec((1, 1, rows_q, dv), q_side,
                             memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((b, h, lq, dv), q.dtype)
    if return_stats:
        stat_spec = pl.BlockSpec((1, 1, 1, rows_q),
                                 lambda bi, hi, gi, ci: (bi, hi, 0, gi),
                                 memory_space=pltpu.VMEM)
        stat_shape = jax.ShapeDtypeStruct((b, h, 1, lq), jnp.float32)
        out_specs = [out_specs, stat_spec, stat_spec]
        out_shape = [out_shape, stat_shape, stat_shape]
    res = pl.pallas_call(
        kernel,
        grid=(b, h, pl.cdiv(lq, rows_q), n_c),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((1, rows_q), jnp.float32),
            pltpu.VMEM((1, rows_q), jnp.float32),
            pltpu.VMEM((dv, rows_q), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_WALK_VMEM_BYTES if plan.walk else None,
        ),
        interpret=interpret,
    )(*args)
    if return_stats:
        out, m, l = res
        return out, m[:, :, 0], l[:, :, 0]
    return res


def _causal_tile(length: int) -> int:
    """Half of the sequence, so that a causal call has tiles to skip (at
    two tiles a side, one of four), and not under 256: a visited tile costs
    about a third of a microsecond whatever its size (the products'
    latency, in a chain through the running max), which at 256 x 256 is
    more than the tile's own work."""
    return max(256, 1 << max((length // 2).bit_length() - 1, 0))


def _resolve_blocks(block_q, block_k, lq, lk, *, causal=False,
                    full_bias: bool = False,
                    dropout: bool = False) -> tuple[int, int]:
    """Tile defaults: upper bounds sized against the v5e ~16 MB scoped-VMEM
    budget, brought down by the call's shape where that lets tiles go.

    The dominant live buffers are the (block_k, block_q) f32 score and
    prob tiles; in-kernel dropout adds a PRNG-bits tile of the same shape
    and a full (…, Lq, Lk) bias streams an extra f32 tile.  2048-row
    blocks went over the budget once those operands landed (measured:
    16.09M/16M clean @4k d=64, 22.73M/16M dropout @2k d=128 — both hard
    compile failures on the chip), so at most 1024x1024 clean (~10 MB
    live), block_k 512 under dropout (its PRNG tile) and 512x512 with a
    full bias (~8 MB live).  A causal call takes `_causal_tile` of its
    shorter side where that is less: 512x512 at 1024 tokens, the caps
    from 2048 on.  Explicit block_q/block_k arguments always win.

    These are the tile of a loop body that holds ONE tile (a sequence
    spelled out, bias and segment ids streamed) and the caps of the walk,
    whose body holds several: there `_plan` cuts the step's rows into
    sub-tiles of `_WALK_TILES` (256 x 1024 in the forward at 4,096
    tokens, four a body) under a scoped-VMEM limit of its own."""
    cap_q, cap_k = (512, 512) if full_bias else \
        (1024, 512 if dropout else 1024)
    if causal:
        tile = _causal_tile(min(lq, lk))
        cap_q, cap_k = min(cap_q, tile), min(cap_k, tile)
    return block_q or cap_q, block_k or cap_k


def _resolve_bwd_blocks(block_q, block_k, lq, lk, *, causal=False,
                        kernel="dq") -> tuple[int, int]:
    """Backward tiles: the forward's (`_resolve_blocks`), at most 512x512,
    and for the dk/dv kernel of a causal call half of that a side (256x256
    at 1024 tokens): its four products a tile make the share of tiles
    visited worth more than the visits.  The backward holds roughly twice
    the forward's live tiles a step (up to four (bk, bq) f32
    score/prob/grad tiles, the PRNG-bits tile, two (bk, d) accumulators),
    and 512x512 keeps both kernels ~7 MB at d=128 with dropout, well under
    the ~16 MB scoped budget.  A caller's SMALLER explicit blocks are
    honored (the VMEM-pressure escape hatch).

    As in `_resolve_blocks`, this is the one-tile body's tile; on the walk
    `_plan` gives dq two sub-tiles of 512 q rows and dk/dv four of 256 key
    rows a body, each against 1,024 rows of the other side
    (`_WALK_TILES`), and the caller's explicit blocks, cut as here, are
    the sub-tile and the walked tile."""
    cap = 512
    if causal and kernel == "dkv":
        cap = min(cap, max(256, _causal_tile(min(lq, lk)) // 2))
    block_q, block_k = _resolve_blocks(block_q, block_k, lq, lk,
                                       causal=causal)
    return min(block_q, cap, lq), min(block_k, cap, lk)


def _flash_bwd_pallas(q, k, v, g, out, m, l, causal, scale,
                      block_q=None, block_k=None, interpret=False,
                      bias=None, q_seg=None, kv_seg=None, dropout_p=0.0,
                      seed=None):
    """Pallas flash backward: two kernels, both O(block²) VMEM.

    dq kernel: grid (b, h, q groups, k chunks) — each q tile accumulates
    dq across the key tiles of the chunks it sees.  dk/dv kernel: grid
    (b, h, k groups, q chunks) — each K/V tile accumulates dk/dv (and its
    bias-grad tile) across the q tiles.  Both walk their tiles in loops
    inside the kernel, bounded by the diagonal, as the forward does, and
    on a call that no grid step owns with several sub-tiles of the step's
    rows a loop body, each with its own accumulator (`_walk`).
    Score tiles are rematerialized from q/k in VMEM (standard flash
    strategy) as ``exp(s - lse)`` from the forward's saved softmax stats,
    ``lse = m + log(l)`` made once in XLA beside ``D``, so no
    stats-recompute pass exists and nothing O(Lq·Lk) ever reaches HBM.
    Dropout re-derives the forward's exact keep mask from the
    `_keep_bits` position hash.

    Bias gradients are emitted per (b, h) as (b, h, 1, lk) partials and
    reduced outside to the bias's broadcast shape; full (…, Lq, Lk)
    biases are NOT handled here (their db is itself O(Lq·Lk) — callers
    fall back to the jnp blockwise path).
    """
    invocation_counts["pallas"] += 1
    if bias is not None and bias.shape[2] > 1:
        raise ValueError("full (Lq, Lk) bias backward not supported "
                         "in the Pallas path")
    gf = g.astype(jnp.float32)
    # D_i = dO_i · O_i (flash-bwd identity; holds under dropout because
    # O already contains the dropped probabilities)
    D4 = jnp.sum(gf * out.astype(jnp.float32), axis=-1)[:, :, None]
    # p = exp(s - lse) needs no divide; a query with no live key has
    # m == _NEG, which absorbs the log
    lse4 = (m.astype(jnp.float32) + jnp.log(
        jnp.maximum(l.astype(jnp.float32), 1e-20)))[:, :, None]
    rest = ((q, k, v, gf.astype(q.dtype), lse4, D4), causal, scale, block_q,
            block_k, interpret, bias, q_seg, kv_seg, dropout_p, seed)
    (dq,) = _flash_bwd_kernel("dq", *rest)
    dk, dv, *db = _flash_bwd_kernel("dkv", *rest)
    if k.shape[1] != q.shape[1]:
        dk, dv = (_per_kv_head(dx, k.shape[1]).astype(x.dtype)
                  for dx, x in ((dk, k), (dv, v)))
    if bias is None:
        return dq, dk, dv, None
    # (b, h, 1, lk) per-(b, h) partials
    db = jnp.swapaxes(db[0][:, :, :k.shape[2]], 2, 3)
    if bias.shape[0] == 1:
        db = jnp.sum(db, axis=0, keepdims=True)
    if bias.shape[1] == 1:
        db = jnp.sum(db, axis=1, keepdims=True)
    return dq, dk, dv, db.astype(bias.dtype)


# zoolint: disable=raw-jit -- an inner jit, inlined into the caller's program (the step that compile_step compiles): it is there for JAX's trace cache, not as a compile site
@functools.partial(jax.jit, static_argnames=_STATIC + ("kernel",))
def _flash_bwd_kernel(kernel, operands, causal, scale, block_q, block_k,
                      interpret, bias, q_seg, kv_seg, dropout_p, seed):
    """One of the two backward kernels, "dq" or "dkv", on its own tiles:
    its results as a list."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v = operands[:3]
    b, h, lq, d = q.shape
    lk, dv = k.shape[2], v.shape[3]   # v and dO have their own width
    share = h // k.shape[1]   # query heads a key-value head serves
    offset = lk - lq
    has_bias = bias is not None
    has_seg = q_seg is not None
    has_drop = dropout_p > 0.0
    streamed = has_bias or has_seg
    # dk/dv walks q, dO and the two float32 stats of a query
    row_bytes = (d + dv) * q.dtype.itemsize + (8 if kernel == "dkv" else 0)
    plan = _plan(kernel, block_q, block_k, lq, lk, row_bytes, causal=causal,
                 streamed=streamed, dropout=has_drop)
    bq, bk = plan.block_q, plan.block_k
    n_q = pl.cdiv(lq, bq)
    n_k = pl.cdiv(lk, bk)
    pad_q = lq % bq != 0
    pad_k = lk % bk != 0
    all_masked = has_bias or has_seg or has_drop or pad_q or pad_k
    if has_bias:
        bb, bh = bias.shape[:2]
    band = _band(plan, offset, all_masked) if plan.walk and causal else None
    _record_schedule(kernel, q, v, plan, causal, all_masked)

    thr = _drop_threshold(dropout_p) if has_drop else None
    inv_keep = 1.0 / (1.0 - dropout_p) if has_drop else None

    def recompute(qs, kb, vb, gb, lse, dd, q0, k0, masked, opt, bi, hi):
        """One tile's (p_t, ds), (bk, bq) in float32, from the scaled q
        tile, the k, v and dO tiles and the queries' stats, (1, bq) rows;
        and the operands, with the rows of a ragged edge zeroed.  The tile
        is as large as its operands."""
        bias_ref, segs, seed_ref = opt
        live = None
        if masked:
            live, k_rows, q_pos, k_pos = _live(
                q0, k0, qs.shape[0], kb.shape[0], lq, lk, offset, causal,
                pad_q, pad_k, segs)
            if pad_q:
                q_rows = q0 + jax.lax.broadcasted_iota(
                    jnp.int32, (qs.shape[0], 1), 0) < lq
                qs, gb = _zero_rows(qs, q_rows), _zero_rows(gb, q_rows)
            if pad_k:
                kb, vb = _zero_rows(kb, k_rows), _zero_rows(vb, k_rows)
        s = _dot(kb, qs, (1, 1))
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        p = jnp.exp(s - lse)
        if live is not None:
            p = jnp.where(live, p, 0.0)
        dp = _dot(vb, gb, (1, 1))
        if has_drop:
            bits = _keep_bits(seed_ref[0], seed_ref[1], bi, hi,
                              q_pos, k_pos)
            t = jnp.where(bits >= thr, inv_keep, 0.0)
            p_t = p * t
            ds = p * (t * dp - dd)
        else:
            p_t = p
            ds = p * (dp - dd)
        if live is not None and (pad_q or pad_k):
            # padded queries read OOB stats (unspecified) and the dk/dv
            # kernel CONTRACTS over queries, so masked entries must be
            # exact 0s, not 0 * NaN
            ds = jnp.where(live, ds, 0.0)
        return p_t, ds, qs, kb, gb

    def optional_refs(refs, i):
        bias_ref = segs = seed_ref = None
        if has_bias:
            bias_ref = refs[i]
            i += 1
        if has_seg:
            segs = refs[i:i + 2]
            i += 2
        if has_drop:
            seed_ref = refs[i]
            i += 1
        return (bias_ref, segs, seed_ref), i

    # ---- dq kernel: grid (b, h, q groups, k chunks) --------------------
    group_q, resident_k, whole_dq = plan.group, plan.resident, plan.whole

    def dq_kernel(*refs):
        q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref = refs[:6]
        opt, i = optional_refs(refs, 6)
        dq_ref, acc_ref = refs[i], refs[i + 1]
        bi = pl.program_id(0)
        hi = pl.program_id(1)
        gi = 0 if whole_dq else pl.program_id(2)
        ci = 0 if whole_dq else pl.program_id(3)

        @pl.when(ci == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        own = [pl.ds(r * bq, bq) for r in range(group_q)]
        if plan.walk:
            chains = [(_scaled(q_ref[0, 0, rows, :], scale),
                       g_ref[0, 0, rows, :], lse_ref[0, 0, :, rows],
                       d_ref[0, 0, :, rows]) for rows in own]

            def chain(r, kv, k0, acc, masked):
                qs, gb, lse, dd = chains[r]
                _, ds, _, kb, _ = recompute(
                    qs, *kv, gb, lse, dd, gi * group_q * bq + r * bq, k0,
                    masked, opt, bi, hi)
                return acc + _dot(kb, ds.astype(kb.dtype), (0, 0))

            accs = _walk(
                plan, band, gi, ci, lq, lk,
                [acc_ref[:, rows] for rows in own],
                lambda at, k0: (k_ref[0, 0, at, :], v_ref[0, 0, at, :]),
                chain, all_masked)
            for rows, acc in zip(own, accs):
                acc_ref[:, rows] = acc
        else:
            lo = ci * resident_k
            for r, rows in enumerate(own):
                q0 = (gi * group_q + r) * bq
                qs = _scaled(q_ref[0, 0, rows, :], scale)
                gb = g_ref[0, 0, rows, :]
                lse, dd = lse_ref[0, 0, :, rows], d_ref[0, 0, :, rows]

                def tile(j, acc, masked, qs=qs, gb=gb, lse=lse, dd=dd, q0=q0):
                    at = _tile_rows(j, lo, bk, resident_k)
                    _, ds, _, kb, _ = recompute(
                        qs, k_ref[0, 0, at, :], v_ref[0, 0, at, :], gb, lse,
                        dd, q0, j * bk, masked, opt, bi, hi)
                    return acc + _dot(kb, ds.astype(kb.dtype), (0, 0))

                plain, need = _k_tile_range(q0, bq, bk, offset, n_k, causal,
                                            all_masked)
                acc_ref[:, rows] = _walk_tiles(
                    lo, resident_k, (plain, need), (0, plain), tile,
                    acc_ref[:, rows])

        @pl.when(ci == pl.cdiv(n_k, resident_k) - 1)
        def _emit():
            # the scale that q carried into s, now on its way out
            dq_ref[0, 0] = (acc_ref[...] * scale).T.astype(dq_ref.dtype)

    # ---- dk/dv kernel: grid (b, h, k groups, q chunks) -----------------
    group_k, resident_q, whole_dkv = plan.group, plan.resident, plan.whole

    def dkv_kernel(*refs):
        q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref = refs[:6]
        opt, i = optional_refs(refs, 6)
        if has_bias:
            dk_ref, dv_ref, db_ref = refs[i:i + 3]
            dk_acc, dv_acc, db_acc = refs[i + 3:i + 6]
        else:
            dk_ref, dv_ref = refs[i:i + 2]
            dk_acc, dv_acc = refs[i + 2:i + 4]
            db_ref = db_acc = None
        bi = pl.program_id(0)
        hi = pl.program_id(1)
        gi = 0 if whole_dkv else pl.program_id(2)
        ci = 0 if whole_dkv else pl.program_id(3)

        @pl.when(ci == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)
            if has_bias:
                db_acc[...] = jnp.zeros_like(db_acc)

        own = [pl.ds(r * bk, bk) for r in range(group_k)]
        if plan.walk:
            chains = [(k_ref[0, 0, rows, :], v_ref[0, 0, rows, :])
                      for rows in own]

            def chain(r, queries, q0, carry, masked):
                p_t, ds, qs, _, gb = recompute(
                    queries[0], *chains[r], *queries[1:], q0,
                    gi * group_k * bk + r * bk, masked, opt, bi, hi)
                return (carry[0] + _dot(ds.astype(qs.dtype), qs, (1, 0)),
                        carry[1] + _dot(p_t.astype(gb.dtype), gb, (1, 0)))

            sums = _walk(
                plan, band, gi, ci, lq, lk,
                [(dk_acc[rows, :], dv_acc[rows, :]) for rows in own],
                lambda at, q0: (_scaled(q_ref[0, 0, at, :], scale),
                                g_ref[0, 0, at, :], lse_ref[0, 0, :, at],
                                d_ref[0, 0, :, at]),
                chain, all_masked)
            for rows, (dk, dv_) in zip(own, sums):
                dk_acc[rows, :], dv_acc[rows, :] = dk, dv_
        else:
            lo = ci * resident_q
            for r, rows in enumerate(own):
                k0 = (gi * group_k + r) * bk
                kb, vb = k_ref[0, 0, rows, :], v_ref[0, 0, rows, :]

                def tile(j, carry, masked, kb=kb, vb=vb, k0=k0):
                    at = _tile_rows(j, lo, bq, resident_q)
                    p_t, ds, qs, _, gb = recompute(
                        _scaled(q_ref[0, 0, at, :], scale), kb, vb,
                        g_ref[0, 0, at, :], lse_ref[0, 0, :, at],
                        d_ref[0, 0, :, at], j * bq, k0, masked, opt, bi, hi)
                    # dk through the scaled q: the scale of s, for nothing
                    dk = carry[0] + _dot(ds.astype(qs.dtype), qs, (1, 0))
                    dv = carry[1] + _dot(p_t.astype(gb.dtype), gb, (1, 0))
                    if has_bias:
                        return dk, dv, carry[2] + jnp.sum(ds, axis=1,
                                                          keepdims=True)
                    return dk, dv

                first, plain = _q_tile_range(k0, bq, bk, offset, n_q, causal,
                                             all_masked)
                carry = (dk_acc[rows, :], dv_acc[rows, :])
                if has_bias:
                    carry += (db_acc[...],)
                carry = _walk_tiles(lo, resident_q, (first, plain),
                                    (plain, n_q), tile, carry)
                dk_acc[rows, :], dv_acc[rows, :] = carry[:2]
                if has_bias:
                    db_acc[...] = carry[2]

        @pl.when(ci == pl.cdiv(n_q, resident_q) - 1)
        def _emit():
            dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)
            if has_bias:
                db_ref[0, 0] = db_acc[...]

    def common_specs(rows_q, rows_k, order):
        """In-specs for q/g/lse/D + k/v + optionals; ``order`` maps grid
        ids -> (q block, k block) for the kernel's grid layout."""
        def im_q(bi, hi, g2, g3):
            return (bi, hi, order(g2, g3)[0], 0)

        def im_k(bi, hi, g2, g3):
            return (bi, hi // share if share > 1 else hi,
                    order(g2, g3)[1], 0)

        def im_stat(bi, hi, g2, g3):
            return (bi, hi, 0, order(g2, g3)[0])

        specs = [
            pl.BlockSpec((1, 1, rows_q, d), im_q, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, rows_k, d), im_k, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, rows_k, dv), im_k, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, rows_q, dv), im_q, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1, rows_q), im_stat,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1, rows_q), im_stat,
                         memory_space=pltpu.VMEM),
        ]
        args = list(operands)
        if has_bias:
            # a column, keys on sublanes like the tile
            specs.append(pl.BlockSpec(
                (1, 1, bk, 1),
                lambda bi, hi, g2, g3: (
                    bi if bb > 1 else 0, hi if bh > 1 else 0,
                    order(g2, g3)[1], 0),
                memory_space=pltpu.VMEM))
            args.append(jnp.swapaxes(bias.astype(jnp.float32), 2, 3))
        if has_seg:
            specs.append(pl.BlockSpec(
                (1, 8, bq),
                lambda bi, hi, g2, g3: (bi, 0, order(g2, g3)[0]),
                memory_space=pltpu.VMEM))
            specs.append(pl.BlockSpec(
                (1, bk, 8),
                lambda bi, hi, g2, g3: (bi, order(g2, g3)[1], 0),
                memory_space=pltpu.VMEM))
            args += _seg_operands(q_seg, kv_seg, b, lq, lk)
        if has_drop:
            specs.append(pl.BlockSpec(
                (2,), lambda bi, hi, g2, g3: (0,),
                memory_space=pltpu.SMEM))
            args.append(seed.astype(jnp.int32))
        return specs, args

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=_WALK_VMEM_BYTES if plan.walk else None)

    if kernel == "dq":
        rows_q, rows_k = group_q * bq, resident_k * bk
        dq_specs, dq_args = common_specs(rows_q, rows_k,
                                         lambda g2, g3: (g2, g3))
        return [pl.pallas_call(
            dq_kernel,
            grid=(b, h, pl.cdiv(lq, rows_q), pl.cdiv(n_k, resident_k)),
            in_specs=dq_specs,
            out_specs=pl.BlockSpec((1, 1, rows_q, d),
                                   lambda bi, hi, gi, ci: (bi, hi, gi, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((d, rows_q), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
        )(*dq_args)]

    rows_q, rows_k = resident_q * bq, group_k * bk
    kv_specs, kv_args = common_specs(rows_q, rows_k, lambda g2, g3: (g3, g2))
    kv_out_specs = [
        pl.BlockSpec((1, 1, rows_k, d),
                     lambda bi, hi, gi, ci: (bi, hi, gi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, rows_k, dv),
                     lambda bi, hi, gi, ci: (bi, hi, gi, 0),
                     memory_space=pltpu.VMEM),
    ]
    # a query head's share of its key-value head's gradient, in float32
    # where several heads share one (summed by `_flash_bwd_pallas`)
    kv_out_shape = [jax.ShapeDtypeStruct(
        (b, h) + x.shape[2:], x.dtype if share == 1 else jnp.float32)
        for x in (k, v)]
    kv_scratch = [pltpu.VMEM((rows_k, d), jnp.float32),
                  pltpu.VMEM((rows_k, dv), jnp.float32)]
    if has_bias:
        kv_out_specs.append(pl.BlockSpec(
            (1, 1, bk, 1), lambda bi, hi, gi, ci: (bi, hi, gi, 0),
            memory_space=pltpu.VMEM))
        kv_out_shape.append(
            jax.ShapeDtypeStruct((b, h, n_k * bk, 1), jnp.float32))
        kv_scratch.append(pltpu.VMEM((bk, 1), jnp.float32))
    return pl.pallas_call(
        dkv_kernel,
        grid=(b, h, pl.cdiv(lk, rows_k), pl.cdiv(n_q, resident_q)),
        in_specs=kv_specs,
        out_specs=kv_out_specs,
        out_shape=kv_out_shape,
        scratch_shapes=kv_scratch,
        compiler_params=params,
        interpret=interpret,
    )(*kv_args)


def _env_flag(name: str) -> bool:
    # same convention as engine.py's ZOO_SHARD_OPTIMIZER: "0"/"" are false
    return os.environ.get(name, "") not in ("", "0")


def _interpret_forced() -> bool:
    return _env_flag("ZOO_FLASH_INTERPRET")


def _pallas_available() -> bool:
    # ZOO_FLASH_FORCE_PALLAS routes to the REAL (non-interpret) kernels on
    # any backend — lowering-only CI: tracing + lower(platforms=("tpu",))
    # then goes through genuine Mosaic lowering with no chip (interpret
    # mode lowers to plain jax ops and exercises none of it; the round-4
    # backward cross-lowering guard was vacuous for exactly that reason).
    # Executing under this knob off-TPU will fail — lower, don't run.
    return (jax.default_backend() == "tpu" or _interpret_forced()
            or _env_flag("ZOO_FLASH_FORCE_PALLAS"))


# ---------------------------------------------------------------------------
# custom_vjp core: array args explicit so bias/segments/seed differentiate
# (or get float0 cotangents) correctly.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash_core(q, k, v, bias, q_seg, kv_seg, seed, causal, scale,
                dropout_p, block_q, block_k):
    return _forward_impl(q, k, v, bias, q_seg, kv_seg, seed, causal, scale,
                         dropout_p, block_q, block_k)


def _forward_impl(q, k, v, bias, q_seg, kv_seg, seed, causal, scale,
                  dropout_p, block_q, block_k, return_stats=False):
    if _pallas_available():
        # A kernel that fails to trace raises: on a TPU nothing degrades
        # to the O(L^2) reference behind the caller's back.
        res = _flash_fwd_pallas(
            q, k, v, causal, scale, block_q, block_k,
            interpret=_interpret_forced(), bias=bias, q_seg=q_seg,
            kv_seg=kv_seg, dropout_p=dropout_p, seed=seed,
            return_stats=return_stats)
        invocation_counts["pallas"] += 1
        return res
    invocation_counts["fallback"] += 1
    out = _attention_reference(q, k, v, causal, scale, bias=bias,
                               q_seg=q_seg, kv_seg=kv_seg,
                               dropout_p=dropout_p, seed=seed)
    return (out, None, None) if return_stats else out


def _fwd(q, k, v, bias, q_seg, kv_seg, seed, causal, scale, dropout_p,
         block_q, block_k):
    # Save the softmax stats (m, l) alongside the output: the backward
    # then needs no stats-recompute pass (a full extra QK^T sweep).
    out, m, l = _forward_impl(q, k, v, bias, q_seg, kv_seg, seed, causal,
                              scale, dropout_p, block_q, block_k,
                              return_stats=True)
    # Named HERE, on the arrays `_bwd` reads: under a jax.checkpoint whose
    # policy saves "attn_context" (apply_remat's "attn") these three are
    # kept and the forward kernel is not run again for them; a name on the
    # caller's copy of `out` keeps an array the backward never reads.
    # Outside such a checkpoint the name is the identity.
    out, m, l = (None if x is None else checkpoint_name(x, "attn_context")
                 for x in (out, m, l))
    return out, (q, k, v, bias, q_seg, kv_seg, seed, out, m, l)


def _bwd(causal, scale, dropout_p, block_q, block_k, res, g):
    """Flash backward.  On TPU (stats saved by the Pallas forward):
    `_flash_bwd_pallas` — two streaming kernels whose score tiles never
    leave VMEM.  Otherwise (CPU or full-(Lq,Lk)-bias grad): blockwise lax.scan over key blocks, recomputing each
    (lq, block_k) score tile from q/k (rematerialisation).  Live memory is
    O(lq·block_k + lk·d) either way; the (lq, lk) matrix is never
    materialized.  Dropout is re-derived from the same `_keep_bits` hash
    the forward used, so no mask is stored."""
    q, k, v, bias, q_seg, kv_seg, seed, out, m_s, l_s = res
    b, h, lq, d = q.shape
    lk, dv = k.shape[2], v.shape[3]
    kv_heads, kv_dtype = k.shape[1], k.dtype
    scale_v = 1.0 / math.sqrt(d) if scale is None else scale
    offset = lk - lq
    has_bias = bias is not None
    has_seg = q_seg is not None
    has_drop = dropout_p > 0.0

    dseg_q = (np.zeros(q_seg.shape, dtype=jax.dtypes.float0)
              if has_seg else None)
    dseg_kv = (np.zeros(kv_seg.shape, dtype=jax.dtypes.float0)
               if has_seg else None)
    dseed = (np.zeros(seed.shape, dtype=jax.dtypes.float0)
             if seed is not None else None)

    full_bias = has_bias and bias.shape[2] > 1
    if m_s is not None and _pallas_available() and not full_bias:
        dq, dk, dv, dbias = _flash_bwd_pallas(
            q, k, v, g, out, m_s, l_s, causal, scale_v,
            block_q=block_q, block_k=block_k,
            interpret=_interpret_forced(), bias=bias, q_seg=q_seg,
            kv_seg=kv_seg, dropout_p=dropout_p, seed=seed)
        return (dq, dk, dv, dbias, dseg_q, dseg_kv, dseed)
    # The fallback scan keeps its own 256 cap: it materializes
    # (b, h, lq, bk) f32 score/grad tiles in HBM, so the forward kernel's
    # tiles would multiply live memory and can OOM long-context
    # training.  A caller's SMALLER explicit block_k is honored.
    bk = min(block_k or 256, 256, lk)
    n_k = -(-lk // bk)
    pad = n_k * bk - lk
    k, v = (_per_query_head(x, h) for x in (k, v))

    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(jnp.float32)
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(jnp.float32)
    # (n_k, b, h, bk, d) so scan iterates key blocks
    kb_s = jnp.moveaxis(kp.reshape(b, h, n_k, bk, d), 2, 0)
    vb_s = jnp.moveaxis(vp.reshape(b, h, n_k, bk, dv), 2, 0)
    kpos_s = jnp.arange(n_k * bk, dtype=jnp.int32).reshape(n_k, bk)
    q_pos = jnp.arange(lq, dtype=jnp.int32)
    if has_bias:
        bb, bh, bq, _ = bias.shape
        bias_p = jnp.pad(bias.astype(jnp.float32),
                         ((0, 0), (0, 0), (0, 0), (0, pad)))
        bias_s = jnp.moveaxis(bias_p.reshape(bb, bh, bq, n_k, bk), 3, 0)
    else:
        bias_s = jnp.zeros((n_k, 1, 1, 1, 1), jnp.float32)
    if has_seg:
        kseg_p = jnp.pad(kv_seg.astype(jnp.int32), ((0, 0), (0, pad)),
                         constant_values=-1)
        kseg_s = jnp.moveaxis(kseg_p.reshape(b, n_k, bk), 1, 0)
        qseg = q_seg.astype(jnp.int32)
    else:
        kseg_s = jnp.zeros((n_k, 1, 1), jnp.int32)
        qseg = None

    def block_scores(kb, kpos, bias_blk, kseg_blk):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb) * scale_v
        if has_bias:
            s = s + bias_blk
        live = (kpos < lk)[None, :]  # (1, bk) -> broadcast (lq, bk)
        if causal:
            live = live & (q_pos[:, None] + offset >= kpos[None, :])
        live = live[None, None]  # (1, 1, lq, bk)
        if has_seg:
            live = live & (qseg[:, None, :, None] ==
                           kseg_blk[:, None, None, :])
        return jnp.where(live, s, _NEG), live

    # pass 1: streaming softmax stats (m, l) per query row
    def stats_step(carry, xs):
        m, l = carry
        kb, kpos, bias_blk, kseg_blk = xs
        s, live = block_scores(kb, kpos, bias_blk, kseg_blk)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1))
        l = l * jnp.exp(m - new_m) + jnp.sum(
            jnp.where(live, jnp.exp(s - new_m[..., None]), 0.0), axis=-1)
        return (new_m, l), None

    if m_s is not None:
        # forward already saved the softmax stats — pass 1 unnecessary
        m = m_s.astype(jnp.float32)
        l = l_s.astype(jnp.float32)
    else:
        m0 = jnp.full((b, h, lq), _NEG, jnp.float32)
        l0 = jnp.zeros((b, h, lq), jnp.float32)
        (m, l), _ = jax.lax.scan(stats_step, (m0, l0),
                                 (kb_s, kpos_s, bias_s, kseg_s))
    l_safe = jnp.maximum(l, 1e-20)
    # D_i = sum_j P~_ij (dO_i · V_j) = dO_i · O_i  (flash-bwd identity;
    # holds with dropout because O already contains the dropped P~)
    D = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # (b, h, lq)
    if has_drop:
        thr = _drop_threshold(dropout_p)
        inv_keep = 1.0 / (1.0 - dropout_p)
        b_idx = jnp.arange(b, dtype=jnp.int32)[:, None, None, None]
        h_idx = jnp.arange(h, dtype=jnp.int32)[None, :, None, None]

    # pass 2: accumulate dQ; emit per-block dK/dV (and dbias tiles)
    def grad_step(dq, xs):
        kb, vb, kpos, bias_blk, kseg_blk = xs
        s, live = block_scores(kb, kpos, bias_blk, kseg_blk)
        p = jnp.where(live, jnp.exp(s - m[..., None]), 0.0) / l_safe[
            ..., None]
        if has_drop:
            bits = _keep_bits(seed[0], seed[1], b_idx, h_idx,
                              q_pos[None, None, :, None],
                              kpos[None, None, None, :])
            t = jnp.where(bits >= thr, inv_keep, 0.0)
            p_t = p * t
        else:
            p_t = p
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vb)
        # softmax jacobian: dL/ds = P (t·dp − D); the q·k scale folds into
        # dq/dk below, while dbias takes the unscaled dL/ds
        ds_raw = p * ((dp * t if has_drop else dp) - D[..., None])
        ds = ds_raw * scale_v
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kb)
        dkb = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        dvb = jnp.einsum("bhqk,bhqd->bhkd", p_t, gf)
        if has_bias:
            db = ds_raw
            if bb == 1:
                db = jnp.sum(db, axis=0, keepdims=True)
            if bh == 1:
                db = jnp.sum(db, axis=1, keepdims=True)
            if bq == 1:
                db = jnp.sum(db, axis=2, keepdims=True)
        else:
            db = jnp.zeros((1, 1, 1, bk), jnp.float32)
        return dq, (dkb, dvb, db)

    dq0 = jnp.zeros_like(qf)
    dq, (dk_s, dv_s, db_s) = jax.lax.scan(
        grad_step, dq0, (kb_s, vb_s, kpos_s, bias_s, kseg_s))
    dk = jnp.moveaxis(dk_s, 0, 2).reshape(b, h, n_k * bk, d)[:, :, :lk]
    dv = jnp.moveaxis(dv_s, 0, 2).reshape(b, h, n_k * bk, dv)[:, :, :lk]
    if has_bias:
        dbias = jnp.moveaxis(db_s, 0, 3).reshape(
            bb, bh, bq, n_k * bk)[..., :lk].astype(bias.dtype)
    else:
        dbias = None
    return (dq.astype(q.dtype), _per_kv_head(dk, kv_heads).astype(kv_dtype),
            _per_kv_head(dv, kv_heads).astype(kv_dtype), dbias, dseg_q,
            dseg_kv, dseed)


_flash_core.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, *, bias=None, q_segment_ids=None,
                    kv_segment_ids=None, dropout_p=0.0, dropout_seed=None):
    """Fused attention: Pallas kernel on TPU, jnp fallback elsewhere.

    Args:
      q, k, v: (B, H, L, D); v may have a last dimension of its own, Dv
        (latent attention: q and k at 192, v at 128).  The output is then
        (B, H, Lq, Dv), the default scale stays 1/sqrt(D), and all three
        kernels read both widths from the operands' shapes.  k and v may
        have fewer heads than q, H_kv dividing H (grouped-query heads):
        query head h reads key-value head h // (H / H_kv) where it lies,
        and a key-value head's gradient is the sum of its query heads'.
      bias: optional additive f32 mask/bias, shape (B|1, H|1, Lq|1, Lk) —
        the BERT (B, 1, 1, L) padding mask streams as (1, block_k) tiles.
      q_segment_ids / kv_segment_ids: optional (B, Lq)/(B, Lk) int arrays;
        attention masked where segments differ (packed sequences).
      dropout_p: attention-prob dropout; requires ``dropout_seed`` (int,
        PRNG key, or (2,) int array).  The mask is hash-derived in-kernel.

    ``block_q``/``block_k`` are the tile, (block_k, block_q) scores; on a
    TPU ``block_q`` is a multiple of 128 (queries lie on lanes).  Default
    tiles come from ``_resolve_blocks`` and ``_resolve_bwd_blocks``: at
    most 1024x1024 (clean), 1024x512 (dropout), 512x512 (full (Lq, Lk)
    bias, and every backward), sized against the v5e ~16 MB scoped-VMEM
    budget, and for a causal call half of the sequence a side where that
    is less (a quarter in the dk/dv kernel), so that tiles above the
    diagonal go — see those functions' docstrings for the measured limits
    that set them.  A call longer than one grid step owns (from 2,048
    causal tokens on) walks: the step's 1,024 rows in two or four
    sub-tiles a loop body against 1,024-row tiles of the other side
    (``_WALK_TILES``), where explicit blocks name the sub-tile and the
    walked tile."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    if h % k.shape[1] or v.shape[1] != k.shape[1]:
        raise ValueError(f"{h} query heads over {k.shape[1]} key and "
                         f"{v.shape[1]} value heads")
    if bias is not None:
        bias = jnp.asarray(bias)
        if bias.ndim != 4 or bias.shape[3] != lk or \
                bias.shape[0] not in (1, b) or bias.shape[1] not in (1, h) \
                or bias.shape[2] not in (1, lq):
            raise ValueError(
                f"bias shape {bias.shape} not broadcastable to "
                f"({b}|1, {h}|1, {lq}|1, {lk})")
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("q_segment_ids and kv_segment_ids must be given "
                         "together")
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    seed = _normalize_seed(dropout_seed) if dropout_p > 0.0 else None
    return _flash_core(q, k, v, bias, q_segment_ids, kv_segment_ids, seed,
                       causal, scale, float(dropout_p), block_q, block_k)


_STEP_FNS: dict = {}


def flash_attention_step(q, k, v, causal=False):
    """:func:`flash_attention` compiled through the choke point.

    Eager callers (bench legs, serving paths outside a train step) get
    the kernel-plane contract: the program lowers via ``compile_step``/
    ``timed_compile`` under the ``kernel_flash_attention`` label, so the
    persistent cache, ``zoo_compile_seconds`` and the HLO feature pipe
    all see it.  ``causal`` selects a separate cached program —
    PlannedStep keys python scalars by type only, so it must not be a
    traced argument."""
    from analytics_zoo_tpu.ops.pallas import kernel_step

    causal = bool(causal)
    fn = _STEP_FNS.get(causal)
    if fn is None:
        def fn(q, k, v, _causal=causal):
            return flash_attention(q, k, v, causal=_causal)

        _STEP_FNS[causal] = fn
    name = "flash_attention_causal" if causal else "flash_attention"
    return kernel_step(name, fn)(q, k, v)
