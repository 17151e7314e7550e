# zoolint: disable-file=raw-pallas-call -- ops/pallas/ is the one home
# for raw pl.pallas_call; everything here ships a jnp fallback oracle and
# lowers under a kernel_* label through the compile choke point.
"""Flash attention — Pallas TPU kernel with streaming softmax.

The hot op behind TransformerLayer/BERT (reference materializes the full
(L, L) score matrix per head, TransformerLayer.scala:137).  This kernel
tiles Q over the grid and streams K/V blocks through VMEM with the
numerically-stable online-softmax accumulation, so HBM traffic is O(L·D)
per head instead of O(L²), and the score block lives only in VMEM where the
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
(``_flash_bwd_pallas``: a dq kernel streaming K/V blocks past each q
block, and a dk/dv/dbias kernel streaming q blocks past each K/V block)
whose rematerialized score tiles never leave VMEM.  Elsewhere — CPU, or
a full (Lq, Lk) bias that needs its own O(Lq·Lk) gradient — a blockwise
lax.scan over key blocks serves as fallback and oracle (O(Lq·block_k)
live memory).  Either way long-context training never materializes the
(L, L) matrix.  On CPU (tests) the forward falls
back to the jnp path automatically; set ``ZOO_FLASH_INTERPRET=1`` to
force the actual Pallas kernels in interpret mode on CPU (CI routing +
grad-oracle tests).
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

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


def _attention_reference(q, k, v, causal, scale, bias=None, q_seg=None,
                         kv_seg=None, dropout_p=0.0, seed=None):
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
    block_q, block_k = _resolve_blocks(block_q, block_k)
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
# Pallas forward
# ---------------------------------------------------------------------------


def _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                      interpret=False, bias=None, q_seg=None, kv_seg=None,
                      dropout_p=0.0, seed=None, return_stats=False):
    """Streaming forward: K/V blocks are a GRID dimension.

    grid = (b, h, n_q, n_k) with the key-block index innermost; Pallas's
    pipeline DMAs exactly one (block_k, d) K and V tile into VMEM per grid
    step (double-buffered against compute), so VMEM holds O(block_q·d +
    block_k·d) — never the whole (lk, d) K/V — and max sequence length is
    bounded by HBM, not VMEM.  Softmax running stats (m, l) and the output
    accumulator persist across the ki steps in VMEM scratch.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    offset = lk - lq  # end-aligned causal diagonal
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    n_q = pl.cdiv(lq, block_q)
    n_k = pl.cdiv(lk, block_k)
    has_bias = bias is not None
    has_seg = q_seg is not None
    has_drop = dropout_p > 0.0
    if has_bias:
        bb, bh, bq, _ = bias.shape
        bq_blk = block_q if bq > 1 else 1

    def kernel(*refs):
        i = 3
        q_ref, k_ref, v_ref = refs[:3]
        if has_bias:
            bias_ref = refs[i]
            i += 1
        if has_seg:
            qseg_ref, kseg_ref = refs[i:i + 2]
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

        bi = pl.program_id(0)
        hi = pl.program_id(1)
        qi = pl.program_id(2)
        ki = pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q_start = qi * block_q
        k_start = ki * block_k

        def compute():
            qb = q_ref[0, 0].astype(jnp.float32)
            kb = k_ref[0, 0].astype(jnp.float32)
            vb = v_ref[0, 0].astype(jnp.float32)
            # Zero padded key rows (lk % block_k != 0): OOB block reads are
            # unspecified, and a NaN there would poison p @ v even with
            # p == 0 at those columns (0 * NaN = NaN).
            k_live = (
                k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, 1), 0) < lk
            )
            kb = jnp.where(k_live, kb, 0.0)
            vb = jnp.where(k_live, vb, 0.0)
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            if has_bias:
                s = s + bias_ref[0, 0].astype(jnp.float32)
            # mask padded key rows (lk % block_k != 0), if causal the
            # end-aligned upper triangle, and cross-segment pairs
            live = k_pos < lk
            if causal:
                live = live & (q_pos + offset >= k_pos)
            if has_seg:
                # q_seg rides as (B, Lq, 8) and kv_seg as (B, 8, Lk): a bare
                # (B, L) operand would need block (1, block) whose
                # second-to-last dim violates Mosaic's (8, 128)-or-full-dim
                # block rule on real TPU (interpret mode does not check).
                sq = qseg_ref[0][:, :1]            # (block_q, 1)
                sk = kseg_ref[0][:1, :]            # (1, block_k)
                live = live & (sq == sk)
            s = jnp.where(live, s, _NEG)
            m, l = m_ref[...], l_ref[...]
            new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - new_m)
            p = jnp.where(live, jnp.exp(s - new_m), 0.0)
            m_ref[...] = new_m
            # l is the full softmax denominator (pre-dropout), so the final
            # acc / l division reproduces dropout-after-softmax semantics
            l_ref[...] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if has_drop:
                bits = _keep_bits(seed_ref[0], seed_ref[1], bi, hi,
                                  q_pos, k_pos)
                p = jnp.where(bits >= _drop_threshold(dropout_p),
                              p * (1.0 / (1.0 - dropout_p)), 0.0)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        if causal:
            # Skip compute for key blocks fully above this query block's
            # diagonal (their DMA is still pipelined, but no MXU work).
            pl.when(k_start <= q_start + block_q - 1 + offset)(compute)
        else:
            compute()

        @pl.when(ki == n_k - 1)
        def _emit():
            o_ref[0, 0] = (
                acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
            ).astype(o_ref.dtype)
            if return_stats:
                m_out_ref[0, 0] = m_ref[...]
                l_out_ref[0, 0] = l_ref[...]

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bi, hi, qi, ki: (bi, hi, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi, ki, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi, ki, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, 1, bq_blk, block_k),
            lambda bi, hi, qi, ki, _bb=bb, _bh=bh, _bq=bq: (
                bi if _bb > 1 else 0, hi if _bh > 1 else 0,
                qi if _bq > 1 else 0, ki),
            memory_space=pltpu.VMEM))
        args.append(bias.astype(jnp.float32))
    if has_seg:
        in_specs.append(pl.BlockSpec(
            (1, block_q, 8), lambda bi, hi, qi, ki: (bi, qi, 0),
            memory_space=pltpu.VMEM))
        in_specs.append(pl.BlockSpec(
            (1, 8, block_k), lambda bi, hi, qi, ki: (bi, 0, ki),
            memory_space=pltpu.VMEM))
        args.append(jnp.broadcast_to(
            q_seg.astype(jnp.int32)[:, :, None], (b, lq, 8)))
        args.append(jnp.broadcast_to(
            kv_seg.astype(jnp.int32)[:, None, :], (b, 8, lk)))
    if has_drop:
        in_specs.append(pl.BlockSpec(
            (2,), lambda bi, hi, qi, ki: (0,),
            memory_space=pltpu.SMEM))
        args.append(seed.astype(jnp.int32))

    grid = (b, h, n_q, n_k)
    out_specs = pl.BlockSpec((1, 1, block_q, d),
                             lambda bi, hi, qi, ki: (bi, hi, qi, 0),
                             memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if return_stats:
        stat_spec = pl.BlockSpec((1, 1, block_q, 1),
                                 lambda bi, hi, qi, ki: (bi, hi, qi, 0),
                                 memory_space=pltpu.VMEM)
        stat_shape = jax.ShapeDtypeStruct((b, h, lq, 1), jnp.float32)
        out_specs = [out_specs, stat_spec, stat_spec]
        out_shape = [out_shape, stat_shape, stat_shape]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    if return_stats:
        out, m, l = res
        return out, m[..., 0], l[..., 0]
    return res


def _resolve_blocks(block_q, block_k,
                    full_bias: bool = False,
                    dropout: bool = False) -> tuple[int, int]:
    """Block defaults sized against the v5e ~16 MB scoped-VMEM budget.

    The dominant live buffers are the (block_q, block_k) f32 score and
    prob tiles; in-kernel dropout adds a PRNG-bits tile of the same shape
    and a full (…, Lq, Lk) bias streams an extra f32 tile.  The r03-tuned
    2048-row blocks left <1% headroom and went over once those operands
    landed (measured: 16.09M/16M clean @4k d=64, 22.73M/16M dropout @2k
    d=128 — both hard compile failures on the chip), so: 1024x1024 clean
    (~10 MB live), block_k 512 under dropout/full-bias (~8 MB live).
    Explicit block_q/block_k arguments always win."""
    if full_bias:
        return block_q or 512, block_k or 512
    if block_q is None:
        block_q = 1024
    if block_k is None:
        block_k = 512 if dropout else 1024
    return block_q, block_k


def _resolve_bwd_blocks(block_q, block_k, lq, lk) -> tuple[int, int]:
    """Backward blocks: 512x512 keeps both kernels' live VMEM ~7 MB at
    d=128 with dropout (f32 q/g/k/v casts + up to four (bq, bk) f32
    score/prob/grad tiles + the PRNG-bits tile + (bq|bk, d) accumulators),
    well under the measured ~16 MB scoped budget that burned the 1024-row
    forward tuning (see _resolve_blocks).  A caller's SMALLER explicit
    blocks are honored (the VMEM-pressure escape hatch); anything larger —
    including the forward's resolved 1024 defaults flowing through
    _flash_core — is capped at 512 because the backward holds roughly
    twice the forward's live tiles per step."""
    return (min(block_q or 512, 512, lq),
            min(block_k or 512, 512, lk))


def _flash_bwd_pallas(q, k, v, g, out, m, l, causal, scale,
                      block_q=None, block_k=None, interpret=False,
                      bias=None, q_seg=None, kv_seg=None, dropout_p=0.0,
                      seed=None):
    """Pallas flash backward: two kernels, both O(block²) VMEM.

    dq kernel: grid (b, h, n_q, n_k) — a q block accumulates dq across
    streamed K/V blocks.  dk/dv kernel: grid (b, h, n_k, n_q) — a K/V
    block accumulates dk/dv (and its bias-grad tile) across streamed q
    blocks.  Score tiles are rematerialized from q/k in VMEM (standard
    flash strategy) using the forward's saved softmax stats (m, l), so
    no stats-recompute pass exists and nothing O(Lq·Lk) ever reaches
    HBM.  Dropout re-derives the forward's exact keep mask from the
    `_keep_bits` position hash.

    Bias gradients are emitted per (b, h) as (b, h, 1, lk) partials and
    reduced outside to the bias's broadcast shape; full (…, Lq, Lk)
    biases are NOT handled here (their db is itself O(Lq·Lk) — callers
    fall back to the jnp blockwise path).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    offset = lk - lq
    invocation_counts["pallas"] += 1
    bq, bk = _resolve_bwd_blocks(block_q, block_k, lq, lk)
    n_q = pl.cdiv(lq, bq)
    n_k = pl.cdiv(lk, bk)
    has_bias = bias is not None
    has_seg = q_seg is not None
    has_drop = dropout_p > 0.0
    if has_bias:
        bb, bh, bq_dim, _ = bias.shape
        if bq_dim > 1:
            raise ValueError("full (Lq, Lk) bias backward not supported "
                             "in the Pallas path")

    gf = g.astype(jnp.float32)
    # D_i = dO_i · O_i (flash-bwd identity; holds under dropout because
    # O already contains the dropped probabilities)
    D = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # (b, h, lq)
    m4 = m.astype(jnp.float32)[..., None]               # (b, h, lq, 1)
    l4 = jnp.maximum(l.astype(jnp.float32), 1e-20)[..., None]
    D4 = D[..., None]

    thr = _drop_threshold(dropout_p) if has_drop else None
    inv_keep = 1.0 / (1.0 - dropout_p) if has_drop else None

    def tiles(q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, d_ref, bias_ref,
              qseg_ref, kseg_ref, seed_ref, bi, hi, qi, ki):
        """Shared per-(q block, k block) recompute: returns
        (p_t, ds_raw, ds, qb, kb, gb) — all f32 tiles.  bi/hi/qi/ki are
        program ids read OUTSIDE any pl.when branch (program_id inside a
        cond branch cannot lower in interpret mode)."""
        q_start = qi * bq
        k_start = ki * bk
        qb = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        gb = g_ref[0, 0].astype(jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        q_live = q_pos < lq
        k_live = k_pos < lk
        # zero padded rows: OOB block reads are unspecified and a NaN
        # would poison the accumulations through 0 * NaN
        qb = jnp.where(q_live, qb, 0.0)
        gb = jnp.where(q_live, gb, 0.0)
        # column-oriented mask built directly from iota: reshaping the
        # (1, bk) i1 vector is a Mosaic "insert minor dim" op that only
        # lowers for 32-bit types on real TPU
        k_live_col = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0)) < lk
        kb = jnp.where(k_live_col, kb, 0.0)
        vb = jnp.where(k_live_col, vb, 0.0)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        live = q_live & k_live
        if causal:
            live = live & (q_pos + offset >= k_pos)
        if has_seg:
            live = live & (qseg_ref[0][:, :1] == kseg_ref[0][:1, :])
        mb = m_ref[0, 0]  # (bq, 1) f32
        lb = l_ref[0, 0]
        db_row = d_ref[0, 0]
        # division and D-subtraction INSIDE the where: padded q rows read
        # OOB stats (NaN/0 in interpret mode, unspecified on hardware) and
        # the dk/dv kernel CONTRACTS over q rows — a NaN there would
        # poison every output element, so masked entries must be exact 0s
        p = jnp.where(live, jnp.exp(s - mb) / lb, 0.0)
        dp = jax.lax.dot_general(
            gb, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if has_drop:
            bits = _keep_bits(seed_ref[0], seed_ref[1], bi, hi,
                              q_pos, k_pos)
            t = jnp.where(bits >= thr, inv_keep, 0.0)
            p_t = p * t
            ds_raw = jnp.where(live, p * (t * dp - db_row), 0.0)
        else:
            p_t = p
            ds_raw = jnp.where(live, p * (dp - db_row), 0.0)
        return p_t, ds_raw, ds_raw * scale, qb, kb, gb

    # ---- dq kernel: grid (b, h, n_q, n_k), key blocks innermost --------
    def dq_kernel(*refs):
        i = 7
        q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, d_ref = refs[:7]
        bias_ref = qseg_ref = kseg_ref = seed_ref = None
        if has_bias:
            bias_ref = refs[i]
            i += 1
        if has_seg:
            qseg_ref, kseg_ref = refs[i:i + 2]
            i += 2
        if has_drop:
            seed_ref = refs[i]
            i += 1
        dq_ref, acc_ref = refs[i], refs[i + 1]
        bi = pl.program_id(0)
        hi = pl.program_id(1)
        qi = pl.program_id(2)
        ki = pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def compute():
            _, _, ds, _, kb, _ = tiles(
                q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, d_ref,
                bias_ref, qseg_ref, kseg_ref, seed_ref, bi, hi, qi, ki)
            acc_ref[...] += jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if causal:
            pl.when(ki * bk <= qi * bq + bq - 1 + offset)(compute)
        else:
            compute()

        @pl.when(ki == n_k - 1)
        def _emit():
            dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)

    # ---- dk/dv kernel: grid (b, h, n_k, n_q), q blocks innermost -------
    def dkv_kernel(*refs):
        i = 7
        q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, d_ref = refs[:7]
        bias_ref = qseg_ref = kseg_ref = seed_ref = None
        if has_bias:
            bias_ref = refs[i]
            i += 1
        if has_seg:
            qseg_ref, kseg_ref = refs[i:i + 2]
            i += 2
        if has_drop:
            seed_ref = refs[i]
            i += 1
        if has_bias:
            dk_ref, dv_ref, db_ref = refs[i:i + 3]
            dk_acc, dv_acc, db_acc = refs[i + 3:i + 6]
        else:
            dk_ref, dv_ref = refs[i:i + 2]
            dk_acc, dv_acc = refs[i + 2:i + 4]
            db_ref = db_acc = None
        bi = pl.program_id(0)
        hi = pl.program_id(1)
        ki = pl.program_id(2)
        qi = pl.program_id(3)

        @pl.when(qi == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)
            if has_bias:
                db_acc[...] = jnp.zeros_like(db_acc)

        def compute():
            p_t, ds_raw, ds, qb, _, gb = tiles(
                q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, d_ref,
                bias_ref, qseg_ref, kseg_ref, seed_ref, bi, hi, qi, ki)
            dk_acc[...] += jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv_acc[...] += jax.lax.dot_general(
                p_t, gb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if has_bias:
                db_acc[...] += jnp.sum(ds_raw, axis=0, keepdims=True)

        if causal:
            pl.when(ki * bk <= qi * bq + bq - 1 + offset)(compute)
        else:
            compute()

        @pl.when(qi == n_q - 1)
        def _emit():
            dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)
            if has_bias:
                db_ref[0, 0] = db_acc[...]

    def common_specs(order):
        """In-specs for q/g/m/l/D + k/v + optionals; ``order`` maps grid
        ids -> (qi, ki) for the kernel's grid layout."""
        def im_q(bi, hi, g2, g3):
            return (bi, hi, order(g2, g3)[0], 0)

        def im_k(bi, hi, g2, g3):
            return (bi, hi, order(g2, g3)[1], 0)

        def im_row(bi, hi, g2, g3):
            return (bi, hi, order(g2, g3)[0], 0)

        specs = [
            pl.BlockSpec((1, 1, bq, d), im_q, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d), im_k, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d), im_k, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, d), im_q, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, 1), im_row, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, 1), im_row, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, 1), im_row, memory_space=pltpu.VMEM),
        ]
        args = [q, k, v, gf.astype(q.dtype), m4, l4, D4]
        if has_bias:
            specs.append(pl.BlockSpec(
                (1, 1, 1, bk),
                lambda bi, hi, g2, g3, _bb=bb, _bh=bh: (
                    bi if _bb > 1 else 0, hi if _bh > 1 else 0, 0,
                    order(g2, g3)[1]),
                memory_space=pltpu.VMEM))
            args.append(bias.astype(jnp.float32))
        if has_seg:
            specs.append(pl.BlockSpec(
                (1, bq, 8),
                lambda bi, hi, g2, g3: (bi, order(g2, g3)[0], 0),
                memory_space=pltpu.VMEM))
            specs.append(pl.BlockSpec(
                (1, 8, bk),
                lambda bi, hi, g2, g3: (bi, 0, order(g2, g3)[1]),
                memory_space=pltpu.VMEM))
            args.append(jnp.broadcast_to(
                q_seg.astype(jnp.int32)[:, :, None], (b, lq, 8)))
            args.append(jnp.broadcast_to(
                kv_seg.astype(jnp.int32)[:, None, :], (b, 8, lk)))
        if has_drop:
            specs.append(pl.BlockSpec(
                (2,), lambda bi, hi, g2, g3: (0,),
                memory_space=pltpu.SMEM))
            args.append(seed.astype(jnp.int32))
        return specs, args

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    dq_specs, dq_args = common_specs(lambda g2, g3: (g2, g3))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, n_q, n_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(*dq_args)

    kv_specs, kv_args = common_specs(lambda g2, g3: (g3, g2))
    kv_out_specs = [
        pl.BlockSpec((1, 1, bk, d),
                     lambda bi, hi, ki, qi: (bi, hi, ki, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bk, d),
                     lambda bi, hi, ki, qi: (bi, hi, ki, 0),
                     memory_space=pltpu.VMEM),
    ]
    kv_out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                    jax.ShapeDtypeStruct(v.shape, v.dtype)]
    kv_scratch = [pltpu.VMEM((bk, d), jnp.float32),
                  pltpu.VMEM((bk, d), jnp.float32)]
    if has_bias:
        kv_out_specs.append(pl.BlockSpec(
            (1, 1, 1, bk), lambda bi, hi, ki, qi: (bi, hi, 0, ki),
            memory_space=pltpu.VMEM))
        kv_out_shape.append(
            jax.ShapeDtypeStruct((b, h, 1, n_k * bk), jnp.float32))
        kv_scratch.append(pltpu.VMEM((1, bk), jnp.float32))
    res = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, n_k, n_q),
        in_specs=kv_specs,
        out_specs=kv_out_specs,
        out_shape=kv_out_shape,
        scratch_shapes=kv_scratch,
        compiler_params=params,
        interpret=interpret,
    )(*kv_args)
    if has_bias:
        dk, dv, db_part = res
        db = db_part[..., :lk]  # (b, h, 1, lk) per-(b,h) partials
        if bb == 1:
            db = jnp.sum(db, axis=0, keepdims=True)
        if bh == 1:
            db = jnp.sum(db, axis=1, keepdims=True)
        dbias = db.astype(bias.dtype)
    else:
        dk, dv = res
        dbias = None
    return dq, dk, dv, dbias


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
    lk = k.shape[2]
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
    # 1024 tuning would quadruple live memory and can OOM long-context
    # training.  A caller's SMALLER explicit block_k is honored.
    bk = min(block_k or 256, 256, lk)
    n_k = -(-lk // bk)
    pad = n_k * bk - lk

    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(jnp.float32)
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(jnp.float32)
    # (n_k, b, h, bk, d) so scan iterates key blocks
    kb_s = jnp.moveaxis(kp.reshape(b, h, n_k, bk, d), 2, 0)
    vb_s = jnp.moveaxis(vp.reshape(b, h, n_k, bk, d), 2, 0)
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
    dv = jnp.moveaxis(dv_s, 0, 2).reshape(b, h, n_k * bk, d)[:, :, :lk]
    if has_bias:
        dbias = jnp.moveaxis(db_s, 0, 3).reshape(
            bb, bh, bq, n_k * bk)[..., :lk].astype(bias.dtype)
    else:
        dbias = None
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias, dseg_q, dseg_kv, dseed)


_flash_core.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, *, bias=None, q_segment_ids=None,
                    kv_segment_ids=None, dropout_p=0.0, dropout_seed=None):
    """Fused attention: Pallas kernel on TPU, jnp fallback elsewhere.

    Args:
      q, k, v: (B, H, L, D).
      bias: optional additive f32 mask/bias, shape (B|1, H|1, Lq|1, Lk) —
        the BERT (B, 1, 1, L) padding mask streams as (1, block_k) tiles.
      q_segment_ids / kv_segment_ids: optional (B, Lq)/(B, Lk) int arrays;
        attention masked where segments differ (packed sequences).
      dropout_p: attention-prob dropout; requires ``dropout_seed`` (int,
        PRNG key, or (2,) int array).  The mask is hash-derived in-kernel.

    Default blocks come from ``_resolve_blocks``: 1024x1024 (clean),
    1024x512 (dropout), 512x512 (full (Lq, Lk) bias), sized against the
    v5e ~16 MB scoped-VMEM budget — see that function's docstring for the
    measured limits that set them."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
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
    full_bias = bias is not None and bias.shape[2] > 1
    block_q, block_k = _resolve_blocks(block_q, block_k, full_bias,
                                       dropout=dropout_p > 0.0)
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
