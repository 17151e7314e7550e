"""Attention ops — the single entry point every attention layer routes
through, so kernel upgrades (Pallas flash attention, ring attention over the
``seq`` mesh axis) swap in under one signature.

Reference behavior being covered: the O(L²) ``multiHeadSelfAttention`` inside
TransformerLayer.scala:137 and BERT.scala's attention with additive mask.
The reference materializes the full (L, L) score matrix per head on CPU; here
the default path is a blockwise-friendly jnp einsum that XLA fuses, and the
hot path is served by a Pallas kernel (ops/pallas) on TPU — including the
*training* configuration (attention dropout on, padded batch with a BERT
(B, 1, 1, L) additive mask): dropout lowers into the kernel via a
counter-based hash PRNG and broadcastable masks stream blockwise, so the
realistic path never falls back to the dense O(L²) route.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def _flash_backend_ok() -> bool:
    # single source of truth for the backend gate (incl. the
    # ZOO_FLASH_INTERPRET CI knob) lives next to the kernel
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _pallas_available,
    )

    return _pallas_available()


def flash_eligible(q_shape, mask_shape, mask_ndim, dropout_p, has_rng,
                   k_len, use_flash="auto"):
    """Pure routing predicate (backend check excluded) — unit-testable.

    Args mirror what :func:`dot_product_attention` sees: ``mask_shape`` is
    None or the mask's shape; flash handles masks broadcastable to
    (B|1, H|1, Lq|1, Lk).  Dropout needs an rng to derive the kernel seed.
    """
    if use_flash == False:  # noqa: E712
        return False
    b, h_, lq, d = q_shape[-4], q_shape[-3], q_shape[-2], q_shape[-1]
    # d % 64 covers BERT-base/GPT-base head sizes
    if lq < 256 or d % 64 != 0:
        return False
    if dropout_p > 0.0 and not has_rng:
        return False
    if mask_shape is not None:
        if mask_ndim != 4:
            return False
        if (mask_shape[0] not in (1, b) or mask_shape[1] not in (1, h_)
                or mask_shape[2] not in (1, lq)
                or mask_shape[3] != k_len):
            return False
    return True


def dot_product_attention(q, k, v, mask=None, dropout_p=0.0, rng=None,
                          causal=False, scale=None, use_flash="auto"):
    """Batched multi-head attention.

    Args:
      q, k, v: (B, H, L, D) arrays; k and v may have H_kv heads, H_kv
        dividing H (grouped-query heads: query head h reads key-value head
        h // (H / H_kv)).
      mask: optional additive mask broadcastable to (B, H, Lq, Lk) — 0 for
        keep, large-negative for drop (reference BERT attention_mask
        convention) — or a boolean mask (True = keep).
      dropout_p: attention-prob dropout (reference attnPDrop).
      causal: lower-triangular masking (reference TransformerLayer
        bidirectional=false path).
      scale: score scale; defaults to 1/sqrt(D).
    """
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    # Kernel plane: a plan's kernel_rules override the auto heuristic —
    # "xla" pins the dense jnp path, "flash" asks for the kernel (the
    # eligibility/backend checks still gate it: an ineligible shape
    # falls through to jnp rather than failing).  No active plan or no
    # "attention" rule leaves use_flash as passed.
    if use_flash == "auto":
        from analytics_zoo_tpu.parallel.plan import resolve_kernel

        pick = resolve_kernel("attention")
        if pick == "xla":
            use_flash = False
        elif pick == "flash":
            use_flash = True
    # Route big attention — masked, dropout, or clean — through the Pallas
    # flash kernel on TPU (O(L·D) HBM traffic); the jnp path serves small /
    # oddly-shaped cases and non-TPU backends.
    if _flash_backend_ok() and flash_eligible(
            q.shape, None if mask is None else mask.shape,
            None if mask is None else mask.ndim, dropout_p,
            rng is not None, k.shape[-2], use_flash):
        from analytics_zoo_tpu.ops.pallas.flash_attention import (
            _NEG,
            flash_attention,
        )

        bias = None
        if mask is not None:
            if mask.dtype == jnp.bool_:
                bias = jnp.where(mask, 0.0, _NEG).astype(jnp.float32)
            else:
                bias = mask.astype(jnp.float32)
        return flash_attention(
            q, k, v, causal, scale, bias=bias,
            dropout_p=float(dropout_p),
            dropout_seed=rng if dropout_p > 0.0 else None)
    if q.ndim == 4 and k.shape[1] != q.shape[1]:
        # grouped-query heads: query head h reads key-value head
        # h // (H / H_kv), without a copy of k and v a query head
        b, h, lq, _ = q.shape
        g = k.shape[-3]
        if mask is not None and mask.ndim == 4:
            mask = mask.reshape(mask.shape[0], -1, h // g if mask.shape[1]
                                > 1 else 1, *mask.shape[2:])
        out = dot_product_attention(
            q.reshape(b, g, h // g, lq, d), k[:, :, None], v[:, :, None],
            mask, dropout_p, rng, causal, scale, use_flash=False)
        return out.reshape(b, h, lq, v.shape[-1])
    scores = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        lq, lk = scores.shape[-2], scores.shape[-1]
        causal_mask = jnp.tril(jnp.ones((lq, lk), bool), lk - lq)
        scores = jnp.where(causal_mask, scores, jnp.finfo(scores.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    # the context under the name the flash path gives its own (the "attn"
    # recomputation policy of parallel/plan.py keeps it; inert elsewhere)
    return checkpoint_name(jnp.einsum("...qk,...kd->...qd", probs, v),
                           "attn_context")


def project_heads(x, kernel, heads, parts=1, bias=None):
    """``x @ kernel`` for x (B, L, D) and kernel (D, parts * heads * d),
    written head-major: ``parts`` arrays (B, heads, L, d), or the one.  The
    product's own output layout takes the place of :func:`split_heads`'
    transpose, and the gradient's products read the heads where they lie,
    so neither direction has a pass that only moves the heads."""
    d_in, width = kernel.shape
    d = width // (parts * heads)
    out = jnp.einsum("bld,dphe->pbhle", x,
                     kernel.reshape(d_in, parts, heads, d))
    if bias is not None:
        out = out + bias.reshape(parts, 1, heads, 1, d)
    return tuple(out) if parts > 1 else out[0]


def split_heads(x, n_heads):
    """(B, L, H*D) -> (B, H, L, D)."""
    b, l, hd = x.shape
    d = hd // n_heads
    return x.reshape(b, l, n_heads, d).transpose(0, 2, 1, 3)


def merge_heads(x):
    """(B, H, L, D) -> (B, L, H*D)."""
    b, h, l, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, l, h * d)
