"""Grouped-query attention for the decoder blocks of
``self_attention._TransformerCore``, described by one record of its kind
and sizes: query heads of their own width, fewer key-value heads that
groups of them share, no bias, no position encoding (a model whose order
comes from its recurrent layers).

    q = u W_q (heads), k = u W_k, v = u W_v (kv_heads), query head h
    reading key-value head h // (heads / kv_heads);
    out = [softmax(q k^T / sqrt(head_dim) + causal) v] W_o

through ``ops.attention.dot_product_attention``, the flash kernels on a
TPU, which read a shared head where it lies: k and v are never copied to
every query head.
"""

from __future__ import annotations

import dataclasses

import jax

from analytics_zoo_tpu.ops.attention import (
    dot_product_attention,
    merge_heads,
    split_heads,
)


@dataclasses.dataclass(frozen=True)
class GroupedQueryAttention:
    """``heads`` query heads over ``kv_heads`` key-value heads, each
    ``head_dim`` wide."""

    heads: int
    kv_heads: int
    head_dim: int

    kind = "full"

    def params(self, rng, d, std):
        ks = jax.random.split(rng, 4)
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return {
            "attn_q_kernel": std * jax.random.normal(ks[0], (d, q)),
            "attn_k_kernel": std * jax.random.normal(ks[1], (d, kv)),
            "attn_v_kernel": std * jax.random.normal(ks[2], (d, kv)),
            "attn_o_kernel": std * jax.random.normal(ks[3], (q, d)),
        }

    def param_count(self, d) -> int:
        return 2 * d * self.head_dim * (self.heads + self.kv_heads)

    def __call__(self, bp, u, causal=True):
        q = split_heads(u @ bp["attn_q_kernel"], self.heads)
        k, v = (split_heads(u @ bp[f"attn_{x}_kernel"], self.kv_heads)
                for x in "kv")
        a = dot_product_attention(q, k, v, causal=causal)
        return merge_heads(a) @ bp["attn_o_kernel"]
