"""Operations the ``gpt2-small`` configuration needs, from its shapes."""

from __future__ import annotations


def matmul_params(cfg) -> int:
    """Weights that a token is multiplied by: every kernel but the two
    embedding tables, which are looked up."""
    d, m = cfg["n_embd"], cfg["n_inner"]
    per_block = d * 3 * d + d * d + d * m + m * d
    return cfg["n_layer"] * per_block + d * cfg["vocab_size"]


def attention_macs_per_example(cfg) -> float:
    """QK^T and PV of one sequence over all blocks, causal counted at
    half."""
    s, d = cfg["n_positions"], cfg["n_embd"]
    return cfg["n_layer"] * 2 * s * s * d * 0.5


def forward_macs_per_example(cfg) -> float:
    return matmul_params(cfg) * cfg["n_positions"] \
        + attention_macs_per_example(cfg)


def train_flops_per_example(cfg) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-accumulate, the backward pass twice the forward's.  What the
    flash kernel recomputes in its backward pass is not counted."""
    return 3.0 * 2.0 * forward_macs_per_example(cfg)
