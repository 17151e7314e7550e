"""Operations the ``ouro-2.6b`` configuration needs, from its shapes."""

from __future__ import annotations


def matmul_params_per_pass(cfg) -> int:
    """Weights a token is multiplied by in one pass: every layer's q, k,
    v and output projections (4 d^2) and its gated feed-forward (3 d m),
    and the head (d V), which every pass evaluates.  The embedding is
    looked up; the exit gate's d is left out."""
    d, m = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * d * d + 3 * d * m) \
        + d * cfg["vocab_size"]


def attention_macs_per_example(cfg) -> float:
    """QK^T and PV of one sequence over every layer application, causal
    counted at half."""
    s, d = cfg["n_positions"], cfg["hidden_size"]
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"] \
        * 2 * s * s * d * 0.5


def forward_macs_per_example(cfg) -> float:
    return cfg["total_ut_steps"] * matmul_params_per_pass(cfg) \
        * cfg["n_positions"] + attention_macs_per_example(cfg)


def train_flops_per_example(cfg) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-accumulate, the backward pass twice the forward's.  What is
    computed again in the backward pass (every layer application, every
    pass's head, the flash kernel's scores) is not counted."""
    return 3.0 * 2.0 * forward_macs_per_example(cfg)
