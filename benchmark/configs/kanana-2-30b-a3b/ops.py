"""Operations the ``kanana-2-30b-a3b`` configuration needs, from its
shapes: multiply-accumulates a token forward, by part; and what one call
of each flash kernel costs at latent attention's two widths."""

from __future__ import annotations


def attention_params(cfg) -> int:
    """Weights of one layer's latent attention that a token is multiplied
    by: Wq, Wkva, Wkvb, Wo (the latent's norm is no product)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope, rope, vd = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (nope + rope) + d * (rank + rope) \
        + rank * h * (nope + vd) + h * vd * d


def score_macs_per_token(cfg) -> float:
    """QK^T over the query/key width and PV over the value width against
    every earlier position, causal counted at half."""
    return cfg["num_attention_heads"] * cfg["n_positions"] * 0.5 \
        * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
           + cfg["v_head_dim"])


def routed_layer_macs_per_token(cfg) -> float:
    """A routed layer's own part: the shared experts, the router over its
    whole width, and the routed experts held here at the expected share of
    a token's picks under even routing, experts held / router width (the
    gauge ``zoo_moe_held_assignments`` says how far a run is from it)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held_share = cfg["n_routed_experts"] / cfg["router_width"]
    return 3 * d * f * cfg["n_shared_experts"] + d * cfg["router_width"] \
        + cfg["num_experts_per_tok"] * held_share * 3 * d * f


def forward_macs_per_token(cfg) -> float:
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    d = cfg["hidden_size"]
    return layers * (attention_params(cfg) + score_macs_per_token(cfg)) \
        + dense * 3 * d * cfg["intermediate_size"] \
        + (layers - dense) * routed_layer_macs_per_token(cfg) \
        + d * cfg["vocab_size"]


def train_flops_per_example(cfg) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-accumulate, the backward pass twice the forward's.  What is
    computed again in the backward pass (a layer application under its
    checkpoint, the flash kernels' scores) is not counted."""
    return 3.0 * 2.0 * forward_macs_per_token(cfg) * cfg["n_positions"]


def flash_call_costs(batch, cfg, itemsize=2):
    """(operations, bytes) of one call of each flash kernel at (B, H, L)
    with q and k ``qk`` wide and v, the output and their cotangents ``vd``
    wide, causal counted at half.  Products, by the width they contract or
    produce: forward QK^T (qk) and PV (vd); the dq kernel QK^T (qk),
    dP = dO V^T (vd) and dQ = dS K (qk); the dk/dv kernel QK^T (qk), dP
    (vd), dV = P^T dO (vd) and dK = dS^T Q (qk).  Bytes: each operand read
    and each result written once."""
    h, l = cfg["num_attention_heads"], cfg["n_positions"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    product = 2.0 * batch * h * l * l * 0.5      # a unit of width
    wide, narrow = (batch * h * l * w * itemsize for w in (qk, vd))
    return {
        "forward": (product * (qk + vd), 2 * wide + 2 * narrow),
        "dq": (product * (2 * qk + vd), 3 * wide + 2 * narrow),
        "dkv": (product * (2 * qk + 2 * vd), 3 * wide + 3 * narrow)}
