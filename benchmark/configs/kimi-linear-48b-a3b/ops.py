"""Operations the ``kimi-linear-48b-a3b`` configuration needs, from its
shapes: multiply-accumulates a token forward, by part; what one call of
each flash kernel costs at latent attention's two widths; and what one call
of each kernel of the KDA walk costs."""

from __future__ import annotations

#: tokens a chunk in the count of the chunked gated delta rule: the count's
#: own, whatever chunk the program walks in (the yardstick stays where the
#: program's choice moves)
KDA_CHUNK = 64


def kda_layers(cfg) -> int:
    return len(cfg["linear_attn_config"]["kda_layers"])


def kda_params(cfg) -> int:
    """Weights of one KDA mixer that a token is multiplied by: the three
    projections, their convolutions' taps, the two low-rank gates (rank =
    a head's width), beta's projection and the output projection."""
    d, kda = cfg["hidden_size"], cfg["linear_attn_config"]
    wide, rank = kda["num_heads"] * kda["head_dim"], kda["head_dim"]
    return 3 * d * wide + 3 * kda["short_conv_kernel_size"] * wide \
        + 2 * (d * rank + rank * wide) + d * kda["num_heads"] + wide * d


def kda_scan_macs_per_token(cfg, chunk=KDA_CHUNK) -> float:
    """The gated delta rule in its chunk-parallel form, a token, over all
    heads (d_k = d_v = w a head): the pair products of a chunk's queries
    and keys with its keys, the lower triangle of each (chunk x w); the
    triangular system solved for the (w + w) columns of beta V and beta K+
    by substitution (chunk / 2 x 2 w); the three products with the state
    (3 w^2) and the pair matrix's with U, the lower triangle (chunk / 2 x
    w)."""
    kda = cfg["linear_attn_config"]
    w = kda["head_dim"]
    return kda["num_heads"] * (chunk * w + chunk * w + 3 * w * w
                               + chunk * w / 2)


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
    """A routed layer's own part: the shared expert, the router over its
    whole width, and the routed experts held here at the expected share of
    a token's picks under even routing, experts held / router width (the
    gauge ``zoo_moe_held_assignments`` says how far a run is from it)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held_share = cfg["num_experts"] / cfg["router_width"]
    return 3 * d * f * cfg["num_shared_experts"] + d * cfg["router_width"] \
        + cfg["num_experts_per_token"] * held_share * 3 * d * f


def forward_macs_per_token(cfg) -> float:
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    d, n_kda = cfg["hidden_size"], kda_layers(cfg)
    return n_kda * (kda_params(cfg) + kda_scan_macs_per_token(cfg)) \
        + (layers - n_kda) * (attention_params(cfg)
                              + score_macs_per_token(cfg)) \
        + dense * 3 * d * cfg["intermediate_size"] \
        + (layers - dense) * routed_layer_macs_per_token(cfg) \
        + d * cfg["vocab_size"]


def train_flops_per_example(cfg) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-accumulate, the backward pass twice the forward's.  What is
    computed again in the backward pass (a layer application under its
    checkpoint, the flash kernels' scores, a KDA layer's chunk-local
    arrays) is not counted."""
    return 3.0 * 2.0 * forward_macs_per_token(cfg) * cfg["n_positions"]


def flash_call_costs(batch, cfg, itemsize=2):
    """(operations, bytes) of one call of each flash kernel at (B, H, L)
    with q and k ``qk`` wide and v, the output and their cotangents ``vd``
    wide, causal counted at half (as ``kanana-2-30b-a3b``'s: the kernels
    and the widths are the same).  Products, by the width they contract or
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


def kda_call_costs(batch, cfg, chunk, itemsize=2):
    """(operations, bytes) of one call of each kernel of the KDA walk
    (``ops/pallas/kda_scan.py``) over (B, H, L) in chunks of ``chunk``
    tokens, heads w wide: the products the chunk-parallel form needs, as
    ``kda_scan_macs_per_token`` counts them (triangles at half, the
    triangular system by substitution), not the ones a kernel spends on
    making them (the inverse by whole-matrix products, masked halves).
    Forward, a chunk of a head: the two pair products' lower triangles
    (chunk^2 x w together), the system's 2 w columns (chunk^2 / 2 x 2 w),
    the three products with the state (chunk x w x w each) and the pair
    matrix's with U (chunk^2 / 2 x w); it reads four (chunk, w) operands
    and the summed log-decay in float32 and writes the output and the
    incoming state in float32.  Backward: the chunk's local arrays again
    and their transpose (three times the forward's local products), seven
    products with the state or its cotangent and the pair matrix's two
    (chunk^2 x w together); it reads the forward's operands, the state and
    dO, and writes five cotangents."""
    kda = cfg["linear_attn_config"]
    w = kda["head_dim"]
    chunks = batch * kda["num_heads"] * -(-cfg["n_positions"] // chunk)
    rows, state = chunk * w * itemsize, w * w * 4
    local = chunk * chunk * w + chunk * chunk / 2 * 2 * w
    return {
        "forward": (chunks * 2.0 * (local + 3 * chunk * w * w
                                    + chunk * chunk * w / 2),
                    chunks * (5 * rows + 2 * rows + state)),
        "backward": (chunks * 2.0 * (3 * local + 7 * chunk * w * w
                                     + chunk * chunk * w),
                     chunks * (9 * rows + 2 * 2 * rows + state))}
