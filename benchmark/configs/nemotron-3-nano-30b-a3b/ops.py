"""Operations the ``nemotron-3-nano-30b-a3b`` configuration needs, from its
shapes: multiply-accumulates a token forward, by part; what one call of
each flash kernel costs at the attention's head width; and what one call of
each kernel of the Mamba-2 walk costs."""

from __future__ import annotations

#: tokens a chunk in the count of the chunked rule: the configuration's
#: own ``chunk_size``
SSD_CHUNK = 128


def pattern(cfg) -> str:
    return cfg["hybrid_override_pattern"]


def mamba_params(cfg) -> int:
    """Weights of one Mamba-2 mixer that a token is multiplied by: the
    in-projection, the convolution's taps and the out-projection (A_log,
    dt_bias, D, the biases and the norm multiply nothing)."""
    d = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return d * (inner + conv + cfg["mamba_num_heads"]) \
        + cfg["conv_kernel"] * conv + inner * d


def ssd_scan_macs_per_token(cfg, chunk=SSD_CHUNK) -> float:
    """The rule in its chunk-parallel form, a token: a group's C B^T over
    the chunk's earlier positions (chunk / 2 x N, shared by the group's
    heads), and a head's (L o C B^T) X~ (chunk / 2 x P), C S_0^T and
    X~^T B (P x N each)."""
    n, p = cfg["ssm_state_size"], cfg["mamba_head_dim"]
    return cfg["n_groups"] * chunk / 2 * n \
        + cfg["mamba_num_heads"] * (chunk / 2 * p + 2 * p * n)


def attention_params(cfg) -> int:
    """W_q, W_k, W_v and W_o of the grouped-query attention."""
    return 2 * cfg["hidden_size"] * cfg["head_dim"] \
        * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def score_macs_per_token(cfg) -> float:
    """QK^T and PV against every earlier position, causal counted at half,
    over the query heads (a key-value head serves several)."""
    return cfg["num_attention_heads"] * cfg["n_positions"] * 0.5 \
        * 2 * cfg["head_dim"]


def routed_layer_macs_per_token(cfg) -> float:
    """A routed layer's own part: the shared expert, the router over its
    whole width, and the routed experts held here at the expected share of
    a token's picks under even routing, experts held / router width (the
    gauge ``zoo_moe_held_assignments`` says how far a run is from it).  An
    expert is two products, up and down, without a gate."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held_share = cfg["n_routed_experts"] / cfg["router_width"]
    return 2 * d * cfg["moe_shared_expert_intermediate_size"] \
        * cfg["n_shared_experts"] + d * cfg["router_width"] \
        + cfg["num_experts_per_tok"] * held_share * 2 * d * f


def forward_macs_per_token(cfg) -> float:
    layers = pattern(cfg)
    return layers.count("M") * (mamba_params(cfg)
                                + ssd_scan_macs_per_token(cfg)) \
        + layers.count("*") * (attention_params(cfg)
                               + score_macs_per_token(cfg)) \
        + layers.count("E") * routed_layer_macs_per_token(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_example(cfg) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-accumulate, the backward pass twice the forward's.  What is
    computed again in the backward pass (a layer application under its
    checkpoint, the flash kernels' scores, a chunk's decays and C B^T) is
    not counted."""
    return 3.0 * 2.0 * forward_macs_per_token(cfg) * cfg["n_positions"]


def flash_call_costs(batch, cfg, itemsize=2):
    """(operations, bytes) of one call of each flash kernel at (B, H, L)
    with heads d wide, causal counted at half.  Products: forward QK^T and
    PV; the dq kernel QK^T, dP = dO V^T and dQ = dS K; the dk/dv kernel
    QK^T, dP, dV = P^T dO and dK = dS^T Q.  Bytes: each operand read and
    each result written once, k, v and their cotangents at the key-value
    heads (the kernels read a shared head where it lies; dK and dV are
    summed over a group's query heads in float32 outside the kernel, whose
    bytes are not counted here)."""
    h, kv, l = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["n_positions"]
    d = cfg["head_dim"]
    product = 2.0 * batch * h * l * l * 0.5 * d
    rows, shared = batch * h * l * d * itemsize, batch * kv * l * d * itemsize
    return {
        "forward": (2 * product, 2 * rows + 2 * shared),
        "dq": (3 * product, 3 * rows + 2 * shared),
        "dkv": (4 * product, 2 * rows + 4 * shared)}


def ssd_call_costs(batch, cfg, chunk, itemsize=2):
    """(operations, bytes) of one call of each kernel of the Mamba-2 walk
    (``ops/pallas/ssd_scan.py``) over (B, heads, L) in chunks of ``chunk``
    tokens: the products the chunk-parallel form needs, as
    ``ssd_scan_macs_per_token`` counts them (triangles at half, a group's
    C B^T once for its heads), not the ones a kernel spends on making them.
    Forward, a chunk: a group's C B^T (chunk^2 / 2 x N) and a head's
    (L o C B^T) X~ (chunk^2 / 2 x P), C S_0^T and X~^T B (chunk x P x N
    each); it reads a head's X~ and its cumulative decay (float32) and a
    group's B and C, and writes a head's Y and incoming state (float32).
    Backward: a group's C B^T again; a head's dM = dY X~^T and M^T dY
    (chunk^2 / 2 x P each), dC and dB of the pair products (chunk^2 / 2 x
    N each) and five products with the state or its cotangent (chunk x P
    x N each); it reads a head's X~, decay, state and dY and a group's B
    and C, and writes a head's dX~ and decay cotangent and a group's dB
    and dC."""
    heads, groups = cfg["mamba_num_heads"], cfg["n_groups"]
    n, p = cfg["ssm_state_size"], cfg["mamba_head_dim"]
    chunks = batch * -(-cfg["n_positions"] // chunk)
    x, decay, state = chunk * p * itemsize, chunk * 4, p * n * 4
    bc = chunk * n * itemsize
    pairs = chunk * chunk / 2
    return {
        "forward": (
            chunks * 2.0 * (groups * pairs * n
                            + heads * (pairs * p + 2 * chunk * p * n)),
            chunks * (heads * (2 * x + decay + state) + groups * 2 * bc)),
        "backward": (
            chunks * 2.0 * (groups * pairs * n
                            + heads * (2 * pairs * p + 2 * pairs * n
                                       + 5 * chunk * p * n)),
            chunks * (heads * (3 * x + 2 * decay + state)
                      + groups * 4 * bc))}
