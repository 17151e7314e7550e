"""The ``nemotron-3-nano-30b-a3b`` configuration as a user of the system
builds it: ``LatentMoEDecoder`` with its blocks as records read from the
configuration's ``hybrid_override_pattern`` (Mamba-2 mixers, grouped-query
attention without positions, routed relu² experts without a gate, a block
without a feed-forward where the pattern has two mixers in a row), the
head, its own next-token loss, Adam."""

from __future__ import annotations

#: what ``build`` was last given, for ``routing_fault``
_BUILT: dict = {}


def blocks(pattern):
    """The pattern's layers as pre-norm blocks of (mixer, feed-forward):
    a mixer ("M" or "*") and the "E" after it are one block, a mixer with
    no "E" after it a block without a feed-forward."""
    out, i = [], 0
    while i < len(pattern):
        if pattern[i] not in "M*":
            raise ValueError(f"{pattern!r}: layer {i} is {pattern[i]!r} "
                             "where a mixer (M or *) begins a block")
        routed = pattern[i + 1:i + 2] == "E"
        out.append((pattern[i], routed))
        i += 2 if routed else 1
    return out


def layout(cfg):
    """The decoder's records, a (mixer, feed-forward) pair a block."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.grouped_attention import (  # noqa: E501
        GroupedQueryAttention,
    )
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import (
        Experts,
    )
    from analytics_zoo_tpu.pipeline.api.keras.layers.ssd_mixer import (
        SSDMixer,
    )

    ssd = SSDMixer(heads=cfg["mamba_num_heads"],
                   head_dim=cfg["mamba_head_dim"],
                   state=cfg["ssm_state_size"], groups=cfg["n_groups"],
                   conv=cfg["conv_kernel"], chunk=cfg["chunk_size"])
    attention = GroupedQueryAttention(
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"])
    experts = Experts(gated=False, activation=cfg["mlp_hidden_act"])
    return [(ssd if mixer == "M" else attention,
             experts if routed else None)
            for mixer, routed in blocks(cfg["hybrid_override_pattern"])]


def build(cfg):
    """The compiled Keras model, parameters not yet made."""
    from analytics_zoo_tpu.ops import ssd
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    # the chunked calls traced from here on are this model's
    ssd.chunk_schedules.clear()
    if (cfg["mlp_hidden_act"] != "relu2" or cfg["tie_word_embeddings"]
            or cfg["mamba_hidden_act"] != "silu" or cfg["use_bias"]
            or cfg["mamba_proj_bias"] or cfg["attention_bias"]
            or cfg["mlp_bias"] or not cfg["use_conv_bias"]
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or not cfg["norm_topk_prob"]
            or cfg["sliding_window"] is not None
            or cfg["norm_eps"] != cfg["layer_norm_epsilon"]):
        raise ValueError(
            "this is Nemotron-H's block: relu2 experts, SiLU in the Mamba-2 "
            "mixer, an untied head, no bias but the convolution's, one "
            "routing group, normalised top-k weights, no window, one eps")
    opt = cfg["optimizer"]
    records = layout(cfg)
    tokens = Input(shape=(cfg["n_positions"],), name="tokens")
    logits = LatentMoEDecoder(
        vocab=cfg["vocab_size"], n_block=len(records),
        n_head=cfg["num_attention_heads"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], layout=records,
        routed_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        experts_held_from=cfg["experts_held_from"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        shared_experts=cfg["moe_shared_expert_intermediate_size"]
        // cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"], rotary_theta=None,
        norm_eps=cfg["norm_eps"],
        initializer_range=cfg["initializer_range"], name="nemotron")(tokens)
    net = Model(tokens, logits, name="nemotron_3_nano_30b_a3b")
    net.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                               beta_2=opt["beta_2"],
                               epsilon=opt["epsilon"]),
                loss=cfg["loss"])
    pairs = blocks(cfg["hybrid_override_pattern"])
    _BUILT.clear()
    _BUILT.update(
        dense_layers=0, routed_layers=sum(r for _, r in pairs),
        ffn_by_layer=["routed" if r else None for _, r in pairs],
        attention_by_layer=["ssd" if m == "M" else "full" for m, _ in pairs],
        router_width=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        experts_held_from=cfg["experts_held_from"],
        experts_per_token=cfg["num_experts_per_tok"], capacity_factor=None,
        experts_gated=False, rotary=False,
        kv_heads=cfg["num_key_value_heads"],
        ssd=(cfg["mamba_num_heads"], cfg["mamba_head_dim"],
             cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"]),
        n_positions=cfg["n_positions"], chunk=cfg["chunk_size"],
        head_dim=cfg["head_dim"], heads=cfg["num_attention_heads"])
    return net


def feature_set(x, y, cfg):
    from analytics_zoo_tpu.feature.dataset import FeatureSet

    return FeatureSet.of(x, y)


def first_gradient(opt_state, params0, cfg):
    """The first step's gradient as the optimizer got it, from the state
    after that step: Adam's first moment is then (1 - beta_1) of it.  A
    leaf at a time and on the host: the float32 state fills the chip."""
    import jax
    import numpy as np
    import optax

    def is_adam(s):
        return isinstance(s, optax.ScaleByAdamState)

    moments = [s for s in jax.tree_util.tree_leaves(opt_state,
                                                    is_leaf=is_adam)
               if is_adam(s)]
    if len(moments) != 1:
        raise ValueError(f"{len(moments)} Adam states in the optimizer "
                         "state, expected one")
    scale = np.float32(1.0 / (1.0 - cfg["optimizer"]["beta_1"]))
    return jax.tree_util.tree_map(lambda m: np.asarray(m) * scale,
                                  moments[0].mu)


def routing_fault(platform):
    """The step as traced has to be the model the configuration states:
    every block's mixer and feed-forward in the pattern's order, the SSD at
    its heads, width, state, groups and taps, attention at its key-value
    heads and without rotary, the router's whole width, the experts held
    and from where, the experts a token, without a gate and without a
    capacity, the loss taken inside the model; every SSD layer in chunks of
    the configuration's chunk over the whole sequence with B and C read by
    group; and on a TPU every attention has to have gone through the
    Pallas flash kernels at its head width and key-value heads, every
    grouped product and every walk over an SSD layer's chunks through its
    kernel, none through a fallback."""
    from analytics_zoo_tpu.ops import ssd
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash
    from analytics_zoo_tpu.ops.pallas import grouped_matmul as grouped
    from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

    built = dict(_BUILT)
    length, chunk = built.pop("n_positions"), built.pop("chunk")
    width, heads = built.pop("head_dim"), built.pop("heads")
    steps = [r for r in self_attention.decoder_records if r["training"]]
    if not steps or any(steps[-1].get(k) != v for k, v in built.items()) \
            or not steps[-1]["loss_blocks"]:
        return f"decoder traced as {steps[-1:]}, expected {built}"
    ssd_heads, p, n, groups, _ = built["ssd"]
    walks = {(r["rule"], r["shape"][1:], r["groups"], r["chunk"])
             for r in ssd.chunk_schedules}
    if walks != {("ssd", (ssd_heads, length, p, n), groups,
                  ssd.chunk_of(length, chunk))}:
        return f"SSD traced in chunks {sorted(walks)}"
    if platform != "tpu":
        return None
    for name, counts in (("flash attention", flash.invocation_counts),
                         ("grouped product", grouped.invocation_counts),
                         ("SSD walk", ssd.invocation_counts)):
        if counts["fallback"] > 0 or counts["pallas"] == 0:
            return f"{name} routing {dict(counts)}"
    if not all(r["kernel"] for r in ssd.chunk_schedules):
        return f"SSD walks traced as {list(ssd.chunk_schedules)}"
    calls = {(r["shape"][1], r["kv_heads"], r["shape"][4], r["value_width"])
             for r in flash.tile_schedules}
    if calls != {(heads, built["kv_heads"], width, width)}:
        return f"flash kernels traced at (heads, key-value heads, " \
               f"widths) {sorted(calls)}"
    return None
