"""The ``kanana-2-30b-a3b`` configuration as a user of the system builds
it: ``LatentMoEDecoder`` (latent attention, a leading dense layer, routed
layers without dropped tokens over the experts held here, shared experts,
the head), its own next-token loss, Adam."""

from __future__ import annotations

#: what ``build`` was last given, for ``routing_fault``
_BUILT: dict = {}


def build(cfg):
    """The compiled Keras model, parameters not yet made."""
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    if (cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or cfg["attention_bias"] or cfg["q_lora_rank"] is not None
            or cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or not cfg["norm_topk_prob"]
            or not cfg["rope_interleave"] or cfg["rope_scaling"] is not None
            or cfg["moe_layer_freq"] != 1
            or cfg["qk_head_dim"] != cfg["qk_nope_head_dim"]
            + cfg["qk_rope_head_dim"]):
        raise ValueError(
            "LatentMoEDecoder is DeepSeek-V3's block without query "
            "compression: SiLU, no bias, an untied head, sigmoid scores, "
            "one group, normalised top-k weights, rotary on adjacent pairs "
            "without scaling, every layer after the dense ones routed")
    opt = cfg["optimizer"]
    tokens = Input(shape=(cfg["n_positions"],), name="tokens")
    logits = LatentMoEDecoder(
        vocab=cfg["vocab_size"], n_block=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        kv_latent_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        routed_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        experts_held_from=cfg["experts_held_from"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        leading_dense=cfg["first_k_dense_replace"],
        rotary_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"], name="kanana")(tokens)
    net = Model(tokens, logits, name="kanana_2_30b_a3b")
    net.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                               beta_2=opt["beta_2"],
                               epsilon=opt["epsilon"]),
                loss=cfg["loss"])
    _BUILT.update(
        dense_layers=cfg["first_k_dense_replace"],
        routed_layers=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        router_width=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        experts_held_from=cfg["experts_held_from"],
        experts_per_token=cfg["num_experts_per_tok"], capacity_factor=None,
        attention="latent",
        qk_width=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        value_width=cfg["v_head_dim"])
    return net


def feature_set(x, y, cfg):
    from analytics_zoo_tpu.feature.dataset import FeatureSet

    return FeatureSet.of(x, y)


def first_gradient(opt_state, params0, cfg):
    """The first step's gradient as the optimizer got it, from the state
    after that step: Adam's first moment is then (1 - beta_1) of it.  A
    leaf at a time and on the host: the float32 state fills the chip, and
    a second tree of its size beside the loaded step does not fit."""
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
    so many dense and routed layers, the router's whole width, the experts
    held and from where, the experts a token, no capacity, latent attention
    at its two widths, the loss taken inside the model; and on a TPU every
    attention has to have gone through the Pallas flash kernels at those
    widths and every grouped product through its kernel, none through a
    fallback."""
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash
    from analytics_zoo_tpu.ops.pallas import grouped_matmul as grouped
    from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

    steps = [r for r in self_attention.decoder_records if r["training"]]
    if not steps or any(steps[-1][k] != v for k, v in _BUILT.items()) \
            or not steps[-1]["loss_blocks"]:
        return f"decoder traced as {steps[-1:]}, expected {_BUILT}"
    if platform != "tpu":
        return None
    for name, counts in (("flash attention", flash.invocation_counts),
                         ("grouped product", grouped.invocation_counts)):
        if counts["fallback"] > 0 or counts["pallas"] == 0:
            return f"{name} routing {dict(counts)}"
    widths = {(r["shape"][4], r["value_width"]) for r in flash.tile_schedules}
    if widths != {(_BUILT["qk_width"], _BUILT["value_width"])}:
        return f"flash kernels traced at widths {sorted(widths)}"
    return None
