"""The ``kimi-linear-48b-a3b`` configuration as a user of the system builds
it: ``LatentMoEDecoder`` with the mixer by layer that the configuration's
``linear_attn_config`` lists (Kimi Delta Attention, or latent attention
without positions), a leading dense layer, routed layers without dropped
tokens over the experts held here, a shared expert, the head, its own
next-token loss, Adam."""

from __future__ import annotations

#: what ``build`` was last given, for ``routing_fault``
_BUILT: dict = {}


def attention_by_layer(cfg):
    """"kda" or "latent" for each of the configuration's layers, from the
    two 1-based lists of ``linear_attn_config``."""
    lists = cfg["linear_attn_config"]
    kda, full = set(lists["kda_layers"]), set(lists["full_attn_layers"])
    layers = range(1, cfg["num_hidden_layers"] + 1)
    if kda & full or kda | full != set(layers):
        raise ValueError(
            f"kda_layers {sorted(kda)} and full_attn_layers {sorted(full)} "
            f"do not part layers 1..{cfg['num_hidden_layers']}")
    return ["kda" if i in kda else "latent" for i in layers]


def build(cfg):
    """The compiled Keras model, parameters not yet made."""
    from analytics_zoo_tpu.ops import linear_attention as linear
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    # the chunked calls traced from here on are this model's: what an
    # earlier model of the process left is not held against it
    linear.chunk_schedules.clear()
    if (cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"]
            or cfg["moe_router_activation_func"] != "sigmoid"
            or cfg["num_expert_group"] != 1 or cfg["topk_group"] != 1
            or not cfg["moe_renormalize"] or cfg["rope_scaling"] is not None
            or cfg["moe_layer_freq"] != 1
            or cfg["num_nextn_predict_layers"] != 0):
        raise ValueError(
            "this is Kimi Linear's block: SiLU, an untied head, latent "
            "attention without query compression and without positions, "
            "sigmoid scores, one group, normalised top-k weights, every "
            "layer after the dense ones routed, no next-token modules")
    opt = cfg["optimizer"]
    kda = cfg["linear_attn_config"]
    kinds = attention_by_layer(cfg)
    tokens = Input(shape=(cfg["n_positions"],), name="tokens")
    logits = LatentMoEDecoder(
        vocab=cfg["vocab_size"], n_block=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], attention=kinds,
        kda_heads=kda["num_heads"], kda_head_dim=kda["head_dim"],
        kda_conv_size=kda["short_conv_kernel_size"],
        kv_latent_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        routed_experts=cfg["router_width"],
        experts_held=cfg["num_experts"],
        experts_held_from=cfg["experts_held_from"],
        experts_per_token=cfg["num_experts_per_token"],
        expert_size=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        leading_dense=cfg["first_k_dense_replace"],
        rotary_theta=None, norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"], name="kimi")(tokens)
    net = Model(tokens, logits, name="kimi_linear_48b_a3b")
    net.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                               beta_2=opt["beta_2"],
                               epsilon=opt["epsilon"]),
                loss=cfg["loss"])
    _BUILT.clear()
    _BUILT.update(
        dense_layers=cfg["first_k_dense_replace"],
        routed_layers=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        router_width=cfg["router_width"],
        experts_held=cfg["num_experts"],
        experts_held_from=cfg["experts_held_from"],
        experts_per_token=cfg["num_experts_per_token"], capacity_factor=None,
        attention_by_layer=kinds, rotary=False,
        kda=(kda["num_heads"], kda["head_dim"],
             kda["short_conv_kernel_size"]),
        qk_width=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        value_width=cfg["v_head_dim"], n_positions=cfg["n_positions"])
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
    so many dense and routed layers, every layer's mixer in the
    configuration's order, KDA at its heads, width and taps, latent
    attention at its two widths and without rotary, the router's whole
    width, the experts held and from where, the experts a token, no
    capacity, the loss taken inside the model; every KDA layer in chunks
    of the program's chunk size over the whole sequence; and on a TPU
    every latent attention has to have gone through the Pallas flash
    kernels at its widths, every grouped product and every walk over a
    KDA layer's chunks through its kernel, none through a fallback."""
    from analytics_zoo_tpu.ops import linear_attention as linear
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash
    from analytics_zoo_tpu.ops.pallas import grouped_matmul as grouped
    from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

    built = dict(_BUILT)
    length = built.pop("n_positions")
    steps = [r for r in self_attention.decoder_records if r["training"]]
    if not steps or any(steps[-1].get(k) != v for k, v in built.items()) \
            or not steps[-1]["loss_blocks"]:
        return f"decoder traced as {steps[-1:]}, expected {built}"
    heads, width = built["kda"][:2]
    chunk, sub = linear.chunk_of(length)
    chunks = {(r["shape"][1:], r["chunk"], r["sub_blocks"])
              for r in linear.chunk_schedules}
    if chunks != {((heads, length, width, width), chunk, chunk // sub)}:
        return f"KDA traced in chunks {sorted(chunks)}"
    if platform != "tpu":
        return None
    for name, counts in (("flash attention", flash.invocation_counts),
                         ("grouped product", grouped.invocation_counts),
                         ("KDA walk", linear.invocation_counts)):
        if counts["fallback"] > 0 or counts["pallas"] == 0:
            return f"{name} routing {dict(counts)}"
    if not all(r["kernel"] for r in linear.chunk_schedules):
        return f"KDA walks traced as {list(linear.chunk_schedules)}"
    widths = {(r["shape"][4], r["value_width"]) for r in flash.tile_schedules}
    if widths != {(built["qk_width"], built["value_width"])}:
        return f"flash kernels traced at widths {sorted(widths)}"
    return None
