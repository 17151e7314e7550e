"""The ``ouro-2.6b`` configuration as a user of the system builds it:
``LoopedDecoder`` (one stack of layers run ``total_ut_steps`` times, the
head and the exit gate after every pass), its exit-gate loss, Adam."""

from __future__ import annotations

#: what ``build`` was last given, for ``routing_fault``
_BUILT: dict = {}


def build(cfg):
    """The compiled Keras model, parameters not yet made."""
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import LoopedDecoder
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    heads, d = cfg["num_attention_heads"], cfg["hidden_size"]
    if (cfg["head_dim"] * heads != d or cfg["num_key_value_heads"] != heads
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or (cfg["n_head"], cfg["n_embd"]) != (heads, d)):
        raise ValueError("LoopedDecoder is Ouro's block: heads of "
                         "hidden_size / heads, as many key-value heads, "
                         "SiLU, an untied head")
    opt = cfg["optimizer"]
    tokens = Input(shape=(cfg["n_positions"],), name="tokens")
    logits = LoopedDecoder(
        vocab=cfg["vocab_size"], n_block=cfg["num_hidden_layers"],
        n_head=heads, hidden_size=d,
        intermediate_size=cfg["intermediate_size"],
        passes=cfg["total_ut_steps"], rotary_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], exit_beta=cfg["exit_beta"],
        initializer_range=cfg["initializer_range"], name="ouro")(tokens)
    net = Model(tokens, logits, name="ouro_2_6b")
    net.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                               beta_2=opt["beta_2"],
                               epsilon=opt["epsilon"]),
                loss=cfg["loss"])
    _BUILT.update(passes=cfg["total_ut_steps"],
                  layers=cfg["num_hidden_layers"])
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
    """The step as traced has to be the loop the configuration states:
    passes x layers layer applications over one set of weights, a head
    evaluation a pass (the exit-gate loss taken inside the model); and on
    a TPU every attention has to have gone through the Pallas flash
    kernel, and none through its reference."""
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash
    from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

    steps = [r for r in self_attention.loop_records if r["training"]]
    want = {"passes": _BUILT["passes"], "layers": _BUILT["layers"],
            "layer_applications": _BUILT["passes"] * _BUILT["layers"],
            "head_evaluations": _BUILT["passes"]}
    if not steps or any(steps[-1][k] != v for k, v in want.items()):
        return f"looped stack traced as {steps[-1:]}, expected {want}"
    counts = dict(flash.invocation_counts)
    if platform == "tpu" and (counts["fallback"] > 0
                              or counts["pallas"] == 0):
        return f"flash attention routing {counts}"
    return None
