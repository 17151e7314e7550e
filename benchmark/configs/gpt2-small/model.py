"""The ``gpt2-small`` configuration as a user of the system builds it:
``TransformerLayer`` and a ``Dense`` head over the vocabulary, Adam."""

from __future__ import annotations


def build(cfg):
    """The compiled Keras model, parameters not yet made."""
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense,
        TransformerLayer,
    )
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    opt = cfg["optimizer"]
    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise ValueError("TransformerLayer's feed-forward is 4 x n_embd")
    tokens = Input(shape=(cfg["n_positions"],), name="tokens")
    h = TransformerLayer(
        vocab=cfg["vocab_size"], seq_len=cfg["n_positions"],
        n_block=cfg["n_layer"], n_head=cfg["n_head"],
        hidden_size=cfg["n_embd"], embedding_drop=cfg["embd_pdrop"],
        attn_drop=cfg["attn_pdrop"], hidden_drop=cfg["resid_pdrop"],
        initializer_range=cfg["initializer_range"],
        name="transformer")(tokens)
    logits = Dense(cfg["vocab_size"], name="lm_head")(h)
    net = Model(tokens, logits, name="gpt2_small")
    net.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                               beta_2=opt["beta_2"],
                               epsilon=opt["epsilon"]),
                loss=cfg["loss"])
    return net


def feature_set(x, y, cfg):
    from analytics_zoo_tpu.feature.dataset import FeatureSet

    return FeatureSet.of(x, y)


def first_gradient(opt_state, params0, cfg):
    """The first step's gradient as the optimizer got it, from the state
    after that step: Adam's first moment is then (1 - beta_1) of it."""
    import jax
    import optax

    def is_adam(s):
        return isinstance(s, optax.ScaleByAdamState)

    moments = [s for s in jax.tree_util.tree_leaves(opt_state,
                                                    is_leaf=is_adam)
               if is_adam(s)]
    if len(moments) != 1:
        raise ValueError(f"{len(moments)} Adam states in the optimizer "
                         "state, expected one")
    scale = 1.0 / (1.0 - cfg["optimizer"]["beta_1"])
    return jax.tree_util.tree_map(lambda m: m * scale, moments[0].mu)


def routing_fault(platform):
    """On a TPU every attention of the step has to have gone through the
    Pallas flash kernel, and none through its reference."""
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash

    counts = dict(flash.invocation_counts)
    if platform != "tpu":
        return None
    if counts["fallback"] > 0 or counts["pallas"] == 0:
        return f"flash attention routing {counts}"
    return None
