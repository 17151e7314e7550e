"""Loss functions — Keras-1 names, JAX-native implementations.

Mirrors the reference's 15 loss wrappers under
``pipeline/api/keras/objectives/*.scala`` (SparseCategoricalCrossEntropy,
BinaryCrossEntropy, CategoricalCrossEntropy, KullbackLeiblerDivergence, hinge
variants, Poisson, CosineProximity, RankHinge, MeanSquaredError, ...).  The
reference wraps BigDL Criterions that run forward/backward natively; here each
loss is a pure ``fn(y_true, y_pred) -> per-sample loss`` differentiated by
``jax.grad`` — the role the reference fills with hand-written backward passes.

All losses reduce over non-batch axes and return shape ``(batch,)``; the
training loop takes the (possibly weighted) mean.  This keeps per-sample
weighting and sequence masking composable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-7


class LossFunction:
    """Callable loss with a name; subclass or wrap a function."""

    #: True where a layer of the model takes the training loss itself
    #: (:class:`InModelLoss`)
    in_model = False

    def __init__(self, fn, name):
        self.fn = fn
        self.name = name

    def __call__(self, y_true, y_pred):
        return self.fn(y_true, y_pred)

    def mean(self, y_true, y_pred, sample_weight=None):
        per_sample = self(y_true, y_pred)
        if sample_weight is not None:
            return jnp.sum(per_sample * sample_weight) / (
                jnp.sum(sample_weight) + _EPS
            )
        return jnp.mean(per_sample)


def _align(y_true, y_pred):
    """Reshape y_true to y_pred's shape when they hold the same number of
    elements.  Guards the classic silent-broadcast bug: (B,) targets vs
    (B, 1) predictions would otherwise broadcast to (B, B) inside an
    elementwise loss."""
    ts, ps = jnp.shape(y_true), jnp.shape(y_pred)
    if ts == ps:
        return y_true
    import math
    if math.prod(ts) == math.prod(ps):
        return jnp.reshape(y_true, ps)
    raise ValueError(
        f"loss target shape {ts} is incompatible with prediction shape {ps}"
    )


def _reduce_rest(x):
    """Mean over all non-batch axes -> (batch,)."""
    if x.ndim <= 1:
        return x
    return jnp.mean(x.reshape(x.shape[0], -1), axis=-1)


def _sum_rest(x):
    if x.ndim <= 1:
        return x
    return jnp.sum(x.reshape(x.shape[0], -1), axis=-1)


def mean_squared_error(y_true, y_pred):
    y_true = _align(y_true, y_pred)
    return _reduce_rest((y_pred - y_true) ** 2)


def mean_absolute_error(y_true, y_pred):
    y_true = _align(y_true, y_pred)
    return _reduce_rest(jnp.abs(y_pred - y_true))


def mean_absolute_percentage_error(y_true, y_pred):
    y_true = _align(y_true, y_pred)
    diff = jnp.abs((y_true - y_pred) / jnp.clip(jnp.abs(y_true), _EPS))
    return 100.0 * _reduce_rest(diff)


def mean_squared_logarithmic_error(y_true, y_pred):
    y_true = _align(y_true, y_pred)
    a = jnp.log(jnp.clip(y_pred, _EPS) + 1.0)
    b = jnp.log(jnp.clip(y_true, _EPS) + 1.0)
    return _reduce_rest((a - b) ** 2)


def binary_crossentropy(y_true, y_pred):
    """Expects probabilities in (0,1) (reference BinaryCrossEntropy.scala)."""
    y_true = _align(y_true, y_pred)
    y_pred = jnp.clip(y_pred, _EPS, 1.0 - _EPS)
    return _reduce_rest(
        -(y_true * jnp.log(y_pred) + (1.0 - y_true) * jnp.log1p(-y_pred))
    )


def binary_crossentropy_from_logits(y_true, logits):
    logits = logits.astype(jnp.float32)  # f32 CE under bf16 compute
    return _reduce_rest(
        jnp.maximum(logits, 0) - logits * y_true
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def categorical_crossentropy(y_true, y_pred):
    """One-hot targets, probability predictions
    (reference CategoricalCrossEntropy.scala)."""
    y_pred = y_pred / jnp.clip(
        jnp.sum(y_pred, axis=-1, keepdims=True), _EPS
    )
    y_pred = jnp.clip(y_pred, _EPS, 1.0)
    return _sum_rest(-y_true * jnp.log(y_pred))


def sparse_categorical_crossentropy(y_true, y_pred):
    """Integer targets, probability predictions (reference
    SparseCategoricalCrossEntropy.scala; BigDL zero-based labels)."""
    y_pred = jnp.clip(y_pred, _EPS, 1.0)
    logp = jnp.log(y_pred)
    labels = y_true.astype(jnp.int32)
    if labels.ndim == logp.ndim:
        labels = labels.squeeze(-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if picked.ndim > 1:
        picked = picked.reshape(picked.shape[0], -1).mean(axis=-1)
    return -picked


def sparse_categorical_crossentropy_from_logits(y_true, logits):
    labels = y_true.astype(jnp.int32)
    if labels.ndim == logits.ndim:
        labels = labels.squeeze(-1)
    if logits.ndim == 2 and labels.ndim == 1:
        # Kernel plane: a plan routing loss.softmax_xent to the fused
        # pallas kernel computes lse - logits[label] without ever
        # materializing the (B, V) log-prob tensor in HBM — numerically
        # the same f32 quantity as the log_softmax path below.  Only
        # the plain (B, V) + (B,) shape routes; anything else (and any
        # plan picking "xla" or carrying no table) takes the XLA path.
        from analytics_zoo_tpu.parallel.plan import resolve_kernel

        if resolve_kernel("loss.softmax_xent") == "fused_softmax_xent":
            from analytics_zoo_tpu.ops.pallas.fused_softmax_xent import (
                softmax_xent,
            )

            return softmax_xent(logits, labels)
    # f32 softmax-CE regardless of compute dtype: a bf16 log-softmax over
    # a 32k-vocab axis loses the tail of the normalizer; the upcast fuses
    # into the reduction while the lm-head matmul stays bf16
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if picked.ndim > 1:
        picked = picked.reshape(picked.shape[0], -1).mean(axis=-1)
    return -picked


def kullback_leibler_divergence(y_true, y_pred):
    y_true_c = jnp.clip(y_true, _EPS, 1.0)
    y_pred_c = jnp.clip(y_pred, _EPS, 1.0)
    return _sum_rest(y_true_c * jnp.log(y_true_c / y_pred_c))


def poisson(y_true, y_pred):
    y_true = _align(y_true, y_pred)
    return _reduce_rest(y_pred - y_true * jnp.log(y_pred + _EPS))


def cosine_proximity(y_true, y_pred):
    def l2(x):
        return x / jnp.clip(
            jnp.linalg.norm(x, axis=-1, keepdims=True), _EPS
        )
    return -_sum_rest(l2(y_true) * l2(y_pred))


def hinge(y_true, y_pred):
    y_true = _align(y_true, y_pred)
    return _reduce_rest(jnp.maximum(1.0 - y_true * y_pred, 0.0))


def squared_hinge(y_true, y_pred):
    y_true = _align(y_true, y_pred)
    return _reduce_rest(jnp.maximum(1.0 - y_true * y_pred, 0.0) ** 2)


def rank_hinge(y_true, y_pred, margin: float = 1.0):
    """Pairwise ranking hinge for (pos, neg)-interleaved batches — reference
    RankHinge.scala (used by KNRM text matching).  Expects batch laid out as
    alternating positive/negative pairs."""
    pos = y_pred[0::2]
    neg = y_pred[1::2]
    loss = jnp.maximum(0.0, margin - pos + neg)
    return jnp.repeat(_reduce_rest(loss), 2)[: y_pred.shape[0]]


_LOSSES = {
    "mse": mean_squared_error,
    "mean_squared_error": mean_squared_error,
    "mae": mean_absolute_error,
    "mean_absolute_error": mean_absolute_error,
    "mape": mean_absolute_percentage_error,
    "mean_absolute_percentage_error": mean_absolute_percentage_error,
    "msle": mean_squared_logarithmic_error,
    "mean_squared_logarithmic_error": mean_squared_logarithmic_error,
    "binary_crossentropy": binary_crossentropy,
    "binary_crossentropy_from_logits": binary_crossentropy_from_logits,
    "categorical_crossentropy": categorical_crossentropy,
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy,
    "sparse_categorical_crossentropy_from_logits":
        sparse_categorical_crossentropy_from_logits,
    "kld": kullback_leibler_divergence,
    "kullback_leibler_divergence": kullback_leibler_divergence,
    "poisson": poisson,
    "cosine_proximity": cosine_proximity,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "rank_hinge": rank_hinge,
}

# Class-style aliases matching reference objective class names
# (pipeline/api/keras/objectives/*.scala).
def MeanSquaredError():
    return LossFunction(mean_squared_error, "mse")


def MeanAbsoluteError():
    return LossFunction(mean_absolute_error, "mae")


def BinaryCrossEntropy():
    return LossFunction(binary_crossentropy, "binary_crossentropy")


def CategoricalCrossEntropy():
    return LossFunction(categorical_crossentropy, "categorical_crossentropy")


def SparseCategoricalCrossEntropy():
    return LossFunction(sparse_categorical_crossentropy,
                        "sparse_categorical_crossentropy")


class InModelLoss(LossFunction):
    """A training loss that a layer of the model takes itself, where its
    logits are made, because it needs the targets beside several sets of
    logits that must not all be live at once (``LoopedDecoder``: every
    pass's logits, weighted by an exit distribution).

    The train step hands the batch's targets to the forward pass
    (``engine.training_targets``), calls no ``mean`` and minimises what
    the layers report under the ``*_cost`` leaves of their state
    (``ops.moe.collect_aux_cost``).  ``fn`` is the loss of the model's
    OUTPUT alone and serves ``evaluate``: for the looped decoder the last
    pass's cross-entropy, the pass inference reads.  Sample weights are
    not supported."""

    in_model = True


def LoopedExitCrossEntropy():
    """sum_t p_t CE(logits_t, y) - beta H(p) over a looped decoder's
    passes (``layers.LoopedDecoder`` takes it and holds beta)."""
    return InModelLoss(sparse_categorical_crossentropy_from_logits,
                       "looped_exit_cross_entropy")


def NextTokenCrossEntropy():
    """Mean cross-entropy of a decoder's logits against the targets, taken
    by the decoder itself where its logits are made
    (``layers.LatentMoEDecoder``: in token blocks, none kept)."""
    return InModelLoss(sparse_categorical_crossentropy_from_logits,
                       "next_token_cross_entropy")


class RankHinge(LossFunction):
    """Pairwise ranking hinge (reference RankHinge.scala)."""

    def __init__(self, margin: float = 1.0):
        self.margin = margin
        super().__init__(self._fn, "rank_hinge")

    def _fn(self, y_true, y_pred):
        return rank_hinge(y_true, y_pred, self.margin)


def get_loss(identifier) -> LossFunction:
    if isinstance(identifier, LossFunction):
        return identifier
    if callable(identifier):
        return LossFunction(identifier,
                            getattr(identifier, "__name__", "custom"))
    if isinstance(identifier, str):
        key = identifier.lower()
        if key == "looped_exit_cross_entropy":
            return LoopedExitCrossEntropy()
        if key == "next_token_cross_entropy":
            return NextTokenCrossEntropy()
        if key in _LOSSES:
            return LossFunction(_LOSSES[key], key)
    raise ValueError(f"unknown loss {identifier!r}")
