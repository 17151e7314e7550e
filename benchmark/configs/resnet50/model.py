"""The ``resnet50`` configuration as a user of the system builds it: the
package's ResNet-50, its ImageNet optimizer, uint8 images normalised on the
device."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def build(cfg):
    """The compiled Keras model, parameters not yet made."""
    from analytics_zoo_tpu.models.resnet import ResNet

    opt = cfg["optimizer"]
    size = cfg["image_size"]
    model = ResNet.image_net(cfg["depth"], classes=cfg["num_classes"],
                             input_shape=(size, size, 3))
    model.compile(
        optimizer=ResNet.imagenet_optimizer(
            base_lr=opt["base_lr"], batch_size=opt["batch_size"],
            steps_per_epoch=opt["steps_per_epoch"],
            warmup_epochs=opt["warmup_epochs"], momentum=opt["momentum"],
            weight_decay=opt["weight_decay"]),
        loss=cfg["loss"])
    return model


_MEAN = np.array([123.675, 116.28, 103.53], np.float32)
_STD = np.array([58.395, 57.12, 57.375], np.float32)


def _normalize(batch):
    # one function object for every FeatureSet: the estimator keys its
    # compiled step on it
    x = (batch["x"].astype(jnp.float32) - jnp.asarray(_MEAN)) \
        / jnp.asarray(_STD)
    return {**batch, "x": x}


def feature_set(x, y, cfg):
    from analytics_zoo_tpu.feature.dataset import FeatureSet

    if not (np.allclose(cfg["input"]["mean"], _MEAN)
            and np.allclose(cfg["input"]["std"], _STD)):
        raise ValueError("the configuration's channel statistics differ "
                         "from the device transform's")
    return FeatureSet.of(x, y).transform_on_device(_normalize)


def first_gradient(opt_state, params0, cfg):
    """The first step's gradient as the optimizer got it, from the state
    after that step: the momentum trace then holds it plus the weight
    decay's share of the parameters."""
    import jax
    import optax

    def is_trace(s):
        return isinstance(s, optax.TraceState)

    traces = [s for s in jax.tree_util.tree_leaves(opt_state,
                                                   is_leaf=is_trace)
              if is_trace(s)]
    if len(traces) != 1:
        raise ValueError(f"{len(traces)} momentum traces in the optimizer "
                         "state, expected one")
    wd = cfg["optimizer"]["weight_decay"]
    return jax.tree_util.tree_map(lambda t, p: t - wd * p,
                                  traces[0].trace, params0)


def routing_fault(platform):
    """No kernel of the kernel plane is on this model's default path."""
    return None
