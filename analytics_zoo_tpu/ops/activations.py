"""Activation registry — reference keras ``Activation`` layer supports these
by name (pipeline/api/keras/layers/Activation and KerasUtils string mapping).
All map to jax.nn primitives so XLA fuses them into the surrounding matmul.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "hard_sigmoid": jax.nn.hard_sigmoid,
    "softmax": lambda x: jax.nn.softmax(x, axis=-1),
    "log_softmax": lambda x: jax.nn.log_softmax(x, axis=-1),
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "elu": jax.nn.elu,
    "selu": jax.nn.selu,
    "gelu": jax.nn.gelu,
    "swish": jax.nn.swish,
    "silu": jax.nn.silu,
    # relu(x)^2 (Primer, arXiv:2109.08668): the experts' of Nemotron-H
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    "linear": lambda x: x,
    None: lambda x: x,
}


class NamedActivation:
    """Picklable by-name activation (model save/load keeps the name, the
    function is resolved at call time)."""

    def __init__(self, name):
        self.name = name

    def __call__(self, x):
        return _ACTIVATIONS[self.name](x)

    def __repr__(self):
        return f"activation({self.name})"


def get_activation(identifier):
    if identifier is None:
        return NamedActivation(None)
    if callable(identifier):
        return identifier
    key = identifier.lower() if isinstance(identifier, str) else identifier
    if key in _ACTIVATIONS:
        return NamedActivation(key)
    raise ValueError(f"unknown activation {identifier!r}")
