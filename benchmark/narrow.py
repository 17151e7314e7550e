"""Rounding to a narrower float type, for the lower-precision control of
the comparison that decides ``correct``: the references call the two
rounders on the operands of every matrix product and on every tensor a
layer hands on, as the program does in bfloat16."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: the types a control may round to, each as the type of the forward pass
#: and the type of the cotangents: (exponent bits, mantissa bits, largest
#: finite value).  An 8-bit computation is the usual recipe: e4m3 forward,
#: e5m2 backward, whose range the cotangents need.
NARROW = {"float8": ((4, 3, 240.0), (5, 2, 57344.0))}
#: the nearest precision below the bfloat16 the configurations compute in
CONTROL = "float8"


def _round(x, exponent_bits, mantissa_bits, top):
    """``x`` rounded by ``lax.reduce_precision``, which the compiler has to
    honour (a convert to a narrower type and back it folds away as excess
    precision).  An 8-bit float has too little range to hold a tensor
    unscaled, so the type is scaled to the tensor's largest value."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return lax.reduce_precision(x / scale, exponent_bits,
                                mantissa_bits) * scale


def rounders(round_to):
    """``(q, qw)`` for ``round_to`` (None: both leave ``x`` untouched).
    ``q`` is for a tensor a layer hands on: rounded forward, and its
    cotangent rounded on the way back, so that the products of the
    backward pass see narrow operands on both sides too.  ``qw`` is for a
    parameter: rounded where it is used, its gradient passed straight
    through, as the float32 master copy of a narrow computation gets it."""
    if round_to is None:
        return (lambda x: x), (lambda x: x)
    forward, backward = NARROW[round_to]

    @jax.custom_vjp
    def q(x):
        return _round(x, *forward)

    q.defvjp(lambda x: (q(x), None),
             lambda _, ct: (_round(ct, *backward),))

    @jax.custom_vjp
    def qw(x):
        return _round(x, *forward)

    qw.defvjp(lambda x: (qw(x), None), lambda _, ct: (ct,))
    return q, qw
