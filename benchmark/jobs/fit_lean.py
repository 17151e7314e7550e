"""The ``fit`` job for a model whose float32 state fills most of the chip.
The program's side is ``fit.py``'s, unchanged.  The reference's side holds
16 bytes a parameter and nothing twice: its step updates parameters and
moments in place (donated), the first gradient goes to the host as soon as
its norms are read, and the parameters it started from are made again from
the key when the change is measured.  ``fit.py``'s ``follow`` keeps the
start, the first gradient and both sides of every step on the device, 36
bytes a parameter."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare
from benchmark.manifest import sibling

fit = sibling(__file__, "fit")
WINDOW_ROWS, CHECK_ROWS, seed_key = fit.WINDOW_ROWS, fit.CHECK_ROWS, \
    fit.seed_key


def follow(reference, sizes, key, batches, round_to=None):
    """``fit.follow``'s numbers, with the reference's state held once."""
    step_fn = jax.jit(lambda p, o, i, x, y: reference.train_step(
        p, o, i, x, y, sizes, round_to), donate_argnums=(0, 1))
    make = jax.jit(lambda k: reference.init_params(k, sizes))
    params = make(key)
    names = compare.leaf_names(params)
    opt_state = reference.init_opt_state(params)
    losses, first, grad_norms = [], None, None
    for i, (x, y) in enumerate(batches):
        params, opt_state, loss, grads = step_fn(
            params, opt_state, np.int32(i), jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        if i == 0:
            grad_norms = np.asarray(compare.leaf_norms(grads))
            first = jax.device_get(grads)
        del grads
    del opt_state
    return {"losses": losses, "grads": first, "grad_norms": grad_norms,
            "delta_norms": np.asarray(compare.change_norms(params,
                                                           make(key))),
            "names": names}


class Job(fit.Job):
    def numbers(self) -> dict:
        reference = self.configuration.module("reference")
        ref = follow(reference, self.sizes, seed_key(self.seed),
                     self._check_batches)
        return compare.compare(self.program, ref, ref["names"])
