"""The ``fit`` job: one Keras model of the program, made once, driven
through its first steps in set-up (the steps the plain reference follows)
and then, the same object, through whole ``fit(nb_epoch=1)`` calls for the
length of the window."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, data

#: streams of the seed: the window's rows and the check steps' differ
WINDOW_ROWS, CHECK_ROWS = 0, 1


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) >> 31),
                              int(seed) & 0x7FFFFFFF)


def follow(reference, sizes, key, batches, round_to=None):
    """The plain reference through the check steps: each step's loss, the
    first gradient and its norm by leaf, and the norm of each leaf's change
    over all the steps."""
    step_fn = jax.jit(lambda p, o, i, x, y: reference.train_step(
        p, o, i, x, y, sizes, round_to))
    params0 = jax.jit(lambda k: reference.init_params(k, sizes))(key)
    params, opt_state = params0, reference.init_opt_state(params0)
    losses, first = [], None
    for i, (x, y) in enumerate(batches):
        params, opt_state, loss, grads = step_fn(
            params, opt_state, np.int32(i), jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        if i == 0:
            first = grads
        del grads
    return {"losses": losses, "grads": first,
            "grad_norms": np.asarray(compare.leaf_norms(first)),
            "delta_norms": np.asarray(compare.change_norms(params, params0)),
            "names": compare.leaf_names(params0)}


class Job:
    #: how the device's trace names the program of one step
    STEP_PROGRAM = "jit_train_step"

    def __init__(self, configuration, traffic: dict, seed: int,
                 platform: str):
        self.configuration = configuration
        self.sizes = configuration.sizes
        self.traffic = traffic
        self.seed = int(seed)
        self.platform = platform
        self.batch = int(traffic["batch"])
        self.steps_per_epoch = int(traffic["steps_per_epoch"])
        self.steps_per_call = self.steps_per_epoch   # fit(nb_epoch=1)
        self.model = None
        self.program = None          # the check steps' numbers
        self._check_batches = None
        self._window_set = None

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        """Data and weights from the seed, the model, and the check steps:
        one-batch ``fit`` calls through the window's own call and feed."""
        model_py = self.configuration.module("model")
        reference = self.configuration.module("reference")
        sizes, batch = self.sizes, self.batch
        n_check = int(self.traffic["check_steps"])
        x, y = data.rows(self.seed, CHECK_ROWS, n_check * batch, sizes)
        self._check_batches = [
            (x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
            for i in range(n_check)]
        x, y = data.rows(self.seed, WINDOW_ROWS,
                         self.steps_per_epoch * batch, sizes)
        self._window_set = model_py.feature_set(x, y, sizes)

        self.model = model = model_py.build(sizes)
        built, _state = model.build_params()
        make = jax.jit(lambda k: reference.init_params(k, sizes))
        key = seed_key(self.seed)
        params0 = make(key)
        if jax.tree_util.tree_structure(built) \
                != jax.tree_util.tree_structure(params0) or any(
                    a.shape != b.shape or a.dtype != b.dtype
                    for a, b in zip(jax.tree_util.tree_leaves(built),
                                    jax.tree_util.tree_leaves(params0))):
            raise ValueError(
                "the reference's parameter tree is not the program's: "
                f"{compare.leaf_names(built)[:4]}... against "
                f"{compare.leaf_names(params0)[:4]}...")
        del built
        model.params = params0       # donated by the first step

        losses, first, grad_norms = [], None, None
        for i, (bx, by) in enumerate(self._check_batches):
            self._fit(model_py.feature_set(bx, by, sizes))
            losses.append(float(model._estimator.history[-1]["loss"]))
            if i == 0:
                first = model_py.first_gradient(model._estimator._opt_state,
                                                make(key), sizes)
                grad_norms = np.asarray(compare.leaf_norms(first))
                # kept on the host until the reference has its own: the
                # window's peak on the device is the program's alone
                first = jax.device_get(first)
        self.program = {
            "losses": losses, "grad_norms": grad_norms, "grads": first,
            "delta_norms": np.asarray(
                compare.change_norms(model.params, make(key)))}
        fault = model_py.routing_fault(self.platform)
        if fault:
            raise RuntimeError(fault)

    def _fit(self, feature_set) -> None:
        self.model.fit(feature_set, batch_size=self.batch, nb_epoch=1)

    # -- the window -----------------------------------------------------
    def window(self, seconds: float) -> dict:
        """Whole ``fit`` calls over the window's FeatureSet until
        ``seconds`` have passed; each ends in the epoch's closing sync, so
        the clock stops on finished work."""
        fits = 0
        t0 = time.perf_counter()
        while True:
            self._fit(self._window_set)
            fits += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        steps = fits * self.steps_per_epoch
        examples = steps * self.batch
        return {"fits": fits, "steps": steps, "examples": examples,
                "elapsed_s": elapsed, "attempted": steps, "failed": 0,
                "end_to_end": {"train_examples_per_s": examples / elapsed}}

    def free(self) -> None:
        """Drop the program's state and data before the reference runs."""
        if self.model is not None:
            self.model._estimator = None
            self.model.params = self.model.state = None
        self.model = self._window_set = None

    # -- correct --------------------------------------------------------
    def numbers(self) -> dict:
        """The program's check steps against the reference's."""
        reference = self.configuration.module("reference")
        ref = follow(reference, self.sizes, seed_key(self.seed),
                     self._check_batches)
        return compare.compare(self.program, ref, ref["names"])
