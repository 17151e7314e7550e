"""KerasNet / Sequential / Model — the user-facing model API.

TPU-native re-design of the reference's
``pipeline/api/keras/models/Topology.scala``:

- ``KerasNet`` (Topology.scala:63-600): compile/fit/evaluate/predict,
  TensorBoard wiring, checkpointing, gradient clipping, ``summary()``.
- ``Model`` (graph, Topology.scala:602-759) and ``Sequential``
  (Topology.scala:825-959).

Where the reference's ``fit`` spins up ``InternalDistriOptimizer`` (Spark jobs
+ block-manager all-reduce, Topology.scala:1076-1259), here ``fit`` builds a
single jit-compiled SPMD train step through
:mod:`analytics_zoo_tpu.pipeline.estimator` — forward, backward, psum over the
``data`` mesh axis, and the optimizer update fused into one XLA program.

Models are also Layers, so they nest (a Sequential inside a Model graph), and
their parameters are ordinary pytrees: ``net.params`` / ``net.state``.
"""

from __future__ import annotations

import os
import pickle
from analytics_zoo_tpu.common.safe_pickle import (
    safe_load,
    safe_loads,
)
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.common.engine import get_zoo_context
from analytics_zoo_tpu.metrics import span
from analytics_zoo_tpu.pipeline.api.keras.engine import (
    GraphFunction,
    InputLayer,
    Layer,
    Variable,
    _ContainerBase,
    canonicalize_names,
)


def _copy_tree(tree):
    """Fresh device buffers for every leaf (donation-safe adoption)."""
    return jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), tree)


def _normalize_names(names) -> tuple:
    """Accept both freeze("a", "b") and freeze(["a", "b"])."""
    if len(names) == 1 and isinstance(names[0], (list, tuple)):
        return tuple(names[0])
    return tuple(names)


class KerasNet(_ContainerBase):
    """Base for trainable containers (reference KerasNet,
    Topology.scala:63-600)."""

    def __init__(self, name=None):
        super().__init__(name=name)
        self.params: dict | None = None
        self.state: dict | None = None
        self._compiled = None   # set by compile()
        self._tensorboard = None  # (log_dir, app_name)
        self._checkpoint = None   # (path, over_write)
        self._grad_clip = None    # ("l2norm", v) | ("const", lo, hi)
        self._estimator = None
        self._predict_fn = None   # cached jitted forward (shape-keyed by jit)
        self._frozen: set = set()  # layer names excluded from training

    # ------------------------------------------------------------------
    # parameter materialization
    # ------------------------------------------------------------------
    def build_params(self, rng=None, force: bool = False):
        """Materialize params/state pytrees (idempotent)."""
        if self.params is not None and not force:
            return self.params, self.state
        if force:
            self.params = self.state = None
        rng = rng if rng is not None else jax.random.PRNGKey(
            get_zoo_context().seed
        )
        self.params = self.init_params(rng)
        self.state = self.init_state()
        return self.params, self.state

    def forward(self, params, inputs, state=None, training=False, rng=None):
        """Pure forward; containers implement via call()."""
        return self.call(params, inputs, state=state, training=training,
                         rng=rng)

    # ------------------------------------------------------------------
    # compile / fit / evaluate / predict  (Topology.scala:135-547)
    # ------------------------------------------------------------------
    def compile(self, optimizer, loss, metrics=None):
        """Configure training (reference ``compile`` Topology.scala:135-166)."""
        from analytics_zoo_tpu.pipeline.api.keras.metrics import get_metric
        from analytics_zoo_tpu.pipeline.api.keras.objectives import get_loss
        from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
            get_optimizer,
        )

        self._compiled = dict(
            optimizer=get_optimizer(optimizer),
            loss=get_loss(loss),
            metrics=[get_metric(m) for m in (metrics or [])],
        )
        self._estimator = None
        return self

    def _require_compiled(self):
        if self._compiled is None:
            raise RuntimeError(
                "model not compiled; call compile(optimizer, loss) first"
            )

    def set_tensorboard(self, log_dir, app_name):
        """Reference Topology.scala:183-202."""
        self._tensorboard = (log_dir, app_name)

    def set_checkpoint(self, path, over_write=True):
        """Reference Topology.scala:245-255."""
        self._checkpoint = (path, over_write)

    def set_gradient_clipping_by_l2_norm(self, clip_norm):
        """Reference Topology.scala (clipping setters ~:168-181)."""
        self._grad_clip = ("l2norm", float(clip_norm))

    def set_constant_gradient_clipping(self, min_value, max_value):
        self._grad_clip = ("const", float(min_value), float(max_value))

    def clear_gradient_clipping(self):
        self._grad_clip = None

    def _make_estimator(self):
        from analytics_zoo_tpu.pipeline.estimator import Estimator

        self._require_compiled()
        est = Estimator(
            self,
            optimizer=self._compiled["optimizer"],
            loss=self._compiled["loss"],
            metrics=self._compiled["metrics"],
            grad_clip=self._grad_clip,
            tensorboard=self._tensorboard,
            checkpoint=self._checkpoint,
        )
        return est

    @span("zoo.keras.fit", fit=True)
    def fit(self, x, y=None, batch_size=32, nb_epoch=10,
            validation_data=None, distributed=True, sample_weight=None,
            autotune=None, plan=None, elastic=None):
        """Train (reference ``fit`` Topology.scala:418-431 →
        InternalDistriOptimizer.train Topology.scala:1076-1259).

        ``autotune=True`` (or ``ZOO_AUTOTUNE=1``) turns on the
        closed-loop tuner: prefetch workers/depth/read-ahead and the
        fused-dispatch K are tuned online from telemetry, with a
        bit-identical loss trajectory (see docs/data-pipeline.md
        "Autotuning").

        ``plan``: sharding plan for params/optimizer state/batch — a
        :class:`~analytics_zoo_tpu.parallel.plan.ShardingPlan` or a
        canned name ("dp"/"zero1"/"fsdp"); ``None`` defers to
        ``ZOO_SHARDING_PLAN``.  Loss trajectory is placement-invariant
        (see docs/parallelism.md).

        ``elastic``: an :class:`~analytics_zoo_tpu.elastic.membership.
        ElasticSession` turns this fit into one elastic training leg —
        it yields with :class:`~analytics_zoo_tpu.elastic.membership.
        GenerationChange` (after a durable snapshot) when the worker
        membership changes (see docs/elastic-training.md)."""
        from analytics_zoo_tpu.feature.dataset import FeatureSet

        train_set = FeatureSet.of(x, y, sample_weight=sample_weight)
        val_set = (FeatureSet.of(*validation_data)
                   if validation_data is not None else None)
        if self._estimator is None:
            self._estimator = self._make_estimator()
        self._estimator.train(
            train_set, batch_size=batch_size, nb_epoch=nb_epoch,
            validation_set=val_set, autotune=autotune, plan=plan,
            elastic=elastic,
        )
        self._sync_nested()
        return self

    def _sync_nested(self):
        """Copy trained subtrees back into nested KerasNet layers
        (pretrained backbones) so backbone.predict sees post-fit weights.
        Copies, not aliases: the nested net may later be fit() directly,
        and its donated buffers must not be this model's live params."""
        for ly in self.layers:
            if isinstance(ly, KerasNet):
                if self.params is not None and ly.name in self.params:
                    ly.params = _copy_tree(self.params[ly.name])
                if self.state is not None and ly.name in self.state:
                    ly.state = _copy_tree(self.state[ly.name])
                ly._sync_nested()

    def evaluate(self, x, y=None, batch_size=32):
        """Reference ``evaluate`` Topology.scala:472-501; returns a dict of
        metric name -> value (loss always included)."""
        from analytics_zoo_tpu.feature.dataset import FeatureSet

        if self._estimator is None:
            self._estimator = self._make_estimator()
        return self._estimator.evaluate(
            FeatureSet.of(x, y), batch_size=batch_size
        )

    def predict(self, x, batch_size=32, distributed=True):
        """Distributed inference (reference ``predict`` Topology.scala:511-547
        → Predictor.scala:155-189: broadcast + per-partition batching; here:
        jitted forward over batches sharded across the mesh)."""
        from analytics_zoo_tpu.feature.dataset import FeatureSet

        self.build_params()
        ctx = get_zoo_context()
        fs = FeatureSet.of(x)
        n = fs.num_samples

        cached = getattr(self, "_predict_fn", None)
        if cached is None or cached[0] is not ctx.compute_dtype:
            # Cached so repeated predict() calls hit jit's shape-keyed
            # compile cache instead of rebuilding a fresh function object
            # (and paying full compilation) every call.  Keyed by compute
            # dtype; invalidated by Sequential.add().  Model state stays f32
            # (BN running stats must not be rounded).
            from analytics_zoo_tpu.common.engine import cast_floats
            dtype = ctx.compute_dtype

            def _fwd(p, s, xb):
                out, _ = self.forward(
                    cast_floats(p, dtype), cast_floats(xb, dtype),
                    state=s, training=False)
                return cast_floats(out, jnp.float32)

            # through the unified partitioner's choke point: predict
            # programs share the persistent compile cache / metering /
            # HLO features with training (parallel/plan.py)
            from analytics_zoo_tpu.parallel.plan import compile_step

            cached = (ctx.compute_dtype,
                      compile_step(_fwd, label="predict_step"))
            self._predict_fn = cached
        fwd = cached[1]
        outs = []
        for batch in fs.batches(batch_size, shuffle=False, drop_last=False,
                                pad_to_batch=ctx.data_parallel_size):
            xb = ctx.shard_batch(batch["x"])
            out = fwd(self.params, self.state, xb)
            # EVERY batch the mesh does not divide is padded, not only
            # the last: drop each one's padded rows before joining
            valid = int(batch["n_valid"])
            outs.append([np.asarray(o)[:valid] for o in out]
                        if isinstance(out, (list, tuple))
                        else np.asarray(out)[:valid])
        if isinstance(outs[0], list):  # multi-output graph
            return [np.concatenate([o[i] for o in outs], axis=0)[:n]
                    for i in range(len(outs[0]))]
        return np.concatenate(outs, axis=0)[:n]

    def predict_classes(self, x, batch_size=32, zero_based_label=True):
        """Reference ``predictClasses`` (Topology.scala:549+)."""
        probs = self.predict(x, batch_size)
        cls = np.argmax(probs, axis=-1)
        return cls if zero_based_label else cls + 1

    # ------------------------------------------------------------------
    # transfer learning: freeze / unfreeze
    # (reference NetUtils.scala freeze/unFreeze + the dogs-vs-cats app's
    # freeze_up_to recipe; here frozen layers get their optimizer updates
    # masked to zero inside the jitted train step — no graph surgery)
    # ------------------------------------------------------------------
    def _validate_layer_names(self, names):
        avail = {ly.name for ly in self.layers}
        unknown = [n for n in names if n not in avail]
        if unknown:
            raise ValueError(
                f"unknown layer(s) {unknown}; available: {sorted(avail)}"
            )

    def freeze(self, *names) -> "KerasNet":
        """Mark the named layers (all layers if none given) non-trainable.

        Reference ``Net.freeze`` (NetUtils.scala): frozen layers keep their
        weights through ``fit``.  Takes effect on the next fit().
        """
        names = _normalize_names(names)
        if not names:
            names = tuple(ly.name for ly in self.layers)
        self._validate_layer_names(names)
        self._frozen.update(names)
        self._estimator = None  # train step must be rebuilt with the mask
        return self

    def unfreeze(self, *names) -> "KerasNet":
        """Reference ``Net.unFreeze``: re-enable training for the named
        layers (all if none given)."""
        names = _normalize_names(names)
        if not names:
            self._frozen.clear()
        else:
            self._validate_layer_names(names)
            self._frozen.difference_update(names)
        self._estimator = None
        return self

    @property
    def frozen_layers(self) -> list[str]:
        return sorted(self._frozen)

    # ------------------------------------------------------------------
    # weights / persistence
    # ------------------------------------------------------------------
    def get_weights(self):
        self.build_params()
        return jax.tree_util.tree_map(np.asarray, self.params)

    def set_weights(self, weights):
        self.build_params()
        jax.tree_util.tree_map(lambda a, b: None, self.params, weights)
        self.params = jax.tree_util.tree_map(jnp.asarray, weights)

    def save_weights(self, path, over_write=True):
        self.build_params()
        if os.path.exists(path) and not over_write:
            raise IOError(f"{path} exists and over_write=False")
        flat, treedef = jax.tree_util.tree_flatten((self.params, self.state))
        np.savez(path, treedef=np.frombuffer(
            pickle.dumps(treedef), dtype=np.uint8),
            **{str(i): np.asarray(a) for i, a in enumerate(flat)})

    def load_weights(self, path):
        data = np.load(path if path.endswith(".npz") else path + ".npz",
                       allow_pickle=False)
        treedef = safe_loads(data["treedef"].tobytes())
        flat = [data[str(i)] for i in range(len(data.files) - 1)]
        self.params, self.state = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(a) for a in flat]
        )

    def _nets(self) -> list["KerasNet"]:
        """Self plus every nested KerasNet, recursively."""
        nets, stack = [self], list(self.layers)
        while stack:
            ly = stack.pop()
            if isinstance(ly, KerasNet):
                nets.append(ly)
                stack.extend(ly.layers)
        return nets

    def load_checkpoint(self, path) -> "KerasNet":
        """Restore weights/state from the LATEST training checkpoint in
        ``path`` (as written by ``set_checkpoint`` during fit) without
        training — the reference's evaluate-from-checkpoint flow
        (tf_optimizer/evaluate_lenet.py; Net.load for .bigdl snapshots)."""
        from analytics_zoo_tpu.pipeline.estimator.estimator import (
            _Checkpointer,
        )

        blob = _Checkpointer(path).latest()
        if blob is None:
            raise FileNotFoundError(f"no checkpoint found under {path}")
        self.params = jax.tree_util.tree_map(jnp.asarray, blob["params"])
        self.state = jax.tree_util.tree_map(jnp.asarray, blob["state"])
        self._sync_nested()
        return self

    def save(self, path, over_write=True):
        """Whole-model save (reference ZooModel.saveModel /
        KerasNet.saveModule): config + weights in one pickle.  Device
        arrays and runtime state are stripped from EVERY net in the tree
        (nested backbones carry their own param copies after
        ``_sync_nested``; leaving them in would pickle each backbone's
        weights twice)."""
        if os.path.exists(path) and not over_write:
            raise IOError(f"{path} exists and over_write=False")
        weights = (
            jax.tree_util.tree_map(np.asarray, (self.params, self.state))
            if self.params is not None else None
        )
        stashed = []
        for net in self._nets():
            stashed.append((net, net.params, net.state, net._estimator,
                            net._compiled, getattr(net, "_predict_fn", None)))
            net.params = net.state = None
            net._estimator = net._compiled = net._predict_fn = None
        try:
            with open(path, "wb") as f:
                pickle.dump({"net": self, "weights": weights}, f)
        finally:
            for net, params, state, est, compiled, pfn in stashed:
                net.params, net.state = params, state
                net._estimator, net._compiled = est, compiled
                net._predict_fn = pfn

    @staticmethod
    def load(path) -> "KerasNet":
        with open(path, "rb") as f:
            blob = safe_load(f)
        net = blob["net"]
        if blob["weights"] is not None:
            net.params, net.state = jax.tree_util.tree_map(
                jnp.asarray, blob["weights"]
            )
            net._sync_nested()  # repopulate nested backbones' copies
        return net

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    @property
    def layers(self) -> list[Layer]:
        raise NotImplementedError

    def summary(self, line_length: int = 100):
        """Layer table like the reference's ``summary()``
        (Topology.scala KerasNet.summary)."""
        lines = []
        lines.append("_" * line_length)
        lines.append(f"{'Layer (type)':<44}{'Output Shape':<28}{'Param #':<12}")
        lines.append("=" * line_length)
        total = 0
        for layer in self.layers:
            if isinstance(layer, InputLayer):
                shape, count = layer._build_shape, 0
            else:
                try:
                    shape = layer.compute_output_shape(
                        (None,) + tuple(layer._build_shape or ())
                    )
                except Exception:
                    shape = "?"
                count = layer.param_count() if layer.built else 0
            total += count
            name = f"{layer.name} ({type(layer).__name__})"
            lines.append(f"{name:<44}{str(shape):<28}{count:<12}")
        lines.append("=" * line_length)
        lines.append(f"Total params: {total:,}")
        lines.append("_" * line_length)
        text = "\n".join(lines)
        print(text)
        return text


class Sequential(KerasNet):
    """Linear stack of layers (reference Sequential,
    Topology.scala:825-959)."""

    def __init__(self, name=None):
        super().__init__(name=name)
        self._layers: list[Layer] = []
        self._output_shape = None  # batch-less

    @property
    def layers(self):
        return self._layers

    def add(self, layer: Layer) -> "Sequential":
        if not self._layers:
            in_shape = layer._input_shape
            if in_shape is None and not layer.built:
                raise ValueError(
                    "first layer needs input_shape=..., as in the reference "
                    "Sequential API"
                )
        else:
            in_shape = self._output_shape
        layer.ensure_built(in_shape)
        out_full = layer.compute_output_shape((None,) + tuple(in_shape or ()))
        self._output_shape = tuple(out_full[1:])
        self._layers.append(layer)
        canonicalize_names(self._layers)
        if self.params is not None:
            # Weights already materialized (a new_graph'd pretrained stack
            # being extended with a fresh head): keep them and init only
            # the new layer — nulling params here would silently retrain
            # the "pretrained" backbone from scratch.
            if not isinstance(layer, InputLayer):
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(get_zoo_context().seed),
                    len(self._layers) - 1)
                p = layer.init_params(rng)  # KerasNet adopts its copy here
                if p:
                    self.params[layer.name] = p
                s = layer.init_state()
                if s:
                    if self.state is None:
                        self.state = {}
                    self.state[layer.name] = s
        self._predict_fn = None  # a cached jitted forward is stale now
        return self

    def build(self, input_shape):
        pass  # layers build incrementally in add()

    @property
    def stateful(self):
        return True

    def get_output_shape(self):
        return (None,) + tuple(self._output_shape or ())

    def get_input_shape(self):
        if not self._layers:
            return None
        first = self._layers[0]
        return (None,) + tuple(first._build_shape or ())

    def init_params(self, rng):
        # A nested KerasNet that already materialized weights (a pretrained
        # backbone from new_graph / load) contributes a COPY of those
        # weights — the transfer-learning contract.  A copy, because the
        # outer model's train step donates its param buffers to XLA; shared
        # arrays would leave the backbone holding deleted buffers after the
        # first step.
        if self.params is not None:
            return _copy_tree(self.params)
        params = {}
        for i, layer in enumerate(self._layers):
            if isinstance(layer, InputLayer):
                continue
            p = layer.init_params(jax.random.fold_in(rng, i))
            if p:
                params[layer.name] = p
        return params

    def init_state(self):
        if self.state is not None:
            return _copy_tree(self.state)
        state = {}
        for layer in self._layers:
            s = layer.init_state()
            if s:
                state[layer.name] = s
        return state

    def call(self, params, inputs, state=None, training=False, rng=None):
        state = state or {}
        new_state = dict(state)
        y = inputs
        for i, layer in enumerate(self._layers):
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            y, s = layer.apply(
                params.get(layer.name, {}), y,
                state=new_state.get(layer.name),
                training=training, rng=lrng,
            )
            if s:  # {} stays omitted — must mirror init_state's filter or
                # a nested stateless KerasNet changes the state treedef
                new_state[layer.name] = s
        return y, new_state

    def compute_output_shape(self, input_shape):
        return (input_shape[0],) + tuple(self._output_shape)

    def ensure_built(self, input_shape):
        # Built incrementally; verify compatibility.
        self.built = True
        self._build_shape = input_shape
        return input_shape

    # ------------------------------------------------------------------
    # transfer learning (reference dogs-vs-cats app recipe:
    # Net.load(...).new_graph(out).freeze_up_to(layer))
    # ------------------------------------------------------------------
    def freeze_up_to(self, *names) -> "Sequential":
        """Freeze every layer from the input up to and including the named
        layer(s) (reference ``freezeUpTo``, NetUtils.scala)."""
        names = _normalize_names(names)
        if not names:
            raise ValueError("freeze_up_to requires at least one layer "
                             "name (use freeze() to freeze everything)")
        self._validate_layer_names(names)
        idx = {ly.name: i for i, ly in enumerate(self._layers)}
        cut = max(idx[n] for n in names)
        return self.freeze(*[ly.name for ly in self._layers[:cut + 1]])

    def new_graph(self, outputs) -> "Sequential":
        """Truncate at the named layer: a new Sequential ending there,
        SHARING layer objects and (if materialized) their weights — the
        reference's ``new_graph(output)`` feature-extraction surgery
        (NetUtils.scala newGraph)."""
        names = [outputs] if isinstance(outputs, str) else list(outputs)
        if len(names) != 1:
            raise ValueError("Sequential.new_graph takes exactly one output"
                             " layer name")
        self._validate_layer_names(names)
        idx = {ly.name: i for i, ly in enumerate(self._layers)}
        cut = idx[names[0]]
        sub = Sequential(name=f"{self.name}_graph")
        sub._layers = list(self._layers[:cut + 1])
        for ly in sub._layers:   # pin: a later sub.add() must not renumber
            ly._auto_named = False
        last = self._layers[cut]
        sub._output_shape = tuple(
            last.compute_output_shape(
                (None,) + tuple(last._build_shape or ())
            )[1:]
        )
        sub.built = True
        sub._build_shape = (self._layers[0]._build_shape
                            if self._layers else None)
        if self.params is not None:
            # Copies: either model may later fit() (donating its buffers);
            # shared arrays would leave the other holding deleted buffers.
            keep = {ly.name for ly in sub._layers}
            sub.params = _copy_tree(
                {k: v for k, v in self.params.items() if k in keep})
            sub.state = _copy_tree(
                {k: v for k, v in (self.state or {}).items() if k in keep})
        return sub


class Model(KerasNet):
    """Graph model from symbolic inputs/outputs (reference Model,
    Topology.scala:602-759)."""

    def __init__(self, input, output, name=None):
        super().__init__(name=name)
        inputs = input if isinstance(input, (list, tuple)) else [input]
        outputs = output if isinstance(output, (list, tuple)) else [output]
        for v in list(inputs) + list(outputs):
            if not isinstance(v, Variable):
                raise TypeError("Model(input, output) takes symbolic "
                                "Variables from Input(...)")
        self._graph = GraphFunction(inputs, outputs)
        self.built = True
        self._build_shape = [tuple(v.shape[1:]) for v in inputs]
        if len(self._build_shape) == 1:
            self._build_shape = self._build_shape[0]
        self._output_vars = outputs

    @property
    def layers(self):
        return self._graph.layers

    @property
    def stateful(self):
        return True

    def get_output_shape(self):
        shapes = [v.shape for v in self._graph.outputs]
        return shapes[0] if len(shapes) == 1 else shapes

    def get_input_shape(self):
        shapes = [v.shape for v in self._graph.inputs]
        return shapes[0] if len(shapes) == 1 else shapes

    def init_params(self, rng):
        if self.params is not None:   # pretrained: adopt a copy (donation
            return _copy_tree(self.params)   # safety — see Sequential)
        params, _ = self._graph.init(rng)
        return params

    def init_state(self):
        if self.state is not None:
            return _copy_tree(self.state)
        _, state = self._graph.init(jax.random.PRNGKey(0))
        return state

    def call(self, params, inputs, state=None, training=False, rng=None):
        return self._graph(params, inputs, state=state, training=training,
                           rng=rng)

    def compute_output_shape(self, input_shape):
        shapes = [v.shape for v in self._graph.outputs]
        return shapes[0] if len(shapes) == 1 else shapes

    # ------------------------------------------------------------------
    # transfer learning (reference NetUtils.scala newGraph/freezeUpTo on
    # the static graph)
    # ------------------------------------------------------------------
    def freeze_up_to(self, *names) -> "Model":
        """Freeze the named layers and every graph ancestor of them."""
        names = _normalize_names(names)
        if not names:
            raise ValueError("freeze_up_to requires at least one layer "
                             "name (use freeze() to freeze everything)")
        self._validate_layer_names(names)
        stack = [n for n in self._graph.nodes if n.layer.name in set(names)]
        seen, frozen = set(), set()
        while stack:
            node = stack.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            if not isinstance(node.layer, InputLayer):
                frozen.add(node.layer.name)
            for v in node.inbound:
                stack.append(v.node)
        return self.freeze(*sorted(frozen))

    def new_graph(self, outputs) -> "Model":
        """A new Model over the same graph, re-rooted at the named layers'
        outputs; weights (if materialized) are shared for retained layers."""
        names = [outputs] if isinstance(outputs, str) else list(outputs)
        self._validate_layer_names(names)
        by_name: dict[str, Any] = {}
        for node in self._graph.nodes:
            by_name.setdefault(node.layer.name, node)
        out_vars = [by_name[n].outputs[0] for n in names]
        # Names were canonicalized when THIS model was built; pin them so
        # the sub-model's canonicalize_names pass can't renumber shared
        # layers (which would corrupt both models' param keys).
        for ly in self.layers:
            ly._auto_named = False
        sub = Model(input=self._graph.inputs, output=out_vars,
                    name=f"{self.name}_graph")
        if self.params is not None:
            # Copies — donation safety, see Sequential.new_graph.
            keep = {ly.name for ly in sub.layers}
            sub.params = _copy_tree(
                {k: v for k, v in self.params.items() if k in keep})
            sub.state = _copy_tree(
                {k: v for k, v in (self.state or {}).items() if k in keep})
        return sub


def merge(inputs, mode="sum", concat_axis=-1, name=None):
    """Functional merge helper (reference Merge.scala / ``merge`` in
    keras API).  Takes symbolic Variables."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.merge import Merge

    return Merge(mode=mode, concat_axis=concat_axis, name=name)(inputs)
