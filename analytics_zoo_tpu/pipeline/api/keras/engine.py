"""Keras-1-style layer/graph engine, re-designed for JAX.

The reference implements this surface as Scala wrappers over BigDL's mutable
``KerasLayer`` modules (reference pipeline/api/keras/layers/*.scala, ~120
files; graph topology in pipeline/api/keras/models/Topology.scala).  The
TPU-native re-design is *functional*: a ``Layer`` owns only static config and
weight *specs*; parameters and mutable state (e.g. BatchNorm running stats)
live in pytrees threaded through pure ``call`` functions, so an entire model
lowers to one jit-compiled XLA program (no per-layer native calls as in the
reference's MKL/JNI path).

Symbolic graph building (``Input``/``Variable``/``Node``) plays the role of
the reference's autograd ``Variable`` over BigDL ``ModuleNode``
(pipeline/api/autograd/math.scala:365-612): calling a layer on Variables
records a node; ``Model(inputs, outputs)`` topologically sorts the recorded
graph into a pure function.

Shape convention (Keras-1, matching the reference's ``computeOutputShape``):
user-facing ``input_shape`` excludes the batch dim; internal full shapes carry
``None`` in position 0.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.common.utils import to_tuple_shape

# ---------------------------------------------------------------------------
# Weight specs & initializers
# ---------------------------------------------------------------------------

_INIT_FNS = {}


def register_init(name):
    def deco(fn):
        _INIT_FNS[name] = fn
        return fn
    return deco


@register_init("zero")
def _zero(rng, shape, dtype):
    return jnp.zeros(shape, dtype)


@register_init("one")
def _one(rng, shape, dtype):
    return jnp.ones(shape, dtype)


@register_init("glorot_uniform")
def _glorot_uniform(rng, shape, dtype):
    fan_in, fan_out = _compute_fans(shape)
    limit = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


@register_init("glorot_normal")
def _glorot_normal(rng, shape, dtype):
    fan_in, fan_out = _compute_fans(shape)
    std = np.sqrt(2.0 / (fan_in + fan_out))
    return std * jax.random.normal(rng, shape, dtype)


@register_init("he_normal")
def _he_normal(rng, shape, dtype):
    fan_in, _ = _compute_fans(shape)
    std = np.sqrt(2.0 / fan_in)
    return std * jax.random.normal(rng, shape, dtype)


@register_init("he_uniform")
def _he_uniform(rng, shape, dtype):
    fan_in, _ = _compute_fans(shape)
    limit = np.sqrt(6.0 / fan_in)
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


@register_init("lecun_uniform")
def _lecun_uniform(rng, shape, dtype):
    fan_in, _ = _compute_fans(shape)
    limit = np.sqrt(3.0 / fan_in)
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


@register_init("uniform")
def _uniform(rng, shape, dtype):
    return jax.random.uniform(rng, shape, dtype, -0.05, 0.05)


@register_init("normal")
def _normal(rng, shape, dtype):
    return 0.05 * jax.random.normal(rng, shape, dtype)


@register_init("orthogonal")
def _orthogonal(rng, shape, dtype):
    return jax.nn.initializers.orthogonal()(rng, shape, dtype)


def _compute_fans(shape):
    """Fan-in/fan-out for conv kernels shaped (..spatial.., in, out) and
    dense kernels shaped (in, out)."""
    if len(shape) < 1:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    receptive = int(np.prod(shape[:-2]))
    return shape[-2] * receptive, shape[-1] * receptive


class NamedInit:
    """Picklable by-name initializer."""

    def __init__(self, name):
        self.name = name

    def __call__(self, rng, shape, dtype):
        return _INIT_FNS[self.name](rng, shape, dtype)

    def __repr__(self):
        return f"init({self.name})"


class ConstInit:
    def __init__(self, value):
        self.value = value

    def __call__(self, rng, shape, dtype):
        return jnp.full(shape, self.value, dtype)


def get_initializer(init) -> Callable:
    """Resolve an init spec (name, callable, or constant) to rng->array fn.

    Mirrors the reference's ``init`` string args on layers (e.g. Dense
    ``init="glorot_uniform"``, keras/layers/core.scala Dense docs).
    """
    if isinstance(init, (int, float)):
        return ConstInit(init)
    if callable(init):
        return init
    if isinstance(init, str) and init in _INIT_FNS:
        return NamedInit(init)
    raise ValueError(f"unknown initializer {init!r}")


class WeightSpec(
    collections.namedtuple("WeightSpec", "name shape init dtype trainable")
):
    pass


# ---------------------------------------------------------------------------
# Symbolic tensors (Variable) and graph nodes
# ---------------------------------------------------------------------------

_uid_counters: dict[str, itertools.count] = collections.defaultdict(
    lambda: itertools.count(1)
)


def unique_name(prefix: str) -> str:
    return f"{prefix}_{next(_uid_counters[prefix])}"


def reset_name_counters() -> None:
    _uid_counters.clear()


class Node:
    """One application of a layer to symbolic inputs."""

    def __init__(self, layer: "Layer", inbound: list["Variable"],
                 outputs: list["Variable"]):
        self.layer = layer
        self.inbound = inbound
        self.outputs = outputs


class Variable:
    """A symbolic tensor: output slot of a Node.

    The TPU-native analogue of the reference autograd ``Variable`` wrapping a
    BigDL ``ModuleNode`` (pipeline/api/autograd/math.scala:365-612).  Math
    operators live in :mod:`analytics_zoo_tpu.pipeline.api.autograd` which
    monkey-patches them onto this class (single class, no wrapper layers).
    """

    def __init__(self, node: Node | None, index: int, shape: tuple,
                 name: str | None = None):
        self.node = node
        self.index = index
        self.shape = tuple(shape)  # full shape, batch dim = None
        self.name = name or unique_name("variable")

    def __repr__(self):
        return f"Variable(name={self.name}, shape={self.shape})"


def Input(shape=None, name: str | None = None) -> Variable:
    """Symbolic model input; ``shape`` excludes the batch dim.

    Reference: ``Input`` autograd/math py + keras (pyzoo
    pipeline/api/keras/layers/topology Input; Scala Topology.scala Input).
    """
    shape = to_tuple_shape(shape)
    layer = InputLayer(input_shape=shape, name=name)
    var = Variable(None, 0, (None,) + shape, name=layer.name)
    node = Node(layer, [], [var])
    var.node = node
    return var


# ---------------------------------------------------------------------------
# The step's targets, for a loss that a layer takes itself
# ---------------------------------------------------------------------------

# entered by a train-step builder for the duration of TRACING the forward
# pass, innermost last (the idiom of parallel/plan.py's active plans)
_TRAINING_TARGETS: list = []


@contextlib.contextmanager
def training_targets(y):
    """Hand the step's targets to the layers of the forward pass traced
    inside the block.  A loss over several sets of logits (a looped
    decoder's passes) is taken where each set is made, one at a time, so
    that they are never all live; such a layer reads the targets with
    :func:`current_targets` and reports its loss under a ``*_cost`` leaf
    of its state (``objectives.InModelLoss``).  ``None`` hands nothing."""
    _TRAINING_TARGETS.append(y)
    try:
        yield
    finally:
        _TRAINING_TARGETS.pop()


def current_targets():
    """The targets of the train step being traced, or None."""
    return _TRAINING_TARGETS[-1] if _TRAINING_TARGETS else None


# ---------------------------------------------------------------------------
# Layer base
# ---------------------------------------------------------------------------


class Layer:
    """Base layer: static config + weight specs; pure functional ``call``.

    Contract (TPU re-design of BigDL ``KerasLayer``):
      - ``build(input_shape)``: declare weights/state via ``add_weight`` /
        ``add_state`` given the (batch-less) input shape.
      - ``call(params, inputs, state=None, training=False, rng=None)``: pure;
        returns outputs, or ``(outputs, new_state)`` if the layer is stateful.
      - ``compute_output_shape(input_shape)``: shape inference, mirroring the
        reference's ``computeOutputShape`` on every layer.
    """

    def __init__(self, input_shape=None, name: str | None = None, **kwargs):
        cls = type(self).__name__.lower()
        # Auto-named layers are canonically renamed when adopted by a
        # container (position-based), so param-tree keys depend only on model
        # structure — not on how many models were built earlier in the
        # process.  Checkpoints therefore resume across fresh processes.
        self._auto_named = name is None
        self.name = name or unique_name(cls)
        self.built = False
        self._weight_specs: list[WeightSpec] = []
        self._state_specs: list[WeightSpec] = []
        self._input_shape = (
            to_tuple_shape(input_shape) if input_shape is not None else None
        )
        self._build_shape = None
        self._config = {}
        if kwargs:
            raise TypeError(f"{type(self).__name__}: unexpected args {kwargs}")

    # -- weights ----------------------------------------------------------
    def add_weight(self, name, shape, init="glorot_uniform",
                   dtype=jnp.float32, trainable=True):
        spec = WeightSpec(name, tuple(int(s) for s in shape),
                          get_initializer(init), dtype, trainable)
        if trainable:
            self._weight_specs.append(spec)
        else:
            self._state_specs.append(spec)
        return spec

    def add_state(self, name, shape, init="zero", dtype=jnp.float32):
        return self.add_weight(name, shape, init, dtype, trainable=False)

    # -- build / init -----------------------------------------------------
    def build(self, input_shape):  # pragma: no cover - default no-op
        del input_shape

    def ensure_built(self, input_shape):
        if not self.built:
            self._weight_specs.clear()
            self._state_specs.clear()
            self.build(input_shape)
            self._build_shape = input_shape
            self.built = True
        return self._build_shape

    def init_params(self, rng) -> dict:
        assert self.built, f"{self.name}: init_params before build"
        params = {}
        for i, spec in enumerate(self._weight_specs):
            params[spec.name] = spec.init(
                jax.random.fold_in(rng, i), spec.shape, spec.dtype
            )
        return params

    def init_state(self) -> dict:
        state = {}
        for spec in self._state_specs:
            state[spec.name] = spec.init(
                jax.random.PRNGKey(0), spec.shape, spec.dtype
            )
        return state

    @property
    def stateful(self) -> bool:
        return bool(self._state_specs)

    # -- forward ----------------------------------------------------------
    def call(self, params, inputs, state=None, training=False, rng=None):
        raise NotImplementedError

    def apply(self, params, inputs, state=None, training=False, rng=None):
        """Normalized forward: always returns (outputs, new_state)."""
        out = self.call(params, inputs, state=state, training=training,
                        rng=rng)
        if self.stateful or isinstance(self, _ContainerBase):
            return out  # stateful layers return (out, state) themselves
        return out, state

    # -- shapes -----------------------------------------------------------
    def compute_output_shape(self, input_shape):
        return input_shape

    # -- symbolic call ----------------------------------------------------
    def __call__(self, x):
        single = not isinstance(x, (list, tuple))
        xs = [x] if single else list(x)
        for v in xs:
            if not isinstance(v, Variable):
                raise TypeError(
                    f"{self.name} called on non-symbolic input {type(v)}; "
                    "use .apply(params, inputs) for concrete arrays"
                )
        in_shapes = [v.shape[1:] for v in xs]
        build_shape = in_shapes[0] if single else in_shapes
        self.ensure_built(build_shape)
        out_shape = self.compute_output_shape(
            xs[0].shape if single else [v.shape for v in xs]
        )
        multi = (isinstance(out_shape, list))
        out_shapes = out_shape if multi else [out_shape]
        outs = [Variable(None, i, s) for i, s in enumerate(out_shapes)]
        node = Node(self, xs, outs)
        for v in outs:
            v.node = node
        return outs if multi else outs[0]

    # -- misc -------------------------------------------------------------
    def param_count(self) -> int:
        assert self.built
        return sum(int(np.prod(s.shape)) for s in self._weight_specs) + sum(
            int(np.prod(s.shape)) for s in self._state_specs
        )

    def get_config(self) -> dict:
        return dict(self._config)

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name})"


class InputLayer(Layer):
    def __init__(self, input_shape=None, name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.built = True
        self._build_shape = self._input_shape

    def call(self, params, inputs, state=None, training=False, rng=None):
        return inputs

    def compute_output_shape(self, input_shape):
        return input_shape


class _ContainerBase(Layer):
    """Marker base for containers (Sequential/Model) whose ``call`` always
    returns (outputs, state)."""


# ---------------------------------------------------------------------------
# Graph executor (shared by Model and autograd-built graphs)
# ---------------------------------------------------------------------------


def canonicalize_names(layers: Sequence["Layer"]) -> None:
    """Rename auto-named layers to position-based canonical names within a
    container (``dense_0``, ``dense_1``, ... in adoption order).  Must run
    before params are materialized."""
    taken = {l.name for l in layers if not l._auto_named}
    counters: dict[str, int] = collections.defaultdict(int)
    for layer in layers:
        if not layer._auto_named:
            continue
        cls = type(layer).__name__.lower()
        while True:
            cand = f"{cls}_{counters[cls]}"
            counters[cls] += 1
            if cand not in taken:
                break
        layer.name = cand
        taken.add(cand)
        layer._auto_named = False
    names = [l.name for l in layers]
    if len(names) != len(set(names)):
        dupes = sorted({n for n in names if names.count(n) > 1})
        raise ValueError(
            f"duplicate layer names in one container: {dupes}; rename the "
            "layers (layers adopted from different containers can collide)"
        )


def topological_nodes(outputs: Sequence[Variable]) -> list[Node]:
    """Topologically sorted nodes reaching ``outputs`` (inputs first)."""
    order: list[Node] = []
    seen: set[int] = set()

    def visit(node: Node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for v in node.inbound:
            visit(v.node)
        order.append(node)

    for v in outputs:
        visit(v.node)
    return order


class GraphFunction:
    """Executable pure function compiled from a symbolic graph.

    Plays the role of BigDL ``StaticGraph`` under the reference's ``Model``
    (Topology.scala:602-759), but as data: a node list + param/state pytrees
    keyed by layer name, executed with jnp — jit/grad/vmap-compatible.
    """

    def __init__(self, inputs: Sequence[Variable], outputs: Sequence[Variable]):
        self.inputs = list(inputs)
        self.outputs = list(outputs)
        self.nodes = topological_nodes(self.outputs)
        self.layers: list[Layer] = []
        seen_layers = set()
        for node in self.nodes:
            if id(node.layer) not in seen_layers:
                seen_layers.add(id(node.layer))
                self.layers.append(node.layer)
        canonicalize_names(self.layers)
        input_ids = {id(v) for v in self.inputs}
        for node in self.nodes:
            if isinstance(node.layer, InputLayer):
                if node.outputs and id(node.outputs[0]) not in input_ids:
                    raise ValueError(
                        "graph contains an Input not listed in `inputs`"
                    )

    def init(self, rng) -> tuple[dict, dict]:
        params, state = {}, {}
        for i, layer in enumerate(self.layers):
            if isinstance(layer, InputLayer):
                continue
            p = layer.init_params(jax.random.fold_in(rng, i))
            if p:
                params[layer.name] = p
            s = layer.init_state()
            if s:
                state[layer.name] = s
        return params, state

    def __call__(self, params, inputs, state=None, training=False, rng=None):
        state = state or {}
        values: dict[int, Any] = {}
        xs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        if len(xs) != len(self.inputs):
            raise ValueError(
                f"expected {len(self.inputs)} inputs, got {len(xs)}"
            )
        for var, x in zip(self.inputs, xs):
            values[id(var)] = x
        new_state = dict(state)
        for i, node in enumerate(self.nodes):
            layer = node.layer
            if isinstance(layer, InputLayer):
                continue
            args = [values[id(v)] for v in node.inbound]
            arg = args[0] if len(args) == 1 else args
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            out, s = layer.apply(
                params.get(layer.name, {}), arg,
                state=new_state.get(layer.name),
                training=training, rng=lrng,
            )
            if s:  # {} stays omitted, mirroring init's `if s:` filter
                new_state[layer.name] = s
            outs = out if isinstance(out, (list, tuple)) else [out]
            for v, o in zip(node.outputs, outs):
                values[id(v)] = o
        results = [values[id(v)] for v in self.outputs]
        result = results[0] if len(results) == 1 else results
        return result, new_state
