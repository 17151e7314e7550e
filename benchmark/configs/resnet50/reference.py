"""Plain reference of the ``resnet50`` configuration: ResNet-50 v1 in
``jax.numpy``, float32, every matrix product at ``highest`` precision.

It imports nothing of the program (``benchmark.narrow`` is the
control's rounding, the benchmark's own).  The weights are made here from the
seed; the harness hands the same tree to the program, whose layer names
the tree follows so that the two can be compared leaf by leaf.

``round_to`` computes the same mathematics in a narrower type as the
program computes in bfloat16: the operands of every convolution and matrix
product and every tensor a layer hands on are rounded to it (an 8-bit type
per-tensor scaled), and so is every cotangent a layer hands back; sums
stay in float32 and a parameter's gradient is not rounded
(``benchmark/narrow.py``).  That is the lower-precision control of the
comparison that decides ``correct``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.narrow import rounders

HIGHEST = lax.Precision.HIGHEST
_DIMNUMS = ("NHWC", "HWIO", "NHWC")


def _blocks(cfg):
    """(name, filters, stride, project) of every bottleneck, in order."""
    out, filters = [], cfg["stem_filters"]
    for si, n in enumerate(cfg["stage_blocks"]):
        for bi in range(n):
            out.append((f"res{si + 2}{chr(97 + bi)}", filters,
                        2 if (si > 0 and bi == 0) else 1, bi == 0))
        filters *= 2
    return out


def conv_shapes(cfg):
    """name -> (kh, kw, cin, cout, stride) of every convolution."""
    w = cfg["stem_filters"]
    shapes = {"stem": (7, 7, 3, w, 2)}
    cin = w
    for name, f, stride, project in _blocks(cfg):
        shapes[f"{name}_a"] = (1, 1, cin, f, stride)
        shapes[f"{name}_b"] = (3, 3, f, f, 1)
        shapes[f"{name}_c"] = (1, 1, f, 4 * f, 1)
        if project:
            shapes[f"{name}_proj"] = (1, 1, cin, 4 * f, stride)
        cin = 4 * f
    return shapes


def init_params(key, cfg):
    """The whole parameter tree from one key, float32."""
    params = {}
    shapes = conv_shapes(cfg)
    keys = jax.random.split(key, len(shapes) + 1)
    for k, (name, (kh, kw, cin, cout, _)) in zip(keys, shapes.items()):
        std = math.sqrt(2.0 / (kh * kw * cin))
        params[f"{name}_conv"] = {
            "kernel": std * jax.random.normal(k, (kh, kw, cin, cout),
                                              jnp.float32)}
        gamma = cfg["init_branch_gamma"] if name.endswith("_c") else 1.0
        params[f"{name}_bn"] = {"gamma": jnp.full((cout,), gamma,
                                                  jnp.float32),
                                "beta": jnp.zeros((cout,), jnp.float32)}
    feat = cfg["stem_filters"] * 2 ** (len(cfg["stage_blocks"]) - 1) * 4
    params["fc"] = {
        "kernel": jax.random.normal(
            keys[-1], (feat, cfg["num_classes"]), jnp.float32)
        / math.sqrt(feat),
        "bias": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return params


def _conv_bn(qs, params, name, x, stride, eps, relu):
    q, qw = qs
    y = q(lax.conv_general_dilated(
        q(x), qw(params[f"{name}_conv"]["kernel"]), (stride, stride), "SAME",
        dimension_numbers=_DIMNUMS, precision=HIGHEST))
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    bn = params[f"{name}_bn"]
    y = (y - mean) * lax.rsqrt(var + eps) * bn["gamma"] + bn["beta"]
    return q(jnp.maximum(y, 0.0) if relu else y)


def _bottleneck(qs, eps, name, stride, project, params, x):
    y = _conv_bn(qs, params, f"{name}_a", x, stride, eps, True)
    y = _conv_bn(qs, params, f"{name}_b", y, 1, eps, True)
    y = _conv_bn(qs, params, f"{name}_c", y, 1, eps, False)
    if project:
        x = _conv_bn(qs, params, f"{name}_proj", x, stride, eps, False)
    return qs[0](jnp.maximum(y + x, 0.0))


def loss_fn(params, x_u8, y, cfg, round_to=None):
    """Mean training loss of one batch of uint8 images (BatchNorm on the
    batch's own statistics)."""
    qs = q, qw = rounders(round_to)
    eps = cfg["bn_epsilon"]
    inp = cfg["input"]
    x = (x_u8.astype(jnp.float32) - jnp.asarray(inp["mean"], jnp.float32)) \
        / jnp.asarray(inp["std"], jnp.float32)
    h = _conv_bn(qs, params, "stem", x, 2, eps, True)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for name, _f, stride, project in _blocks(cfg):
        # one block's activations at a time in the backward pass: float32
        # at the timed batch would not fit beside them all
        block = jax.checkpoint(functools.partial(
            _bottleneck, qs, eps, name, stride, project))
        h = block({k: v for k, v in params.items() if k.startswith(name)}, h)
    h = q(jnp.mean(h, axis=(1, 2)))
    logits = q(jnp.dot(h, qw(params["fc"]["kernel"]), precision=HIGHEST)
               + params["fc"]["bias"])
    probs = jnp.clip(jax.nn.softmax(logits, axis=-1),
                     cfg["loss_clip_epsilon"], 1.0)
    picked = jnp.take_along_axis(jnp.log(probs),
                                 y.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.mean(picked)


def learning_rate(step, opt):
    """The configuration's schedule at optimizer step ``step`` (from 0)."""
    step = jnp.asarray(step, jnp.float32)
    warmup = opt["warmup_epochs"] * opt["steps_per_epoch"]
    mult = jnp.asarray(1.0, jnp.float32)
    for b in opt["decay_epochs"]:
        mult = mult * jnp.where(step / opt["steps_per_epoch"] >= b,
                                opt["decay"], 1.0)
    factor = jnp.where(step < warmup, step / max(warmup, 1), mult)
    return opt["base_lr"] * opt["batch_size"] / 256.0 * factor


def train_step(params, momentum, step, x_u8, y, cfg, round_to=None):
    """One SGD step: gradient, decoupled-in-name-only weight decay added to
    it, momentum, scheduled rate.  Returns the new parameters, the new
    momentum, the loss and the gradient as the optimizer got it."""
    opt = cfg["optimizer"]
    loss, grads = jax.value_and_grad(loss_fn)(params, x_u8, y, cfg, round_to)
    momentum = jax.tree_util.tree_map(
        lambda g, p, m: g + opt["weight_decay"] * p + opt["momentum"] * m,
        grads, params, momentum)
    lr = learning_rate(step, opt)
    params = jax.tree_util.tree_map(lambda p, m: p - lr * m,
                                    params, momentum)
    return params, momentum, loss, grads


def init_opt_state(params):
    return jax.tree_util.tree_map(jnp.zeros_like, params)
