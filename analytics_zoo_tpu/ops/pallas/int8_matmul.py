# zoolint: disable-file=raw-pallas-call -- ops/pallas/ is the one home
# for raw pl.pallas_call; everything here ships a jnp fallback oracle and
# lowers under a kernel_* label through the compile choke point.
"""Weight-stationary int8 matmul with per-channel scales.

The serving tier's weight-only quantization
(:func:`analytics_zoo_tpu.pipeline.inference.quantize.quantize_params_for_plan`)
stores int8 values + a per-output-channel f32 scale.  Without a kernel
the only consumer path is dequantize-then-dot: the int8 weight is
expanded to f32 in HBM (4x the traffic the quantization just saved)
before a plain f32 matmul.  This kernel keeps the weight int8 through
HBM *and* VMEM — blocks are cast in-register on their way into the MXU
and the per-channel scale is applied once to the f32 accumulator — so
weight traffic stays at 1 byte/param.

``int8_matmul(x, values, scale)``: x (M, K) f32/bf16, values (K, N)
int8, scale (N,) f32 → (M, N) in x's dtype.  The jnp fallback
(dequantize + f32 dot, scale applied after) is the numerical oracle;
tolerance ~1e-5 relative (accumulation order).  CPU runs the fallback,
``ZOO_KERNEL_INTERPRET=1`` forces the kernel in interpret mode.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

_BLOCK_M = 128
_BLOCK_N = 128
_BLOCK_K = 256

invocation_counts = {"pallas": 0, "fallback": 0}


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "") not in ("", "0")


def _interpret_forced() -> bool:
    return _env_flag("ZOO_KERNEL_INTERPRET")


def _pallas_available() -> bool:
    return (jax.default_backend() == "tpu" or _interpret_forced()
            or _env_flag("ZOO_KERNEL_FORCE_PALLAS"))


def _reference(x, values, scale):
    out = jnp.dot(x.astype(jnp.float32), values.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return (out * scale.astype(jnp.float32)[None, :]).astype(x.dtype)


def _mm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k):
    import jax.experimental.pallas as pl

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 → f32 happens HERE, in-register: the weight block arrived in
    # VMEM still 1 byte/param
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _matmul_pallas(x, values, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m0, k0 = x.shape
    _, n0 = values.shape
    bm = min(_BLOCK_M, -(-m0 // 8) * 8)
    bn = min(_BLOCK_N, -(-n0 // 128) * 128)
    bk = min(_BLOCK_K, -(-k0 // 128) * 128)
    m = -(-m0 // bm) * bm
    n = -(-n0 // bn) * bn
    k = -(-k0 // bk) * bk
    if (m, k) != (m0, k0):
        x = jnp.pad(x, ((0, m - m0), (0, k - k0)))
    if (k, n) != values.shape:
        values = jnp.pad(values, ((0, k - k0), (0, n - n0)))
    if n != n0:
        scale = jnp.pad(scale, (0, n - n0))
    grid = (m // bm, n // bn, k // bk)
    out = pl.pallas_call(
        functools.partial(_mm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, values, scale.astype(jnp.float32).reshape(1, -1))
    return out[:m0, :n0]


def int8_matmul(x, values, scale):
    """``(x @ dequantize(values, scale))`` with the weight kept int8
    through HBM and VMEM.  x (M, K) float, values (K, N) int8, scale
    (N,) f32 per-output-channel; returns (M, N) in x's dtype."""
    if _pallas_available():
        out = _matmul_pallas(x, values, scale,
                             interpret=_interpret_forced())
        invocation_counts["pallas"] += 1
        return out
    invocation_counts["fallback"] += 1
    return _reference(x, values, scale)
