"""Mamba-2's mixer (arXiv:2405.21060; the "M" layer of Nemotron-H) for the
decoder blocks of ``self_attention._TransformerCore``: its leaves, its
forward pass and the numbers it reports, described by one record of its
kind and sizes.

For the normed state u (B, L, D), heads of P channels (I = heads x P), a
state of N, groups of heads that share B and C:

    [z, xBC, dt~] = u W_in                  (I, I + 2 G N, heads wide)
    xBC = SiLU(conv(xBC) + b_conv)          causal depthwise, ``conv`` taps
    [x, B, C] = xBC                         x in heads, B and C in groups
    dt = softplus(dt~ + dt_bias) a head;    A = -exp(A_log) a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    out = RMSNorm_groups(y o SiLU(z)) W_out

the rule by ``ops.ssd.chunked_ssd``, the norm over G groups of I / G
channels with one gain over all I, the gate before it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import named_scope

from analytics_zoo_tpu.metrics.tracing import SSD_SCOPE
from analytics_zoo_tpu.ops.attention import merge_heads, split_heads

#: A = exp(A_log) a head is drawn uniform in this range; the step dt a head
#: log-uniform in that one, floored at ``DT_FLOOR`` (Mamba's own draws)
A_RANGE, DT_RANGE, DT_FLOOR = (1.0, 16.0), (1e-3, 0.1), 1e-4


@dataclasses.dataclass(frozen=True)
class SSDMixer:
    """``heads`` of ``head_dim`` channels, a state of ``state`` a head,
    ``groups`` of heads sharing B and C, a convolution of ``conv`` taps,
    ``chunk`` tokens a chunk of the rule (None: ``ops.ssd.CHUNK``)."""

    heads: int
    head_dim: int
    state: int
    groups: int
    conv: int = 4
    chunk: int | None = None

    kind = "ssd"
    #: what a layer reports a step (``chunked_ssd``'s numbers)
    NUMBERS = ("ssd_chunk_log_decay_min", "ssd_dt_max", "ssd_state_rms")

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels of xBC, which the convolution runs over."""
        return self.inner + 2 * self.groups * self.state

    def record(self) -> tuple:
        """(heads, P, N, groups, taps), as the decoder's records give it."""
        return (self.heads, self.head_dim, self.state, self.groups,
                self.conv)

    def params(self, rng, d, std):
        """The leaves: the in- and out-projections drawn normal at ``std``,
        the convolution's taps uniform in +-1/sqrt(taps) with its bias 0,
        A_log and dt_bias as Mamba draws them, D 1 and the norm's gain 1."""
        ks = iter(jax.random.split(rng, 5))
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            next(ks), (self.heads,), minval=jnp.log(DT_RANGE[0]),
            maxval=jnp.log(DT_RANGE[1]))), DT_FLOOR)
        return {
            "ssd_in_kernel": std * jax.random.normal(
                next(ks), (d, self.inner + self.conv_width + self.heads)),
            "ssd_conv": jax.random.uniform(
                next(ks), (self.conv, self.conv_width), minval=-1.0,
                maxval=1.0) / self.conv ** 0.5,
            "ssd_conv_bias": jnp.zeros((self.conv_width,)),
            # softplus^-1(dt)
            "ssd_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "ssd_a_log": jnp.log(jax.random.uniform(
                next(ks), (self.heads,), minval=A_RANGE[0],
                maxval=A_RANGE[1])),
            "ssd_d": jnp.ones((self.heads,)),
            "ssd_norm": jnp.ones((self.inner,)),
            "ssd_out_kernel": std * jax.random.normal(next(ks),
                                                      (self.inner, d)),
        }

    def param_count(self, d) -> int:
        return d * (self.inner + self.conv_width + self.heads) \
            + (self.conv + 1) * self.conv_width + 3 * self.heads \
            + self.inner + self.inner * d

    def __call__(self, bp, u, eps):
        """The mixer of the normed state ``u`` (B, L, D): the branch (B, L,
        D) and the rule's numbers."""
        from analytics_zoo_tpu.ops.ssd import chunked_ssd

        f32 = jnp.float32
        l = u.shape[1]
        with named_scope(SSD_SCOPE):
            zxbcdt = u @ bp["ssd_in_kernel"]
            z = zxbcdt[..., :self.inner]
            xbc = zxbcdt[..., self.inner:self.inner + self.conv_width]
            dt = zxbcdt[..., self.inner + self.conv_width:]
            taps = bp["ssd_conv"]
            n = taps.shape[0]
            past = jnp.pad(xbc, ((0, 0), (n - 1, 0), (0, 0)))
            # y_t = sum_i w_i z_{t - (n - 1) + i}, zeros before the start
            xbc = jax.nn.silu(
                sum(past[:, i:i + l] * taps[i] for i in range(n))
                + bp["ssd_conv_bias"])
            groups = self.groups * self.state
            x = split_heads(xbc[..., :self.inner], self.heads)
            bmat = split_heads(xbc[..., self.inner:self.inner + groups],
                               self.groups)
            cmat = split_heads(xbc[..., self.inner + groups:], self.groups)
            dt = jax.nn.softplus(dt.astype(f32)
                                 + bp["ssd_dt_bias"].astype(f32))
            a = -jnp.exp(bp["ssd_a_log"].astype(f32))
            y, stats = chunked_ssd(x, dt.transpose(0, 2, 1), a, bmat, cmat,
                                   chunk=self.chunk)
            y = merge_heads(
                (y.astype(f32) + bp["ssd_d"].astype(f32)[:, None, None]
                 * x.astype(f32)).astype(x.dtype))
            out = _gated_group_norm(y, z, bp["ssd_norm"], self.groups, eps) \
                @ bp["ssd_out_kernel"]
        return out, {"ssd_" + key: value for key, value in stats.items()}


def _gated_group_norm(y, z, gamma, groups, eps):
    """RMSNorm over ``groups`` groups of channels of y o SiLU(z), with one
    gain over all of them, in float32, handed on in y's dtype."""
    f32 = jnp.float32
    g = (y.astype(f32) * jax.nn.silu(z.astype(f32)))
    g = g.reshape(g.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    return (g.reshape(y.shape) * gamma.astype(f32)).astype(y.dtype)
