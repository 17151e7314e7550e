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
channels with one gain over all I, the gate before it.  From the
in-projection to the out-projection every array keeps the projections'
token-major layout (B, L, channels) in u's dtype: the rule reads x, B and C
where the convolution wrote them and writes y where the skip, the gate and
the norm read it, and those three are one expression, float32 only inside
it.  No head is split out or merged back.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import named_scope

from analytics_zoo_tpu.metrics.tracing import SSD_SCOPE
from analytics_zoo_tpu.ops import ssd

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
        D) and the rule's numbers (the rule called through the module, as
        it stands when the mixer runs)."""
        f32 = jnp.float32
        tokens = u.shape[:2]
        l = tokens[1]
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
            x = xbc[..., :self.inner]
            bmat, cmat = (
                xbc[..., self.inner + at:self.inner + at + groups].reshape(
                    tokens + (self.groups, self.state))
                for at in (0, groups))
            dt = jax.nn.softplus(dt.astype(f32)
                                 + bp["ssd_dt_bias"].astype(f32))
            a = -jnp.exp(bp["ssd_a_log"].astype(f32))
            y, stats = ssd.chunked_ssd(
                x.reshape(tokens + (self.heads, self.head_dim)), dt, a,
                bmat, cmat, chunk=self.chunk)
            g = _skip_gate_norm(y.reshape(x.shape), x, z, bp["ssd_d"],
                                bp["ssd_norm"], self.groups, eps)
            out = g @ bp["ssd_out_kernel"]
        return out, {"ssd_" + key: value for key, value in stats.items()}


def _skip_gate_norm(y, x, z, d, gamma, groups, eps):
    """RMSNorm over ``groups`` groups of channels of (y + D x) o SiLU(z),
    with one gain over all of them: y, x and z (B, L, I) in their dtype, D
    one a head, float32 inside, handed on in y's dtype.  The groups' mean
    squares are taken, and their scales spread back over the channels, as
    products (``ssd.spread``): the (B, L, I) arrays are never reshaped.
    The result is written once, behind an optimization barrier: fused into
    the out-projection's weight gradient instead, the whole float32 norm
    was made again inside that product."""
    f32 = jnp.float32
    width = y.shape[-1] // groups
    g = (y.astype(f32)
         + jnp.repeat(d.astype(f32), y.shape[-1] // d.shape[0])
         * x.astype(f32)) * jax.nn.silu(z.astype(f32))
    scale = jax.lax.rsqrt(ssd.run_sums(jnp.square(g), width) / width + eps)
    return jax.lax.optimization_barrier(
        (g * ssd.spread(scale, width) * gamma.astype(f32)).astype(y.dtype))
