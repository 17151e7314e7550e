"""Transformer layers — TransformerLayer (GPT-style decoder stack) and BERT.

Reference: pipeline/api/keras/layers/TransformerLayer.scala:56 (embedding +
position embedding + n_block blocks; ``multiHeadSelfAttention`` :137 builds
the full O(L²) attention via Conv1D projections) and BERT.scala:66 (adds
token-type embeddings and an additive attention mask; pooler on [CLS]).

TPU re-design: projections are single fused (D, 3D) matmuls on the MXU;
attention routes through :func:`analytics_zoo_tpu.ops.attention.
dot_product_attention` so the Pallas flash kernel / ring-attention (seq-axis
sharded) variants swap in without touching this layer.  Long-context support
(absent in the reference, SURVEY.md §5) is a mesh-axis concern handled in
``analytics_zoo_tpu.parallel``.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import named_scope
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.metrics.tracing import (
    FFN_SCOPE,
    HEAD_SCOPE,
    MIXER_SCOPE,
)
from analytics_zoo_tpu.ops.attention import (
    dot_product_attention,
    merge_heads,
    project_heads,
    split_heads,
)
from analytics_zoo_tpu.pipeline.api.keras.engine import (
    Layer,
    current_targets,
    get_initializer,
)


def _dense_init(rng, shape, std):
    return std * jax.random.normal(rng, shape)


class _TransformerCore(Layer):
    """Shared block stack for TransformerLayer and BERT."""

    def __init__(self, n_block, n_head, hidden_size, intermediate_size=None,
                 hidden_drop=0.1, attn_drop=0.1, initializer_range=0.02,
                 bidirectional=False, activation="gelu", remat=False,
                 moe_experts=0, moe_top_k=2, moe_capacity_factor=1.25,
                 moe_aux_weight=0.01, norm="layer", norm_placement="after",
                 norm_eps=1e-5, rotary_theta=None, gated_ffn=False,
                 use_bias=True, attention="full", kv_latent_rank=None,
                 qk_nope_dim=None, qk_rope_dim=None, v_head_dim=None,
                 routed_experts=0, experts_held=None, experts_held_from=0,
                 experts_per_token=None, expert_size=None, shared_experts=0,
                 routed_scale=1.0, leading_dense=0, kda_heads=None,
                 kda_head_dim=None, kda_conv_size=4, input_shape=None,
                 name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.n_block = int(n_block)
        self.n_head = int(n_head)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size or 4 * hidden_size)
        self.hidden_drop = float(hidden_drop)
        self.attn_drop = float(attn_drop)
        self.initializer_range = float(initializer_range)
        self.bidirectional = bool(bidirectional)
        # The block is assembled from what the configuration says; the
        # defaults are the source system's block (GPT-1's), so
        # TransformerLayer and BERT keep their parameter trees.
        #   norm: "layer" (gamma and beta) or "rms" (gamma, in float32);
        #   norm_placement: "after" h = norm(h + branch(h)), "before"
        #     h = h + branch(norm(h)), "around" h = h + norm(branch(norm(h)))
        #     (four norms a block: ln1, ln2 about attention, ln3, ln4
        #     about the feed-forward);
        #   rotary_theta: rotary positions on q and k (rotate-half over the
        #     whole head) where a number; None leaves positions to the
        #     embedding;
        #   gated_ffn: (act(u Wgate) * (u Wfc)) Wout in place of
        #     act(u Wfc) Wout;  use_bias: no ``*_bias`` leaf when False;
        #   attention: "full" (one fused qkv kernel, heads of hidden_size /
        #     n_head) or "latent" (DeepSeek's MLA without query compression:
        #     q = u Wq in heads of qk_nope_dim + qk_rope_dim; [c, k_r] =
        #     u Wkva with c kv_latent_rank wide and ONE rope key k_r for all
        #     heads; [k_n, v] = RMSNorm(c) Wkvb in heads of qk_nope_dim +
        #     v_head_dim; rotary on q_r and k_r in adjacent pairs; scores
        #     over [q_n, q_r] . [k_n, k_r] / sqrt(qk_nope_dim + qk_rope_dim),
        #     values and output v_head_dim a head; with ``rotary_theta``
        #     None the rotary step is left out and the model has no
        #     positions), or "kda" (Kimi Delta Attention, a recurrence over
        #     the sequence: ``_kda_mixer``), or the kind of every block in
        #     turn, a sequence of ``n_block`` of those names: the mixer
        #     differs by layer as the feed-forward does;
        #   routed_experts: E > 0 makes the feed-forward of every block from
        #     ``leading_dense`` on a routed one (ops.moe.held_experts_ffn):
        #     a router over all E experts, sigmoid scores, the bias-corrected
        #     top ``experts_per_token``, no capacity and no dropped token; of
        #     the E experts this worker holds ``experts_held`` (all by
        #     default), from ``experts_held_from``, each a gated feed-forward
        #     ``expert_size`` wide; beside them ``shared_experts`` x
        #     ``expert_size`` of gated feed-forward on every token.  The
        #     blocks before ``leading_dense`` keep the dense feed-forward.
        if norm not in ("layer", "rms"):
            raise ValueError(f"norm must be 'layer' or 'rms'; got {norm!r}")
        if norm_placement not in ("after", "before", "around"):
            raise ValueError("norm_placement must be 'after', 'before' or "
                             f"'around'; got {norm_placement!r}")
        self.norm = norm
        self.norm_placement = norm_placement
        self.norm_eps = float(norm_eps)
        self.rotary_theta = None if rotary_theta is None \
            else float(rotary_theta)
        self.gated_ffn = bool(gated_ffn)
        self.use_bias = bool(use_bias)
        if moe_experts and (self.gated_ffn or not self.use_bias):
            raise ValueError("the routed feed-forward is the plain one "
                             "with biases: no gated_ffn, no use_bias=False")
        by_layer = (attention,) * self.n_block \
            if isinstance(attention, str) else tuple(attention)
        if len(by_layer) != self.n_block or any(
                kind not in ("full", "latent", "kda") for kind in by_layer):
            raise ValueError(
                "attention must be 'full', 'latent' or 'kda', or one of "
                f"them for each of the {self.n_block} blocks; got "
                f"{attention!r}")
        #: the kind of every block, and the one name where all are alike
        self.attention_by_layer = by_layer
        self.attention = by_layer[0] if len(set(by_layer)) == 1 \
            else "by_layer"
        self.latent = None
        if "latent" in by_layer:
            self.latent = tuple(int(x) for x in (
                kv_latent_rank, qk_nope_dim, qk_rope_dim, v_head_dim))
            if self.use_bias:
                raise ValueError("latent attention has no bias")
            if self.rotary_theta is not None and self.latent[2] % 2:
                raise ValueError(
                    "latent attention under rotary positions turns pairs: "
                    f"qk_rope_dim {self.latent[2]} is odd")
        self.kda = None
        if "kda" in by_layer:
            if self.use_bias or self.bidirectional:
                raise ValueError("kda is causal and has no bias")
            width = int(kda_head_dim or self.hidden_size // self.n_head)
            #: heads, a head's key and value width (the rank of the two
            #: low-rank gates too), taps of the causal convolutions
            self.kda = (int(kda_heads or self.n_head), width,
                        int(kda_conv_size))
        self.routed_experts = int(routed_experts)
        self.leading_dense = int(leading_dense)
        if self.routed_experts:
            if moe_experts or not self.gated_ffn or self.use_bias:
                raise ValueError("routed_experts are gated feed-forwards "
                                 "without bias, and not moe_experts'")
            self.experts_held = int(experts_held or routed_experts)
            self.experts_held_from = int(experts_held_from)
            self.experts_per_token = int(experts_per_token)
            self.expert_size = int(expert_size)
            self.shared_experts = int(shared_experts)
            self.routed_scale = float(routed_scale)
            if self.experts_per_token > self.routed_experts or not (
                    0 <= self.experts_held_from and self.experts_held_from
                    + self.experts_held <= self.routed_experts):
                raise ValueError(
                    f"experts {self.experts_held_from}.."
                    f"{self.experts_held_from + self.experts_held - 1} "
                    f"held, top-{self.experts_per_token}, of "
                    f"{self.routed_experts}")
        # moe_experts > 0 swaps every block's dense feed-forward for a
        # routed mixture of experts (ops.moe.routed_ffn: GShard top-k +
        # capacity, dense-dispatch so the GSPMD train step shards the
        # expert dim over the mesh `expert` axis).  The layer becomes
        # stateful: its per-step state carries the load-balancing aux loss
        # (raw + pre-weighted) and the capacity drop fraction — the
        # estimator adds every `moe_aux_cost` state leaf to the training
        # loss, so expert collapse is penalized out of the box.
        self.moe_experts = int(moe_experts)
        self.moe_top_k = int(moe_top_k)
        self.moe_capacity_factor = float(moe_capacity_factor)
        self.moe_aux_weight = float(moe_aux_weight)
        if self.moe_experts and self.moe_top_k > self.moe_experts:
            raise ValueError(
                f"moe_top_k={moe_top_k} > moe_experts={moe_experts}")
        # remat: recompute each block's activations in the backward pass
        # (jax.checkpoint) — live memory drops from O(n_block) to O(1)
        # block activations for ~1/3 more FLOPs, the standard trade for
        # training deep stacks near the HBM limit.  Accepts True/"full"
        # (recompute everything), "dots" (save matmul outputs —
        # checkpoint_dots_with_no_batch_dims: less recompute, more
        # memory), or "attn" (keep what parallel/plan.py's
        # REMAT_KEPT_NAMES lists: the flash kernels' output and softmax
        # statistics, exactly what their backward kernels read, and the
        # feed-forward's output — the backward re-derives the rest but
        # runs no flash-attention forward and no down product a second
        # time).  The best point is hardware-dependent; the transformer
        # bench sweeps it.
        if remat in (False, None):
            self.remat = None
        elif remat in (True, "full"):
            self.remat = "full"
        elif remat in ("dots", "attn"):
            self.remat = str(remat)
        else:
            raise ValueError(
                f"remat must be bool, 'full', 'dots' or 'attn'; "
                f"got {remat!r}")
        from analytics_zoo_tpu.ops.activations import get_activation

        self.act = get_activation(activation)

    # -- param construction (nested; overrides the flat-spec default) ------
    @property
    def _n_norms(self):
        return 4 if self.norm_placement == "around" else 2

    def _attention_kind(self, index):
        """The mixer of block ``index``; of a stack whose blocks are all
        alike where the caller has no index."""
        if index is None:
            if self.attention == "by_layer":
                raise ValueError("the attention kind differs by layer: "
                                 "which block?")
            return self.attention
        return self.attention_by_layer[index]

    def _kda_params(self, rng):
        """A KDA mixer's leaves: three projections to heads x width, a
        causal depthwise convolution on each (taps x channels, drawn
        uniform in +-1/sqrt(taps), a depthwise convolution's usual draw:
        at ``initializer_range`` the values would fall under the output
        norm's eps), the decay's low-rank gate with ``dt_bias`` a channel
        and ``A_log`` a head (A uniform in 1..16, the step dt log-uniform
        in 0.001..0.1 behind a softplus: a token's log-decay
        -A softplus(. + dt_bias) then lies between about -2 and -0.001),
        beta's projection a head, the output's low-rank gate with its bias,
        the output norm's gain over a head's width and the output
        projection."""
        d, std = self.hidden_size, self.initializer_range
        heads, width, taps = self.kda
        wide, rank = heads * width, width
        ks = iter(jax.random.split(rng, 14))

        def conv():
            return jax.random.uniform(next(ks), (taps, wide), minval=-1.0,
                                      maxval=1.0) / taps ** 0.5

        dt = jnp.exp(jax.random.uniform(
            next(ks), (wide,), minval=jnp.log(1e-3), maxval=jnp.log(0.1)))
        return {
            "kda_q_kernel": _dense_init(next(ks), (d, wide), std),
            "kda_k_kernel": _dense_init(next(ks), (d, wide), std),
            "kda_v_kernel": _dense_init(next(ks), (d, wide), std),
            "kda_q_conv": conv(), "kda_k_conv": conv(), "kda_v_conv": conv(),
            "kda_f_a_kernel": _dense_init(next(ks), (d, rank), std),
            "kda_f_b_kernel": _dense_init(next(ks), (rank, wide), std),
            # softplus^-1(dt)
            "kda_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "kda_a_log": jnp.log(jax.random.uniform(
                next(ks), (heads,), minval=1.0, maxval=16.0)),
            "kda_b_kernel": _dense_init(next(ks), (d, heads), std),
            "kda_g_a_kernel": _dense_init(next(ks), (d, rank), std),
            "kda_g_b_kernel": _dense_init(next(ks), (rank, wide), std),
            "kda_g_bias": jnp.zeros((wide,)),
            "kda_o_norm": jnp.ones((width,)),
            "kda_o_kernel": _dense_init(next(ks), (wide, d), std),
        }

    def _is_routed(self, index):
        return bool(self.routed_experts) and index is not None \
            and index >= self.leading_dense

    def _block_params(self, rng, index=None):
        """One block's leaves; ``index`` tells a routed block from the
        leading dense ones where the feed-forward differs by layer."""
        d, m = self.hidden_size, self.intermediate_size
        std = self.initializer_range
        ks = jax.random.split(rng, 6)
        # the keys of what the options above add, apart from the six that
        # the older blocks draw from
        more = iter(jax.random.split(jax.random.fold_in(rng, 1), 11))
        bias = {}
        kind = self._attention_kind(index)
        if kind == "kda":
            p = self._kda_params(jax.random.fold_in(rng, 2))
        elif kind == "latent":
            rank, nope, rope, vd = self.latent
            h = self.n_head
            p = {
                "q_kernel": _dense_init(next(more), (d, h * (nope + rope)),
                                        std),
                "kv_a_kernel": _dense_init(next(more), (d, rank + rope), std),
                "kv_a_norm": jnp.ones((rank,)),
                "kv_b_kernel": _dense_init(next(more),
                                           (rank, h * (nope + vd)), std),
                "o_kernel": _dense_init(next(more), (h * vd, d), std),
            }
        else:
            p = {
                "qkv_kernel": _dense_init(ks[0], (d, 3 * d), std),
                "proj_kernel": _dense_init(ks[1], (d, d), std),
            }
            bias = {"qkv_bias": 3 * d, "proj_bias": d}
        for i in range(1, self._n_norms + 1):
            p[f"ln{i}_gamma"] = jnp.ones((d,))
            if self.norm == "layer":
                p[f"ln{i}_beta"] = jnp.zeros((d,))
        if self._is_routed(index):
            e, held, f = self.routed_experts, self.experts_held, \
                self.expert_size
            p.update({
                "router_kernel": _dense_init(next(more), (d, e), std),
                # DeepSeek-V3's e_score_correction_bias: it picks, takes no
                # gradient, and is a load balancer's to set; 0 picks by
                # the scores alone
                "router_bias": jnp.zeros((e,)),
                "experts_gate": _dense_init(next(more), (held, d, f), std),
                "experts_up": _dense_init(next(more), (held, d, f), std),
                "experts_down": _dense_init(next(more), (held, f, d), std),
            })
            if self.shared_experts:
                sf = self.shared_experts * f
                p.update({
                    "shared_gate_kernel": _dense_init(next(more), (d, sf),
                                                      std),
                    "shared_fc_kernel": _dense_init(next(more), (d, sf), std),
                    "shared_out_kernel": _dense_init(next(more), (sf, d),
                                                     std),
                })
        elif self.moe_experts:
            e = self.moe_experts
            p.update({
                "moe_gate": _dense_init(ks[2], (d, e), std),
                "moe_w1": _dense_init(ks[3], (e, d, m), std),
                "moe_b1": jnp.zeros((e, m)),
                "moe_w2": _dense_init(ks[4], (e, m, d), std),
                "moe_b2": jnp.zeros((d,)),
            })
        else:
            p.update({
                "fc_kernel": _dense_init(ks[2], (d, m), std),
                "out_kernel": _dense_init(ks[3], (m, d), std),
            })
            bias.update(fc_bias=m, out_bias=d)
            if self.gated_ffn:
                p["gate_kernel"] = _dense_init(ks[4], (d, m), std)
                bias["gate_bias"] = m
        if self.use_bias:
            p.update({k: jnp.zeros((n,)) for k, n in bias.items()})
        return p

    @property
    def stateful(self):
        # MoE stacks report their aux loss / drop fraction through the
        # layer-state channel; the estimator adds every `moe_aux_cost`
        # leaf to the training loss
        return self.moe_experts > 0

    def init_state(self):
        if not self.moe_experts:
            return {}
        return {"moe_aux_loss": jnp.zeros((), jnp.float32),
                "moe_aux_cost": jnp.zeros((), jnp.float32),
                "moe_drop_fraction": jnp.zeros((), jnp.float32)}

    def _moe_state(self, aux, drop):
        return {"moe_aux_loss": aux,
                "moe_aux_cost": self.moe_aux_weight * aux,
                "moe_drop_fraction": drop}

    def _per_block_param_count(self, index=None):
        d, m = self.hidden_size, self.intermediate_size
        b = 1 if self.use_bias else 0
        norms = self._n_norms * d * (2 if self.norm == "layer" else 1)
        attn = 3 * d * d + d * d + b * 4 * d + norms    # qkv + proj + norms
        kind = self._attention_kind(index)
        if kind == "kda":
            heads, width, taps = self.kda
            wide, rank = heads * width, width
            attn = 4 * d * wide + 3 * taps * wide + 2 * d * rank \
                + 2 * rank * wide + 2 * wide + heads + d * heads + width \
                + norms
        elif kind == "latent":
            rank, nope, rope, vd = self.latent
            h = self.n_head
            attn = d * h * (nope + rope) + d * (rank + rope) + rank \
                + rank * h * (nope + vd) + h * vd * d + norms
        if self._is_routed(index):
            f = self.expert_size
            return attn + d * self.routed_experts + self.routed_experts \
                + 3 * d * f * (self.experts_held + self.shared_experts)
        if self.moe_experts:
            e = self.moe_experts
            return attn + d * e + e * (2 * d * m + m) + d
        n_in = 2 if self.gated_ffn else 1
        return attn + (n_in + 1) * d * m + b * (n_in * m + d)

    @staticmethod
    def _ln(x, gamma, beta, eps=1e-5):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * jnp.reciprocal(jnp.sqrt(var + eps)) * gamma \
            + beta

    def _drop(self, x, p, training, rng, salt):
        if not training or p <= 0.0 or rng is None:
            return x
        key = jax.random.fold_in(rng, salt)
        keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
        return jnp.where(keep, x / (1.0 - p), 0.0)

    def _run_blocks(self, blocks, h, mask, training, rng):
        return self._run_blocks_aux(blocks, h, mask, training, rng)[0]

    def _run_blocks_aux(self, blocks, h, mask, training, rng):
        """Run the stack; also return (mean aux loss, mean drop fraction)
        over the MoE blocks (zeros for a dense stack).

        The remat policy is PLAN-resolved: a ``remat_rules`` entry on
        the sharding plan being compiled (matched against this layer's
        name) overrides the per-layer ``remat=`` flag, which stays the
        trace-time default — so activation checkpointing is memory-plan
        configuration, with one jax.checkpoint site (``apply_remat``)."""
        from analytics_zoo_tpu.parallel.plan import (
            apply_remat,
            resolve_remat,
        )

        policy = resolve_remat(getattr(self, "name", None) or "blocks",
                               default=self.remat)
        body = apply_remat(self._block_forward_aux, policy,
                           static_argnums=(3,))
        aux = jnp.zeros((), jnp.float32)
        drop = jnp.zeros((), jnp.float32)
        n_moe = 0
        for bi, bp in enumerate(blocks):
            brng = jax.random.fold_in(rng, bi) if rng is not None else None
            h, a, dr = body(bp, h, mask, training, brng)
            if "moe_gate" in bp:  # static: params structure is traced once
                n_moe += 1
                aux = aux + a
                drop = drop + dr
        if n_moe:
            aux, drop = aux / n_moe, drop / n_moe
        return h, aux, drop

    def _block_forward(self, bp, h, mask, training, brng):
        # single-output view kept for pipeline-parallel stage builders
        # (parallel/pipeline.py), which carry dense blocks only
        return self._block_forward_aux(bp, h, mask, training, brng)[0]

    def _norm(self, x, bp, i):
        """The block's ``i``-th norm (1-based) of ``x``."""
        gamma = bp[f"ln{i}_gamma"]
        if self.norm == "layer":
            return self._ln(x, gamma, bp[f"ln{i}_beta"], self.norm_eps)
        return _rms_norm(x, gamma, self.norm_eps)

    def _branch(self, branch, h, bp, first, training, brng, salt):
        """One residual branch under the configured norm placement; its
        norms are ``first`` and, around a branch, ``first + 1``."""
        place = self.norm_placement
        out = branch(self._norm(h, bp, first) if place != "after" else h)
        out = self._drop(out, self.hidden_drop, training, brng, salt)
        if place == "after":
            return self._norm(h + out, bp, first)
        if place == "around":
            out = self._norm(out, bp, first + 1)
        return h + out

    def _kda_mixer(self, bp, u):
        """Kimi Delta Attention of the normed state ``u`` (B, L, D): q, k
        and v each a projection, a causal depthwise convolution and SiLU;
        q and k of unit length a head; the log-decay a key channel
        g = -exp(A_log) softplus(u Wf1 Wf2 + dt_bias) and the step size a
        head beta = sigmoid(u Wb), both float32; the gated delta rule over
        the sequence (``ops.linear_attention.chunked_kda``); then a head's
        RMSNorm times sigmoid(u Wg1 Wg2 + b_g), and the output projection.
        Returns the branch (B, L, D) and the rule's two numbers."""
        from analytics_zoo_tpu.ops.linear_attention import chunked_kda

        heads, width, _taps = self.kda
        b, l, _ = u.shape
        f32 = jnp.float32

        def conv_heads(name):
            z = u @ bp[f"kda_{name}_kernel"]
            taps = bp[f"kda_{name}_conv"]
            n = taps.shape[0]
            past = jnp.pad(z, ((0, 0), (n - 1, 0), (0, 0)))
            # y_t = sum_i w_i z_{t - (n - 1) + i}, zeros before the start
            y = sum(past[:, i:i + l] * taps[i] for i in range(n))
            return split_heads(jax.nn.silu(y), heads)

        def unit(x):    # x / sqrt(|x|^2 + 1e-6) a head, in float32
            x32 = x.astype(f32)
            return (x32 * jax.lax.rsqrt(
                jnp.sum(jnp.square(x32), axis=-1, keepdims=True)
                + 1e-6)).astype(x.dtype)

        q, k, v = unit(conv_heads("q")), unit(conv_heads("k")), \
            conv_heads("v")
        decay = (u @ bp["kda_f_a_kernel"]) @ bp["kda_f_b_kernel"]
        g = -jnp.exp(bp["kda_a_log"].astype(f32))[:, None, None] \
            * split_heads(jax.nn.softplus(
                decay.astype(f32) + bp["kda_dt_bias"].astype(f32)), heads)
        beta = jax.nn.sigmoid((u @ bp["kda_b_kernel"]).astype(f32))
        o, stats = chunked_kda(q, k, v, g, beta.transpose(0, 2, 1),
                               scale=width ** -0.5)
        gate = (u @ bp["kda_g_a_kernel"]) @ bp["kda_g_b_kernel"] \
            + bp["kda_g_bias"]
        o = _rms_norm(o, bp["kda_o_norm"], self.norm_eps) \
            * split_heads(jax.nn.sigmoid(gate.astype(f32)),
                          heads).astype(o.dtype)
        return merge_heads(o) @ bp["kda_o_kernel"], stats

    def _block_forward_aux(self, bp, h, mask, training, brng):
        aux = drop = jnp.zeros((), jnp.float32)

        def dense(x, name):
            y = x @ bp[name + "_kernel"]
            return y + bp[name + "_bias"] if self.use_bias else y

        def latent_attention(u):
            rank, nope, rope, vd = self.latent
            b, l, _ = u.shape
            if self.rotary_theta is None:
                # no positions: the slices as made
                q = split_heads(u @ bp["q_kernel"], self.n_head)
                c, k_r = jnp.split(u @ bp["kv_a_kernel"], [rank], axis=-1)
                k_r = k_r[:, None]
            else:
                # adjacent pairs: the halves side by side (on q and k
                # alike, so the scores are the pairs' own), then the
                # rotate-half form; the kernels' columns hold that order,
                # so the products write it, q's heads as they lie, and
                # one pass turns the rope lanes and leaves q's nope lanes
                q = rotary(project_heads(
                    u, _rope_halves(bp["q_kernel"], self.n_head, rope),
                    self.n_head), self.rotary_theta, rope)
                c, k_r = jnp.split(
                    u @ _rope_halves(bp["kv_a_kernel"], 1, rope), [rank],
                    axis=-1)
                k_r = rotary(k_r[:, None], self.rotary_theta)
            kv = split_heads(
                _rms_norm(c, bp["kv_a_norm"], self.norm_eps)
                @ bp["kv_b_kernel"], self.n_head)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_r, (b, self.n_head, l, rope))], axis=-1)
            a = dot_product_attention(q, k, kv[..., nope:],
                                      causal=not self.bidirectional)
            return merge_heads(a) @ bp["o_kernel"]

        def attention(u):
            nonlocal aux
            # static: the tree is traced once, and a block's leaves say
            # which mixer it has
            if "kda_q_kernel" in bp:
                y, stats = self._kda_mixer(bp, u)
                aux = {"kda_" + key: value for key, value in stats.items()}
                return y
            if "q_kernel" in bp:
                return latent_attention(u)
            if self.rotary_theta is None:
                q, k, v = (split_heads(x, self.n_head) for x in
                           jnp.split(dense(u, "qkv"), 3, axis=-1))
            else:
                # the heads as the product writes them, and the rotation
                # one pass over each of q and k (``rotary``)
                q, k, v = project_heads(
                    u, bp["qkv_kernel"], self.n_head, parts=3,
                    bias=bp["qkv_bias"] if self.use_bias else None)
                q = rotary(q, self.rotary_theta)
                k = rotary(k, self.rotary_theta)
            a = dot_product_attention(
                q, k, v, mask=mask,
                dropout_p=self.attn_drop if training else 0.0,
                rng=(jax.random.fold_in(brng, 3)
                     if brng is not None else None),
                causal=not self.bidirectional,
            )
            return dense(merge_heads(a), "proj")

        def gated(u, prefix):
            f = self.act(u @ bp[prefix + "gate_kernel"]) \
                * (u @ bp[prefix + "fc_kernel"])
            return f @ bp[prefix + "out_kernel"]

        def feed_forward(u):
            nonlocal aux, drop
            if "router_kernel" in bp:
                from analytics_zoo_tpu.ops.moe import held_experts_ffn

                b, l, d = u.shape
                # ``aux`` of a routed block is the route's counts (beside a
                # KDA mixer's numbers where the block has one)
                f, route = held_experts_ffn(
                    u.reshape(b * l, d), bp["router_kernel"],
                    bp["router_bias"], bp["experts_gate"], bp["experts_up"],
                    bp["experts_down"], first_held=self.experts_held_from,
                    top_k=self.experts_per_token,
                    routed_scale=self.routed_scale, activation=self.act)
                aux = {**aux, **route} if isinstance(aux, dict) else route
                f = f.reshape(b, l, d)
                if "shared_gate_kernel" in bp:
                    f = f + gated(u, "shared_")
                return checkpoint_name(f, "ffn_out")
            if "moe_gate" in bp:
                from analytics_zoo_tpu.ops.moe import routed_ffn

                # routed FFN behind the residual: an over-capacity
                # token's zero expert output degrades to identity, never
                # to a zeroed activation (tests/test_moe_layer.py pins it)
                f, aux, drop = routed_ffn(
                    u, bp["moe_gate"], bp["moe_w1"], bp["moe_b1"],
                    bp["moe_w2"], bp["moe_b2"], top_k=self.moe_top_k,
                    capacity_factor=self.moe_capacity_factor,
                    activation=self.act)
                return f
            f = self.act(dense(u, "gate" if self.gated_ffn else "fc"))
            if self.gated_ffn:
                f = f * dense(u, "fc")
            # named for the "attn" policy: the norm after (or around) the
            # branch reads this in the backward pass, and keeping it
            # spares the down product's second run (PERF.md, PR 29)
            return checkpoint_name(dense(f, "out"), "ffn_out")

        n = self._n_norms // 2
        # the device's parts of a block, by name in every operation's
        # ``op_name`` (metadata only: ``metrics/tracing.py``)
        with named_scope(MIXER_SCOPE):
            h = self._branch(attention, h, bp, 1, training, brng, 1)
        with named_scope(FFN_SCOPE):
            h = self._branch(feed_forward, h, bp, 1 + n, training, brng, 2)
        return h, aux, drop


def _rms_norm(x, gamma, eps):
    """x * rsqrt(mean(x^2) + eps) * gamma, taken in float32 whatever the
    compute dtype (a bf16 mean of squares over the width loses the
    statistic), handed on in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                          + eps)
    return (x32 * scale * gamma.astype(jnp.float32)).astype(x.dtype)


#: Trace-time record of each rotary rotation traced, newest last (as
#: ``decoder_records``): ``form`` ("fused": the one pass of ``rotary``, from
#: the projection's head-major output to the turned heads and back, with no
#: float32 copy and no transpose of its own; "fallback" would name a
#: rotation taken in passes of its own, which no path takes), ``width`` (the
#: lanes turned a head) and ``heads``.
rotary_records: collections.deque = collections.deque(maxlen=256)


def rotary(x, theta, turned=None):
    """Rotary positions 0..L-1 on the head-major x (B, H, L, w), in the
    rotate-half form over a head's last ``turned`` lanes (all of them by
    default; the others go through unturned): x cos + rotate_half(x) sin,
    the angle of pair i at position p being p * theta^(-2i/turned).  One
    pass each way: the float32 arithmetic stays in the pass and is rounded
    once to x's dtype, rotate_half is a product with a signed permutation
    (each output is +-one input, so the product is exact), and the
    backward is dy cos - rotate_half(dy sin) in the same form."""
    turned = x.shape[-1] if turned is None else int(turned)
    rotary_records.append({"form": "fused", "width": turned,
                           "heads": x.shape[1]})
    return _rotary(x, float(theta), turned)


def _rotary_tables(length, width, theta, turned):
    """cos and sin (length, width), float32: the angles of the last
    ``turned`` lanes as the rotate-half form pairs them, 1 and 0 on the
    lanes before them."""
    inv_freq = theta ** (-jnp.arange(0, turned, 2, dtype=jnp.float32)
                         / turned)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    kept = (length, width - turned)
    return (jnp.concatenate([jnp.ones(kept), jnp.cos(angles)], axis=-1),
            jnp.concatenate([jnp.zeros(kept), jnp.sin(angles)], axis=-1))


def _rotate_half(width, turned, dtype):
    """(width, width) signed permutation: x @ it is rotate_half of x's last
    ``turned`` lanes, (-x2, x1), and 0 on the lanes before them."""
    half, first = turned // 2, width - turned
    lanes = np.arange(half) + first
    m = np.zeros((width, width), np.float32)
    m[lanes + half, lanes] = -1.0
    m[lanes, lanes + half] = 1.0
    return jnp.asarray(m, dtype)


def _turn(x, theta, turned, backward):
    length, width = x.shape[-2:]
    cos, sin = _rotary_tables(length, width, theta, turned)
    half = jnp.einsum("...ld,ed->...le" if backward else "...ld,de->...le",
                      x, _rotate_half(width, turned, x.dtype),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + half * sin).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _rotary(x, theta, turned):
    return _turn(x, theta, turned, backward=False)


# the transpose of rotate_half is -rotate_half: dx = dy cos + (dy2, -dy1)
# sin, the halves' angles being equal, read from dy as it lies
_rotary.defvjp(lambda x, theta, turned: (_rotary(x, theta, turned), None),
               lambda theta, turned, _, dy: (_turn(dy, theta, turned, True),))


def _pairs_to_halves(x):
    """(..., 2n) adjacent pairs (x0, x1), (x2, x3), ... -> the pairs' first
    members, then their second: ``rotary``'s rotate-half form on the
    result turns the published pairs (``rope_interleave``)."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _rope_halves(kernel, heads, rope):
    """A projection's kernel (D, heads * w) with the last ``rope`` columns
    of each head in ``_pairs_to_halves``' order: its product is the
    projection's with those lanes so ordered, column for column."""
    d, width = kernel.shape
    k = kernel.reshape(d, heads, width // heads)
    return jnp.concatenate([k[..., :-rope], _pairs_to_halves(k[..., -rope:])],
                           axis=-1).reshape(d, width)


class TransformerLayer(_TransformerCore):
    """GPT-style stack (reference TransformerLayer.scala:56).

    Inputs: ``[tokens, positions]`` int arrays of shape (B, L)
    (matching the reference's two-input contract), output (B, L, D).
    """

    def __init__(self, vocab, seq_len, n_block=12, n_head=12,
                 hidden_size=768, embedding_drop=0.1, **kwargs):
        super().__init__(n_block=n_block, n_head=n_head,
                         hidden_size=hidden_size, **kwargs)
        self.vocab = int(vocab)
        self.seq_len = int(seq_len)
        self.embedding_drop = float(embedding_drop)

    @classmethod
    def init_with_default_params(cls, vocab, seq_len, n_block=12, n_head=12,
                                 hidden_size=768, **kwargs):
        """Reference companion-object constructor."""
        return cls(vocab, seq_len, n_block, n_head, hidden_size, **kwargs)

    def build(self, input_shape):
        pass  # params are nested; built in init_params

    def init_params(self, rng):
        std = self.initializer_range
        ks = jax.random.split(rng, 2 + self.n_block)
        return {
            "tok_embed": _dense_init(ks[0], (self.vocab, self.hidden_size),
                                     std),
            "pos_embed": _dense_init(ks[1],
                                     (self.seq_len, self.hidden_size), std),
            "blocks": [self._block_params(ks[2 + i])
                       for i in range(self.n_block)],
        }

    def param_count(self):
        d, v = self.hidden_size, self.vocab
        per_block = self._per_block_param_count()
        return v * d + self.seq_len * d + self.n_block * per_block

    def call(self, params, inputs, state=None, training=False, rng=None):
        if isinstance(inputs, (list, tuple)):
            tokens, positions = inputs[0], inputs[1]
        else:
            tokens = inputs
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        h = jnp.take(params["tok_embed"], tokens.astype(jnp.int32), axis=0)
        h = h + jnp.take(params["pos_embed"], positions.astype(jnp.int32),
                         axis=0)
        h = self._drop(h, self.embedding_drop, training, rng, 0)
        out, aux, drop = self._run_blocks_aux(params["blocks"], h, None,
                                              training, rng)
        if self.moe_experts:
            return out, self._moe_state(aux, drop)
        return out

    def compute_output_shape(self, input_shape):
        if isinstance(input_shape, list):
            input_shape = input_shape[0]
        return tuple(input_shape) + (self.hidden_size,)


class BERT(_TransformerCore):
    """BERT encoder (reference BERT.scala:66).

    Inputs: ``[token_ids, token_type_ids, position_ids, attention_mask]``
    (the reference's four-input contract); outputs ``[sequence_output,
    pooled_output]``.
    """

    def __init__(self, vocab=40990, hidden_size=768, n_block=12, n_head=12,
                 seq_len=512, intermediate_size=3072, hidden_p_drop=0.1,
                 attn_p_drop=0.1, type_vocab=2, **kwargs):
        super().__init__(n_block=n_block, n_head=n_head,
                         hidden_size=hidden_size,
                         intermediate_size=intermediate_size,
                         hidden_drop=hidden_p_drop, attn_drop=attn_p_drop,
                         bidirectional=True, **kwargs)
        self.vocab = int(vocab)
        self.seq_len = int(seq_len)
        self.type_vocab = int(type_vocab)

    def build(self, input_shape):
        pass

    def init_params(self, rng):
        std = self.initializer_range
        d = self.hidden_size
        ks = jax.random.split(rng, 4 + self.n_block)
        return {
            "tok_embed": _dense_init(ks[0], (self.vocab, d), std),
            "pos_embed": _dense_init(ks[1], (self.seq_len, d), std),
            "type_embed": _dense_init(ks[2], (self.type_vocab, d), std),
            "embed_ln_gamma": jnp.ones((d,)),
            "embed_ln_beta": jnp.zeros((d,)),
            "pooler_kernel": _dense_init(ks[3], (d, d), std),
            "pooler_bias": jnp.zeros((d,)),
            "blocks": [self._block_params(ks[4 + i])
                       for i in range(self.n_block)],
        }

    def param_count(self):
        d = self.hidden_size
        per_block = self._per_block_param_count()
        return ((self.vocab + self.seq_len + self.type_vocab) * d + 2 * d
                + d * d + d + self.n_block * per_block)

    def call(self, params, inputs, state=None, training=False, rng=None):
        tokens, token_types, positions, attn_mask = (
            list(inputs) + [None] * (4 - len(inputs))
            if isinstance(inputs, (list, tuple)) else [inputs, None, None,
                                                       None]
        )
        b, l = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(l), (b, l))
        h = jnp.take(params["tok_embed"], tokens.astype(jnp.int32), axis=0)
        h = h + jnp.take(params["pos_embed"], positions.astype(jnp.int32),
                         axis=0)
        if token_types is not None:
            h = h + jnp.take(params["type_embed"],
                             token_types.astype(jnp.int32), axis=0)
        h = self._ln(h, params["embed_ln_gamma"], params["embed_ln_beta"])
        h = self._drop(h, self.hidden_drop, training, rng, 0)
        mask = None
        if attn_mask is not None:
            # additive mask: (B, L) 1/0 -> (B, 1, 1, L) 0/-1e9
            # (reference BERT.scala attention-mask preprocessing)
            mask = (1.0 - attn_mask[:, None, None, :].astype(h.dtype)) \
                * jnp.finfo(h.dtype).min
        seq, aux, drop = self._run_blocks_aux(params["blocks"], h, mask,
                                              training, rng)
        pooled = jnp.tanh(
            seq[:, 0] @ params["pooler_kernel"] + params["pooler_bias"]
        )
        if self.moe_experts:
            return [seq, pooled], self._moe_state(aux, drop)
        return [seq, pooled]

    def compute_output_shape(self, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) \
            else input_shape
        b, l = shape[0], shape[1]
        return [(b, l, self.hidden_size), (b, self.hidden_size)]


# -- the looped decoder's head: loss and gradient in one walk ---------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _weighted_ce(n, kernel, s, targets, w, head_grad):
    """(sum_tok w CE(s kernel, targets), CE a token float32 (B, L),
    ``head_grad`` + d sum / d kernel), walked in ``n`` blocks of positions.
    The weights ``w`` are an input, so a block's gradient is made while its
    logits are live and the backward pass only scales what was kept.

    Private to this file's decoders (``LoopedDecoder`` a pass,
    ``LatentMoEDecoder`` once): the third result is the head's gradient at
    cotangent one, summed over the passes it is threaded through, and is
    right only where the caller adds the first results with weight one and
    hands the sum with the last ``head_grad`` to ``_deliver``; ``kernel``
    and ``head_grad`` get no cotangent here, and none flows through CE."""
    return _weighted_ce_fwd(n, kernel, s, targets, w, head_grad)[0]


def _weighted_ce_fwd(n, kernel, s, targets, w, head_grad):
    b, l = targets.shape

    def blocked(x):     # (B, L, ...) -> (n, B, L / n, ...)
        return jnp.moveaxis(
            x.reshape((b, n, l // n) + x.shape[2:]), 1, 0)

    def whole(x):       # and back
        return jnp.moveaxis(x, 0, 1).reshape((b, l) + x.shape[3:])

    def block(head_grad, args):
        s_blk, y_blk, w_blk = args
        logits = (s_blk @ kernel).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = y_blk[..., None] == jnp.arange(logits.shape[-1])
        picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        # rounded where the transpose of the logits' astype would round it
        dlogits = ((jnp.exp(logits - lse[..., None]) - onehot)
                   * w_blk[..., None]).astype(s.dtype)
        head_grad = head_grad + jnp.einsum(
            "bld,blv->dv", s_blk, dlogits,
            preferred_element_type=jnp.float32)
        return head_grad, (lse - picked, dlogits @ kernel.T)

    head_grad, (ce, ds) = jax.lax.scan(
        block, head_grad, (blocked(s), blocked(targets), blocked(w)))
    ce = whole(ce)
    return (jnp.sum(w * ce), ce, head_grad), (ce, whole(ds))


def _weighted_ce_bwd(_n, kept, cotangents):
    ce, ds = kept
    g = cotangents[0]
    return None, (g * ds).astype(ds.dtype), None, g * ce, None


_weighted_ce.defvjp(_weighted_ce_fwd, _weighted_ce_bwd)


@jax.custom_vjp
def _deliver(total, kernel, head_grad):
    """``total``, whose cotangent g reaches ``kernel`` as g x ``head_grad``:
    where the sum that ``_weighted_ce`` made over the passes is handed to
    the head, once.  ``kernel`` is a float32 view of the head, so that the
    view's own transpose rounds the sum to the head's dtype."""
    return total


_deliver.defvjp(lambda total, kernel, head_grad: (total, head_grad),
                lambda head_grad, g: (g, g * head_grad, None))


#: Trace-time record of each looped stack traced, newest last (plain
#: arithmetic on static structure, like ``flash_attention.tile_schedules``:
#: jit traces once, so it counts compilations): ``layer``, ``training``,
#: ``passes``, ``layers``, ``layer_applications`` (passes x layers, over one
#: set of weights), ``head_evaluations`` (one a pass where the layer takes
#: the exit-gate loss itself, one where only the last pass's logits are
#: made), ``head_products`` (products over the vocabulary that the layer
#: traces: 3 x passes x ``loss_blocks`` under its own loss, a block's logits
#: and both products of its gradient, all in the forward pass, and none in
#: the backward pass; 1 otherwise, logits_T, to which differentiation adds
#: its two), ``loop`` (how the passes are traced: unrolled), ``remat`` (the policy
#: resolved for a layer application), ``kept`` (the ``checkpoint_name``s that
#: policy keeps for the backward pass; none under ``"full"``) and
#: ``loss_blocks`` (token blocks a pass's head and cross-entropy are taken in;
#: 0 without the loss).
loop_records: collections.deque = collections.deque(maxlen=16)


class LoopedDecoder(_TransformerCore):
    """Looped language model (Ouro's shape): token embedding, then ONE
    stack of ``n_block`` layers run ``passes`` times over the same weights,
    the final RMSNorm after every pass, an untied head and an exit gate.

    A layer is RMSNorm around both branches (four norms), rotary positions
    on q and k, causal attention, a gated SiLU feed-forward, no bias.  For
    tokens x: h = E[x]; for pass t = 1..T: h = s_t = RMSNorm(stack(h));
    logits_t = s_t W_head; lambda_t = sigmoid(s_t . w_exit + b_exit).
    Input (B, L) token ids, output logits_T (B, L, vocab).

    Trained with ``loss="looped_exit_cross_entropy"`` the layer takes the
    loss itself, a pass at a time in blocks of ``loss_block`` tokens, so
    that only one block's logits are ever live: with exit distribution
    p_t = lambda_t prod_{j<t} (1 - lambda_j), p_T = prod_{j<T}
    (1 - lambda_j), a token costs sum_t p_t CE(logits_t, y) - exit_beta
    H(p).  It reports the mean under ``loop_exit_cost`` of its state,
    which the train step adds to the loss, with ``loop_exit_mass`` (mean
    p_t) and ``loop_pass_loss`` (mean CE_t), a number a pass.  With any
    other loss only logits_T is made.

    The head's gradients are made where its logits are made: p_t comes
    from the gates, not from CE_t, so a token's weight p_t / N is known in
    the forward pass, and while a block's logits are live the walk also
    makes ``dlogits = (softmax - onehot) x weight``, ``dlogits @ W_head^T``
    and ``s^T @ dlogits``.  The backward pass of the head scales what was
    kept by the cotangent and runs no product over the vocabulary.  Kept:
    for the layer ONE float32 sum of the head's gradient over blocks and
    passes (hidden x vocab x 4 bytes, 403 MB at 2048 x 49152), handed to
    the head once; a pass its ``ds`` in the compute dtype (33.5 MB at
    (2, 4096, 2048) bf16, 134 MB over four passes) and CE_t (B, L) float32
    (32 KB).  On a v5e the step of 4 passes over 6 layers is 4.2% shorter
    than with the logits made again and its peak 0.63 GB lower (PERF.md,
    PR 31).

    Every layer application is one ``jax.checkpoint`` under the ``"attn"``
    policy (``remat=``; a plan's ``remat_rules`` override it).  Besides its
    input it keeps the attention's output with the two rows of softmax
    statistics, which is all the flash backward kernels read apart from q,
    k and v, and the feed-forward's output, which the norm around that
    branch reads: the backward pass makes the norms, q, k, v, the
    projections, gate and up again, but runs no attention forward and no
    down product twice.  Kept an application: 2 x B x L x hidden in the
    compute dtype and 2 x B x heads x L float32, 68.1 MB at (2, 4096)
    tokens, 16 heads of 128, bf16; over 4 passes of 6 layers the step's
    peak on a v5e grew by 2.46 GB for a step 5.7% shorter (PERF.md, PR 29).
    With less room, ``remat="full"`` keeps the input alone.
    """

    def __init__(self, vocab, n_block, n_head, hidden_size,
                 intermediate_size, passes=4, rotary_theta=1e6,
                 norm_eps=1e-6, exit_beta=0.05, loss_block=2048,
                 remat="attn", **kwargs):
        super().__init__(
            n_block=n_block, n_head=n_head, hidden_size=hidden_size,
            intermediate_size=intermediate_size, hidden_drop=0.0,
            attn_drop=0.0, activation="silu", remat=remat, norm="rms",
            norm_placement="around", norm_eps=norm_eps,
            rotary_theta=rotary_theta, gated_ffn=True, use_bias=False,
            **kwargs)
        self.vocab = int(vocab)
        self.passes = int(passes)
        self.exit_beta = float(exit_beta)
        self.loss_block = int(loss_block)
        if self.passes < 1:
            raise ValueError(f"passes={passes} < 1")

    def build(self, input_shape):
        pass  # params are nested; built in init_params

    def init_params(self, rng):
        std, d = self.initializer_range, self.hidden_size
        ks = jax.random.split(rng, 3 + self.n_block)
        return {
            "tok_embed": _dense_init(ks[0], (self.vocab, d), std),
            "blocks": [self._block_params(ks[3 + i])
                       for i in range(self.n_block)],
            "final_gamma": jnp.ones((d,)),
            "head_kernel": _dense_init(ks[1], (d, self.vocab), std),
            "exit_kernel": _dense_init(ks[2], (d, 1), std),
            "exit_bias": jnp.zeros((1,)),
        }

    def param_count(self):
        d = self.hidden_size
        return (2 * self.vocab * d + 2 * d + 1
                + self.n_block * self._per_block_param_count())

    @property
    def stateful(self):
        return True

    def init_state(self):
        per_pass = jnp.zeros((self.passes,), jnp.float32)
        return {"loop_exit_cost": jnp.zeros((), jnp.float32),
                "loop_exit_mass": per_pass, "loop_pass_loss": per_pass}

    def compute_output_shape(self, input_shape):
        if isinstance(input_shape, list):
            input_shape = input_shape[0]
        return tuple(input_shape) + (self.vocab,)

    # -- one pass's head, gate and loss ---------------------------------
    def _loss_blocks(self, batch, length):
        """How many blocks of positions a pass's cross-entropy is taken
        in: the fewest that divide ``length`` and keep a block of the
        whole batch at ``loss_block`` tokens or under."""
        return next((n for n in range(1, length + 1)
                     if length % n == 0
                     and batch * (length // n) <= self.loss_block), length)

    def _exit_tail(self, params, s, targets, log_survive, last):
        """After one pass: its cost to the loss, mean exit mass and mean
        CE, and log prod_{j<=t} (1 - lambda_j) for the next.  ``last``:
        the final pass takes what is left, whatever its gate says.
        ``params["head_grad"]``, the running sum of the head's gradient,
        is replaced by the sum with this pass's in it (``_weighted_ce``)."""
        z = (s.astype(jnp.float32)
             @ params["exit_kernel"].astype(jnp.float32))[..., 0] \
            + params["exit_bias"].astype(jnp.float32)
        log_p = log_survive if last \
            else jax.nn.log_sigmoid(z) + log_survive
        p = jnp.exp(log_p)
        # sum_t p_t CE_t - beta H(p) = sum_t p_t (CE_t + beta log p_t);
        # the first term a token at weight p / N through the head
        weighted, ce, params["head_grad"] = _weighted_ce(
            self._loss_blocks(*targets.shape), params["head_kernel"], s,
            targets.astype(jnp.int32), p / p.size, params["head_grad"])
        cost = weighted + self.exit_beta * jnp.mean(p * log_p)
        return (cost, jnp.mean(p), jnp.mean(ce),
                log_survive + jax.nn.log_sigmoid(-z))

    def _exit_loss(self, params, states, targets):
        """The layer's state under its own loss, from the passes' states,
        which are taken one at a time (``call`` makes each as it is asked
        for, so a pass's tail is traced right after the pass)."""
        log_survive = jnp.zeros(targets.shape, jnp.float32)
        # one dict for every pass's tail, each of which replaces its
        # "head_grad": the sum travels beside the weights because
        # ``_exit_tail`` keeps its arguments and results (the benchmark's
        # tests plant their fault by replacing it)
        tail_params = {**params, "head_grad": jnp.zeros(
            params["head_kernel"].shape, jnp.float32)}
        costs, mass, pass_loss = [], [], []
        for t, s in enumerate(states):
            # the head's scope around the tail alone: the pass's blocks are
            # traced when ``states`` is asked for ``s``, outside it
            with named_scope(HEAD_SCOPE):
                cost, p_mean, ce_mean, log_survive = self._exit_tail(
                    tail_params, s, targets, log_survive,
                    t == self.passes - 1)
            costs.append(cost)
            mass.append(p_mean)
            pass_loss.append(ce_mean)
        # the costs are added here with weight one, which is what makes the
        # sum over the passes the head's gradient (``_weighted_ce``)
        with named_scope(HEAD_SCOPE):
            total = _deliver(
                sum(costs), params["head_kernel"].astype(jnp.float32),
                tail_params["head_grad"])
        return {"loop_exit_cost": total, "loop_exit_mass": jnp.stack(mass),
                "loop_pass_loss": jnp.stack(pass_loss)}

    def call(self, params, inputs, state=None, training=False, rng=None):
        from analytics_zoo_tpu.parallel.plan import (
            REMAT_KEPT_NAMES,
            apply_remat,
            resolve_remat,
        )

        tokens = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        targets = current_targets() if training else None
        h = jnp.take(params["tok_embed"], tokens.astype(jnp.int32), axis=0)

        # The final norm keeps its input alone for the backward pass, as a
        # layer application does under its policy.  The tail is NOT under
        # jax.checkpoint: recomputed on the chip in the backward pass its
        # float32 arithmetic lost the gate's gradient, which is a small
        # difference between the passes' large shares (PERF.md, PR 27); it
        # keeps a few (B, L) arrays a pass.
        final = apply_remat(
            lambda gamma, h: _rms_norm(h, gamma, self.norm_eps), "full")

        def one_pass(h):
            h = self._run_blocks(params["blocks"], h, None, training, None)
            with named_scope(HEAD_SCOPE):
                return final(params["final_gamma"], h)

        takes_loss = targets is not None
        policy = resolve_remat(self.name or "blocks", default=self.remat)
        loss_blocks = self._loss_blocks(*tokens.shape) if takes_loss else 0
        loop_records.append({
            "layer": self.name, "training": bool(training),
            "passes": self.passes, "layers": self.n_block,
            "layer_applications": self.passes * self.n_block,
            "head_evaluations": self.passes if takes_loss else 1,
            "head_products": 3 * self.passes * loss_blocks
            if takes_loss else 1,
            "loop": "unrolled", "remat": policy,
            "kept": list(REMAT_KEPT_NAMES.get(policy, ())),
            "loss_blocks": loss_blocks})
        # The passes are unrolled: on the chip a step is 1.8% shorter than
        # with them in a lax.scan (PERF.md, PR 27), for a longer compile.
        if not takes_loss:
            for _ in range(self.passes):
                h = one_pass(h)
            with named_scope(HEAD_SCOPE):
                logits = h @ params["head_kernel"]
            # training under another loss: nothing of the gate's to report
            return logits, \
                self.init_state() if training or state is None else state

        def states():   # a pass is made when its tail asks for its state
            nonlocal h
            for _ in range(self.passes):
                h = one_pass(h)
                yield h

        loss_state = self._exit_loss(params, states(), targets)
        with named_scope(HEAD_SCOPE):
            logits = h @ params["head_kernel"]
        return logits, loss_state


#: Trace-time record of each ``LatentMoEDecoder`` traced, newest last (as
#: ``loop_records``): ``layer``, ``training``, ``dense_layers`` and
#: ``routed_layers``, ``router_width`` (experts the router scores),
#: ``experts_held`` and ``experts_held_from``, ``experts_per_token``,
#: ``capacity_factor`` (None: the routed layer has none and drops nothing),
#: ``attention`` (the mixer where every layer has the same, "by_layer"
#: otherwise) with ``attention_by_layer`` (every layer's: "latent" or
#: "kda"), ``rotary`` (whether latent attention turns its rope slices),
#: ``kda`` (heads, a head's width and convolution taps; None without such a
#: layer), ``qk_width`` and ``value_width`` of a latent head, ``remat`` and
#: ``kept`` (the policy of a layer
#: application and the ``checkpoint_name``s it keeps), ``loss_blocks``
#: (token blocks the head's cross-entropy is taken in under the layer's own
#: loss; 0 without it).
decoder_records: collections.deque = collections.deque(maxlen=16)


class LatentMoEDecoder(_TransformerCore):
    """Decoder-only language model of DeepSeek-V3's shape, one
    expert-parallel worker's share: token embedding, ``n_block`` blocks of
    RMSNorm before each branch, latent attention (MLA, no query
    compression) and a gated SiLU feed-forward that is dense in the first
    ``leading_dense`` blocks and routed after them (sigmoid router over
    ``routed_experts``, bias-corrected top ``experts_per_token``, no
    capacity and no dropped token, ``experts_held`` of the experts here,
    ``shared_experts`` beside them), a final RMSNorm and an untied head.
    Input (B, L) token ids, output logits (B, L, vocab).  ``vocab`` may be
    this worker's slice of the vocabulary: ids and targets then lie in it.

    The mixer may differ by layer: ``attention`` is "latent", or a
    sequence of ``n_block`` names, "latent" or "kda" (Kimi Delta
    Attention, ``_TransformerCore._kda_mixer``, sized by ``kda_heads``,
    ``kda_head_dim`` and ``kda_conv_size``; its two low-rank gates have a
    head's width as their rank); ``rotary_theta=None`` leaves latent
    attention without positions (a model whose order comes from KDA's
    convolutions and recurrence).  With a KDA layer the state also carries, a KDA layer,
    ``kda_chunk_log_decay_min``, ``kda_sub_block_log_decay_min`` and
    ``kda_state_rms`` (``ops.linear_attention.chunked_kda``'s numbers).

    Trained with ``loss="next_token_cross_entropy"`` the layer takes the
    mean cross-entropy itself in blocks of ``loss_block`` tokens, the
    head's gradient made while a block's logits are live (as
    ``LoopedDecoder`` does, one pass), and reports it under
    ``lm_loss_cost`` of its state.  With any other loss it hands the
    logits on.  Its state also carries, a routed layer, the assignments
    that fell on held experts (``moe_held_assignments``), the fullest held
    expert's load over the mean (``moe_load_max_over_mean``), the windows
    its walk of the held rows ran (``moe_walk_windows``) and, summed, the
    held assignments not multiplied (``moe_dropped_assignments``, always
    0), which the estimator publishes as gauges at the epoch's closing
    sync.

    Every layer application is one ``jax.checkpoint`` under the ``"attn"``
    policy: kept are the attention's output with the two rows of softmax
    statistics (all that the flash backward kernels read besides q, k and
    v; of a KDA layer the rule's output and its chunks' incoming states,
    67 + 134 MB at (2, 32, 4096, 128) in chunks of 128), the feed-forward's
    output and the route's integers (the sort's
    permutation and the group sizes: 0.2 MB a layer at 8,192 tokens and
    top-6, a top-k and a sort to make again).
    """

    def __init__(self, vocab, n_block, n_head, hidden_size,
                 intermediate_size, kv_latent_rank, qk_nope_dim, qk_rope_dim,
                 v_head_dim, routed_experts, experts_per_token, expert_size,
                 experts_held=None, experts_held_from=0, shared_experts=0,
                 routed_scale=1.0, leading_dense=1, rotary_theta=1e6,
                 norm_eps=1e-6, loss_block=2048, remat="attn",
                 attention="latent", **kwargs):
        super().__init__(
            n_block=n_block, n_head=n_head, hidden_size=hidden_size,
            intermediate_size=intermediate_size, hidden_drop=0.0,
            attn_drop=0.0, activation="silu", remat=remat, norm="rms",
            norm_placement="before", norm_eps=norm_eps,
            rotary_theta=rotary_theta, gated_ffn=True, use_bias=False,
            attention=attention, kv_latent_rank=kv_latent_rank,
            qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim,
            v_head_dim=v_head_dim, routed_experts=routed_experts,
            experts_held=experts_held, experts_held_from=experts_held_from,
            experts_per_token=experts_per_token, expert_size=expert_size,
            shared_experts=shared_experts, routed_scale=routed_scale,
            leading_dense=leading_dense, **kwargs)
        self.vocab = int(vocab)
        self.loss_block = int(loss_block)
        self.n_routed = max(self.n_block - self.leading_dense, 0)
        self.n_kda = self.attention_by_layer.count("kda")
        if "full" in self.attention_by_layer:
            raise ValueError("LatentMoEDecoder mixes by latent attention "
                             "or by KDA; got "
                             f"{list(self.attention_by_layer)}")

    def build(self, input_shape):
        pass  # params are nested; built in init_params

    def init_params(self, rng):
        std, d = self.initializer_range, self.hidden_size
        ks = jax.random.split(rng, 2 + self.n_block)
        return {
            "tok_embed": _dense_init(ks[0], (self.vocab, d), std),
            "blocks": [self._block_params(ks[2 + i], i)
                       for i in range(self.n_block)],
            "final_gamma": jnp.ones((d,)),
            "head_kernel": _dense_init(ks[1], (d, self.vocab), std),
        }

    def param_count(self):
        return (2 * self.vocab * self.hidden_size + self.hidden_size
                + sum(self._per_block_param_count(i)
                      for i in range(self.n_block)))

    @property
    def stateful(self):
        return True

    #: what the state carries a KDA layer: ``chunked_kda``'s numbers
    KDA_NUMBERS = ("kda_chunk_log_decay_min", "kda_sub_block_log_decay_min",
                   "kda_state_rms")

    def init_state(self):
        per_layer = jnp.zeros((self.n_routed,), jnp.float32)
        state = {"lm_loss_cost": jnp.zeros((), jnp.float32),
                 "moe_held_assignments": per_layer,
                 "moe_load_max_over_mean": per_layer,
                 "moe_walk_windows": per_layer,
                 "moe_dropped_assignments": jnp.zeros((), jnp.float32)}
        if self.n_kda:
            per_kda = jnp.zeros((self.n_kda,), jnp.float32)
            state.update({key: per_kda for key in self.KDA_NUMBERS})
        return state

    def compute_output_shape(self, input_shape):
        if isinstance(input_shape, list):
            input_shape = input_shape[0]
        return tuple(input_shape) + (self.vocab,)

    _loss_blocks = LoopedDecoder._loss_blocks

    def _mean_ce(self, params, s, targets):
        """Mean next-token cross-entropy of the normed state ``s`` through
        the head, in token blocks, gradient made with the logits."""
        weights = jnp.full(targets.shape, 1.0 / targets.size, jnp.float32)
        total, _ce, head_grad = _weighted_ce(
            self._loss_blocks(*targets.shape), params["head_kernel"], s,
            targets.astype(jnp.int32), weights,
            jnp.zeros(params["head_kernel"].shape, jnp.float32))
        return _deliver(total, params["head_kernel"].astype(jnp.float32),
                        head_grad)

    def call(self, params, inputs, state=None, training=False, rng=None):
        from analytics_zoo_tpu.parallel.plan import (
            REMAT_KEPT_NAMES,
            apply_remat,
            resolve_remat,
        )

        tokens = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
        targets = current_targets() if training else None
        policy = resolve_remat(self.name or "blocks", default=self.remat)
        body = apply_remat(self._block_forward_aux, policy,
                           static_argnums=(3,))
        loss_blocks = self._loss_blocks(*tokens.shape) \
            if targets is not None else 0
        rank, nope, rope, vd = self.latent or (None, 0, 0, None)
        decoder_records.append({
            "layer": self.name, "training": bool(training),
            "dense_layers": self.n_block - self.n_routed,
            "routed_layers": self.n_routed,
            "router_width": self.routed_experts,
            "experts_held": self.experts_held,
            "experts_held_from": self.experts_held_from,
            "experts_per_token": self.experts_per_token,
            "capacity_factor": None, "attention": self.attention,
            "attention_by_layer": list(self.attention_by_layer),
            "rotary": self.rotary_theta is not None, "kda": self.kda,
            "qk_width": nope + rope, "value_width": vd, "remat": policy,
            "kept": list(REMAT_KEPT_NAMES.get(policy, ())),
            "loss_blocks": loss_blocks})

        h = jnp.take(params["tok_embed"], tokens.astype(jnp.int32), axis=0)
        routes, rules = [], []
        for bp in params["blocks"]:
            h, numbers, _ = body(bp, h, None, training, None)
            if "router_kernel" in bp:   # static: the tree is traced once
                routes.append(numbers)
            if "kda_q_kernel" in bp:
                rules.append(numbers)
        with named_scope(HEAD_SCOPE):
            s = apply_remat(
                lambda gamma, h: _rms_norm(h, gamma, self.norm_eps),
                "full")(params["final_gamma"], h)
            cost = self._mean_ce(params, s, targets) \
                if targets is not None else jnp.zeros((), jnp.float32)

        def over_layers(key):
            return jnp.stack([r[key] for r in routes]) if routes \
                else jnp.zeros((0,), jnp.float32)

        new_state = {
            "lm_loss_cost": cost,
            "moe_held_assignments": over_layers("held_assignments"),
            "moe_load_max_over_mean": over_layers("load_max_over_mean"),
            "moe_walk_windows": over_layers("walk_windows"),
            "moe_dropped_assignments": jnp.sum(
                over_layers("dropped_assignments"))}
        if rules:
            new_state.update({
                key: jnp.stack([r[key] for r in rules])
                for key in self.KDA_NUMBERS})
        if not training and state is not None:
            new_state = state
        with named_scope(HEAD_SCOPE):
            logits = s @ params["head_kernel"]
        return logits, new_state
