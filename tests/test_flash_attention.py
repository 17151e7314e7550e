"""Flash-attention correctness: custom_vjp blockwise backward vs the dense
reference, including ragged lengths (lk % block != 0) and end-aligned causal
masking with lq != lk.  Runs on CPU (the Pallas forward is TPU-only; the
blockwise backward runs everywhere)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops.pallas.flash_attention import (
    _attention_reference,
    flash_attention,
)


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("lq,lk", [(64, 64), (300, 300), (64, 300),
                                   (37, 128)])
def test_forward_matches_reference(causal, lq, lk):
    q = _rand((2, 2, lq, 8), 0)
    k = _rand((2, 2, lk, 8), 1)
    v = _rand((2, 2, lk, 8), 2)
    got = flash_attention(q, k, v, causal, None, 128, 128)
    want = _attention_reference(q, k, v, causal, 1.0 / np.sqrt(8))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("lq,lk", [(64, 64), (300, 300), (64, 300)])
def test_blockwise_backward_matches_reference(causal, lq, lk):
    q = _rand((1, 2, lq, 8), 3)
    k = _rand((1, 2, lk, 8), 4)
    v = _rand((1, 2, lk, 8), 5)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, 128, 128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            _attention_reference(q, k, v, causal, 1.0 / np.sqrt(8)) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_backward_memory_is_blockwise():
    """The full (lq, lk) score matrix must never appear in the backward
    jaxpr — only (lq, block_k) tiles.  (The CPU *forward* fallback is dense
    by design; on TPU the Pallas kernel serves the forward.)"""
    from analytics_zoo_tpu.ops.pallas.flash_attention import _bwd

    lq = lk = 512
    q = _rand((1, 1, lq, 8), 6)
    k = _rand((1, 1, lk, 8), 7)
    v = _rand((1, 1, lk, 8), 8)
    out = flash_attention(q, k, v, True, None, 128, 128)
    g = jnp.ones_like(out)
    jaxpr = jax.make_jaxpr(
        lambda res, g: _bwd(True, None, 0.0, 128, 128, res, g))(
            (q, k, v, None, None, None, None, out, None, None), g)
    text = str(jaxpr).replace(" ", "")
    assert f"1,1,{lq},{lk}]" not in text, (
        "full (lq, lk) score matrix materialized in backward")
    assert "1,1,512,128]" in text  # block tiles are present


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("lq,lk", [(128, 128), (128, 384), (100, 260)])
def test_pallas_kernel_interpret_matches_reference(causal, lq, lk):
    """Run the ACTUAL Pallas kernel (grid-streamed K/V, scratch
    accumulators) in interpret mode on CPU and compare against the dense
    oracle — so the kernel logic itself is CI-tested without a TPU."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import _flash_fwd_pallas

    q = _rand((2, 2, lq, 8), 10)
    k = _rand((2, 2, lk, 8), 11)
    v = _rand((2, 2, lk, 8), 12)
    got = _flash_fwd_pallas(q, k, v, causal, 1.0 / np.sqrt(8), 64, 64,
                            interpret=True)
    want = _attention_reference(q, k, v, causal, 1.0 / np.sqrt(8))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Round-4 training-path features: additive bias/mask, segment ids, dropout
# (VERDICT r03 item 1 — flash must serve the REAL training config)
# ---------------------------------------------------------------------------


def _grad_check(loss_flash, loss_ref, args, rtol=1e-4, atol=1e-4):
    n = len(args)
    g1 = jax.grad(loss_flash, argnums=tuple(range(n)))(*args)
    g2 = jax.grad(loss_ref, argnums=tuple(range(n)))(*args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


@pytest.mark.parametrize("bias_shape", [(2, 1, 1, 300), (1, 1, 200, 300),
                                        (2, 2, 200, 300)])
def test_bias_forward_and_grad(bias_shape):
    """Additive bias in every broadcast form — incl. the BERT (B,1,1,L)
    padding-mask convention (reference BERT.scala:66) — matches the dense
    oracle in both forward and all grads (incl. dbias)."""
    q = _rand((2, 2, 200, 8), 0)
    k = _rand((2, 2, 300, 8), 1)
    v = _rand((2, 2, 300, 8), 2)
    bias = _rand(bias_shape, 3) * 2.0

    def f_flash(q, k, v, bias):
        return jnp.sum(flash_attention(q, k, v, False, None, 128, 128,
                                       bias=bias) ** 2)

    def f_ref(q, k, v, bias):
        return jnp.sum(_attention_reference(
            q, k, v, False, 1.0 / np.sqrt(8), bias=bias) ** 2)

    np.testing.assert_allclose(
        flash_attention(q, k, v, False, None, 128, 128, bias=bias),
        _attention_reference(q, k, v, False, 1.0 / np.sqrt(8), bias=bias),
        rtol=2e-5, atol=2e-5)
    _grad_check(f_flash, f_ref, (q, k, v, bias))


def test_padding_mask_fully_masked_rows_zero():
    """BERT-style key-padding mask with some rows fully masked: output 0
    for those queries (kernel l->0 semantics), no NaNs in grads."""
    q = _rand((2, 2, 256, 8), 4)
    k = _rand((2, 2, 256, 8), 5)
    v = _rand((2, 2, 256, 8), 6)
    keep = np.ones((2, 1, 1, 256), np.float32)
    keep[1] = 0.0  # batch 1: ALL keys masked
    # finfo.min mask (the BERT-layer convention) sits below the kernel's
    # -1e30 running-max floor, so fully-masked rows emit exact zeros
    bias = jnp.asarray((1.0 - keep) * np.finfo(np.float32).min)
    out = flash_attention(q, k, v, False, None, 128, 128, bias=bias)
    np.testing.assert_allclose(out[1], 0.0, atol=1e-6)
    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, False, None, 128, 128, bias=bias)))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_segment_ids_forward_and_grad():
    """Packed-sequence segment masking (new TPU capability; the reference
    has no packing, SequenceShaper truncation only)."""
    q = _rand((2, 2, 200, 8), 7)
    k = _rand((2, 2, 200, 8), 8)
    v = _rand((2, 2, 200, 8), 9)
    rng = np.random.default_rng(0)
    segs = jnp.asarray(np.sort(rng.integers(0, 3, size=(2, 200)), axis=1)
                       .astype(np.int32))

    got = flash_attention(q, k, v, False, None, 64, 64,
                          q_segment_ids=segs, kv_segment_ids=segs)
    want = _attention_reference(q, k, v, False, 1.0 / np.sqrt(8),
                                q_seg=segs, kv_seg=segs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, False, None, 64, 64,
            q_segment_ids=segs, kv_segment_ids=segs) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_attention_reference(
            q, k, v, False, 1.0 / np.sqrt(8), q_seg=segs,
            kv_seg=segs) ** 2)

    _grad_check(f_flash, f_ref, (q, k, v))


@pytest.mark.parametrize("causal", [False, True])
def test_dropout_forward_and_grad(causal):
    """Hash-derived dropout: the custom blockwise backward must reproduce
    the forward's exact mask (no stored mask) — grads match autodiff
    through the dense reference using the same hash."""
    q = _rand((1, 2, 200, 8), 10)
    k = _rand((1, 2, 200, 8), 11)
    v = _rand((1, 2, 200, 8), 12)
    seed = jnp.asarray([123, 7], jnp.int32)

    got = flash_attention(q, k, v, causal, None, 64, 64,
                          dropout_p=0.3, dropout_seed=seed)
    want = _attention_reference(q, k, v, causal, 1.0 / np.sqrt(8),
                                dropout_p=0.3, seed=seed)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal, None, 64, 64, dropout_p=0.3,
            dropout_seed=seed) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_attention_reference(
            q, k, v, causal, 1.0 / np.sqrt(8), dropout_p=0.3,
            seed=seed) ** 2)

    _grad_check(f_flash, f_ref, (q, k, v))


def test_dropout_statistics():
    """Dropout keeps ~(1-p) of probs and preserves the mean (inverted
    scaling); different seeds give different masks."""
    q = _rand((1, 1, 256, 8), 13)
    k = _rand((1, 1, 256, 8), 14)
    v = jnp.ones((1, 1, 256, 8), jnp.float32)
    clean = flash_attention(q, k, v, False, None, 128, 128)
    d1 = flash_attention(q, k, v, False, None, 128, 128,
                         dropout_p=0.5, dropout_seed=1)
    d2 = flash_attention(q, k, v, False, None, 128, 128,
                         dropout_p=0.5, dropout_seed=2)
    assert not np.allclose(d1, d2)
    # with v=1 every output row = sum of kept scaled probs; mean ~ 1
    np.testing.assert_allclose(np.mean(np.asarray(d1)), 
                               np.mean(np.asarray(clean)), rtol=0.05)


def test_pallas_kernel_interpret_training_config():
    """The ACTUAL Pallas kernel (interpret mode on CPU) with the full
    training config — padding mask + segment ids + dropout + causal —
    vs the dense oracle."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import _flash_fwd_pallas

    q = _rand((2, 2, 130, 64), 15)
    k = _rand((2, 2, 130, 64), 16)
    v = _rand((2, 2, 130, 64), 17)
    keep = np.ones((2, 1, 1, 130), np.float32)
    keep[:, :, :, 100:] = 0.0
    bias = jnp.asarray((1.0 - keep) * -1e30)
    segs = jnp.asarray(
        np.repeat([[0] * 70 + [1] * 60], 2, 0).astype(np.int32))
    seed = jnp.asarray([5, 9], jnp.int32)
    got = _flash_fwd_pallas(q, k, v, True, 0.125, 64, 64, interpret=True,
                            bias=bias, q_seg=segs, kv_seg=segs,
                            dropout_p=0.2, seed=seed)
    want = _attention_reference(q, k, v, True, 0.125, bias=bias,
                                q_seg=segs, kv_seg=segs, dropout_p=0.2,
                                seed=seed)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_routing_training_config_reaches_pallas(monkeypatch):
    """VERDICT r03 weak #1 regression test: dot_product_attention with a
    BERT-style padded mask AND attention dropout (the realistic training
    config) must route to the Pallas kernel — exercised end-to-end in
    interpret mode on CPU."""
    import analytics_zoo_tpu.ops.pallas.flash_attention as fa
    from analytics_zoo_tpu.ops.attention import dot_product_attention

    monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    q = _rand((2, 2, 256, 64), 18)
    k = _rand((2, 2, 256, 64), 19)
    v = _rand((2, 2, 256, 64), 20)
    keep = np.ones((2, 1, 1, 256), np.float32)
    keep[:, :, :, 200:] = 0.0
    mask = jnp.asarray((1.0 - keep) * -1e9)
    rng = jax.random.PRNGKey(0)
    before = fa.invocation_counts["pallas"]
    out = dot_product_attention(q, k, v, mask=mask, dropout_p=0.1, rng=rng)
    assert fa.invocation_counts["pallas"] == before + 1, (
        "training-config attention (mask + dropout) fell back to the "
        "dense path")
    assert np.isfinite(np.asarray(out)).all()
    # grads flow through the custom blockwise backward
    g = jax.grad(lambda q: jnp.sum(dot_product_attention(
        q, k, v, mask=mask, dropout_p=0.1, rng=rng) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_flash_eligible_predicate():
    from analytics_zoo_tpu.ops.attention import flash_eligible

    q4 = (2, 12, 512, 64)
    # clean
    assert flash_eligible(q4, None, None, 0.0, False, 512)
    # BERT padding mask
    assert flash_eligible(q4, (2, 1, 1, 512), 4, 0.0, False, 512)
    # full bias
    assert flash_eligible(q4, (2, 12, 512, 512), 4, 0.0, False, 512)
    # dropout with rng ok, without rng not
    assert flash_eligible(q4, None, None, 0.1, True, 512)
    assert not flash_eligible(q4, None, None, 0.1, False, 512)
    # short seq / odd head dim stay on the jnp path
    assert not flash_eligible((2, 12, 128, 64), None, None, 0.0, False, 128)
    assert not flash_eligible((2, 12, 512, 40), None, None, 0.0, False, 512)
    # non-broadcastable mask shapes
    assert not flash_eligible(q4, (3, 1, 1, 512), 4, 0.0, False, 512)
    assert not flash_eligible(q4, (512, 512), 2, 0.0, False, 512)
    # explicit opt-out
    assert not flash_eligible(q4, None, None, 0.0, False, 512,
                              use_flash=False)


def test_bert_training_forward_routes_to_pallas(monkeypatch):
    """End-to-end: BERT layer *training* forward (attention dropout on,
    padded attention mask — reference BERT.scala:66 semantics) lowers to
    the Pallas flash kernel, not the dense O(L²) path.  VERDICT r03
    item 1 acceptance."""
    import analytics_zoo_tpu.ops.pallas.flash_attention as fa
    from analytics_zoo_tpu.pipeline.api.keras.layers import BERT

    monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    layer = BERT(vocab=100, hidden_size=768, n_block=1, n_head=12,
                 seq_len=256, intermediate_size=256)
    params = layer.init_params(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 256), jnp.int32)
    types = jnp.zeros((2, 256), jnp.int32)
    attn_mask = jnp.asarray(
        np.repeat([[1] * 200 + [0] * 56], 2, 0).astype(np.float32))
    before = fa.invocation_counts["pallas"]
    seq, pooled = layer.call(params, [tokens, types, None, attn_mask],
                             training=True, rng=jax.random.PRNGKey(1))
    assert fa.invocation_counts["pallas"] > before, (
        "BERT training attention (dropout + padding mask) did not route "
        "to the Pallas kernel")
    assert np.isfinite(np.asarray(seq)).all()


def test_transformer_training_forward_routes_to_pallas(monkeypatch):
    """GPT-style TransformerLayer training (causal + attention dropout)
    lowers to the Pallas flash kernel."""
    import analytics_zoo_tpu.ops.pallas.flash_attention as fa
    from analytics_zoo_tpu.pipeline.api.keras.layers import TransformerLayer

    monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    layer = TransformerLayer(vocab=100, seq_len=256, n_block=1, n_head=4,
                             hidden_size=256, intermediate_size=256)
    params = layer.init_params(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 256), jnp.int32)
    before = fa.invocation_counts["pallas"]
    out = layer.call(params, tokens, training=True,
                     rng=jax.random.PRNGKey(1))
    assert fa.invocation_counts["pallas"] > before, (
        "TransformerLayer training attention (causal + dropout) did not "
        "route to the Pallas kernel")
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("causal", [False, True])
def test_attention_stats_matches_reference(causal):
    """(out, m, l) partial form: kernel (interpret) vs jnp reference, and
    the combine identity — two disjoint key halves merged with the flash
    update must equal full attention."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _attention_stats_reference,
        _flash_fwd_pallas,
    )

    q = _rand((2, 2, 128, 64), 60)
    k = _rand((2, 2, 128, 64), 61)
    v = _rand((2, 2, 128, 64), 62)
    got = _flash_fwd_pallas(q, k, v, causal, 0.125, 64, 64,
                            interpret=True, return_stats=True)
    want = _attention_stats_reference(q, k, v, causal, 0.125)
    for a, b, name in zip(got, want, ("out", "m", "l")):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=name)

    if not causal:
        # combine two halves of the keys -> full attention
        o1, m1, l1 = _attention_stats_reference(q, k[:, :, :64],
                                                v[:, :, :64], False, 0.125)
        o2, m2, l2 = _attention_stats_reference(q, k[:, :, 64:],
                                                v[:, :, 64:], False, 0.125)
        m12 = np.maximum(m1, m2)
        a1, a2 = np.exp(m1 - m12), np.exp(m2 - m12)
        l12 = l1 * a1 + l2 * a2
        acc = (np.asarray(o1) * np.asarray(l1)[..., None] * a1[..., None]
               + np.asarray(o2) * np.asarray(l2)[..., None]
               * a2[..., None])
        full = _attention_reference(q, k, v, False, 0.125)
        np.testing.assert_allclose(acc / l12[..., None], full, rtol=1e-4,
                                   atol=1e-4)


import contextlib
import re


@contextlib.contextmanager
def _mosaic_module_spy():
    """Capture the raw (pre-serialization) Mosaic module of every pallas
    kernel lowered inside the block, and on exit reject the op class the
    chip compiler rejects but client-side lowering does not: a
    ``vector.shape_cast`` on a sub-32-bit element type that changes the
    minor dimension ("Insertion of minor dim that is not a no-op only
    supported for 32-bit types" — apply-vector-layout runs inside libtpu,
    so without this scan the failure only surfaces on the real chip; it
    did, twice, in round 4)."""
    import jax._src.tpu_custom_call as tcc

    captured = []
    orig = tcc._lower_mosaic_module_to_asm

    def spy(module, *a, **k):
        captured.append(str(module.operation))
        return orig(module, *a, **k)

    tcc._lower_mosaic_module_to_asm = spy
    try:
        yield
    finally:
        tcc._lower_mosaic_module_to_asm = orig
    # a vacuously-green guard is worse than none: if a jax upgrade stops
    # routing pallas lowering through the patched hook, fail loudly
    assert captured, (
        "Mosaic spy captured no modules — pallas lowering no longer goes "
        "through jax._src.tpu_custom_call._lower_mosaic_module_to_asm; "
        "re-point the spy")
    pat = re.compile(
        r"vector\.shape_cast.*?:\s*vector<([0-9x]+)x(i1|i8|i16|bf16|f16)>"
        r"\s*to\s*vector<([0-9x]+)x(?:i1|i8|i16|bf16|f16)>")
    bad = []
    for mod in captured:
        for m in pat.finditer(mod):
            src_minor = m.group(1).split("x")[-1]
            dst_minor = m.group(3).split("x")[-1]
            if src_minor != dst_minor:
                bad.append(m.group(0))
    assert not bad, (
        "sub-32-bit shape_cast changing the minor dim — lowers client-side "
        "but Mosaic's apply-vector-layout rejects it on the chip; build the "
        f"mask in the target orientation with broadcasted_iota instead: {bad}")


def test_mosaic_tpu_lowering_all_variants():
    """Cross-lower every production flash configuration for the TPU backend
    (no chip needed: Mosaic's block-shape validation — second-to-last dim
    divisible by 8 or full, last divisible by 128 or full — runs at lowering
    time).  Interpret-mode numerics tests cannot catch these; the round-4
    chip run failed exactly here on the (1, block) segment-id specs."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _flash_fwd_pallas,
        _resolve_blocks,
    )

    B, H, L, D = 2, 2, 4096, 64
    q = jnp.zeros((B, H, L, D), jnp.bfloat16)
    segs = jnp.zeros((B, L), jnp.int32)
    bias = jnp.zeros((B, 1, 1, L), jnp.float32)
    seed = jnp.asarray([3, 11], jnp.int32)
    full_bias = jnp.zeros((B, 1, L, L), jnp.float32)
    variants = {
        "clean": dict(),
        "causal": dict(causal=True),
        "bias_dropout": dict(bias=bias, dropout_p=0.1, seed=seed),
        "full_bias": dict(bias=full_bias),
        "causal_seg_dropout": dict(causal=True, q_seg=segs, kv_seg=segs,
                                   dropout_p=0.1, seed=seed),
        "stats": dict(return_stats=True),
    }
    with _mosaic_module_spy():
        for name, kw in variants.items():
            b = kw.get("bias")
            causal = kw.pop("causal", False)
            bq, bk = _resolve_blocks(
                None, None, L, L, causal=causal,
                full_bias=b is not None and b.shape[-2] > 1,
                dropout=kw.get("dropout_p", 0) > 0)

            def fn(q, kw=kw, causal=causal, bq=bq, bk=bk):
                return _flash_fwd_pallas(q, q, q, causal, 0.125, bq, bk, **kw)

            jax.jit(fn).trace(q).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("variant", [
    "clean", "causal", "bias", "bias_dropout", "seg_causal_dropout",
])
def test_pallas_backward_interpret_matches_reference(variant, monkeypatch):
    """The Pallas backward kernels (dq + dk/dv/dbias), run in interpret
    mode via the REAL custom_vjp route (ZOO_FLASH_INTERPRET -> pallas fwd
    saves stats -> pallas bwd), must match the dense oracle's grads for
    every training variant, on ragged multi-block shapes."""
    monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    b, h, lq, lk, d = 2, 2, 600, 700, 8
    q = _rand((b, h, lq, d), 30)
    k = _rand((b, h, lk, d), 31)
    v = _rand((b, h, lk, d), 32)
    rng = np.random.default_rng(3)
    segs_q = jnp.asarray(np.sort(rng.integers(0, 3, (b, lq)), 1), jnp.int32)
    segs_k = jnp.asarray(np.sort(rng.integers(0, 3, (b, lk)), 1), jnp.int32)
    bias = _rand((b, 1, 1, lk), 33) * 2.0
    seed = jnp.asarray([5, 9], jnp.int32)
    cfg = {
        "clean": (False, {}, {}),
        "causal": (True, {}, {}),
        "bias": (False, {"bias": bias}, {"bias": bias}),
        "bias_dropout": (False,
                         {"bias": bias, "dropout_p": 0.1,
                          "dropout_seed": seed},
                         {"bias": bias, "dropout_p": 0.1, "seed": seed}),
        "seg_causal_dropout": (True,
                               {"q_segment_ids": segs_q,
                                "kv_segment_ids": segs_k,
                                "dropout_p": 0.1, "dropout_seed": seed},
                               {"q_seg": segs_q, "kv_seg": segs_k,
                                "dropout_p": 0.1, "seed": seed}),
    }
    causal, kw_flash, kw_ref = cfg[variant]
    import analytics_zoo_tpu.ops.pallas.flash_attention as fa
    before = fa.invocation_counts["pallas"]

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None,
                                       **kw_flash) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_attention_reference(
            q, k, v, causal, 1.0 / np.sqrt(d), **kw_ref) ** 2)

    _grad_check(f_flash, f_ref, (q, k, v), rtol=5e-4, atol=5e-4)
    # fwd + bwd kernels both fired (no silent jnp fallback)
    assert fa.invocation_counts["pallas"] >= before + 2, (
        "Pallas forward/backward did not both fire")
    if "bias" in variant:
        db1 = jax.grad(lambda bias: jnp.sum(flash_attention(
            q, k, v, causal, None,
            **{**kw_flash, "bias": bias}) ** 2))(bias)
        db2 = jax.grad(lambda bias: jnp.sum(_attention_reference(
            q, k, v, causal, 1.0 / np.sqrt(d),
            **{**kw_ref, "bias": bias}) ** 2))(bias)
        np.testing.assert_allclose(db1, db2, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("D", [64, 128])
def test_mosaic_tpu_lowering_backward(D):
    """Cross-lower the Pallas BACKWARD kernels for the TPU backend at the
    production shapes — the same no-chip Mosaic block-rule guard as the
    forward test (a bwd-spec regression otherwise only fails on the
    chip).  head_dim 128 is the transformer-bench config (hidden 2560 /
    20 heads); 64 is BERT-base."""
    B, H, L = 2, 2, 4096
    q = jnp.zeros((B, H, L, D), jnp.bfloat16)
    segs = jnp.zeros((B, L), jnp.int32)
    bias = jnp.zeros((B, 1, 1, L), jnp.float32)
    seed = jnp.asarray([3, 11], jnp.int32)
    variants = {
        "clean": dict(),
        "bias_dropout": dict(bias=bias, dropout_p=0.1, dropout_seed=seed),
        "seg_causal": dict(causal=True, q_segment_ids=segs,
                           kv_segment_ids=segs),
    }
    import os

    # FORCE_PALLAS (not INTERPRET): interpret-mode pallas lowers to plain
    # jax ops and never reaches Mosaic, which made this guard vacuous in
    # round 4 — the i1 minor-dim shape_cast sailed through to the chip.
    # The forced route traces the REAL kernels; lowering needs no TPU.
    os.environ["ZOO_FLASH_FORCE_PALLAS"] = "1"
    try:
        with _mosaic_module_spy():
            for name, kw in variants.items():
                causal = kw.pop("causal", False)

                def fn(q, kw=kw, causal=causal):
                    return jnp.sum(flash_attention(q, q, q, causal, 0.125,
                                                   **kw) ** 2)

                jax.jit(jax.grad(fn)).trace(q).lower(
                    lowering_platforms=("tpu",))
    finally:
        os.environ.pop("ZOO_FLASH_FORCE_PALLAS", None)


# ---------------------------------------------------------------------------
# The tile schedule (ROADMAP S4): operands in the inputs' dtype, causal
# tiles skipped, masks only on the diagonal
# ---------------------------------------------------------------------------

#: (rel_l2, rel_max) against the dense float32 oracle on the same inputs.
#: float32: the order of the sums alone differs.  bf16: the output and
#: the gradients are rounded to bf16 (2^-9 an element), and so are P and
#: dS at their products; what chip_smoke.py holds the kernels to on a TPU.
SCHEDULE_TOL = {"float32": (1e-5, 1e-4), "bfloat16": (1e-2, 4e-2)}


def _fresh_schedules():
    """The kernels are jitted, so a call that an earlier test traced at
    the same shapes records nothing: drop their traces and the record."""
    import analytics_zoo_tpu.ops.pallas.flash_attention as fa

    fa._flash_fwd_pallas.clear_cache()
    fa._flash_bwd_kernel.clear_cache()
    fa.tile_schedules.clear()
    return fa.tile_schedules


def _rel(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    diff = got - want
    return (float(np.linalg.norm(diff) / np.linalg.norm(want)),
            float(np.abs(diff).max() / np.abs(want).max()))


def _classes_by_position(lq, lk, sub_q, sub_k, all_masked):
    """(skipped, plain, masked) sub-tiles of a causal call by where their
    positions stand to the end-aligned diagonal, over the tiles' whole
    extent: a ragged edge is not the diagonal's business (a call that has
    one masks every tile it visits)."""
    n_q, n_k = -(-lq // sub_q), -(-lk // sub_k)
    live = (np.arange(n_q * sub_q)[:, None] + (lk - lq)
            >= np.arange(n_k * sub_k)[None, :])
    counts = {"skipped": 0, "plain": 0, "masked": 0}
    for i in range(n_q):
        for j in range(n_k):
            tile = live[i * sub_q:(i + 1) * sub_q, j * sub_k:(j + 1) * sub_k]
            counts["skipped" if not tile.any() else "masked"
                   if all_masked or not tile.all() else "plain"] += 1
    return counts["skipped"], counts["plain"], counts["masked"]


#: (lq, lk, block_q, block_k, d, dv, schedule, tiles a loop body issues)
SCHEDULED = {
    # 4 x 4 tiles that one grid step spells out
    "unrolled": (512, 512, 128, 128, 16, 16, "unrolled", 10),
    # 6 x 6 tiles: a step's three sub-tiles a body against one key tile
    "walk_n3": (1536, 1536, 256, 256, 16, 16, "walk", 3),
    "walk_n2": (1280, 1280, 128, 128, 16, 16, "walk", 2),
    # the cells' form: four sub-tiles a body against a tile of twice their
    # rows, the diagonal in sub-tiles of 128 x 128 (all three classes)
    "walk_n4_sub_tiles": (1024, 1024, 128, 256, 16, 16, "walk", 4),
    "walk_value_width": (1024, 1024, 128, 256, 24, 16, "walk", 4),
    # the end-aligned diagonal: query i sees keys 0..512 + i
    "walk_lk_longer": (512, 1024, 128, 128, 16, 16, "walk", 4),
    # a ragged length masks every tile it visits
    "walk_ragged": (1000, 1000, 128, 128, 16, 16, "walk", 4),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SCHEDULED))
def test_tile_schedule_matches_reference(case, dtype, monkeypatch):
    """Forward and all three gradients of a causal call against the dense
    float32 oracle, through the real custom_vjp route in interpret mode, on
    the spelled-out path and on the walk (`SCHEDULED`); and the trace-time
    record: which schedule, how many tiles a body, and the sub-tiles by
    class as the positions give them."""
    monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    lq, lk, block_q, block_k, d, dv, schedule, per_body = SCHEDULED[case]
    q, k, v, g = (_rand((1, 2, length, width), 70 + i).astype(dtype)
                  for i, (length, width) in enumerate(
                      [(lq, d), (lk, d), (lk, dv), (lq, dv)]))
    scale = 0.25

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, scale, block_q, block_k)

    def dense(q, k, v):
        return _attention_reference(q, k, v, True, scale)

    wide = [x.astype(jnp.float32) for x in (q, k, v)]
    schedules = _fresh_schedules()
    got = [flash(q, k, v), *jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)]
    want = [dense(*wide), *jax.grad(loss(dense), argnums=(0, 1, 2))(*wide)]
    tol_l2, tol_max = SCHEDULE_TOL[dtype]
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.dtype(dtype) and a.shape == b.shape, name
        rel_l2, rel_max = _rel(a, b)
        assert rel_l2 <= tol_l2 and rel_max <= tol_max, (
            f"{name} {dtype}: rel_l2 {rel_l2:.3g} (<= {tol_l2}), "
            f"rel_max {rel_max:.3g} (<= {tol_max})")
    ragged = lq % block_q != 0 or lk % block_k != 0
    for record in schedules:
        assert record["operand_dtype"] == dtype
        assert record["shape"] == (1, 2, lq, lk, d)
        assert record["value_width"] == dv
        assert (record["schedule"], record["tiles_per_body"]) \
            == (schedule, per_body), record
        assert record["resident_rows"] >= (
            lq if record["kernel"] == "dkv" else lk), record
        assert (record["skipped"], record["plain"], record["masked"]) \
            == _classes_by_position(lq, lk, *record["sub_blocks"], ragged), \
            record
    assert {r["kernel"] for r in schedules} == {"forward", "dq", "dkv"}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_tile_schedule_record_at_the_cell_shape(dtype, monkeypatch):
    """The trace-time record of `gpt2-small-fit`'s call, (8, 12, 1024, 64)
    causal: tiles skipped in all three kernels, masks on the diagonal's
    tiles only, operands in the inputs' dtype.  Traced, not run."""
    monkeypatch.setenv("ZOO_FLASH_FORCE_PALLAS", "1")
    q = jax.ShapeDtypeStruct((8, 12, 1024, 64), jnp.dtype(dtype))
    schedules = _fresh_schedules()
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2)),
        q, q, q)
    records = {r["kernel"]: r for r in schedules}
    assert set(records) == {"forward", "dq", "dkv"}
    for record in records.values():
        assert record["shape"] == (8, 12, 1024, 1024, 64)
        assert record["operand_dtype"] == dtype
        bq, bk = record["blocks"]
        assert bq == bk and 1024 % bq == 0
        diagonal = 1024 // bq
        assert record["masked"] == diagonal
        assert record["skipped"] == diagonal * (diagonal - 1) // 2 > 0
        assert record["plain"] == record["skipped"]
        # one grid step owns the sequence and spells its visits out: the
        # walk (PR 36) is not this cell's path
        assert record["schedule"] == "unrolled"
        assert record["tiles_per_body"] == record["plain"] + record["masked"]
        assert record["resident_rows"] == 1024
        assert record["sub_blocks"] == record["blocks"]


#: what the three long cells call: (shape of q and k, width of v)
LONG_CELLS = {
    # `kanana-2-30b-a3b-fit`, and `kimi-linear-48b-a3b-fit`'s one MLA layer
    "kanana": ((2, 32, 4096, 192), 128),
    "ouro": ((2, 16, 4096, 128), 128),
}


@pytest.mark.parametrize("cell", sorted(LONG_CELLS))
def test_tile_schedule_record_at_the_long_cells_shapes(cell, monkeypatch):
    """The trace-time records of the 4,096-token cells' calls, causal bf16:
    all three kernels walk, two or more independent sub-tiles a loop body,
    with the whole other side of a head resident; the forward and dk/dv
    cut the diagonal in 256 x 256 sub-tiles and compute 136 of them for the
    128 the causal half counts; dq keeps two sub-tiles of 512 rows and the
    diagonal at its tile, 36 of 512 x 512 for 32 (four of 256 timed
    4.74 ms against 4.18 at kanana's shape on a v5e: PERF.md section 6,
    PR 36).  Traced, not run."""
    monkeypatch.setenv("ZOO_FLASH_FORCE_PALLAS", "1")
    shape, value_width = LONG_CELLS[cell]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (value_width,), jnp.bfloat16)
    schedules = _fresh_schedules()
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2)),
        q, q, v)
    records = {r["kernel"]: r for r in schedules}
    assert set(records) == {"forward", "dq", "dkv"}
    for kernel, record in records.items():
        assert record["shape"] == shape[:3] + (4096, shape[3])
        assert record["value_width"] == value_width
        assert record["schedule"] == "walk"
        assert record["tiles_per_body"] >= 2
        assert record["resident_rows"] == 4096
        sub_q, sub_k = record["sub_blocks"]
        counted = (4096 // sub_q) * (4096 // sub_k) / 2  # the causal half
        visited = record["plain"] + record["masked"]
        assert record["skipped"] + visited == 2 * counted
        assert (record["skipped"], record["plain"], record["masked"]) \
            == _classes_by_position(4096, 4096, sub_q, sub_k, False)
        if kernel == "dq":
            assert (sub_q, sub_k) == (512, 512) and visited == 36
        else:
            assert (sub_q, sub_k) == (256, 256) and visited == 136
            assert visited <= 1.07 * counted
    assert records["forward"]["blocks"] == (256, 1024)
    assert records["dq"]["blocks"] == (512, 1024)
    assert records["dkv"]["blocks"] == (1024, 256)
    assert [records[k]["tiles_per_body"] for k in ("forward", "dq", "dkv")] \
        == [4, 2, 4]


def test_tile_schedule_masks_every_tile_of_a_call_that_needs_it(monkeypatch):
    """Bias, segment ids, dropout or a ragged edge: no plain tile."""
    monkeypatch.setenv("ZOO_FLASH_FORCE_PALLAS", "1")
    q = jax.ShapeDtypeStruct((2, 2, 1024, 64), jnp.bfloat16)
    ragged = jax.ShapeDtypeStruct((2, 2, 1000, 64), jnp.bfloat16)
    segs = jax.ShapeDtypeStruct((2, 1024), jnp.int32)
    bias = jax.ShapeDtypeStruct((2, 1, 1, 1024), jnp.float32)
    calls = {
        "dropout": lambda: jax.eval_shape(
            lambda q: flash_attention(q, q, q, causal=True, dropout_p=0.1,
                                      dropout_seed=3), q),
        "segments": lambda: jax.eval_shape(
            lambda q, s: flash_attention(q, q, q, causal=True,
                                         q_segment_ids=s,
                                         kv_segment_ids=s), q, segs),
        "bias": lambda: jax.eval_shape(
            lambda q, b: flash_attention(q, q, q, causal=True, bias=b),
            q, bias),
        "ragged": lambda: jax.eval_shape(
            lambda q: flash_attention(q, q, q, causal=True), ragged),
    }
    for name, call in calls.items():
        schedules = _fresh_schedules()
        call()
        (record,) = schedules
        assert record["plain"] == 0 and record["masked"] > 0, (name, record)
        assert record["skipped"] > 0, (name, record)


@pytest.mark.parametrize("lq,lk,bq,bk", [
    (1024, 1024, 256, 256), (1024, 1024, 512, 256), (1024, 1024, 128, 512),
    (600, 700, 128, 256), (700, 600, 256, 128), (256, 1024, 128, 128),
    # on the walk (PR 36): the cells' tiles, a sub-tile smaller than the
    # walked tile on either side, both signs of the offset, ragged edges
    (4096, 4096, 256, 1024), (4096, 4096, 512, 1024), (4096, 4096, 1024, 256),
    (2048, 2048, 128, 512), (1536, 1536, 256, 256), (2048, 3072, 256, 512),
    (3072, 2048, 256, 256), (1000, 1000, 128, 128), (1100, 1500, 128, 256),
])
@pytest.mark.parametrize("all_masked", [False, True])
def test_tile_ranges_agree_with_the_positions(lq, lk, bq, bk, all_masked):
    """`_k_tile_range` (forward, dq) and `_q_tile_range` (dk/dv) put every
    tile in the class that its positions give it, end-aligned diagonal
    and ragged edges included; and where a kernel walks (`_plan`), the
    tiles and the diagonal band's sub-tiles that it issues (`_walk_visits`)
    cover every live position exactly once, none of them wholly dead, the
    plain ones wholly live."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _k_tile_range,
        _plan,
        _q_tile_range,
        _walk_visits,
    )

    ragged = lq % bq != 0 or lk % bk != 0
    for kernel in ("forward", "dq", "dkv"):
        plan = _plan(kernel, bq, bk, lq, lk, 64, causal=True, streamed=False)
        if not plan.walk or (ragged and not all_masked):
            continue
        edge = max(lq, lk) + 4096   # room for a ragged group's overhang
        seen = np.zeros((edge, edge), np.int8)
        alive = (np.arange(edge)[:, None] + (lk - lq)
                 >= np.arange(edge)[None, :])
        visits = list(_walk_visits(plan, lq, lk, True, all_masked))
        for q0, rows_q, k0, rows_k, masked in visits:
            at = (slice(q0, q0 + rows_q), slice(k0, k0 + rows_k))
            seen[at] += 1
            assert alive[at].any(), (kernel, q0, k0, "a dead sub-tile")
            assert masked or alive[at].all(), (kernel, q0, k0, "not plain")
            assert masked == (all_masked or not alive[at].all())
        assert seen.max() == 1, (kernel, "a position visited twice")
        assert seen[:lq, :lk][alive[:lq, :lk]].all(), (
            kernel, "a live position not visited")
        assert len(visits) > 0

    offset = lk - lq
    n_q, n_k = -(-lq // bq), -(-lk // bk)
    # over the tiles' whole extent: a ragged edge is not the diagonal's
    # business (a call that has one masks every tile it visits)
    live = (np.arange(n_q * bq)[:, None] + offset
            >= np.arange(n_k * bk)[None, :])
    want = {}
    for i in range(n_q):
        for j in range(n_k):
            tile = live[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            want[i, j] = ("skipped" if not tile.any() else "masked"
                          if all_masked or not tile.all() else "plain")

    def name(j, plain, need):
        return "plain" if j < plain else "masked" if j < need else "skipped"

    by_q = {(i, j): name(j, *_k_tile_range(i * bq, bq, bk, offset, n_k,
                                           True, all_masked))
            for i in range(n_q) for j in range(n_k)}
    by_k = {}
    for j in range(n_k):
        first, plain = _q_tile_range(j * bk, bq, bk, offset, n_q, True,
                                     all_masked)
        for i in range(n_q):
            by_k[i, j] = ("skipped" if i < first else "masked"
                          if i < plain else "plain")
    assert by_q == want
    assert by_k == want


def test_mosaic_tpu_lowering_gpt2_cell():
    """`gpt2-small-fit`'s own call, (8, 12, 1024, 64) causal bf16,
    forward and backward, cross-lowered for the TPU backend."""
    import os

    q = jnp.zeros((8, 12, 1024, 64), jnp.bfloat16)
    os.environ["ZOO_FLASH_FORCE_PALLAS"] = "1"
    try:
        with _mosaic_module_spy():
            jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True).astype(jnp.float32)),
                argnums=(0, 1, 2))).trace(q, q, q).lower(
                    lowering_platforms=("tpu",))
    finally:
        os.environ.pop("ZOO_FLASH_FORCE_PALLAS", None)


# ---------------------------------------------------------------------------
# A value width of its own (latent attention: q and k at 192, v at 128)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route", ["fallback", "interpret"])
@pytest.mark.parametrize("causal", [True, False])
def test_value_width_unlike_query_width(route, causal, monkeypatch):
    """q and k 24 wide, v and the output 16 wide, through the custom_vjp:
    the forward and all three gradients against the dense reference, by
    the blockwise fallback and by the three kernels in interpret mode; the
    default scale is 1/sqrt of the query's width."""
    if route == "interpret":
        monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    q, k = _rand((1, 2, 512, 24), 80), _rand((1, 2, 512, 24), 81)
    v, g = _rand((1, 2, 512, 16), 82), _rand((1, 2, 512, 16), 83)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * g)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, None, 128, 128)

    def dense(q, k, v):
        return _attention_reference(q, k, v, causal, 1.0 / np.sqrt(24))

    schedules = _fresh_schedules()
    got = [flash(q, k, v), *jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)]
    want = [dense(q, k, v), *jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)]
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)
    assert got[0].shape == (1, 2, 512, 16) and got[3].shape == v.shape
    if route == "interpret":
        assert {r["kernel"] for r in schedules} == {"forward", "dq", "dkv"}
        for record in schedules:
            assert record["shape"] == (1, 2, 512, 512, 24)
            assert record["value_width"] == 16


def test_value_columns_are_independent_to_the_bit(monkeypatch):
    """The value width only says how many columns P multiplies: the kernels'
    output and dq, dk at equal widths are, bit for bit, what the two halves
    of v give side by side (the softmax never sees v), and the record of an
    equal-width call is the one it always was, with the width beside it."""
    monkeypatch.setenv("ZOO_FLASH_INTERPRET", "1")
    q, k, v, g = (_rand((1, 2, 512, 16), 90 + i) for i in range(4))

    def run(v, g):
        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, None, 128, 128) * g)
        return (flash_attention(q, k, v, True, None, 128, 128),
                *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    schedules = _fresh_schedules()
    whole = run(v, g)
    equal = [dict(r) for r in schedules]
    left, right = run(v[..., :8], g[..., :8]), run(v[..., 8:], g[..., 8:])
    np.testing.assert_array_equal(
        whole[0], jnp.concatenate([left[0], right[0]], axis=-1))
    np.testing.assert_array_equal(
        whole[3], jnp.concatenate([left[3], right[3]], axis=-1))
    # dq and dk sum the halves' shares: equal to rounding, not to the bit
    for i in (1, 2):
        np.testing.assert_allclose(whole[i], left[i] + right[i],
                                   rtol=1e-5, atol=1e-6)
    for record in equal:
        assert record["shape"] == (1, 2, 512, 512, 16)
        assert record["value_width"] == 16
        assert record["blocks"] == (128, 128)
        assert (record["skipped"], record["plain"], record["masked"]) \
            == (6, 6, 4)
