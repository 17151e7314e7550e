"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on one TPU chip, in one process, through the
entry points a user calls:

1. device   ``init_zoo_context()`` finds a TPU;
2. train    ResNet-50 at ImageNet shape, per-chip batch 256, ``fit`` on
            uint8 data normalised on the device, then ``evaluate`` and
            ``predict``;
3. serve    the same model after 320 more steps (BatchNorm settles),
            saved, loaded by ``ClusterServing`` and asked for 32 images
            through the in-memory broker: every answer held to
            ``model.predict``, and shown to tell the records apart;
4. transformer  a GPT-shaped stack for 4 ``fit`` steps through the Pallas
            flash-attention kernel;
5. kernels  each Pallas kernel once at a real width against its module's
            ``jnp`` reference, compiled, on the chip.

``--chips 4`` runs another path and no phase above: the same ResNet-50
``fit`` on a four-chip data-parallel mesh against a one-chip mesh, then
two ``plan="fsdp"`` steps that must leave each chip at most 0.3 of the
parameter and optimizer bytes it holds under dp.

Every phase prints one JSON line; a phase that raises ends the run with
its traceback and a non-zero exit.  Without a TPU the script exits
non-zero before any phase runs.  The last line of a run that passed is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Numbers printed here are a smoke run's, not a benchmark's: few steps, one
reading each.

Phase bodies take their sizes as arguments; ``tests/test_chip_smoke.py``
drives them at toy size on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import logging
import math
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Tolerances of the comparisons below, by what is compared.
#: a served answer against ``model.predict`` in the server's precision:
#: the server runs the float32 the model was saved in, so the reference is
#: ``predict`` under a float32 context.  Same weights, same arithmetic, two
#: programs.  Probabilities, absolute; and the root mean square difference
#: of log-probabilities over the classes, which also holds an answer that
#: spreads its probability thin
SERVE_PROB_ATOL = 2e-2
SERVE_LOGPROB_RMS = 5e-2
#: four-chip against one-chip loss, relative, step by step (bf16 compute,
#: another reduction order)
MESH_LOSS_RTOL = 2e-2
#: kernel against reference, as (relative L2 error, largest error over the
#: reference's largest magnitude).  bf16: inputs and the kernel's matmul
#: operands carry 8 bits of mantissa.  f32 through the MXU: the kernel's
#: f32 dot runs as bf16 passes.  f32 elementwise: order of operations and
#: the transcendental units only.
KERNEL_TOL = {
    "bf16": (2e-2, 4e-2),
    "f32_matmul": (1e-2, 2e-2),
    "f32": (1e-4, 1e-3),
}


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def _check(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def step_losses(log_dir: str, app_name: str) -> list[float]:
    """Every step's loss that ``fit`` logged under
    ``set_tensorboard(log_dir, app_name)``, in step order."""
    from analytics_zoo_tpu.tensorboard.record import (
        decode_event_scalars,
        read_records,
    )

    train_dir = os.path.join(log_dir, app_name, "train")
    by_step = {}  # an epoch's last step is logged again by the next epoch
    for fname in sorted(os.listdir(train_dir)):
        with open(os.path.join(train_dir, fname), "rb") as fh:
            for rec in read_records(fh):
                for _wall, step, tag, value in decode_event_scalars(rec):
                    if tag == "Loss":
                        by_step[step] = value
    return [by_step[step] for step in sorted(by_step)]


def compile_report() -> dict:
    """Per label: compile seconds and persistent-cache hits/misses, from
    the compile plane's own counters."""
    from analytics_zoo_tpu.metrics import snapshot

    fields = {"zoo_compile_seconds": ("seconds", "sum"),
              "zoo_compile_cache_hits_total": ("hits", "value"),
              "zoo_compile_cache_misses_total": ("misses", "value")}
    out: dict = {}
    for s in snapshot()["samples"]:
        if s["name"] in fields:
            field, key = fields[s["name"]]
            value = float(s[key])
            out.setdefault(s["labels"]["label"], {})[field] = \
                round(value, 2) if field == "seconds" else int(value)
    return out


def peak_bytes() -> list:
    """``peak_bytes_in_use`` of each device (None where the backend
    reports no memory statistics, as the CPU does)."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def synthetic_images(seed: int, n: int, image_size: int, classes: int):
    """uint8 images of 4 x 4 colour patches and labels, made from ``seed``.
    Patches, not per-pixel noise: noise averages to the same features for
    every image, and a net then answers them all alike."""
    rng = np.random.default_rng(seed)
    patches = rng.integers(0, 256, size=(n, 4, 4, 3), dtype=np.uint8)
    side = image_size // 4
    x = np.repeat(np.repeat(patches, side, axis=1), side, axis=2)
    y = rng.integers(0, classes, size=(n,)).astype(np.int32)
    return x, y


def normalized(x_uint8):
    """Host twin of the on-device ``_normalize`` of the ResNet example."""
    from examples.resnet.train_imagenet import _MEAN, _STD

    return (x_uint8.astype(np.float32) - _MEAN) / _STD


@contextlib.contextmanager
def count_compiles():
    """XLA compilations inside the ``with`` block (the example's
    ``_CompileCounter`` on ``jax_log_compiles``)."""
    import jax

    from examples.resnet.train_imagenet import _CompileCounter

    counter = _CompileCounter()
    jax.config.update("jax_log_compiles", True)
    logging.getLogger("jax").addHandler(counter)
    try:
        yield counter
    finally:
        jax.config.update("jax_log_compiles", False)
        logging.getLogger("jax").removeHandler(counter)


def image_set(image_size, classes, n, seed):
    """``n`` uint8 images made from ``seed`` as a ``FeatureSet`` that is
    normalised on the device, as the ResNet example feeds ``fit``."""
    from analytics_zoo_tpu.feature.dataset import FeatureSet
    from examples.resnet.train_imagenet import _normalize

    return FeatureSet.of(*synthetic_images(
        seed, n, image_size, classes)).transform_on_device(_normalize)


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    import jax

    from analytics_zoo_tpu import init_zoo_context

    ctx = init_zoo_context("chip smoke", seed=0)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if ctx.platform != "tpu" or dev.platform != "tpu":
        sys.exit(f"chip_smoke.py: needs a TPU, JAX found {device}")
    if device["count"] < chips:
        sys.exit(f"chip_smoke.py: --chips {chips} on {device}")
    say("device", **device, mesh=dict(ctx.mesh.shape))
    return device


# ---------------------------------------------------------------------------
# phase 2: train
# ---------------------------------------------------------------------------


def phase_train(model, out_dir, image_size=224, classes=1000,
                per_chip_batch=256, warmup_batches=2, steps=6,
                first_loss_range=(5.5, 9.0), n_eval=256):
    """``fit`` / ``evaluate`` / ``predict`` of a compiled image classifier.
    Returns the report; the model keeps its trained weights."""
    from analytics_zoo_tpu.common.engine import get_zoo_context

    ctx = get_zoo_context()
    batch = per_chip_batch * max(ctx.data_parallel_size, 1)
    log_dir = os.path.join(out_dir, "tb")
    model.set_tensorboard(log_dir, "train")
    # the example's run: a warm-up fit that compiles, then the timed one
    warm = image_set(image_size, classes, batch * warmup_batches, seed=1)
    train = image_set(image_size, classes, batch * steps, seed=0)
    t0 = time.perf_counter()
    model.fit(warm, batch_size=batch, nb_epoch=1)
    warm_s = time.perf_counter() - t0
    with count_compiles() as counter:
        t_timed = time.perf_counter()
        model.fit(train, batch_size=batch, nb_epoch=1)
        timed_s = time.perf_counter() - t_timed
    compiles = counter.count
    losses = step_losses(log_dir, "train")
    _check(len(losses) == warmup_batches + steps,
           f"{len(losses)} losses logged for "
           f"{warmup_batches + steps} steps")
    _check(all(math.isfinite(v) for v in losses),
           f"non-finite loss: {losses}")
    lo, hi = first_loss_range
    _check(lo <= losses[0] <= hi,
           f"first loss {losses[0]} outside [{lo}, {hi}]")
    _check(compiles == 0,
           f"{compiles} XLA compilations during the timed steps")

    x_u8, y = synthetic_images(7, n_eval, image_size, classes)
    x = normalized(x_u8)
    scores = model.evaluate(x, y, batch_size=min(batch, n_eval))
    _check(all(math.isfinite(float(v)) for v in scores.values()),
           f"evaluate returned {scores}")
    probs = model.predict(x, batch_size=min(batch, n_eval))
    _check(probs.shape == (n_eval, classes),
           f"predict shape {probs.shape}")
    _check(bool(np.isfinite(probs).all()), "predict returned non-finite")
    _check(bool(np.allclose(probs.sum(axis=-1), 1.0, atol=1e-2)),
           "predicted rows are not probabilities")
    report = {
        "batch": batch, "image_size": image_size, "steps_timed": steps,
        "losses": [round(v, 4) for v in losses],
        "compiles_timed": compiles,
        "warmup_fit_seconds": round(warm_s, 2),
        "timed_fit_seconds": round(timed_s, 3),
        "evaluate": {k: round(float(v), 4) for k, v in scores.items()},
        "predict_shape": list(probs.shape),
        "seconds": round(time.perf_counter() - t0, 2),
        "peak_bytes_in_use": peak_bytes(),
    }
    say("train", **report)
    return report


# ---------------------------------------------------------------------------
# phase 3: serve
# ---------------------------------------------------------------------------


def _logprob_rms(answers, reference):
    """``[i, j]``: answer i against the reference's row for record j, as the
    root mean square difference of log-probabilities over the classes."""
    log_a = np.log(np.maximum(answers, 1e-30))
    log_r = np.log(np.maximum(reference, 1e-30))
    return np.sqrt(((log_a[:, None, :] - log_r[None, :, :]) ** 2).mean(-1))


def phase_serve(model, out_dir, image_size=224, classes=1000,
                per_chip_batch=256, settle_batches=8, settle_epochs=40,
                n_records=32, batch_size=8, timeout_s=600.0):
    """Save ``model``, serve it from ``ClusterServing`` on a thread over
    the in-memory broker, and hold every answer to ``model.predict``.

    First ``fit`` goes on for ``settle_batches * settle_epochs`` steps: a
    BatchNorm's running statistics (momentum 0.99) are 8% of the way from
    their initial 0 and 1 after phase 2's eight steps, the inference
    forward that uses them saturates, and a net that gives every image
    class k at probability 1.0 agrees with itself however its records are
    routed.

    Two references.  ``predict`` under a float32 context computes what the
    server computes, and every answer is held to it closely.  ``predict``
    as the context runs it — bf16 on a TPU — differs from both by what
    bf16 costs through the net's depth, which is reported, and must still
    leave every answer nearest its own record."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.common.engine import get_zoo_context
    from analytics_zoo_tpu.serving import (
        ClusterServing,
        ClusterServingHelper,
        InMemoryBroker,
        InputQueue,
        OutputQueue,
    )

    t0 = time.perf_counter()
    batch = per_chip_batch * max(get_zoo_context().data_parallel_size, 1)
    model.fit(image_set(image_size, classes, batch * settle_batches, seed=3),
              batch_size=batch, nb_epoch=settle_epochs)
    settle_s = time.perf_counter() - t0

    shape = (image_size, image_size, 3)
    broker = InMemoryBroker()
    # the saved model (100 MB for ResNet-50) is needed until the server
    # has loaded it, and would crowd what a chip call may bring back
    with tempfile.TemporaryDirectory(dir=out_dir) as tmp:
        model_path = os.path.join(tmp, "model.zoo")
        model.save(model_path, over_write=True)
        helper = ClusterServingHelper(
            model_path=model_path, batch_size=batch_size, data_shape=shape,
            top_n=classes,
            log_dir=os.path.join(out_dir, "serving_logs"))
        serving = ClusterServing(helper, broker=broker)
    # the power-of-two pad buckets a batch of up to batch_size can need
    buckets = [1 << i for i in range((batch_size - 1).bit_length() + 1)]
    t_warm = time.perf_counter()
    serving.model.warmup([shape], batch_sizes=buckets)
    warm_s = time.perf_counter() - t_warm

    x_u8, _ = synthetic_images(11, n_records, image_size, 2)
    x = normalized(x_u8)
    as_configured = model.predict(x, batch_size=batch_size)
    ctx = get_zoo_context()
    init_zoo_context(ctx.config, compute_dtype="float32")
    expected = model.predict(x, batch_size=batch_size)
    init_zoo_context(ctx.config)

    served = []
    server = threading.Thread(
        target=lambda: served.append(
            serving.run(max_records=n_records, idle_timeout=timeout_s)),
        daemon=True)
    server.start()
    inq, outq = InputQueue(broker=broker), OutputQueue(broker=broker)
    t_req = time.perf_counter()
    for i in range(n_records):
        inq.enqueue_image(f"img-{i}", x[i])
    server.join(timeout=timeout_s)
    if server.is_alive():
        serving.stop()
        raise AssertionError(
            f"serving loop still running after {timeout_s}s")
    req_s = time.perf_counter() - t_req
    _check(served == [n_records],
           f"server counted {served}, sent {n_records}")
    results = outq.dequeue()
    _check(sorted(results) == sorted(f"img-{i}" for i in range(n_records)),
           f"answers for {sorted(results)}")
    _check(outq.dequeue() == {}, "a record was answered twice")

    # the whole distribution comes back (top_n=classes), best class first
    served_probs = np.zeros_like(expected)
    for i in range(n_records):
        top = results[f"img-{i}"]
        _check(sorted(cls for cls, _ in top) == list(range(classes)),
               f"img-{i}: {len(top)} classes answered")
        for cls, prob in top:
            served_probs[i, cls] = prob
    np.savez(os.path.join(out_dir, "serve_probs.npz"), served=served_probs,
             predict_float32=expected, predict_as_configured=as_configured)
    top1 = np.array([results[f"img-{i}"][0][0] for i in range(n_records)])
    rows = np.arange(n_records)
    others = ~np.eye(n_records, dtype=bool)
    dist = _logprob_rms(served_probs, expected)
    own, other_min = np.diag(dist), float(dist[others].min())
    worst_abs = float(np.abs(served_probs - expected).max())
    _check(worst_abs <= SERVE_PROB_ATOL,
           f"served probability off by {worst_abs} > {SERVE_PROB_ATOL}")
    _check(float(own.max()) <= SERVE_LOGPROB_RMS,
           f"img-{int(own.argmax())}: log-probabilities off by "
           f"{own.max():.3g} rms > {SERVE_LOGPROB_RMS}")
    # the same top-1, unless predict's own leaders lie closer together
    # than the log-probabilities are held to
    gap = np.log(expected.max(-1) / expected[rows, top1])
    _check(float(gap.max()) <= SERVE_LOGPROB_RMS,
           f"img-{int(gap.argmax())}: served top-1 "
           f"{int(top1[gap.argmax()])}, predict says "
           f"{int(expected[gap.argmax()].argmax())}")
    # What that agreement can show.  Answers that were all alike would agree
    # with predict however the records were routed, reordered, or cut from
    # the wrong rows of a padded batch: held to any record's reference but
    # its own, an answer has to fail.
    distinct = len(set(top1.tolist()))
    _check(distinct > 1, "every record was given the same top-1 class")
    _check(other_min > SERVE_LOGPROB_RMS,
           f"an answer lies {other_min:.3g} rms from another record's "
           f"reference: held to {SERVE_LOGPROB_RMS}, it would pass for it")
    dist_c = _logprob_rms(served_probs, as_configured)
    strays = [(int(i), int(j)) for i, j in zip(rows, dist_c.argmin(1))
              if i != j]
    _check(not strays, f"answers nearest another record's predict: {strays}")
    report = {
        "settle_steps": settle_batches * settle_epochs,
        "settle_seconds": round(settle_s, 2),
        "records": n_records, "answered": len(results),
        "distinct_top1": distinct,
        "mean_top1_prob": round(float(expected.max(axis=-1).mean()), 4),
        "against_float32_predict": {
            "top1_equal": int((top1 == expected.argmax(-1)).sum()),
            "max_abs_prob_diff": float(f"{worst_abs:.3g}"),
            "logprob_rms_own_max": float(f"{own.max():.3g}"),
            "logprob_rms_other_min": float(f"{other_min:.3g}")},
        "against_predict_as_configured": {
            "compute_dtype": str(np.dtype(ctx.compute_dtype or np.float32)),
            "top1_equal": int((top1 == as_configured.argmax(-1)).sum()),
            "max_abs_prob_diff": float(
                f"{np.abs(served_probs - as_configured).max():.3g}"),
            "logprob_rms_own_mean": float(f"{np.diag(dist_c).mean():.3g}"),
            "logprob_rms_own_max": float(f"{np.diag(dist_c).max():.3g}"),
            "logprob_rms_other_min": float(f"{dist_c[others].min():.3g}")},
        "warmup_buckets": buckets, "warmup_seconds": round(warm_s, 2),
        "request_seconds": round(req_s, 3),
        "seconds": round(time.perf_counter() - t0, 2),
    }
    say("serve", **report)
    return report


# ---------------------------------------------------------------------------
# phase 4: transformer through the flash kernel
# ---------------------------------------------------------------------------


def phase_transformer(out_dir, blocks=12, hidden=768, heads=12, seq=1024,
                      vocab=32768, batch=8, steps=4, dropout=0.1,
                      expect_pallas=True):
    """The GPT-shaped stack of ``tools/transformer_bench.py`` for ``steps``
    ``fit`` steps.  ``expect_pallas``: the attention must have gone through
    the Pallas kernel and never through its reference (false only where
    there is no TPU: the CPU rehearsal)."""
    from analytics_zoo_tpu.ops.pallas import flash_attention as flash
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense,
        TransformerLayer,
    )

    t0 = time.perf_counter()
    before = dict(flash.invocation_counts)
    tokens = Input(shape=(seq,), name="tokens")
    h = TransformerLayer(vocab=vocab, seq_len=seq, n_block=blocks,
                         n_head=heads, hidden_size=hidden,
                         embedding_drop=0.0, attn_drop=dropout,
                         hidden_drop=dropout)(tokens)
    logits = Dense(vocab, name="lm_head")(h)
    net = Model(tokens, logits, name="gpt_smoke")
    net.compile(optimizer="adam",
                loss="sparse_categorical_crossentropy_from_logits")
    rng = np.random.default_rng(0)
    x = rng.integers(0, vocab, size=(batch * steps, seq)).astype(np.int32)
    y = rng.integers(0, vocab, size=(batch * steps, seq)).astype(np.int32)
    log_dir = os.path.join(out_dir, "tb")
    net.set_tensorboard(log_dir, "transformer")
    net.fit(x, y, batch_size=batch, nb_epoch=1)
    losses = step_losses(log_dir, "transformer")
    _check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    _check(all(math.isfinite(v) for v in losses),
           f"non-finite loss: {losses}")
    counts = {k: flash.invocation_counts[k] - before[k] for k in before}
    if expect_pallas:
        _check(counts["pallas"] > 0 and counts["fallback"] == 0,
               f"flash attention routing {counts}")
    report = {
        "blocks": blocks, "hidden": hidden, "seq": seq, "batch": batch,
        "losses": [round(v, 4) for v in losses],
        "flash_attention": counts,
        "seconds": round(time.perf_counter() - t0, 2),
        "peak_bytes_in_use": peak_bytes(),
    }
    say("transformer", **report)
    return report


# ---------------------------------------------------------------------------
# phase 5: kernels against their references
# ---------------------------------------------------------------------------


def _errors(got, ref) -> tuple[float, float]:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    diff = got - ref
    scale = max(float(np.abs(ref).max()), 1e-30)
    rel_l2 = float(np.linalg.norm(diff) / max(np.linalg.norm(ref), 1e-30))
    return rel_l2, float(np.abs(diff).max()) / scale


def _hold(name, got, ref, tol_key, errors):
    _check(bool(np.isfinite(np.asarray(got, np.float32)).all()),
           f"{name}: non-finite output")
    rel_l2, rel_max = _errors(got, ref)
    errors[name] = {"rel_l2": rel_l2, "rel_max": rel_max, "tol": tol_key}
    tol_l2, tol_max = KERNEL_TOL[tol_key]
    _check(rel_l2 <= tol_l2 and rel_max <= tol_max,
           f"{name}: rel_l2 {rel_l2:.3g} (<= {tol_l2}), "
           f"rel_max {rel_max:.3g} (<= {tol_max})")


def phase_kernels(flash_shape=(4, 12, 2048, 64), xent_shape=(4096, 50304),
                  int8_shape=(256, 2048, 1000),
                  adam_shapes=((3, 3, 512, 512), (2048, 1000), (1000,)),
                  adam_updates=3, expect_pallas=True):
    """One compiled call of each Pallas kernel against its module's own
    ``jnp`` reference, computed in float32 at the highest matmul precision
    (the TPU's default runs an f32 dot as bf16 passes) on the same
    device."""
    import jax
    import jax.numpy as jnp
    import optax

    from analytics_zoo_tpu.ops.pallas import (
        flash_attention as flash,
        fused_adam as adam,
        fused_softmax_xent as xent,
        int8_matmul as i8,
        kernel_invocation_counts,
    )

    t0 = time.perf_counter()
    before = kernel_invocation_counts()
    errors: dict = {}
    key = jax.random.PRNGKey(0)

    # flash attention, causal, bf16: forward and gradient
    b, h, l, d = flash_shape
    kq, kk, kv, kg, key = jax.random.split(key, 5)
    q, k, v, g = (jax.random.normal(kk_, flash_shape, jnp.bfloat16)
                  for kk_ in (kq, kk, kv, kg))
    scale = 1.0 / math.sqrt(d)

    def flash_loss(q, k, v):
        out = flash.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    def ref_attention(q, k, v):
        return flash._attention_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), True, scale)

    def ref_loss(q, k, v):
        return jnp.sum(ref_attention(q, k, v) * g.astype(jnp.float32))

    out = jax.jit(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True))(q, k, v)
    grads = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref_out = jax.jit(ref_attention)(q, k, v)
        ref_grads = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    _check(out.shape == flash_shape and out.dtype == jnp.bfloat16,
           f"flash out {out.shape} {out.dtype}")
    _hold("flash_fwd", out, ref_out, "bf16", errors)
    for name, got, ref in zip(("dq", "dk", "dv"), grads, ref_grads):
        _hold(f"flash_{name}", got, ref, "bf16", errors)
    del out, grads, ref_out, ref_grads, q, k, v, g

    # softmax cross-entropy, f32: forward and gradient
    kx, kl, kw, key = jax.random.split(key, 4)
    logits = jax.random.normal(kx, xent_shape, jnp.float32) * 2.0
    labels = jax.random.randint(kl, xent_shape[:1], 0, xent_shape[1])
    weights = jax.random.uniform(kw, xent_shape[:1], jnp.float32)
    loss, dlogits = jax.jit(jax.value_and_grad(
        lambda x: jnp.sum(xent.softmax_xent(x, labels) * weights)))(logits)
    per_example = jax.jit(xent.softmax_xent)(logits, labels)
    with jax.default_matmul_precision("highest"):
        ref_per_example, ref_lse = jax.jit(xent._reference_fwd)(
            logits, labels)
        ref_dlogits = jax.jit(xent._reference_bwd)(
            logits, labels, ref_lse, weights)
    _hold("xent_fwd", per_example, ref_per_example, "f32", errors)
    _hold("xent_grad", dlogits, ref_dlogits, "f32", errors)
    _check(math.isfinite(float(loss)), "xent loss not finite")
    del logits, dlogits, ref_dlogits

    # int8 weight matmul, f32 activations
    m, kdim, n = int8_shape
    ka, kb, ks, key = jax.random.split(key, 4)
    a = jax.random.normal(ka, (m, kdim), jnp.float32)
    w = jax.random.randint(kb, (kdim, n), -127, 128).astype(jnp.int8)
    s = jax.random.uniform(ks, (n,), jnp.float32, 0.5, 1.5) / 127.0
    got = jax.jit(i8.int8_matmul)(a, w, s)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(i8._reference)(a, w, s)
    _check(got.shape == (m, n), f"int8_matmul out {got.shape}")
    _hold("int8_matmul", got, ref, "f32_matmul", errors)

    # fused Adam against optax.adam, a few updates over a small tree
    keys = jax.random.split(key, len(adam_shapes) + 1)
    params = {f"leaf{i}": jax.random.normal(kk_, shape, jnp.float32)
              for i, (kk_, shape) in enumerate(zip(keys, adam_shapes))}
    fused, plain = adam.fused_adam(1e-3), optax.adam(1e-3)

    def run(opt):
        @jax.jit
        def update(p, st, i):
            grads = jax.tree_util.tree_map(
                lambda a: jnp.sin(a * (i + 1.0)), p)
            upd, st = opt.update(grads, st, p)
            return optax.apply_updates(p, upd), st

        p, st = params, opt.init(params)
        for i in range(adam_updates):
            p, st = update(p, st, jnp.float32(i))
        return p, st

    (p_fused, st_fused), (p_plain, st_plain) = run(fused), run(plain)
    for name in params:
        _hold(f"adam_{name}", p_fused[name], p_plain[name], "f32", errors)
        _hold(f"adam_nu_{name}", st_fused[0].nu[name], st_plain[0].nu[name],
              "f32", errors)

    after = kernel_invocation_counts()
    counts = {name: {k: after[name][k] - before.get(name, {}).get(k, 0)
                     for k in after[name]} for name in after}
    if expect_pallas:
        for name in ("flash_attention", "fused_softmax_xent",
                     "int8_matmul", "fused_adam"):
            _check(counts[name]["pallas"] > 0
                   and counts[name]["fallback"] == 0,
                   f"{name} routing {counts[name]}")
    report = {
        "errors": {k: {"rel_l2": float(f"{v['rel_l2']:.3g}"),
                       "rel_max": float(f"{v['rel_max']:.3g}"),
                       "tol": v["tol"]} for k, v in errors.items()},
        "tolerances": KERNEL_TOL, "invocations": counts,
        "seconds": round(time.perf_counter() - t0, 2),
        "peak_bytes_in_use": peak_bytes(),
    }
    say("kernels", **report)
    return report


# ---------------------------------------------------------------------------
# --chips 4: data-parallel fit across the host's chips
# ---------------------------------------------------------------------------


def _devices_holding(tree) -> list:
    import jax

    ids = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        ids |= {s.device.id for s in leaf.addressable_shards}
    return sorted(ids)


def phase_mesh(make_model, out_dir, chips=4, image_size=224, classes=1000,
               global_batch=256, steps=4, fsdp_steps=2,
               fsdp_bytes_ratio=0.3):
    """The same ``fit`` on a ``{"data": chips}`` mesh and on a one-device
    mesh, same seed and data, then ``fsdp_steps`` more steps under
    ``plan="fsdp"`` on the wide mesh."""
    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.parallel import per_chip_bytes

    t0 = time.perf_counter()
    all_ids = sorted(d.id for d in jax.devices()[:chips])

    ctx = init_zoo_context(seed=0, mesh_shape={"data": chips})
    _check(ctx.data_parallel_size == chips, f"mesh {dict(ctx.mesh.shape)}")
    log_dir = os.path.join(out_dir, "tb")
    wide = make_model()
    wide.set_tensorboard(log_dir, f"dp{chips}")
    wide.fit(image_set(image_size, classes, global_batch * steps, seed=0),
             batch_size=global_batch, nb_epoch=1)
    wide_losses = step_losses(log_dir, f"dp{chips}")
    probe = ctx.shard_batch(
        {"x": np.zeros((global_batch, image_size, image_size, 3),
                       np.uint8)})
    batch_ids = _devices_holding(probe)
    _check(batch_ids == all_ids, f"batch lives on devices {batch_ids}")
    shard_rows = {s.data.shape[0]
                  for s in probe["x"].addressable_shards}
    _check(shard_rows == {global_batch // chips},
           f"batch shards hold {shard_rows} rows")
    est = wide._estimator
    dp_bytes = per_chip_bytes((wide.params, est._opt_state))
    dp_ids = _devices_holding(wide.params)

    wide.fit(image_set(image_size, classes, global_batch * fsdp_steps,
                       seed=5),
             batch_size=global_batch, nb_epoch=1, plan="fsdp")
    # same event file: the estimator opened it at the first fit
    fsdp_losses = step_losses(log_dir, f"dp{chips}")[steps:]
    _check(len(fsdp_losses) == fsdp_steps,
           f"{len(fsdp_losses)} losses for {fsdp_steps} fsdp steps")
    est = wide._estimator
    fsdp_bytes = per_chip_bytes((wide.params, est._opt_state))
    param_ids = _devices_holding(wide.params)
    opt_ids = _devices_holding(est._opt_state)
    _check(param_ids == all_ids and opt_ids == all_ids,
           f"fsdp parameters on {param_ids}, optimizer state on {opt_ids}")
    _check(fsdp_bytes <= fsdp_bytes_ratio * dp_bytes,
           f"fsdp holds {fsdp_bytes} B a chip, dp {dp_bytes} B: "
           f"more than {fsdp_bytes_ratio} of it")
    _check(all(math.isfinite(v) for v in fsdp_losses),
           f"non-finite fsdp loss {fsdp_losses}")
    wide_peak = peak_bytes()
    del wide, est, probe
    gc.collect()

    # One device of the same process.  An explicit {"data": 1} alone folds
    # the idle devices back into the data axis (engine._infer_mesh_shape),
    # so the one-chip mesh is named as a single one-device slice.
    ctx = init_zoo_context(seed=0, mesh_shape={"data": 1},
                           dcn_shape={"data": 1},
                           slice_groups=[jax.devices()[:1]])
    _check(ctx.num_devices == 1, f"mesh {dict(ctx.mesh.shape)}")
    one = make_model()
    one.set_tensorboard(log_dir, "dp1")
    one.fit(image_set(image_size, classes, global_batch * steps, seed=0),
            batch_size=global_batch, nb_epoch=1)
    one_losses = step_losses(log_dir, "dp1")
    _check(_devices_holding(one.params) == all_ids[:1],
           "one-chip run is not on one chip")

    _check(len(wide_losses) == len(one_losses) == steps,
           f"{len(wide_losses)} and {len(one_losses)} losses")
    rel = [abs(a - b) / max(abs(b), 1e-30)
           for a, b in zip(wide_losses, one_losses)]
    _check(all(math.isfinite(v) for v in wide_losses + one_losses),
           f"non-finite loss {wide_losses} {one_losses}")
    _check(max(rel) <= MESH_LOSS_RTOL,
           f"losses differ by {max(rel):.3g}: {wide_losses} vs {one_losses}")
    report = {
        "chips": chips, "global_batch": global_batch,
        f"losses_{chips}_chips": [round(v, 4) for v in wide_losses],
        "losses_1_chip": [round(v, 4) for v in one_losses],
        "max_rel_diff": float(f"{max(rel):.3g}"),
        "batch_on_devices": batch_ids, "dp_params_on_devices": dp_ids,
        "fsdp_losses": [round(v, 4) for v in fsdp_losses],
        "fsdp_params_on_devices": param_ids,
        "fsdp_opt_state_on_devices": opt_ids,
        "per_chip_bytes": {"dp": dp_bytes, "fsdp": fsdp_bytes,
                           "ratio": round(fsdp_bytes / dp_bytes, 4),
                           "ratio_at_most": fsdp_bytes_ratio},
        "peak_bytes_in_use_after_wide": wide_peak,
        "peak_bytes_in_use": peak_bytes(),
        "seconds": round(time.perf_counter() - t0, 2),
    }
    say("mesh", **report)
    return report


# ---------------------------------------------------------------------------


def resnet50():
    from analytics_zoo_tpu.models.resnet import ResNet

    model = ResNet.image_net(50, classes=1000, input_shape=(224, 224, 3))
    model.compile(
        optimizer=ResNet.imagenet_optimizer(batch_size=256,
                                            steps_per_epoch=5004),
        loss="sparse_categorical_crossentropy")
    return model


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the four-chip fit and the one-chip fit "
                         "it is compared with")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    from analytics_zoo_tpu import native
    from analytics_zoo_tpu.common.compile_cache import (
        maybe_enable_persistent_cache,
    )

    device = phase_device(args.chips)
    # this run's own event files and model only: an earlier run's losses
    # would be read back with this one's
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    cache = maybe_enable_persistent_cache(os.path.join(REPO, ".jax_cache"))
    # built here, from the committed source, for this host's CPU
    built = native.build_native(force=True) is not None
    say("setup", compile_cache=cache,
        compile_cache_from_env=bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        native_library="built from zoonative.cpp" if built
        else "not built: numpy path in use")

    if args.chips == 4:
        phase_mesh(resnet50, OUT_DIR, chips=4)
    else:
        model = resnet50()
        phase_train(model, OUT_DIR)
        phase_serve(model, OUT_DIR)
        del model
        gc.collect()
        phase_transformer(OUT_DIR)
        gc.collect()
        phase_kernels()
    say("compiles", by_label=compile_report(),
        total_seconds=round(time.perf_counter() - t_start, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
