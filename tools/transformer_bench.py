"""Transformer (GPT-style) training benchmark — the MXU-bound counterpart
to the memory-bound ResNet-50 headline.

Drives the framework's own API end-to-end: keras Model(tokens ->
TransformerLayer -> Dense(vocab)) compiled through the estimator's jitted
SPMD train step, causal attention routed through the Pallas flash kernel
(ops/attention.py auto-routing).  A timed window ends in ``float(loss)``:
fetching a scalar that depends on the whole step returns only when it
has run.

FLOP accounting (conservative, executed-work):
  fwd = 2 * matmul_params * tokens + n_block * 4 * B * S^2 * D * 0.5
  (causal attention counted at half — the flash kernel skips fully-masked
  blocks); train = 3 * fwd.

Usage: python tools/transformer_bench.py [--seq 1024] [--batch 8]
       [--blocks 12] [--hidden 768] [--steps 10] [--out FILE.json]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def run(seq=1024, batch=8, blocks=12, hidden=768, heads=12, vocab=32768,
        steps=10, remat=False, attn_drop=0.1, hidden_drop=0.1):
    """``remat``: False, True/"full", "dots" or "attn" — the
    TransformerLayer checkpoint policy (sweep on hardware; the best
    memory/recompute point is device-dependent)."""
    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense,
        TransformerLayer,
    )

    ctx = init_zoo_context("transformer bench", seed=0)
    tokens = Input(shape=(seq,), name="tokens")
    h = TransformerLayer(vocab=vocab, seq_len=seq, n_block=blocks,
                         n_head=heads, hidden_size=hidden,
                         embedding_drop=0.0, attn_drop=attn_drop,
                         hidden_drop=hidden_drop, remat=remat)(tokens)
    logits = Dense(vocab, name="lm_head")(h)
    net = Model(tokens, logits, name="gpt_bench")
    net.compile(optimizer="adam",
                loss="sparse_categorical_crossentropy_from_logits")
    est = net._make_estimator()
    params, state = est.model.build_params()
    opt_state = est.optimizer.init(params)
    params, opt_state, state = jax.device_put(
        (params, opt_state, state), ctx.replicated())
    step_fn = est._build_train_step()

    rng = np.random.default_rng(0)
    x = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
    y = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
    sharded = ctx.shard_batch({"x": x, "y": y})
    seed_arr = np.asarray(0, np.int32)

    t0 = time.perf_counter()
    params, opt_state, state, loss = step_fn(
        params, opt_state, state, seed_arr, np.asarray(0, np.int32),
        sharded)
    float(loss)  # fetch-forced
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(steps):
        params, opt_state, state, loss = step_fn(
            params, opt_state, state, seed_arr,
            np.asarray(i + 1, np.int32), sharded)
    float(loss)
    dt = (time.perf_counter() - t0) / steps

    # matmul params: everything except embeddings (lookups, ~0 flops)
    n_all = sum(int(np.prod(p.shape))
                for p in jax.tree_util.tree_leaves(params))
    n_embed = vocab * hidden + seq * hidden
    n_matmul = n_all - n_embed
    tokens_per_step = batch * seq
    fwd = 2 * n_matmul * tokens_per_step \
        + blocks * 4 * batch * seq * seq * hidden * 0.5
    # per-chip accounting: the global batch is sharded over the data axis
    dp = max(ctx.data_parallel_size, 1)
    train_flops = 3 * fwd / dp
    d = jax.devices()[0]
    out = {
        "metric": "gpt_transformer_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_step / dt / dp, 1),
        "unit": "tokens/sec/chip",
        "step_ms": round(dt * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "params_m": round(n_all / 1e6, 1),
        "batch": batch, "seq": seq, "blocks": blocks, "hidden": hidden,
        "remat": remat, "attn_drop": attn_drop,
        "hidden_drop": hidden_drop, "loss": round(float(loss), 3),
        "platform": d.platform, "device_kind": d.device_kind,
        "train_flops_per_step": train_flops,
    }
    if d.platform == "tpu":
        from analytics_zoo_tpu.analysis.costmodel import resolve_peaks

        peak = resolve_peaks(d.platform, d.device_kind).flops
        out["mfu"] = round(train_flops / dt / peak, 4)
        out["peak_flops_assumed"] = peak
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--blocks", type=int, default=12)
    p.add_argument("--hidden", type=int, default=768)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--remat", nargs="?", const="full", default=False,
                   choices=["full", "dots", "attn"],
                   help="jax.checkpoint per transformer block; optional "
                        "policy argument (default 'full')")
    p.add_argument("--attn-drop", type=float, default=0.1)
    p.add_argument("--hidden-drop", type=float, default=0.1)
    p.add_argument("--out", default=None)
    a = p.parse_args()
    r = run(seq=a.seq, batch=a.batch, blocks=a.blocks, hidden=a.hidden,
            heads=a.heads, steps=a.steps, remat=a.remat,
            attn_drop=a.attn_drop, hidden_drop=a.hidden_drop)
    print(json.dumps(r))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(r, f, indent=1)


if __name__ == "__main__":
    main()
