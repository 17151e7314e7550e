"""The device's parts of a step (``metrics/tracing.py``: ``zoo.mixer``,
``zoo.ffn``, ``zoo.head``), read where the benchmark reads them: in the
``op_name`` of the train step as ``fit`` compiles it, here on the CPU, for
three toy models (a ``LatentMoEDecoder`` with a KDA, a latent and a routed
layer; a ``LoopedDecoder`` of two passes; a ``TransformerLayer`` under a
``Dense`` head).  Every product, loop and custom call of the blocks carries
one block name, in the forward pass, under ``transpose(`` and under
``rematted_computation``; the head's carry ``zoo.head``; no path holds two;
and the scopes are metadata: with them patched to ``nullcontext`` the
compiled program is the same to the byte without its metadata and its
stack-frame tables, but for the names XLA gives some instructions from
their ``op_name`` (a nested ``jit``'s, such as ``jit_silu_.6``, or a
Pallas call's on the chip: ``jvp_jit__flash_fwd_pallas__.1`` becomes
``_flash_fwd_pallas.1``, PERF.md, PR 38): numbered in order of their first
appearance, the two are the same to the byte, and each name that differs
keeps its function's."""

import contextlib
import re

import numpy as np
import pytest

from analytics_zoo_tpu.metrics.tracing import FFN_SCOPE, HEAD_SCOPE, \
    MIXER_SCOPE
from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention

PARTS = (MIXER_SCOPE, FFN_SCOPE, HEAD_SCOPE)
L, B, V = 32, 8, 80     # the vocabulary is no other size of the toys
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%[\w.\-]+ = (?:\(.*?\)|\S+) ([a-z\-]+)\(.*?'
    r'op_name="([^"]*)"', re.M)
METADATA = re.compile(r", metadata=\{[^}]*\}")
STACK_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
NAME = re.compile(r"%([\w.\-]+)")


def _latent():
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder

    tokens = Input(shape=(L,), name="tokens")
    out = LatentMoEDecoder(
        vocab=V, n_block=2, n_head=2, hidden_size=64, intermediate_size=96,
        attention=["kda", "latent"], kda_heads=2, kda_head_dim=16,
        kda_conv_size=4, kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, routed_experts=8, experts_held=2,
        experts_per_token=2, expert_size=32, shared_experts=1,
        leading_dense=1, rotary_theta=None, name="latent")(tokens)
    return Model(tokens, out), "next_token_cross_entropy", False


def _looped():
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import LoopedDecoder

    tokens = Input(shape=(L,), name="tokens")
    out = LoopedDecoder(vocab=V, n_block=1, n_head=2, hidden_size=64,
                        intermediate_size=96, passes=2, name="looped")(tokens)
    return Model(tokens, out), "looped_exit_cross_entropy", False


def _dense_head():
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense,
        TransformerLayer,
    )

    tokens, positions = Input(shape=(L,)), Input(shape=(L,))
    h = TransformerLayer(vocab=V, seq_len=L, n_block=1, n_head=2,
                         hidden_size=64, embedding_drop=0.0,
                         name="blocks")([tokens, positions])
    out = Dense(V, name="lm_head")(h)
    return Model([tokens, positions], out), \
        "sparse_categorical_crossentropy", True


MODELS = {"latent_moe_decoder": _latent, "looped_decoder": _looped,
          "transformer_under_dense": _dense_head}


def _compiled_step(build) -> str:
    """The text of the train step that one ``fit`` step compiled."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    init_zoo_context("step scopes", seed=3)
    model, loss, positions = build()
    model.compile(optimizer=Adam(lr=1e-3), loss=loss)
    rng = np.random.default_rng(0)
    x = rng.integers(0, V, (B, L)).astype(np.int32)
    y = rng.integers(0, V, (B, L)).astype(np.int32)
    if positions:
        x = [x, np.tile(np.arange(L, dtype=np.int32), (B, 1))]
    model.fit(x, y, batch_size=B, nb_epoch=1)
    texts = [exe.as_text()
             for step in model._estimator._train_step_fns.values()
             for exe in step._exes.values()]
    assert len(texts) == 1
    return texts[0]


@pytest.fixture(scope="module", params=sorted(MODELS))
def steps(request):
    """(model, the step as compiled, the step with the scopes patched to
    ``nullcontext``)."""
    scoped = _compiled_step(MODELS[request.param])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(self_attention, "named_scope",
                   lambda _name: contextlib.nullcontext())
        plain = _compiled_step(MODELS[request.param])
    return request.param, scoped, plain


def _without_metadata(text: str) -> str:
    out, skipping = [], False
    for line in text.splitlines():
        if line in STACK_TABLES:
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        if not skipping:
            out.append(METADATA.sub("", line))
    return "\n".join(out)


def _phase(scope: str) -> str:
    if "rematted_computation" in scope:
        return "made again"
    return "backward" if "transpose(" in scope else "forward"


def test_every_product_loop_and_call_of_the_model_names_its_part(steps):
    model, scoped, _plain = steps
    found, outside = {}, []
    for opcode, scope in INSTRUCTION.findall(scoped):
        names = [p for p in PARTS if p in scope]
        assert len(names) <= 1, scope          # no path holds two
        if opcode not in ("dot", "while", "custom-call"):
            continue
        if names:
            found.setdefault(names[0], set()).add((opcode, _phase(scope)))
        elif "threefry" not in scope:          # the step's own random key
            outside.append((opcode, scope))
    if model == "transformer_under_dense":
        # the Dense head's product and its two gradient products
        assert len(outside) <= 3 and all(
            opcode == "dot" and "/jit(" not in scope
            for opcode, scope in outside), outside
        assert HEAD_SCOPE not in found
    else:
        assert outside == []
        assert ("dot", "forward") in found[HEAD_SCOPE]
    for part in (MIXER_SCOPE, FFN_SCOPE):
        assert {("dot", "forward"), ("dot", "backward")} <= found[part]
    if model != "transformer_under_dense":
        # a decoder's layer application is checkpointed: what it makes
        # again carries its part too
        for part in (MIXER_SCOPE, FFN_SCOPE):
            assert ("dot", "made again") in found[part], found[part]
    if model == "latent_moe_decoder":
        # the routed walk: a loop and the grouped products' calls
        assert {("while", "forward"), ("while", "backward")} \
            <= found[FFN_SCOPE]


def _numbered(text: str) -> str:
    seen: dict = {}
    return NAME.sub(
        lambda m: "%" + seen.setdefault(m.group(1), str(len(seen))), text)


def _stem(name: str) -> str:
    return re.sub(r"[^a-z]", "", name.rsplit(".", 1)[0].lower())


def test_the_scopes_are_metadata_alone(steps):
    _model, scoped, plain = steps
    assert any(p in scoped for p in PARTS)
    assert not any(p in plain for p in PARTS)
    scoped, plain = _without_metadata(scoped), _without_metadata(plain)
    assert _numbered(scoped) == _numbered(plain)
    renamed = {(a, b) for a, b in zip(NAME.findall(scoped),
                                      NAME.findall(plain)) if a != b}
    for a, b in renamed:
        short, long_ = sorted((_stem(a), _stem(b)), key=len)
        assert short and short in long_, (a, b)
