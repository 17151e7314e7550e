"""The Pallas kernels of the main path, compiled at real widths by the
installed TPU compiler for a v5e chip that is described, not attached
(section 2 of the on-chip-measurement guide).  Nothing runs: a compile that
passes says the chip's compiler accepts the kernel at this shape, nothing
about results or times.  ``chip_smoke.py`` runs the same kernels against
their references on the chip.

Keep these in ONE file: only one process may hold the TPU library, and the
worker that gets this file is the one that loads it."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def real_kernels(monkeypatch):
    """Route to the real (non-interpret) kernels off-TPU, and keep the
    persistent cache out of it: an entry compiled for a described chip
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("ZOO_FLASH_FORCE_PALLAS", "1")
    monkeypatch.setenv("ZOO_KERNEL_FORCE_PALLAS", "1")
    monkeypatch.delenv("ZOO_FLASH_INTERPRET", raising=False)
    monkeypatch.delenv("ZOO_KERNEL_INTERPRET", raising=False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _flash(shape, grad, padding_bias=False, segments=False,
           value_width=None, **kw):
    """(fn, arg specs) for flash attention on (B, H, L, D) bf16, v
    ``value_width`` wide where given."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import flash_attention

    b, _h, l, _d = shape
    args = [(shape, jnp.bfloat16)] * 2 \
        + [(shape[:3] + (value_width or shape[3],), jnp.bfloat16)]
    if padding_bias:
        args.append(((b, 1, 1, l), jnp.float32))  # the BERT mask
    if segments:
        args += [((b, l), jnp.int32)] * 2

    def fwd(q, k, v, *rest):
        rest = list(rest)
        extra = {"bias": rest.pop(0)} if padding_bias else {}
        if segments:
            extra["q_segment_ids"], extra["kv_segment_ids"] = rest
        return flash_attention(q, k, v, **kw, **extra)

    if not grad:
        return fwd, args

    def loss(q, k, v, *rest):
        return jnp.sum(fwd(q, k, v, *rest).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)), args


def _routed(tokens, width, router, held, expert, top_k):
    """(fn, arg specs) for the gradient of the routed feed-forward without
    dropped tokens: the sort and the grouped products of top_k x tokens
    assignments over the experts held."""
    from analytics_zoo_tpu.ops.moe import held_experts_ffn

    def loss(u, route, bias, gate, up, down):
        y, _ = held_experts_ffn(u, route, bias, gate, up, down, first_held=0,
                                top_k=top_k, routed_scale=2.448)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    return jax.grad(loss, argnums=(0, 1, 3, 4, 5)), [
        ((tokens, width), jnp.bfloat16), ((width, router), jnp.bfloat16),
        ((router,), jnp.bfloat16), ((held, width, expert), jnp.bfloat16),
        ((held, width, expert), jnp.bfloat16),
        ((held, expert, width), jnp.bfloat16)]


def _kda(shape):
    """(fn, arg specs) for the gradient of the chunked gated delta rule
    with a decay a key channel on (B, H, L, D) bf16 heads: the two Pallas
    kernels of the walk over a sequence's chunks, a chunk's local
    arithmetic and its ``jax.vjp`` inside them."""
    from analytics_zoo_tpu.ops.linear_attention import chunked_kda

    def loss(q, k, v, g, beta):
        o, _ = chunked_kda(q, k, v, g, beta, scale=shape[3] ** -0.5)
        return jnp.sum(jnp.square(o.astype(jnp.float32)))

    return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), \
        [(shape, jnp.bfloat16)] * 3 + [(shape, jnp.float32),
                                       (shape[:3], jnp.float32)]


def _xent(shape):
    from analytics_zoo_tpu.ops.pallas.fused_softmax_xent import softmax_xent

    def fn(logits, labels):
        return jax.value_and_grad(
            lambda x: jnp.sum(softmax_xent(x, labels)))(logits)

    return fn, [(shape, jnp.float32), (shape[:1], jnp.int32)]


def _int8(m, k, n):
    from analytics_zoo_tpu.ops.pallas.int8_matmul import int8_matmul

    return int8_matmul, [((m, k), jnp.float32), ((k, n), jnp.int8),
                         ((n,), jnp.float32)]


def _adam(shapes):
    import optax

    from analytics_zoo_tpu.ops.pallas.fused_adam import fused_adam

    opt = fused_adam(1e-3)

    def fn(*leaves):
        params = dict(enumerate(leaves))
        upd, _state = opt.update(params, opt.init(params), params)
        return optax.apply_updates(params, upd)

    return fn, [(s, jnp.float32) for s in shapes]


CASES = {
    "flash_fwd": lambda: _flash((8, 12, 2048, 64), False, causal=True),
    "flash_fwd_bwd_causal":
        lambda: _flash((4, 12, 2048, 64), True, causal=True),
    # `gpt2-small-fit`'s own call
    "flash_fwd_bwd_gpt2_cell":
        lambda: _flash((8, 12, 1024, 64), True, causal=True),
    # `ouro-2.6b-fit`'s own call: heads of 128, four 1024-row chunks
    "flash_fwd_bwd_ouro_cell":
        lambda: _flash((2, 16, 4096, 128), True, causal=True),
    # `kanana-2-30b-a3b-fit`'s own calls: latent attention's q and k at
    # 192, v and the output at 128; 49,152 assignments over 16 of 128
    # experts of width 768
    "flash_fwd_bwd_kanana_cell":
        lambda: _flash((2, 32, 4096, 192), True, causal=True,
                       value_width=128),
    "routed_experts_kanana_cell":
        lambda: _routed(8192, 2048, 128, 16, 768, 6),
    # the shape `_resolve_blocks` sizes its VMEM caps against
    "flash_fwd_bwd_vmem_caps":
        lambda: _flash((1, 2, 4096, 128), True, causal=True,
                       dropout_p=0.1, dropout_seed=7),
    "flash_fwd_bwd_dropout":
        lambda: _flash((4, 32, 1024, 80), True, causal=True,
                       dropout_p=0.1, dropout_seed=7),
    "flash_fwd_bwd_padding_bias":
        lambda: _flash((4, 20, 1024, 128), True, padding_bias=True),
    "flash_fwd_bwd_segments_unaligned":
        lambda: _flash((2, 12, 1000, 64), True, causal=True,
                       segments=True),
    # the walk (PR 36) off the cells' shapes: a ragged length, whose last
    # group overhangs the sequence; 16,384 tokens, whose K and V stay
    # resident in two chunks; queries longer than keys, whose first groups
    # see no key.  Each puts the diagonal's tiles under a `cond`.
    "flash_fwd_bwd_walk_ragged":
        lambda: _flash((2, 4, 3000, 128), True, causal=True),
    "flash_fwd_bwd_walk_chunked":
        lambda: _flash((1, 8, 16384, 128), True, causal=True),
    "flash_fwd_bwd_walk_not_causal":
        lambda: _flash((2, 4, 4096, 128), True),
    # `kimi-linear-48b-a3b-fit`'s own call: 32 KDA heads of 128
    "kda_walk_fwd_bwd_kimi_cell": lambda: _kda((2, 32, 4096, 128)),
    "softmax_xent_fwd_bwd": lambda: _xent((4096, 50304)),
    "int8_matmul": lambda: _int8(256, 2048, 1000),
    "fused_adam":
        lambda: _adam(((3, 3, 512, 512), (2048, 1000), (1000,))),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, real_kernels):
    fn, arg_specs = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"{case}: no Mosaic kernel in:\n" \
        + text[:2000]
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 16 << 30  # the chip's memory


def _ouro_application_gradient(one_chip, **policy):
    """The compiled gradient of one checkpointed layer application of
    `ouro-2.6b-fit` (2 x 4,096 tokens, 16 heads of 128, bf16), as text."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import LoopedDecoder

    layer = LoopedDecoder(vocab=49152, n_block=1, n_head=16,
                          hidden_size=2048, intermediate_size=5632, **policy)

    def loss(blocks, h):
        # the cotangent reads the output, as the next layer's would, so
        # the application's forward pass is no dead code
        out = layer._run_blocks(blocks, h, None, True, None)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    blocks = jax.eval_shape(
        lambda: [layer._block_params(jax.random.PRNGKey(0))])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        (blocks, jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16)))
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *args).compile().as_text()


@pytest.mark.parametrize("policy, forward_calls",
                         [({}, 1), ({"remat": "full"}, 2)],
                         ids=["the_layers_own", "full"])
def test_a_layer_application_runs_the_forward_kernel_once(
        policy, forward_calls, one_chip, real_kernels):
    """The gradient of one checkpointed layer application of
    `ouro-2.6b-fit` (2 x 4,096 tokens, 16 heads of 128, bf16): under the
    looped decoder's own policy the compiled program calls the Mosaic
    forward kernel, the one that returns three arrays, once; under
    ``"full"`` the backward pass calls it again.  dq and dk/dv once each.
    The kernels are told apart as the benchmark's readers tell them."""
    from benchmark import xplane

    text = _ouro_application_gradient(one_chip, **policy)
    marked = f"/{xplane.KERNEL_TARGET}/"
    returned = sorted(
        int(name.rpartition(marked)[2])
        for name in map(xplane.short_name, text.splitlines())
        if marked in name)
    assert returned == [1, 2] + [3] * forward_calls, returned


def test_a_layer_application_writes_no_float32_copy_of_q_or_k(
        one_chip, real_kernels):
    """Rotary positions and the head split are one fusion a tensor and
    direction (``self_attention.rotary`` on ``project_heads``' output):
    the float32 arithmetic stays inside it, so no instruction outside a
    fusion of the compiled gradient of one `ouro-2.6b-fit` layer
    application writes a float32 array of q's size (2 x 4,096 x 16 x 128
    values, in any shape).  PR 38's program wrote such copies."""
    text = _ouro_application_gradient(one_chip)
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    size = 2 * 4096 * 16 * 128
    written = []
    for name, block in _computations(text).items():
        if name in fused:
            continue
        for line in block.splitlines()[1:]:
            shape = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) [a-z][\w-]*\(",
                             line)
            if shape and any(
                    math.prod(map(int, dims.split(","))) == size
                    for dims in re.findall(r"f32\[([\d,]+)\]",
                                           shape.group(1))):
                written.append(line.strip()[:160])
    assert not written, written


def _computations(text):
    """An HLO module's computations by name."""
    return {block.split(" (", 1)[0].rpartition("%")[2]: block
            for block in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)}


def _convolutions(computations, name, what=" convolution("):
    """Convolutions (or ``what``) in the HLO computation ``name`` and in
    what it calls."""
    text = computations[name]
    return text.count(what) + sum(
        _convolutions(computations, called, what)
        for called in re.findall(r"(?:calls|body)=%([\w.\-]+)", text))


def test_the_looped_head_makes_its_gradient_in_its_forward_loops(
        one_chip, real_kernels):
    """The gradient of `ouro-2.6b-fit`'s four-pass tail (head, exit gate
    and loss after each pass: (2, 4096, 2048) bf16 states, a (2048, 49152)
    head, blocks of 2,048 tokens): four ``while`` loops over the token
    blocks, each with the block's logits and both products of its gradient,
    and no product over the vocabulary beside them (with the logits made
    again in the backward pass it was eight loops and sixteen products).
    The temporaries are the float32 sum of the head's gradient, 403 MB, and
    what a pass keeps; the bf16 logits of a block live in the output's
    buffer: 0.47 GB here."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import LoopedDecoder

    passes, vocab = 4, 49152
    layer = LoopedDecoder(vocab=vocab, n_block=1, n_head=16,
                          hidden_size=2048, intermediate_size=5632,
                          passes=passes)

    def tail(params, states, targets):
        return layer._exit_loss(params, states, targets)["loop_exit_cost"]

    def described(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"head_kernel": described((2048, vocab)),
              "exit_kernel": described((2048, 1)),
              "exit_bias": described((1,))}
    compiled = jax.jit(jax.grad(tail, argnums=(0, 1))).lower(
        params, [described((2, 4096, 2048))] * passes,
        described((2, 4096), jnp.int32)).compile()
    text = compiled.as_text()
    computations = _computations(text)
    loops = re.findall(r" while\(.*body=%([\w.\-]+)", text)
    assert len(loops) == passes, loops
    assert [_convolutions(computations, body) for body in loops] \
        == [3] * passes
    assert text.count(" convolution(") == 3 * passes
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


#: Mosaic calls of the one walk in a routed layer's gradient: 4 in the
#: forward rule's loop, 9 in the backward rule's (the parent's whole-buffer
#: walk held 12, PR 33's two walks 26)
ROUTED_LAYER_KERNELS = 13


def _kanana_layer():
    from analytics_zoo_tpu.pipeline.api.keras.layers import LatentMoEDecoder

    return LatentMoEDecoder(
        vocab=16032, n_block=2, n_head=32, hidden_size=2048,
        intermediate_size=6144, kv_latent_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, routed_experts=128, experts_held=16,
        experts_per_token=6, expert_size=768, shared_experts=2,
        routed_scale=2.448)


def test_a_routed_latent_layer_application_by_its_kernels(one_chip,
                                                          real_kernels):
    """The gradient of one checkpointed routed layer application of
    `kanana-2-30b-a3b-fit` (2 x 4,096 tokens, 32 heads of 192/128, 16 of
    128 experts held, bf16) for a described v5e: each flash kernel once (the
    forward kernel is not run again for what the policy kept), and the one
    walk of the held rows (``ops/moe.py``: windows of 8,192 of the 49,152
    sorted rows) in its two loops and in no ``conditional``: thirteen
    Mosaic calls.  The forward rule's loop holds the three grouped products
    and the sum of a token's rows; the backward rule's the gate and up
    products made again (the policy keeps the feed-forward's output, not
    its hidden rows), the cotangent through the down product, the three
    experts' gradients (the transposed kernel, adding to the running sums
    in place), the rows' two and the sum of a token's rows.  PR 33's two
    whole walks held 26.  Told apart by what the benchmark's readers read:
    the instruction's name and the arrays returned."""
    from benchmark import xplane

    layer = _kanana_layer()

    def loss(bp, h):
        from analytics_zoo_tpu.parallel.plan import apply_remat

        body = apply_remat(layer._block_forward_aux, layer.remat,
                           static_argnums=(3,))
        out = body(bp, h, None, True, None)[0]
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    bp = jax.eval_shape(
        lambda: layer._block_params(jax.random.PRNGKey(0), 1))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        (bp, jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16)))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile()
    text = compiled.as_text()
    marked = f"/{xplane.KERNEL_TARGET}/"
    kernels = [name for name in map(xplane.short_name, text.splitlines())
               if marked in name]
    flash = sorted(int(k.rpartition(marked)[2]) for k in kernels
                   if "flash" in k)
    assert flash == [1, 2, 3], kernels
    grouped = [k for k in kernels if "flash" not in k]
    assert len(grouped) == ROUTED_LAYER_KERNELS and all(
        k.endswith(marked + "1") for k in grouped), kernels
    assert text.count(" conditional(") == 0
    # (XLA's own loops, a sort's or a gather's, hold no kernel)
    loops = [_convolutions(_computations(text), body, "tpu_custom_call")
             for body in re.findall(r" while\(.*body=%([\w.\-]+)", text)]
    assert sorted(n for n in loops if n) == [4, 9], loops
    # the attention's arrays and, of the routed path, a window's 8,192 rows
    # with their hidden activations and the running sums: 1,102,237,184
    # bytes, where the walk of all 49,152 rows wanted 1,236,356,096 (commit
    # a76fa78, PR 32's tree, compiled for this described chip by PR 34)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


def test_the_kanana_step_compiles_whole_for_v5e(one_chip, real_kernels):
    """`kanana-2-30b-a3b-fit`'s train step as ``fit`` builds it (1 dense +
    4 routed layers at the published widths, 2 x 4,096 tokens, bf16 compute
    over float32 state, Adam) for a described v5e: 15 flash calls and, in
    each of four routed layers, the thirteen kernels of the one walk, in
    no ``conditional``; 6.9 GB of arguments (parameters and both moments, 12
    bytes each of 576 M) and under 4 GB of temporaries, so that the
    harness's check steps' second float32 copy of the parameters (2.3 GB)
    fits beside the loaded step under the allocator's 16.9 GB."""
    import numpy as np

    from analytics_zoo_tpu import init_zoo_context
    from benchmark import data
    from benchmark.manifest import Manifest

    manifest = Manifest()
    cell = manifest.cell("kanana-2-30b-a3b-fit")
    configuration = manifest.configuration(cell["config"])
    sizes = configuration.sizes
    batch = manifest.traffic(cell["traffic"])["batch"]
    init_zoo_context("kanana step compile",
                     compute_dtype=sizes["compute_dtype"])
    model_py = configuration.module("model")
    model = model_py.build(sizes)
    est = model._make_estimator()
    x, y = data.rows(0, 0, batch, sizes)
    step = est._build_train_step(
        getattr(model_py.feature_set(x, y, sizes), "device_transform", None),
        1, est._resolved_plan())
    reference = configuration.module("reference")
    params = jax.eval_shape(lambda k: reference.init_params(k, sizes),
                            jax.random.PRNGKey(0))
    _, state = model.build_params()
    opt_state = jax.eval_shape(est.optimizer.init, params)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, opt_state, state, np.int32(0), np.int32(0),
         {"x": x, "y": y}))
    compiled = step._jitted.lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 15 + 4 * ROUTED_LAYER_KERNELS
    assert text.count(" conditional(") == 0
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(6.91e9, rel=0.01)
    assert memory.temp_size_in_bytes < 4.0e9
    assert model_py.routing_fault("cpu") is None


def _kimi_step_text(one_chip) -> str:
    """`kimi-linear-48b-a3b-fit`'s train step as ``fit`` builds it, at a
    size that compiles in seconds (a KDA layer and a routed latent one,
    heads of 128 and 192/128, 2 x 512 tokens), compiled for a v5e."""
    import numpy as np

    from analytics_zoo_tpu import init_zoo_context
    from benchmark import data
    from benchmark.manifest import Manifest

    configuration = Manifest().configuration("kimi-linear-48b-a3b", {
        "hidden_size": 256, "num_attention_heads": 2, "kv_lora_rank": 128,
        "num_hidden_layers": 2, "linear_attn_config": {
            "full_attn_layers": [2], "head_dim": 128, "kda_layers": [1],
            "num_heads": 2, "short_conv_kernel_size": 4},
        "num_experts": 2, "router_width": 8, "moe_intermediate_size": 256,
        "num_experts_per_token": 2, "intermediate_size": 512,
        "vocab_size": 1024, "n_positions": 512})
    sizes = configuration.sizes
    init_zoo_context("kimi step scopes", compute_dtype=sizes["compute_dtype"])
    model_py = configuration.module("model")
    model = model_py.build(sizes)
    est = model._make_estimator()
    x, y = data.rows(0, 0, 2, sizes)
    step = est._build_train_step(
        getattr(model_py.feature_set(x, y, sizes), "device_transform", None),
        1, est._resolved_plan())
    params = jax.eval_shape(
        lambda k: configuration.module("reference").init_params(k, sizes),
        jax.random.PRNGKey(0))
    _, state = model.build_params()
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, jax.eval_shape(est.optimizer.init, params), state,
         np.int32(0), np.int32(0), {"x": x, "y": y}))
    return step._jitted.lower(*args).compile().as_text()


def test_the_step_s_parts_rename_only_pallas_calls_on_the_chip(
        one_chip, real_kernels, monkeypatch):
    """The three scopes of ``metrics/tracing.py`` are metadata, and on the
    chip's compiler they also rename the Pallas calls whose ``op_name``
    they split (``jvp_jit__kda_walk_forward__.1`` ->
    ``_kda_walk_forward.1``, the name the backward calls already had): the
    compiled step without metadata and stack frames, with its instructions
    numbered in order of their first appearance, is the step compiled with
    the scopes patched to ``nullcontext``; every name that differs is a
    ``tpu_custom_call``'s or one of its results'; every kernel carries its
    part (PERF.md, PR 38)."""
    import contextlib

    from analytics_zoo_tpu.pipeline.api.keras.layers import self_attention
    from benchmark import xplane

    scoped = _kimi_step_text(one_chip)
    monkeypatch.setattr(self_attention, "named_scope",
                        lambda _name: contextlib.nullcontext())
    plain = _kimi_step_text(one_chip)
    line = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
    metadata = re.compile(r", metadata=\{[^}]*\}")

    def instructions(text):
        return [(name, metadata.sub("", rest))
                for name, rest in line.findall(text)]

    def numbered(rows):
        seen = {}
        for name, _rest in rows:
            seen.setdefault(name, str(len(seen)))
        return [re.sub(r"%([\w.\-]+)", lambda m: "%" + seen.get(
            m.group(1), m.group(1)), rest) for _name, rest in rows]

    a, b = instructions(scoped), instructions(plain)
    assert numbered(a) == numbered(b)
    kernels = {name for name, rest in a if xplane.KERNEL_TARGET in rest}
    renamed = [(x, y) for (x, rx), (y, _ry) in zip(a, b) if x != y]
    assert renamed
    for x, _y in renamed:
        rest = dict(a)[x]
        assert x in kernels or any(
            f"get-tuple-element(%{k})" in rest for k in kernels), x
    parts = {}
    for name, op_name in re.findall(
            r'^\s*(?:ROOT )?%([\w.\-]+) = .*?custom_call_target='
            r'"tpu_custom_call".*?op_name="([^"]*)"', scoped, re.M):
        found = [p for p in ("zoo.mixer", "zoo.ffn", "zoo.head")
                 if p in op_name]
        assert len(found) == 1, (name, op_name)
        parts.setdefault(found[0], set()).add(
            re.sub(r"[^a-z]", "", name.rsplit(".", 1)[0]))
    assert {"kdawalkforward", "flashfwdpallas"} <= parts["zoo.mixer"]
    assert {"gmm", "tgmm"} <= parts["zoo.ffn"]
