"""The chunked gated delta rule with a decay a key channel
(``ops/linear_attention.py``, Kimi Delta Attention's recurrence) and its
Pallas kernels (``ops/pallas/kda_scan.py``), at toy size on the CPU in
float32: the chunk-parallel form against the recurrence, token by token,
of the ``kimi-linear-48b-a3b`` configuration's plain reference
(``benchmark/configs/kimi-linear-48b-a3b/reference.py``, which imports
nothing of the program), forward and every gradient; and the kernels in
interpret mode against the scans that are their oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import linear_attention as linear
from benchmark.manifest import Manifest

WIDTH = 16
SCALE = WIDTH ** -0.5


@pytest.fixture(scope="module")
def recurrence():
    reference = Manifest().configuration(
        "kimi-linear-48b-a3b").module("reference")
    return lambda *args: reference.delta_rule(*args, SCALE)


def _inputs(length, hard, seed=1, batch=2, heads=2, width=WIDTH):
    """Unit queries and keys, values, log-decays (mild: a token forgets
    e^-0.05 or so; hard: every fourth channel e^-4 to e^-4.5, a chunk of
    16 e^-69), step sizes, and an output cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (batch, heads, length, width)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    g = -jnp.exp(jax.random.normal(ks[3], shape) - 3.0)
    if hard:
        g = jnp.where(jnp.arange(width) % 4 == 0,
                      -(4.0 + 0.5 * jax.random.uniform(ks[3], shape)), g)
    return (unit(jax.random.normal(ks[0], shape)),
            unit(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape), g,
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))), \
        jax.random.normal(ks[5], shape)


#: (sequence length, chunk asked for): on the chunk grid and off it, one
#: sub-block a chunk and several, a chunk longer than the sequence
GRIDS = [(64, 16), (50, 16), (64, 32), (100, 64), (24, 64)]


@pytest.mark.parametrize("length, chunk", GRIDS)
@pytest.mark.parametrize("hard", [False, True], ids=["mild", "hard_decay"])
def test_chunked_form_against_the_recurrence(recurrence, length, chunk,
                                             hard):
    args, weight = _inputs(length, hard)

    @jax.jit
    def both(args):
        # each traced and compiled once, not run op by op
        return recurrence(*args), \
            linear.chunked_kda(*args, scale=SCALE, chunk=chunk), \
            jax.grad(lambda *a: jnp.sum(linear.chunked_kda(
                *a, scale=SCALE, chunk=chunk)[0] * weight),
                argnums=range(5))(*args), \
            jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight),
                     argnums=range(5))(*args)

    want, (got, stats), grads, ref_grads = both(args)
    np.testing.assert_allclose(got, want, atol=2e-6)
    c, sub = linear.chunk_of(length, chunk)
    if hard:
        # what the form's exponentials had to survive: under -60 a chunk
        assert float(stats["chunk_log_decay_min"]) \
            < -60.0 * (min(c, length) // 16)
        assert float(stats["chunk_log_decay_min"]) > -linear.CLAMP * c / sub
        # ... and the form is exact: no sub-block passes the bound
        assert -linear.CLAMP < float(stats["sub_block_log_decay_min"]) \
            < -4.0 * (min(sub, length) - 1)
    assert 0.05 < float(stats["state_rms"]) < 1.0
    for name, got, ref in zip("qkvgb", grads, ref_grads):
        np.testing.assert_allclose(
            got, ref, atol=5e-6 * float(jnp.abs(ref).max()), err_msg=name)
    record = linear.chunk_schedules[-1]
    assert record == {"rule": "kda",
                      "shape": (2, 2, length, WIDTH, WIDTH), "chunk": c,
                      "chunks": -(-length // c), "sub_blocks": c // sub,
                      "kernel": False}


@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_keys_that_resemble_each_other(recurrence, chunk):
    """Tokens that share most of their state have keys that nearly agree
    (cosine 0.8 here, as a decoder's do at seeded weights), step sizes near
    1 and little decay: the triangular system is then dense, and its
    inverse taken by powers loses every digit (the first form of this
    module read 1e21 here in chunks of 64 and NaN in 128).  The block
    substitution is as good as the recurrence."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    shape = (1, 2, 256, 32)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    k = unit(0.5 * jax.random.normal(ks[0], (1, 2, 1, 32))
             + 0.5 * jax.random.normal(ks[1], shape) * 3 / 32 ** 0.5)
    assert float(jnp.mean(jnp.einsum("bhld,bhmd->bhlm", k, k))) > 0.75
    args = (unit(jax.random.normal(ks[2], shape)), k,
            jax.random.normal(ks[3], shape),
            -jnp.exp(jax.random.normal(ks[4], shape) - 5.0),
            jax.nn.sigmoid(jax.random.normal(ks[5], shape[:3]) + 2.0))
    got, stats = linear.chunked_kda(*args, scale=1.0, chunk=chunk)
    np.testing.assert_allclose(got, recurrence(*args) / SCALE, atol=5e-6)
    assert 0.1 < float(stats["state_rms"]) < 1.0


def test_a_decay_past_the_bound_is_held_finite(recurrence):
    """A sub-block that forgets by more than e^-80 is outside the form's
    exact range (the module says so, and the gauge shows it): the column
    factor is held at e^80, nothing overflows, and the rows of sequences
    whose decay is mild are untouched by their neighbour's."""
    (q, k, v, g, beta), _ = _inputs(32, hard=False)
    g = g.at[0].set(-6.0)
    got, stats = linear.chunked_kda(q, k, v, g, beta, scale=SCALE, chunk=16)
    assert float(stats["chunk_log_decay_min"]) == pytest.approx(-96.0)
    # 15 tokens after a sub-block's first row: past the bound, and shown
    assert float(stats["sub_block_log_decay_min"]) == pytest.approx(-90.0)
    assert float(stats["sub_block_log_decay_min"]) < -linear.CLAMP
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got[1], recurrence(q, k, v, g, beta)[1],
                               atol=2e-6)


def _chunks(length=96, chunk=32, heads=4, seed=0, dtype=jnp.float32):
    """The five arrays the rule's walks take, (X, N, C, 128), and dO."""
    (q, k, v, g, beta), do = _inputs(length, False, seed, 1, heads, 128)

    def chunked(a):
        return a.reshape(heads, length // chunk, chunk, 128)

    bf = beta[..., None]
    return tuple(chunked(a).astype(dtype) for a in (q, k, bf * k, bf * v)) \
        + (jnp.cumsum(chunked(g), axis=2),), chunked(do).astype(dtype)


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
    from analytics_zoo_tpu.ops.pallas import kda_scan

    return kda_scan


def test_the_kernels_in_interpret_mode_against_the_scans(interpret):
    chunks, do = _chunks()
    want = linear.walk_forward_scan(16, SCALE, *chunks)
    got = interpret.walk_forward(16, SCALE, *chunks)
    for name, a, b in zip(("o", "states", "final"), got, want):
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)
    # a sequence starts from a zero state, whatever the scratch held
    assert not np.any(got[1][:, 0])
    want = linear.walk_backward_scan(16, SCALE, *chunks, want[1], do)
    got = interpret.walk_backward(16, SCALE, *chunks, got[1], do)
    for name, a, b in zip(("q", "k", "kb", "vb", "gsum"), got, want):
        np.testing.assert_allclose(
            a, b, atol=1e-6 * float(jnp.abs(b).max()), err_msg=name)


def test_a_state_carried_in_bfloat16_is_caught_here(interpret, monkeypatch):
    """The chip's comparison cannot tell a state that is rounded to
    bfloat16 from chunk to chunk from the float32 one (the bfloat16 noise
    of the rest of a step hides it: ``benchmark/limits/
    kimi-linear-48b-a3b-fit.json``); the oracle above can, a hundred times
    over its tolerance."""
    step = linear.walk_step

    def rounded(state, *local):
        out, o = step(state, *local)
        return out.astype(jnp.bfloat16).astype(jnp.float32), o

    chunks, _ = _chunks()
    want = linear.walk_forward_scan(16, SCALE, *chunks)
    monkeypatch.setattr(interpret, "walk_step", rounded)
    interpret._kda_walk_forward.clear_cache()
    try:
        got = interpret.walk_forward(16, SCALE, *chunks)
    finally:
        interpret._kda_walk_forward.clear_cache()
    assert float(jnp.abs(got[0] - want[0]).max()) > 1e-4
    assert float(jnp.abs(got[2] - want[2]).max()) > 1e-4


def test_the_kernels_read_no_chunk_out_of_turn(interpret):
    """The forward walk has to make a chunk's output from the chunks
    before it alone, and the backward walk a chunk's cotangents from the
    chunks after it alone: rows and states that a chunk must not read are
    NaN here, and what it writes is the oracle's to the digit."""
    chunks, do = _chunks()
    clean = interpret.walk_forward(16, SCALE, *chunks)
    nan = jnp.full_like(chunks[0][:, :1], jnp.nan)
    # the last chunk's rows are NaN: chunks 0 and 1, and the state that
    # the last one starts from, are the clean walk's
    poisoned = tuple(jnp.concatenate([a[:, :2], nan.astype(a.dtype)], 1)
                     for a in chunks)
    o, states, final = interpret.walk_forward(16, SCALE, *poisoned)
    np.testing.assert_array_equal(o[:, :2], clean[0][:, :2])
    np.testing.assert_array_equal(states, clean[1])
    assert bool(jnp.all(jnp.isnan(o[:, 2]))) \
        and bool(jnp.all(jnp.isnan(final)))
    # the first chunk's output cotangent and incoming state are NaN:
    # chunks 1 and 2 of every cotangent are the clean walk's
    grads = interpret.walk_backward(16, SCALE, *chunks, clean[1], do)
    got = interpret.walk_backward(
        16, SCALE, *chunks, clean[1].at[:, 0].set(jnp.nan),
        do.at[:, 0].set(jnp.nan))
    for name, a, b in zip(("q", "k", "kb", "vb", "gsum"), got, grads):
        np.testing.assert_array_equal(a[:, 1:], b[:, 1:], err_msg=name)
        assert bool(jnp.all(jnp.isnan(a[:, 0]))), name


def test_bfloat16_kernels_against_the_float32_oracle(interpret):
    """Operands in bfloat16, sums, gates, the inverse and the state in
    float32: each result within bfloat16's noise of the float32 oracle's
    (under 1% of its norm), forward and backward."""
    chunks, do = _chunks(dtype=jnp.bfloat16)

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    want = linear.walk_forward_scan(16, SCALE, *f32(chunks))
    got = interpret.walk_forward(16, SCALE, *chunks)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    want_grads = linear.walk_backward_scan(16, SCALE, *f32(chunks), want[1],
                                           f32(do))
    got_grads = interpret.walk_backward(16, SCALE, *chunks, got[1], do)
    assert [g.dtype for g in got_grads] == [c.dtype for c in chunks]
    for name, a, b in zip(("o", "q", "k", "kb", "vb", "gsum"),
                          (got[0],) + got_grads, (want[0],) + want_grads):
        gap = float(jnp.linalg.norm(f32(a) - b) / jnp.linalg.norm(b))
        assert gap < 0.01, (name, gap)


@pytest.mark.parametrize("policy, forward_walks", [("attn", 1), ("full", 2)])
def test_a_checkpointed_rule_keeps_its_states(interpret, policy,
                                              forward_walks):
    """Under the ``"attn"`` policy the rule's output and its chunks'
    incoming states are kept (named in the forward rule, where the
    backward rule reads them), so the gradient of a checkpointed
    application traces the forward kernel once; under ``"full"`` again."""
    from analytics_zoo_tpu.parallel.plan import REMAT_KEPT_NAMES, apply_remat

    assert linear.STATE_NAME in REMAT_KEPT_NAMES["attn"]
    (q, k, v, g, beta), weight = _inputs(32, False, width=128)
    before = dict(linear.invocation_counts)

    def rule(q, k, v, g, beta):
        return linear.chunked_kda(q, k, v, g, beta, scale=SCALE)[0]

    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(jnp.square(
        apply_remat(rule, policy)(*a))), argnums=(0, 1, 2, 3, 4)))(
            q, k, v, g, beta))
    assert text.count("name=_kda_walk_forward") == forward_walks
    assert text.count("name=_kda_walk_backward") == 1
    assert linear.invocation_counts["pallas"] > before["pallas"]
    assert linear.invocation_counts["fallback"] == before["fallback"]
    assert linear.chunk_schedules[-1]["kernel"] is True
