"""Mamba-2's chunked rule (``ops/ssd.py``) and its Pallas kernels
(``ops/pallas/ssd_scan.py``), at toy size on the CPU: the chunk-parallel
form against the recurrence, token by token, of the
``nemotron-3-nano-30b-a3b`` configuration's plain reference
(``benchmark/configs/nemotron-3-nano-30b-a3b/reference.py``, which imports
nothing of the program), forward and every gradient, at a mild and a hard
decay, on and off the chunk grid, in float32 and in bfloat16; and the
kernels in interpret mode against the scans that are their oracle, with
what they must not read filled with NaN."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import ssd
from benchmark.manifest import Manifest


@pytest.fixture(scope="module")
def recurrence():
    reference = Manifest().configuration(
        "nemotron-3-nano-30b-a3b").module("reference")
    return reference.ssd_rule


def _inputs(length, hard, seed=1, batch=2, heads=4, groups=2, p=8, n=16):
    """x, dt, A, B, C and an output cotangent.  Mild: a token's dt A about
    -0.05; hard: every other head at A = -16 with dt near 0.3, so that a
    chunk of 16 tokens sums under -60."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, heads, length))
                         - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=-1.0,
                                    maxval=0.5))
    if hard:
        a = jnp.where(jnp.arange(heads) % 2 == 0, -16.0, a)
        dt = jnp.where((jnp.arange(heads) % 2 == 0)[:, None],
                       0.3 + 0.05 * jax.random.uniform(ks[6], dt.shape), dt)
    return (jax.random.normal(ks[0], (batch, heads, length, p)), dt, a,
            jax.random.normal(ks[3], (batch, groups, length, n)),
            jax.random.normal(ks[4], (batch, groups, length, n))), \
        jax.random.normal(ks[5], (batch, heads, length, p))


#: (sequence length, chunk asked for): on the chunk grid and off it, a
#: chunk longer than the sequence
GRIDS = [(64, 16), (50, 16), (64, 32), (100, 64), (24, 64)]


@pytest.mark.parametrize("length, chunk", GRIDS)
@pytest.mark.parametrize("hard", [False, True], ids=["mild", "hard_decay"])
def test_chunked_form_against_the_recurrence(recurrence, length, chunk,
                                             hard):
    args, weight = _inputs(length, hard)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        got, stats = ssd.chunked_ssd(*args, chunk=chunk)
        np.testing.assert_allclose(got, want,
                                   atol=2e-6 * float(jnp.abs(want).max()))
        grads = jax.grad(lambda *a: jnp.sum(ssd.chunked_ssd(
            *a, chunk=chunk)[0] * weight), argnums=range(5))(*args)
        ref_grads = jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight),
                             argnums=range(5))(*args)
    for name, g, ref in zip(("x", "dt", "A", "B", "C"), grads, ref_grads):
        np.testing.assert_allclose(
            g, ref, atol=5e-6 * float(jnp.abs(ref).max()), err_msg=name)
    q = ssd.chunk_of(length, chunk)
    if hard:
        # what the form's exponentials had to survive: under -60 a chunk
        assert float(stats["chunk_log_decay_min"]) \
            < -60.0 * min(q, length) / 16
    assert float(stats["dt_max"]) == pytest.approx(float(args[1].max()))
    assert 0.0 < float(stats["state_rms"]) < 10.0
    assert ssd.chunk_schedules[-1] == {
        "rule": "ssd", "shape": (2, 4, length, 8, 16), "groups": 2,
        "chunk": q, "chunks": -(-length // q), "kernel": False}


def test_bfloat16_operands_against_the_float32_recurrence(recurrence):
    """x, B and C in bfloat16 (the products' operands), the decay and the
    state in float32: within bfloat16's noise of the float32 recurrence,
    forward and backward."""
    args, weight = _inputs(96, hard=True)
    bf = tuple(t.astype(jnp.bfloat16) if i in (0, 3, 4) else t
               for i, t in enumerate(args))
    want = recurrence(*args)
    got, _ = ssd.chunked_ssd(*bf, chunk=32)
    assert got.dtype == jnp.bfloat16
    gap = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert gap < 0.01, gap
    grads = jax.grad(lambda *a: jnp.sum(ssd.chunked_ssd(
        *a, chunk=32)[0].astype(jnp.float32) * weight),
        argnums=range(5))(*bf)
    ref_grads = jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight),
                         argnums=range(5))(*args)
    for name, g, ref in zip(("x", "dt", "A", "B", "C"), grads, ref_grads):
        gap = float(jnp.linalg.norm(g.astype(jnp.float32) - ref)
                    / jnp.linalg.norm(ref))
        assert gap < 0.02, (name, gap)


def _walk_inputs(dtype=jnp.float32, hard=True):
    """The four arrays the walks take, (G, R, n, ...), and dY: 2 groups of
    2 heads, 3 chunks of 32, P 128, N 128."""
    (x, dt, a, b, c), dy = _inputs(96, hard, seed=2, batch=1, heads=4,
                                   groups=2, p=128, n=128)
    xdt = (x * dt[..., None]).reshape(2, 2, 3, 32, 128).astype(dtype)
    a_sum = jnp.cumsum((dt * a[:, None]).reshape(2, 2, 3, 32), axis=3)
    return (xdt, a_sum[:, :, :, None, :], b.reshape(2, 3, 32, 128).astype(
        dtype), c.reshape(2, 3, 32, 128).astype(dtype)), \
        dy.reshape(2, 2, 3, 32, 128).astype(dtype)


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
    from analytics_zoo_tpu.ops.pallas import ssd_scan

    return ssd_scan


def test_the_kernels_in_interpret_mode_against_the_scans(interpret):
    chunks, dy = _walk_inputs()
    want = ssd.walk_forward_scan(*chunks)
    got = interpret.walk_forward(*chunks)
    for name, g, w in zip(("y", "states", "final"), got, want):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()),
                                   err_msg=name)
    # a sequence starts from a zero state, whatever the scratch held
    assert not np.any(got[1][:, :, 0])
    want = ssd.walk_backward_scan(*chunks, want[1], dy)
    got = interpret.walk_backward(*chunks, got[1], dy)
    for name, g, w in zip(("xdt", "a", "b", "c"), got, want):
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_the_kernels_read_no_chunk_out_of_turn(interpret):
    """The forward walk makes a chunk's output from the chunks before it
    alone, and the backward walk a chunk's cotangents from the chunks after
    it alone: rows and states that a chunk must not read are NaN here, and
    what it writes is the clean walk's to the digit."""
    chunks, dy = _walk_inputs()
    clean = interpret.walk_forward(*chunks)
    # the last chunk's rows are NaN, in a head's arrays and a group's
    poisoned = tuple(t.at[:, :, 2].set(jnp.nan) if t.ndim == 5
                     else t.at[:, 2].set(jnp.nan) for t in chunks)
    y, states, final = interpret.walk_forward(*poisoned)
    np.testing.assert_array_equal(y[:, :, :2], clean[0][:, :, :2])
    np.testing.assert_array_equal(states, clean[1])
    assert bool(jnp.all(jnp.isnan(y[:, :, 2]))) \
        and bool(jnp.all(jnp.isnan(final)))
    # the first chunk's output cotangent and incoming state are NaN
    grads = interpret.walk_backward(*chunks, clean[1], dy)
    got = interpret.walk_backward(*chunks,
                                  clean[1].at[:, :, 0].set(jnp.nan),
                                  dy.at[:, :, 0].set(jnp.nan))
    for name, g, w in zip(("xdt", "a", "b", "c"), got, grads):
        at = (slice(None),) * (2 if g.ndim == 5 else 1)
        np.testing.assert_array_equal(g[at + (slice(1, None),)],
                                      w[at + (slice(1, None),)],
                                      err_msg=name)
        assert bool(jnp.all(jnp.isnan(g[at + (0,)]))), name


def test_bfloat16_kernels_against_the_float32_oracle(interpret):
    """Operands in bfloat16, sums, the decay and the state in float32:
    each result within bfloat16's noise of the float32 oracle's, forward
    and backward."""
    chunks, dy = _walk_inputs(jnp.bfloat16)

    def f32(tree):
        return jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), tree)

    want = ssd.walk_forward_scan(*f32(chunks))
    got = interpret.walk_forward(*chunks)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    want_grads = ssd.walk_backward_scan(*f32(chunks), want[1], f32(dy))
    got_grads = interpret.walk_backward(*chunks, got[1], dy)
    assert [g.dtype for g in got_grads] == [t.dtype for t in chunks]
    for name, g, w in zip(("y", "xdt", "a", "b", "c"),
                          (got[0],) + got_grads, (want[0],) + want_grads):
        gap = float(jnp.linalg.norm(f32(g) - w) / jnp.linalg.norm(w))
        assert gap < 0.01, (name, gap)


def test_a_state_carried_in_bfloat16_is_caught_here(interpret, monkeypatch):
    """A state rounded to bfloat16 from chunk to chunk: the oracle tells it
    from the float32 one many times over its tolerance."""
    step = ssd.chunk_step

    def rounded(state, *chunk):
        out, y = step(state, *chunk)
        return out.astype(jnp.bfloat16).astype(jnp.float32), y

    chunks, _ = _walk_inputs(hard=False)
    want = ssd.walk_forward_scan(*chunks)
    monkeypatch.setattr(interpret, "chunk_step", rounded)
    interpret._ssd_walk_forward.clear_cache()
    try:
        got = interpret.walk_forward(*chunks)
    finally:
        interpret._ssd_walk_forward.clear_cache()
    scale = float(jnp.abs(want[2]).max())
    assert float(jnp.abs(got[2] - want[2]).max()) > 1e-4 * scale


@pytest.mark.parametrize("policy, forward_walks", [("attn", 1), ("full", 2)])
def test_a_checkpointed_rule_keeps_its_states(interpret, policy,
                                              forward_walks):
    """Under the ``"attn"`` policy the rule's output and its chunks'
    incoming states are kept (named in the forward rule, where the
    backward rule reads them), so the gradient of a checkpointed
    application traces the forward kernel once; under ``"full"`` again."""
    from analytics_zoo_tpu.parallel.plan import REMAT_KEPT_NAMES, apply_remat

    assert ssd.STATE_NAME in REMAT_KEPT_NAMES["attn"]
    (x, dt, a, b, c), _ = _inputs(32, False)
    before = dict(ssd.invocation_counts)

    def rule(x, dt, b, c):
        return ssd.chunked_ssd(x, dt, a, b, c)[0]

    text = str(jax.make_jaxpr(jax.grad(lambda *t: jnp.sum(jnp.square(
        apply_remat(rule, policy)(*t))), argnums=(0, 1, 2, 3)))(
            x, dt, b, c))
    assert text.count("name=_ssd_walk_forward") == forward_walks
    assert text.count("name=_ssd_walk_backward") == 1
    assert ssd.invocation_counts["pallas"] > before["pallas"]
    assert ssd.invocation_counts["fallback"] == before["fallback"]
    assert ssd.chunk_schedules[-1]["kernel"] is True


def test_heads_that_do_not_part_into_groups_are_refused():
    (x, dt, a, b, c), _ = _inputs(16, False, heads=3, groups=2)
    with pytest.raises(ValueError, match="3 heads do not part into 2"):
        ssd.chunked_ssd(x, dt, a, b, c)
