"""Mamba-2's chunked rule (``ops/ssd.py``) and its Pallas kernels
(``ops/pallas/ssd_scan.py``), at toy size on the CPU: the chunk-parallel
form against the recurrence, token by token, of the
``nemotron-3-nano-30b-a3b`` configuration's plain reference
(``benchmark/configs/nemotron-3-nano-30b-a3b/reference.py``, which imports
nothing of the program and takes its heads head-major), forward and every
gradient, at a mild and a hard decay, on and off the chunk grid, in float32
and in bfloat16; the kernels in interpret mode, on the projections'
token-major blocks, against the head-major scans that are their oracle,
with what they must not read filled with NaN; and the mixer as traced,
which splits out no head and merges none back."""

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

    def token_major(x, dt, a, b, c):
        """The reference's rule on ``chunked_ssd``'s token-major arrays."""
        y = reference.ssd_rule(*(jnp.moveaxis(t, 1, 2) for t in (x, dt)), a,
                               *(jnp.moveaxis(t, 1, 2) for t in (b, c)))
        return jnp.moveaxis(y, 2, 1)

    return token_major


def _inputs(length, hard, seed=1, batch=2, heads=4, groups=2, p=8, n=16):
    """x, dt, A, B, C and an output cotangent, token-major: x (B, L, H, P),
    dt (B, L, H), B and C (B, L, G, N).  Mild: a token's dt A about -0.05;
    hard: every other head at A = -16 with dt near 0.3, so that a chunk of
    16 tokens sums under -60."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, length, heads))
                         - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=-1.0,
                                    maxval=0.5))
    if hard:
        a = jnp.where(jnp.arange(heads) % 2 == 0, -16.0, a)
        dt = jnp.where(jnp.arange(heads) % 2 == 0,
                       0.3 + 0.05 * jax.random.uniform(ks[6], dt.shape), dt)
    return (jax.random.normal(ks[0], (batch, length, heads, p)), dt, a,
            jax.random.normal(ks[3], (batch, length, groups, n)),
            jax.random.normal(ks[4], (batch, length, groups, n))), \
        jax.random.normal(ks[5], (batch, length, heads, p))


#: (sequence length, chunk asked for): on the chunk grid and off it, a
#: chunk longer than the sequence
GRIDS = [(64, 16), (50, 16), (64, 32), (100, 64), (24, 64)]


def _against_the_recurrence(recurrence, args, weight, chunk):
    """The rule's output and its five gradients against the recurrence's,
    in float32 at the highest precision."""
    def both(args):
        return recurrence(*args), ssd.chunked_ssd(*args, chunk=chunk), \
            jax.grad(lambda *a: jnp.sum(ssd.chunked_ssd(
                *a, chunk=chunk)[0] * weight), argnums=range(5))(*args), \
            jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight),
                     argnums=range(5))(*args)

    with jax.default_matmul_precision("highest"):
        # each traced and compiled once, not run op by op
        want, (got, stats), grads, ref_grads = jax.jit(both)(args)
    np.testing.assert_allclose(got, want,
                               atol=2e-6 * float(jnp.abs(want).max()))
    for name, g, ref in zip(("x", "dt", "A", "B", "C"), grads, ref_grads):
        np.testing.assert_allclose(
            g, ref, atol=5e-6 * float(jnp.abs(ref).max()), err_msg=name)
    return stats


@pytest.mark.parametrize("length, chunk", GRIDS)
@pytest.mark.parametrize("hard", [False, True], ids=["mild", "hard_decay"])
def test_chunked_form_against_the_recurrence(recurrence, length, chunk,
                                             hard):
    args, weight = _inputs(length, hard)
    stats = _against_the_recurrence(recurrence, args, weight, chunk)
    q = ssd.chunk_of(length, chunk)
    if hard:
        # what the form's exponentials had to survive: under -60 a chunk
        assert float(stats["chunk_log_decay_min"]) \
            < -60.0 * min(q, length) / 16
    assert float(stats["dt_max"]) == pytest.approx(float(args[1].max()))
    assert 0.0 < float(stats["state_rms"]) < 10.0
    # the rule's sizes (B, H, L, P, N), whatever the arrays' layout
    assert ssd.chunk_schedules[-1] == {
        "rule": "ssd", "shape": (2, 4, length, 8, 16), "groups": 2,
        "chunk": q, "chunks": -(-length // q), "kernel": False,
        "layout": "tokens"}


@pytest.mark.parametrize("length, chunk", [(64, 16), (50, 16)],
                         ids=["on_the_grid", "off_the_grid"])
@pytest.mark.parametrize("groups", [1, 4], ids=["one_group", "a_group_a_head"])
def test_the_kernels_in_the_rule_against_the_recurrence(
        interpret, recurrence, length, chunk, groups):
    """The whole rule through the interpret-mode kernels, forward and every
    gradient, with all heads in one group and with a group a head."""
    args, weight = _inputs(length, True, groups=groups)
    before = dict(ssd.invocation_counts)
    _against_the_recurrence(recurrence, args, weight, chunk)
    assert ssd.invocation_counts["pallas"] > before["pallas"]
    assert ssd.invocation_counts["fallback"] == before["fallback"]
    record = ssd.chunk_schedules[-1]
    assert (record["shape"], record["groups"], record["kernel"],
            record["layout"]) == ((2, 4, length, 8, 16), groups, True,
                                  "tokens")


def test_bfloat16_operands_against_the_float32_recurrence(recurrence):
    """x, B and C in bfloat16 (the products' operands), the decay and the
    state in float32: within bfloat16's noise of the float32 recurrence,
    forward and backward."""
    args, weight = _inputs(96, hard=True)
    bf = tuple(t.astype(jnp.bfloat16) if i in (0, 3, 4) else t
               for i, t in enumerate(args))

    @jax.jit
    def both(args, bf):
        return recurrence(*args), ssd.chunked_ssd(*bf, chunk=32)[0], \
            jax.grad(lambda *a: jnp.sum(ssd.chunked_ssd(
                *a, chunk=32)[0].astype(jnp.float32) * weight),
                argnums=range(5))(*bf), \
            jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight),
                     argnums=range(5))(*args)

    want, got, grads, ref_grads = both(args, bf)
    assert got.dtype == jnp.bfloat16
    gap = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert gap < 0.01, gap
    for name, g, ref in zip(("x", "dt", "A", "B", "C"), grads, ref_grads):
        gap = float(jnp.linalg.norm(g.astype(jnp.float32) - ref)
                    / jnp.linalg.norm(ref))
        assert gap < 0.02, (name, gap)


#: P = 64: two heads share a lane tile; P = 128: a head fills one
HEAD_WIDTHS = pytest.mark.parametrize("p", [64, 128],
                                      ids=["p64_two_heads_a_tile", "p128"])


def _walk_inputs(dtype=jnp.float32, hard=True, p=128):
    """The four arrays the kernels take, token-major, and dY: 2 groups of 2
    heads of ``p``, N 128, 3 chunks of 32; X~ and dY (1, 96, 4 p), the
    decay (1, 2, 3, 2, 32), B and C (1, 96, 256)."""
    (x, dt, a, b, c), dy = _inputs(96, hard, seed=2, batch=1, heads=4,
                                   groups=2, p=p, n=128)
    xdt = (x * dt[..., None]).reshape(1, 96, 4 * p).astype(dtype)
    steps = (dt * a).reshape(1, 3, 32, 2, 2).transpose(0, 3, 1, 4, 2)
    return (xdt, jnp.cumsum(steps, axis=-1),
            b.reshape(1, 96, 256).astype(dtype),
            c.reshape(1, 96, 256).astype(dtype)), \
        dy.reshape(1, 96, 4 * p).astype(dtype)


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("ZOO_KERNEL_INTERPRET", "1")
    from analytics_zoo_tpu.ops.pallas import ssd_scan

    return ssd_scan


def test_the_layouts_convert_both_ways():
    """``heads_of`` takes a group's R heads of P channels out of the
    token-major lanes as the scans want them; ``tokens_of`` puts them
    back."""
    chunks, dy = _walk_inputs(p=64)
    xdt, a, b, c = chunks
    heads = ssd.heads_of(xdt, a)
    assert heads.shape == (2, 2, 3, 32, 64)
    # group 1's second head, chunk 2, token 5 of it, channel 7
    assert heads[1, 1, 2, 5, 7] == xdt[0, 2 * 32 + 5, (1 * 2 + 1) * 64 + 7]
    assert ssd.heads_of(b, a, False)[1, 2, 5, 7] == b[0, 69, 128 + 7]
    np.testing.assert_array_equal(ssd.tokens_of(heads, 1), xdt)
    np.testing.assert_array_equal(
        ssd.tokens_of(ssd.heads_of(c, a, False), 1), c)


@HEAD_WIDTHS
def test_the_kernels_in_interpret_mode_against_the_scans(interpret, p):
    chunks, dy = _walk_inputs(p=p)
    want = ssd.walk_forward_by_scan(*chunks)
    got = interpret.walk_forward(*chunks)
    for name, g, w in zip(("y", "states", "final"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()),
                                   err_msg=name)
    # a sequence starts from a zero state, whatever the scratch held
    assert not np.any(got[1][:, :, 0])
    want = ssd.walk_backward_by_scan(*chunks, want[1], dy)
    got = interpret.walk_backward(*chunks, got[1], dy)
    for name, g, w, t in zip(("xdt", "a", "b", "c"), got, want, chunks):
        assert g.shape == w.shape == t.shape, name
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


@HEAD_WIDTHS
def test_the_kernels_read_no_chunk_out_of_turn(interpret, p):
    """The forward walk makes a chunk's output from the chunks before it
    alone, and the backward walk a chunk's cotangents from the chunks after
    it alone: rows and states that a chunk must not read are NaN here, and
    what it writes is the clean walk's to the digit."""
    chunks, dy = _walk_inputs(p=p)
    clean = interpret.walk_forward(*chunks)
    last = slice(64, 96)            # the last chunk's tokens
    xdt, a, b, c = chunks
    poisoned = (xdt.at[:, last].set(jnp.nan), a.at[:, :, 2].set(jnp.nan),
                b.at[:, last].set(jnp.nan), c.at[:, last].set(jnp.nan))
    y, states, final = interpret.walk_forward(*poisoned)
    np.testing.assert_array_equal(y[:, :64], clean[0][:, :64])
    np.testing.assert_array_equal(states, clean[1])
    assert bool(jnp.all(jnp.isnan(y[:, last]))) \
        and bool(jnp.all(jnp.isnan(final)))
    # the first chunk's output cotangent and incoming state are NaN
    grads = interpret.walk_backward(*chunks, clean[1], dy)
    got = interpret.walk_backward(*chunks,
                                  clean[1].at[:, :, 0].set(jnp.nan),
                                  dy.at[:, :32].set(jnp.nan))
    for name, g, w in zip(("xdt", "a", "b", "c"), got, grads):
        later, first = ((slice(None), slice(None), slice(1, None)),
                        (slice(None), slice(None), 0)) if name == "a" \
            else ((slice(None), slice(32, None)), (slice(None), slice(0, 32)))
        np.testing.assert_array_equal(g[later], w[later], err_msg=name)
        assert bool(jnp.all(jnp.isnan(g[first]))), name


def test_bfloat16_kernels_against_the_float32_oracle(interpret):
    """Operands in bfloat16, sums, the decay and the state in float32:
    each result within bfloat16's noise of the float32 oracle's, forward
    and backward."""
    chunks, dy = _walk_inputs(jnp.bfloat16)

    def f32(tree):
        return jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), tree)

    want = ssd.walk_forward_by_scan(*f32(chunks))
    got = interpret.walk_forward(*chunks)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    want_grads = ssd.walk_backward_by_scan(*f32(chunks), want[1], f32(dy))
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
    want = ssd.walk_forward_by_scan(*chunks)
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


def test_spread_puts_each_value_over_its_channels_and_sums_them_back():
    t = jnp.arange(6.0).reshape(2, 3) - 2.5
    np.testing.assert_array_equal(ssd.spread(t, 4), jnp.repeat(t, 4, -1))
    u = jnp.arange(24.0).reshape(2, 12)
    np.testing.assert_array_equal(ssd.run_sums(u, 4),
                                  u.reshape(2, 3, 4).sum(-1))
    # each is the other's transpose
    _, back = jax.vjp(lambda s: ssd.spread(s, 4), t)
    np.testing.assert_array_equal(back(u)[0], ssd.run_sums(u, 4))


def _jaxpr_eqns(jaxpr):
    """Every equation of a closed jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_eqns(sub)


def test_the_traced_mixer_splits_out_no_head(interpret):
    """The mixer as traced through the kernels, forward and gradient, under
    the checkpoint the decoder puts it in: no transpose of an array of x's
    or y's size (B x L x heads x P), no such array in float32 reshaped,
    transposed, padded or handed to the kernels, and the kernels' operands
    in the projections' token-major layout, bfloat16.  (The scan that
    stands in for the kernels off the chip converts layouts: it is not
    traced here.)"""
    from analytics_zoo_tpu.parallel.plan import apply_remat
    from analytics_zoo_tpu.pipeline.api.keras.layers.ssd_mixer import (
        SSDMixer,
    )

    mixer = SSDMixer(heads=4, head_dim=64, state=128, groups=2, chunk=32)
    batch, length, width = 2, 64, 96
    bp = jax.tree_util.tree_map(
        lambda t: t.astype(jnp.bfloat16),
        mixer.params(jax.random.PRNGKey(0), width, 0.02))
    u = jnp.ones((batch, length, width), jnp.bfloat16)
    size = batch * length * mixer.inner

    def loss(bp, u):
        out = apply_remat(lambda bp, u: mixer(bp, u, 1e-5)[0], "attn")(bp, u)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    walks = 0
    for fn in (lambda bp, u: mixer(bp, u, 1e-5)[0], jax.grad(loss)):
        for eqn in _jaxpr_eqns(jax.make_jaxpr(fn)(bp, u).jaxpr):
            ins = [v.aval for v in eqn.invars if hasattr(v.aval, "shape")]
            name = eqn.primitive.name
            if name == "transpose":
                assert all(t.size != size for t in ins), eqn
            if name in ("reshape", "transpose", "pad", "concatenate",
                        "pallas_call"):
                assert not [t for t in ins if t.size == size
                            and t.dtype == jnp.float32], eqn
            if name == "pallas_call":
                walks += 1
                lanes = {t.shape[-1] for t in ins if t.ndim == 3}
                assert lanes <= {mixer.inner,
                                 mixer.groups * mixer.state}, eqn
                assert all(t.dtype == jnp.bfloat16 for t in ins
                           if t.ndim == 3), eqn
    # the forward alone; then the gradient's forward walk and its backward
    assert walks == 3
    assert ssd.chunk_schedules[-1]["layout"] == "tokens"
