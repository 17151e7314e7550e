"""Latency-hiding plane acceptance (ISSUE 15): bucketed gradient
overlap keeps every canned plan's GSPMD trajectory to float32 rounding
(same reduction grouping — the bucket boundaries only reorder the
schedule), the explicit chunked/ring spellings are ulp-recorded,
elastic resume rides through a bucketed plan bit-exact, a kill -9
during an async checkpoint write leaves the previous COMPLETE snapshot
loadable, the fsdp gather-prefetch program compiles under its own
label and warm-starts from the persistent cache in a second process,
the overlap-aware roofline reproduces the old additive model at
exposed=1.0, and a bucketed fused step reproduces the serial two-phase
loop bit for bit."""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(8, 4))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return x, y


def _fit(plan, epochs=2, ckpt_dir=None, mesh_size=8):
    """One training leg under ``plan`` on a {data: mesh_size} mesh;
    absolute epoch target so a second call with the same ckpt_dir
    RESUMES (the test_elastic_resume idiom)."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    zoo.init_zoo_context(seed=3, mesh_shape={"data": mesh_size})
    x, y = _data()
    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    if ckpt_dir:
        m.set_checkpoint(ckpt_dir)
    m.fit(x, y, batch_size=32, nb_epoch=epochs, plan=plan)
    res = m.evaluate(x, y, batch_size=32)
    return {"losses": [h["loss"] for h in m._estimator.history],
            "eval": res,
            "params": [np.asarray(leaf)
                       for leaf in jax.tree_util.tree_leaves(m.params)]}


# ---------------------------------------------------------------------------
# Tentpole pin: overlap vs serial trajectories, per plan
# ---------------------------------------------------------------------------


class TestOverlapTrajectory:
    @pytest.mark.parametrize("plan", ["zero1", "zero2", "zero3", "fsdp"])
    def test_gspmd_overlap_is_the_serial_plan_to_rounding(self, plan):
        """`<plan>+overlap` through the estimator traces the SAME
        reduction grouping as the serial plan: bucketing only chains the
        gradients with ``optimization_barrier``.  The two COMPILED
        programs still sum in different orders on the CPU since jax
        0.9.0: after two epochs a leaf differs by up to one float32 eps
        of its largest element, an epoch's reported loss (its last
        step's scalar) and ``evaluate``'s by one ulp (1.5883808135986328
        against 1.5883806943893433).  Held: four eps of the leaf's scale,
        two ulps of a loss."""
        serial = _fit(plan)
        overlap = _fit(plan + "+overlap")
        eps = np.finfo(np.float32).eps
        for a, b in zip(serial["params"], overlap["params"], strict=True):
            np.testing.assert_allclose(
                b, a, rtol=0, atol=4 * eps * np.max(np.abs(a)))
        assert serial["eval"]["accuracy"] == overlap["eval"]["accuracy"]
        for a, b in zip(serial["losses"] + [serial["eval"]["loss"]],
                        overlap["losses"] + [overlap["eval"]["loss"]],
                        strict=True):
            assert abs(np.float32(a) - np.float32(b)) \
                <= 2 * np.spacing(np.float32(a)), (plan, serial, overlap)

    def test_explicit_bucketed_and_ring_are_ulp_recorded(self, zoo_ctx):
        """The explicit shard_map spellings (chunked psum_scatter /
        ppermute ring) recompose the flat vector per chunk — a
        different compiled program, recorded at the zero1-vs-dp ulp
        tolerance rather than pinned bitwise."""
        import optax

        from analytics_zoo_tpu.parallel import make_zero1_train_step
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
        from analytics_zoo_tpu.pipeline.api.keras.objectives import (
            get_loss,
        )

        x, y = _data()
        batch = {"x": jnp.asarray(x[:64]), "y": jnp.asarray(y[:64])}
        loss = get_loss("sparse_categorical_crossentropy")
        opt = optax.adam(1e-2)

        def leg(**kw):
            m = Sequential()
            m.add(Dense(16, activation="relu", input_shape=(8,)))
            m.add(Dense(4, activation="softmax"))
            m.compile(optimizer="adam",
                      loss="sparse_categorical_crossentropy")
            params, state = m.build_params(jax.random.PRNGKey(0))
            step, init = make_zero1_train_step(m, loss, opt, **kw)
            opt_state = init(params)
            ls = []
            for _ in range(4):
                params, opt_state, state, l = step(
                    params, opt_state, state, jax.random.PRNGKey(0),
                    batch)
                ls.append(float(l))
            return ls

        base = leg()
        bucketed = leg(bucket_bytes=256)
        ring = leg(bucket_bytes=256, ring=True)
        np.testing.assert_allclose(bucketed, base, rtol=2e-5)
        np.testing.assert_allclose(ring, base, rtol=2e-5)


def test_elastic_resume_through_bucketed_plan(tmp_path):
    """A checkpoint written mid-run under zero2+overlap resumes
    bit-exact: same mesh + same plan => same programs, and the bucketed
    schedule does not leak into the snapshot layout."""
    plan = "zero2+overlap"
    full = _fit(plan, epochs=4)
    ckdir = str(tmp_path / "ck_overlap")
    first = _fit(plan, epochs=2, ckpt_dir=ckdir)
    assert first["losses"] == full["losses"][:2]
    resumed = _fit(plan, epochs=4, ckpt_dir=ckdir)
    assert len(resumed["losses"]) == 2, resumed["losses"]
    assert resumed["losses"] == full["losses"][2:]
    assert resumed["eval"]["loss"] == full["eval"]["loss"]


# ---------------------------------------------------------------------------
# Async checkpointing: kill -9 mid-write leaves the previous snapshot
# ---------------------------------------------------------------------------

_CRASH_CHILD = r"""
import os, signal, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

from analytics_zoo_tpu.pipeline.estimator.estimator import _Checkpointer

root = sys.argv[1]
ck = _Checkpointer(path=root, keep=3)
ck.save("good", {"params": jnp.asarray(np.arange(64, dtype=np.float32)),
                 "step": 1})
ck._pending.join()  # 'good' is durably complete (data + rename fsynced)
print("GOOD_DONE", flush=True)
# a payload big enough that pickling + fsync takes hundreds of ms on
# this host: save() returns after the device-side snapshot, the daemon
# starts writing, and SIGKILL lands mid-write
big = jnp.asarray(np.arange((32 << 20) // 4, dtype=np.float32))
ck.save("bad", {"params": big, "step": 2})
os.kill(os.getpid(), signal.SIGKILL)
"""


def test_kill9_mid_async_write_leaves_previous_checkpoint(tmp_path):
    """THE crash-safety pin: kill -9 while the writer daemon is
    serializing leaves (a) no advanced LATEST pointer and (b) the
    previous complete snapshot loadable."""
    root = str(tmp_path / "ck")
    os.makedirs(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ZOO_ASYNC_CHECKPOINT", None)
    r = subprocess.run([sys.executable, "-c", _CRASH_CHILD, root],
                       env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr)
    assert "GOOD_DONE" in r.stdout

    from analytics_zoo_tpu.pipeline.estimator.estimator import (
        _Checkpointer,
    )

    with open(os.path.join(root, _Checkpointer.LATEST)) as f:
        assert f.read().strip() == "ckpt-good.pkl"
    ck = _Checkpointer(path=root, keep=3)
    snap = ck.latest()
    assert snap is not None
    assert snap["step"] == 1
    np.testing.assert_array_equal(
        snap["params"], np.arange(64, dtype=np.float32))


def test_sync_fallback_env_knob(tmp_path, monkeypatch):
    """ZOO_ASYNC_CHECKPOINT=0 runs the write inline: no writer thread
    is left pending and the snapshot is complete when save returns."""
    from analytics_zoo_tpu.pipeline.estimator.estimator import (
        _Checkpointer,
    )

    monkeypatch.setenv("ZOO_ASYNC_CHECKPOINT", "0")
    root = str(tmp_path / "ck_sync")
    ck = _Checkpointer(path=root, keep=3)
    fname = ck.save("s", {"params": jnp.ones((8,)), "step": 5})
    assert ck._pending is None
    assert os.path.exists(fname)
    assert ck.latest()["step"] == 5


# ---------------------------------------------------------------------------
# fsdp gather prefetch: own compile label + persistent-cache warm start
# ---------------------------------------------------------------------------

_PREFETCH_CHILD = r"""
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.metrics import get_registry, snapshot
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

zoo.init_zoo_context(seed=0, mesh_shape={"data": 8})
m = Sequential()
m.add(Dense(16, activation="relu", input_shape=(8,)))
m.add(Dense(4, activation="softmax"))
m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
rng = np.random.default_rng(0)
batch = {"x": rng.normal(size=(32, 8)).astype(np.float32),
         "y": rng.integers(0, 4, size=(32,)).astype(np.int32)}
m._make_estimator().warmup(batch, plan="fsdp+overlap")
out = {"hits": 0, "misses": 0, "compiled": []}
for s in snapshot(get_registry())["samples"]:
    if s["name"] == "zoo_compile_cache_hits_total":
        out["hits"] += s["value"]
    elif s["name"] == "zoo_compile_cache_misses_total":
        out["misses"] += s["value"]
    elif s["name"] == "zoo_compile_seconds":
        out["compiled"].append(s["labels"]["label"])
print("RESULT " + json.dumps(out))
"""


def test_prefetch_compiles_own_label_and_warm_starts(tmp_path):
    """fsdp+overlap (gather prefetch + bucketed grads) lowers through
    the choke point under its OWN label — a different program from
    serial fsdp — and a second process over the same ZOO_COMPILE_CACHE
    compiles it as a pure persistent-cache hit."""

    def run(cache):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   ZOO_COMPILE_CACHE=str(cache))
        env.pop("ZOO_SHARDING_PLAN", None)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        r = subprocess.run([sys.executable, "-c", _PREFETCH_CHILD],
                           env=env, cwd=REPO, capture_output=True,
                           text=True, timeout=420)
        assert r.returncode == 0, r.stdout + "\n" + r.stderr
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        return json.loads(line[len("RESULT "):])

    cache = tmp_path / "cc"
    cold = run(cache)
    labels = set(cold["compiled"])
    assert any("fsdp+overlap" in lb for lb in labels), labels
    assert cold["misses"] > 0 and cold["hits"] == 0, cold
    warm = run(cache)
    assert warm["misses"] == 0, warm
    assert warm["hits"] == cold["misses"], (cold, warm)


# ---------------------------------------------------------------------------
# Overlap-aware roofline: unit matrix
# ---------------------------------------------------------------------------


class TestOverlapRoofline:
    FEATURES = {"matmul_flops": 4e9, "bytes_accessed": 1e9,
                "collective_bytes": 5e9}

    def _peaks(self):
        from analytics_zoo_tpu.analysis.costmodel import PeakTable

        return PeakTable(flops=1e12, hbm_bytes_per_s=1e12,
                         link_bytes_per_s=1e10,
                         dispatch_overhead_s=0.001, hbm_bytes=int(1e10))

    def test_serial_reproduces_additive_model(self):
        """exposed=1.0 (every serial plan) must be EXACTLY the old
        ``max(compute, mem) + collectives + overhead/k`` model."""
        from analytics_zoo_tpu.analysis.costmodel import (
            predict_step_seconds,
        )

        peaks = self._peaks()
        f = self.FEATURES
        old = max(f["matmul_flops"] / peaks.flops,
                  f["bytes_accessed"] / peaks.hbm_bytes_per_s) \
            + f["collective_bytes"] / peaks.link_bytes_per_s \
            + peaks.dispatch_overhead_s
        for plan in (None, "dp", "zero2", "fsdp"):
            assert predict_step_seconds(f, peaks=peaks, plan=plan) == old

    def test_overlap_hides_collectives_behind_compute(self):
        from analytics_zoo_tpu.analysis.costmodel import (
            predict_step_seconds,
        )

        peaks = self._peaks()
        serial = predict_step_seconds(self.FEATURES, peaks=peaks,
                                      plan="zero2")
        overlap = predict_step_seconds(self.FEATURES, peaks=peaks,
                                       plan="zero2+overlap")
        assert overlap < serial
        # exposed=0.25 of the 0.5s collective serializes; the hidden
        # 0.375s exceeds compute (0.004s) so it sets the max() term
        assert overlap == pytest.approx(0.5 * 0.75 + 0.5 * 0.25 + 0.001)

    def test_feature_driven_exposure_beats_plan_table(self):
        """When the HLO actually contains async start/done pairs, the
        measured overlapped bytes win over the plan-name table."""
        from analytics_zoo_tpu.analysis.costmodel import (
            predict_step_seconds,
        )

        peaks = self._peaks()
        f = dict(self.FEATURES, overlapped_collective_bytes=5e9)
        fully_hidden = predict_step_seconds(f, peaks=peaks, plan="dp")
        # exposed=0: the whole 0.5s is overlappable -> max() term
        assert fully_hidden == pytest.approx(0.5 + 0.001)

    def test_exposed_fraction_clamped(self):
        from analytics_zoo_tpu.analysis.costmodel import (
            predict_step_seconds,
        )

        peaks = self._peaks()
        lo = predict_step_seconds(self.FEATURES, peaks=peaks,
                                  exposed_fraction=-3.0)
        hi = predict_step_seconds(self.FEATURES, peaks=peaks,
                                  exposed_fraction=7.0)
        assert lo == predict_step_seconds(self.FEATURES, peaks=peaks,
                                          exposed_fraction=0.0)
        assert hi == predict_step_seconds(self.FEATURES, peaks=peaks,
                                          exposed_fraction=1.0)

    def test_plan_exposed_fraction_table(self):
        from analytics_zoo_tpu.analysis.costmodel import (
            EXPOSED_FRACTIONS,
            plan_exposed_fraction,
        )

        assert plan_exposed_fraction(None) == 1.0
        assert plan_exposed_fraction("zero2") == 1.0
        assert plan_exposed_fraction("zero2+overlap") \
            == EXPOSED_FRACTIONS["overlap"]
        assert plan_exposed_fraction("fsdp+overlap+remat_full") \
            == EXPOSED_FRACTIONS["overlap"]


# ---------------------------------------------------------------------------
# The bucketed fused step against the serial two-phase loop
# ---------------------------------------------------------------------------


def _two_phase_and_bucketed(plan_name, steps=8, dim=1 << 16, n_chunks=4,
                            lr=0.05):
    """Serial two-phase loop and bucketed fused step for one plan family
    ("zero2": params replicated, grads bucket-reduce-scattered; "zero3":
    params stored sharded, gather-on-use with a prefetch-style barrier
    chain) on a comm-bound synthetic over the 8-device mesh.

    The serial leg is the naive loop: backward dispatch, blocking host
    sync so the grads are materialized before the per-bucket reduction
    dispatches, sync again, THEN the next feed.  The bucketed leg issues
    ONE fused dispatch with the barrier-chained per-bucket psum_scatter
    and assembles the next feed while the device runs.  Both reduce over
    the SAME chunk boundaries with an elementwise update.  Returns each
    leg's final parameters and losses, and the fused program's lowered
    text."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n = 8
    mesh = jax.make_mesh((n,), ("data",))
    cm = dim // n_chunks
    m = cm // n                      # one device's slice of one bucket
    slices = [(i * cm, (i + 1) * cm) for i in range(n_chunks)]
    sharded = plan_name == "zero3"
    x_sharding = NamedSharding(mesh, P("data", None))
    base = np.arange(n * dim, dtype=np.float32).reshape(n, dim)

    def feed(step):
        # the per-step host data plane: deterministic batch assembly
        # on host, then the H2D put
        return jax.device_put(np.sin(base * 1e-3 + step * 0.13),
                              x_sharding)

    def local_grad(w, x):
        # analytic elementwise gradient of 0.5*mean((w-x)^2): no
        # cross-element reductions feed the update, so XLA cannot
        # reorder the math between the two differently-fused programs
        # — the bitwise pin is structural, not lucky
        return (w - x) * (2.0 / dim), jnp.sum((w - x) ** 2) / dim

    def gather_params(w_sh, chained):
        # zero3 forward: regather the per-bucket param pieces
        # (gather-on-use); the bucketed leg chains them with barriers —
        # the double-buffered prefetch schedule pinned at HLO level
        token, chunks = None, []
        for k in range(n_chunks):
            piece = w_sh[0, k * m:(k + 1) * m]
            if chained and token is not None:
                piece, token = jax.lax.optimization_barrier(
                    (piece, token))
            full = jax.lax.all_gather(piece, "data", tiled=True)
            token = full
            chunks.append(full)
        return jnp.concatenate(chunks)

    def reduce_chunk(chunk):
        return jax.lax.psum_scatter(
            chunk, "data", scatter_dimension=0, tiled=True) / n

    def updated_piece(w, w_sh, red, k, lo):
        # elementwise SGD on this device's slice of bucket k
        if sharded:
            return w_sh[0, k * m:(k + 1) * m] - lr * red
        idx = jax.lax.axis_index("data")
        return jax.lax.dynamic_slice(w, (lo + idx * m,), (m,)) \
            - lr * red

    # ---- serial (two-phase) programs -------------------------------
    def bwd_body(w, x):
        if sharded:
            w = gather_params(w, chained=False)
        g, loss = local_grad(w, x[0])
        return g[None], jax.lax.psum(loss, "data")[None] / n

    w_spec = P("data", None) if sharded else P()

    def make_red_chunk(k, lo, hi):
        def body(w, g):
            red = reduce_chunk(g[0][lo:hi])
            piece = updated_piece(w, w, red, k, lo)
            if sharded:
                return piece[None]
            return jax.lax.all_gather(piece, "data", tiled=True)
        out = P("data", None) if sharded else P()
        return shard_map(body, mesh=mesh, in_specs=(w_spec, P("data", None)),
                         out_specs=out, check_rep=False)

    def concat_fn(*chunks):
        return jnp.concatenate(chunks, axis=1 if sharded else 0)

    # ---- bucketed (fused) program ----------------------------------
    def fused_body(w_in, x):
        w = gather_params(w_in, chained=True) if sharded else w_in
        g, loss = local_grad(w, x[0])
        token, outs = None, []
        for k, (lo, hi) in enumerate(slices):
            c = g[lo:hi]
            if token is not None:
                # issue-order pin: bucket k's reduce-scatter is chained
                # behind bucket k-1's, matching the
                # backward-completion order plan.constrain_grads pins
                c, token = jax.lax.optimization_barrier((c, token))
            red = reduce_chunk(c)
            token = red
            piece = updated_piece(w, w_in, red, k, lo)
            outs.append(piece[None] if sharded else
                        jax.lax.all_gather(piece, "data", tiled=True))
        new_w = jnp.concatenate(outs, axis=1 if sharded else 0)
        return new_w, jax.lax.psum(loss, "data")[None] / n

    f_bwd = jax.jit(shard_map(
        bwd_body, mesh=mesh, in_specs=(w_spec, P("data", None)),
        out_specs=(P("data", None), P("data")), check_rep=False))
    f_red = [jax.jit(make_red_chunk(k, lo, hi))
             for k, (lo, hi) in enumerate(slices)]
    f_concat = jax.jit(concat_fn)
    f_fused = jax.jit(shard_map(
        fused_body, mesh=mesh, in_specs=(w_spec, P("data", None)),
        out_specs=((P("data", None) if sharded else P()), P("data")),
        check_rep=False))

    def w0():
        full = np.cos(np.arange(dim, dtype=np.float32) * 2e-3)
        if not sharded:
            return jax.device_put(jnp.asarray(full),
                                  NamedSharding(mesh, P()))
        # zero3 storage: device i's row = the concat of its m-slices
        # of each bucket (the strategies._shard_of chip layout)
        rows = np.stack([
            np.concatenate([full[lo + i * m: lo + (i + 1) * m]
                            for lo, _ in slices])
            for i in range(n)])
        return jax.device_put(jnp.asarray(rows), x_sharding)

    def run_serial():
        w, x = w0(), feed(0)
        losses = []
        for s in range(steps):
            g, loss = f_bwd(w, x)
            jax.block_until_ready(g)   # grads must land before the
            # per-bucket reduction dispatches can be issued
            w = f_concat(*[f(w, g) for f in f_red])
            jax.block_until_ready(w)   # naive loop: sync, THEN feed
            x = feed(s + 1)
            losses.append(float(np.asarray(loss)[0]))
        return np.asarray(w), losses

    def run_bucketed():
        w, x = w0(), feed(0)
        losses = []
        for s in range(steps):
            w, loss = f_fused(w, x)    # one fused dispatch
            x = feed(s + 1)            # next feed hides behind it
            losses.append(float(np.asarray(loss)[0]))
        return np.asarray(w), losses

    return run_serial(), run_bucketed(), \
        f_fused.lower(w0(), feed(0)).as_text()


@pytest.mark.parametrize("plan", ["zero2", "zero3"])
def test_bucketed_fused_step_is_the_two_phase_loop_bit_for_bit(plan):
    """Eight steps either way end at the same parameters and report the
    same losses, and the fused program reduces in the four buckets that
    were planned, each chained behind the one before."""
    (w_serial, l_serial), (w_bucketed, l_bucketed), fused = \
        _two_phase_and_bucketed(plan, n_chunks=4)
    assert np.array_equal(w_serial, w_bucketed)
    assert max(abs(a - b) for a, b in zip(l_serial, l_bucketed)) == 0.0
    assert l_serial[-1] < l_serial[0]
    assert fused.count("reduce_scatter") == 4
    # one barrier between consecutive buckets; zero3 chains its four
    # parameter gathers the same way
    assert fused.count("optimization_barrier") == (6 if plan == "zero3"
                                                   else 3)


def test_async_save_is_complete_and_latest_returns_it(tmp_path, monkeypatch):
    """The default asynchronous save snapshots on the caller's thread and
    writes on the daemon: once the writer is joined the file under its
    final name is whole, and ``latest()`` gives the payload back."""
    from analytics_zoo_tpu.pipeline.estimator.estimator import (
        _Checkpointer,
    )

    monkeypatch.delenv("ZOO_ASYNC_CHECKPOINT", raising=False)
    params = np.arange(1 << 20, dtype=np.float32) * 1e-3
    ck = _Checkpointer(path=str(tmp_path / "ck_async"), keep=2)
    for step in range(3):
        fname = ck.save(f"s{step}", {"params": jnp.asarray(params),
                                     "step": step})
    assert ck._pending is not None
    ck._pending.join(timeout=60)
    assert not ck._pending.is_alive()
    assert os.path.exists(fname)
    snap = ck.latest()
    assert snap["step"] == 2
    np.testing.assert_array_equal(snap["params"], params)
