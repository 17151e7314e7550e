"""Fused multi-step dispatch (ZOO_STEPS_PER_DISPATCH) + compile plane.

The fused-path contract under test: K>1 changes ONLY how many
Python→device round-trips an epoch costs — the loss trajectory, final
params, checkpoints and resume behavior are bit-identical to K=1
(per-inner-step RNG folds on the global step index; partial tail chunks
fall back to the single step).  Plus the count of dispatches at K = 16
and the measure_pure_step probe cache.
"""

import os

import numpy as np
import pytest

import jax


def _data(n=256):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(8, 4))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return x, y


def _model():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    return m


def _init_ctx(k, **cfg_kwargs):
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.engine import ZooConfig

    return zoo.init_zoo_context(ZooConfig(
        seed=3, mesh_shape={"data": 8}, steps_per_dispatch=k,
        **cfg_kwargs))


def _fit(k, epochs=2, **cfg_kwargs):
    """One full training run at steps_per_dispatch=k; returns per-epoch
    losses, final params (host), and eval metrics."""
    _init_ctx(k, **cfg_kwargs)
    x, y = _data()
    m = _model()
    m.fit(x, y, batch_size=32, nb_epoch=epochs)
    params = jax.tree_util.tree_map(np.asarray, m._estimator.model.params)
    return ([h["loss"] for h in m._estimator.history], params,
            m.evaluate(x, y, batch_size=32))


def _assert_tree_bitwise(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestFusedTrajectoryEquality:
    def test_k4_bitwise_equal_to_k1(self):
        """The acceptance contract: K=4 fused training reproduces the
        K=1 loss trajectory and final weights BIT-FOR-BIT (8 steps/epoch
        = 2 fused dispatches)."""
        l1, p1, e1 = _fit(1)
        l4, p4, e4 = _fit(4)
        assert l1 == l4  # bitwise: float equality, no tolerance
        _assert_tree_bitwise(p1, p4)
        assert e1 == e4

    def test_partial_tail_chunk_falls_back_to_single_step(self):
        """K=3 over 8 steps/epoch: 2 fused chunks + 2 single-step tail
        dispatches — still bit-identical."""
        l1, p1, _ = _fit(1)
        l3, p3, _ = _fit(3)
        assert l1 == l3
        _assert_tree_bitwise(p1, p3)

    def test_fused_composes_with_prefetch_plane(self):
        """ZOO_STEPS_PER_DISPATCH and the PR-4 host data plane
        (ZOO_PREFETCH_WORKERS) stack: the chunked feeder consumes the
        prefetched stream, trajectory still bit-identical."""
        l1, p1, _ = _fit(1)
        lp, pp, _ = _fit(4, prefetch_workers=2, prefetch_depth=4)
        assert l1 == lp
        _assert_tree_bitwise(p1, pp)

    def test_mid_epoch_resume_matches_k1(self, tmp_path):
        """Crash after a MID-EPOCH checkpoint (iteration 12 of 16 —
        epoch 2, batch 4) and resume with K=4: the continuation must
        replay epochs 2-4 bit-identically to an uninterrupted K=1 run."""
        from analytics_zoo_tpu.common.triggers import SeveralIteration
        from analytics_zoo_tpu.feature.dataset import FeatureSet

        full_losses, full_params, full_eval = _fit(1, epochs=4)

        ckdir = str(tmp_path / "ck")
        x, y = _data()

        # leg 1 (K=4): 2 epochs, checkpoint every 4 optimizer steps
        _init_ctx(4)
        m = _model()
        m.set_checkpoint(ckdir)
        est = m._make_estimator()
        m._estimator = est
        est.train(FeatureSet.of(x, y), batch_size=32, nb_epoch=2,
                  checkpoint_trigger=SeveralIteration(4))
        # simulate the crash window: drop everything newer than the
        # mid-epoch-2 snapshot (iteration 12 -> next_batch=4 of epoch 2)
        removed = 0
        for f in os.listdir(ckdir):
            if not (f.startswith("ckpt-") and f.endswith(".pkl")):
                continue  # the LATEST pointer / partial tmp files
            tag = int(f.split("-")[1].split(".")[0])
            if tag > 12:
                os.remove(os.path.join(ckdir, f))
                removed += 1
        assert removed >= 1  # the epoch-2-complete snapshot existed

        # leg 2 (K=4, fresh estimator/process-equivalent): resume to 4
        _init_ctx(4)
        m2 = _model()
        m2.set_checkpoint(ckdir)
        est2 = m2._make_estimator()
        m2._estimator = est2
        est2.train(FeatureSet.of(x, y), batch_size=32, nb_epoch=4)
        assert est2.global_step == 32
        resumed_losses = [h["loss"] for h in est2.history]
        # history covers the resumed partial epoch 2 plus epochs 3-4
        assert len(resumed_losses) == 3
        assert resumed_losses == full_losses[1:]
        _assert_tree_bitwise(
            jax.tree_util.tree_map(np.asarray, m2.params), full_params)
        assert m2.evaluate(x, y, batch_size=32) == full_eval


class TestLocalEstimatorFusion:
    def test_local_k4_bitwise_equal_to_k1(self):
        """LocalEstimator.fit(steps_per_dispatch=4): same scan-fusion
        contract as the distributed estimator, on the no-mesh path.
        192 samples / batch 32 = 6 steps/epoch -> 1 fused chunk + 2
        tail singles at K=4."""
        from analytics_zoo_tpu.pipeline.estimator import LocalEstimator

        _init_ctx(1)
        x, y = _data()
        x, y = x[:192], y[:192]

        def run(k):
            from analytics_zoo_tpu.pipeline.api.keras import Sequential
            from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

            m = Sequential()
            m.add(Dense(16, activation="relu", input_shape=(8,)))
            m.add(Dense(4, activation="softmax"))
            m.build_params()
            est = LocalEstimator(
                m, "sparse_categorical_crossentropy", "adam")
            est.fit(x, y, batch_size=32, epochs=2, seed=7,
                    steps_per_dispatch=k)
            return est.history, jax.tree_util.tree_map(
                np.asarray, m.params)

        h1, p1 = run(1)
        h4, p4 = run(4)
        assert h1 == h4
        _assert_tree_bitwise(p1, p4)


class TestPureStepProbe:
    def test_repeated_probes_reuse_compiled_step(self):
        """Satellite: measure_pure_step must not re-jit per call — the
        first probe pays (and reports) compile, re-probes report 0.0
        warmup and measure steady state."""
        _init_ctx(1)
        x, y = _data()
        m = _model()
        est = m._make_estimator()
        batch = {"x": x[:32], "y": y[:32]}
        est.measure_pure_step(batch, n_steps=2)
        first_warm = est.last_probe_warmup_seconds
        assert first_warm is not None and first_warm > 0.0
        dt = est.measure_pure_step(batch, n_steps=2)
        assert est.last_probe_warmup_seconds == 0.0
        # steady-state probe is far below the compile-included warmup
        assert dt < first_warm

    def test_probe_does_not_thrash_fit_cache(self):
        """A probe with device_transform=None and a fit with a transform
        keep SEPARATE cache entries (the old single-slot cache rebuilt
        the jit on every alternation)."""
        _init_ctx(1)
        x, y = _data()
        m = _model()
        est = m._make_estimator()
        batch = {"x": x[:32], "y": y[:32]}
        est.measure_pure_step(batch, n_steps=1)
        plan_key = est._resolved_plan().cache_key()
        fn_probe = est._train_step_fns[(None, 1, plan_key)]
        dev_tf = lambda b: b  # noqa: E731
        est._train_step_for(dev_tf, 1)
        est.measure_pure_step(batch, n_steps=1)
        assert est._train_step_fns[(None, 1, plan_key)] is fn_probe
        assert len(est._train_step_fns) == 2


class TestEstimatorWarmup:
    def test_warmup_compiles_and_records_metrics(self, tmp_path,
                                                 monkeypatch):
        """warmup() AOT-compiles the K=1 and scan-K steps through the
        compile plane; a second warmup at the same shapes is served from
        the persistent cache (hit counter moves, not the miss one)."""
        from analytics_zoo_tpu.common import compile_cache
        from analytics_zoo_tpu.metrics import (
            MetricsRegistry,
            set_registry,
            snapshot,
        )

        # the cache this test places must be the one in force
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        reg = MetricsRegistry(enabled=True)
        prev = set_registry(reg)
        try:
            _init_ctx(4, compile_cache=str(tmp_path / "cc"))
            x, y = _data()
            m = _model()
            est = m._make_estimator()
            secs = est.warmup({"x": x[:32], "y": y[:32]})
            assert set(secs) == {"train_step", "train_step_scan4"}
            assert all(v > 0 for v in secs.values())

            def series(name):
                return {tuple(sorted((s.get("labels") or {}).items())): s
                        for s in snapshot(reg)["samples"]
                        if s["name"] == name}

            hist = series("zoo_compile_seconds")
            assert (("label", "train_step"),) in hist
            assert (("label", "train_step_scan4"),) in hist

            est2 = m._make_estimator()
            est2.warmup({"x": x[:32], "y": y[:32]})
            hits = series("zoo_compile_cache_hits_total")
            got = sum(s["value"] for s in hits.values())
            assert got >= 2, hits  # both re-compiles were cache hits
        finally:
            set_registry(prev)
            compile_cache.disable_persistent_cache()


class TestSeveralIterationStride:
    def test_boundary_crossing_keeps_cadence_under_k(self):
        """Under stride-K iteration observation, SeveralIteration(n)
        fires at the first boundary past each multiple of n (NOT at
        lcm(K, n)); the classic one-step walk keeps the historical
        exact-multiple behavior."""
        from analytics_zoo_tpu.common.triggers import (
            SeveralIteration,
            TrainingState,
        )

        t = SeveralIteration(100)
        st = TrainingState(epoch=1, iteration=0)
        fired = []
        for it in range(16, 801, 16):  # K=16 dispatch boundaries
            st.iteration = it
            if t(st):
                fired.append(it)
        assert fired == [112, 208, 304, 400, 512, 608, 704, 800]

        t1 = SeveralIteration(3)
        fired1 = []
        for it in range(1, 10):
            st.iteration = it
            if t1(st):
                fired1.append(it)
        assert fired1 == [3, 6, 9]
        # same-iteration re-call (epoch-boundary callback): historical
        # exact-hit rule, idempotent overwrite
        assert t1(st) and st.iteration == 9


class TestWarmupEdges:
    def test_warmup_rejects_bad_k_before_touching_cache(self):
        _init_ctx(1)
        x, y = _data()
        m = _model()
        est = m._make_estimator()
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            est.warmup({"x": x[:32], "y": y[:32]}, steps_per_dispatch=0)
        assert (None, 0) not in est._train_step_fns

    def test_warmup_uses_fit_opt_placement_under_zero1(self, monkeypatch):
        """ZOO_SHARD_OPTIMIZER=1: warmup must place opt_state exactly
        like fit (_place_opt_state), or it compiles a program fit never
        dispatches."""
        monkeypatch.setenv("ZOO_SHARD_OPTIMIZER", "1")
        _init_ctx(4)
        x, y = _data()
        m = _model()
        est = m._make_estimator()
        m._estimator = est
        secs = est.warmup({"x": x[:32], "y": y[:32]})
        # ZOO_SHARD_OPTIMIZER resolves to the zero1 plan, and plan
        # programs carry per-plan compile labels (parallel/plan.py)
        assert set(secs) == {"train_step_zero1",
                             "train_step_scan4_zero1"}
        m.fit(x, y, batch_size=32, nb_epoch=1)  # reuses the warmed fns
        assert est.global_step == 8


@pytest.mark.quick
def test_k16_makes_a_sixteenth_of_the_dispatches_bitwise():
    """128 steps an epoch at K = 1, 4 and 16: the same losses to the bit,
    and ``zoo_train_step_dispatch_seconds`` counts 128 / K dispatches an
    epoch."""
    from analytics_zoo_tpu.metrics import MetricsRegistry, set_registry

    x, y = _data(n=2048)
    losses, dispatches = {}, {}
    for k in (1, 4, 16):
        registry = MetricsRegistry()
        prev = set_registry(registry)
        try:
            _init_ctx(k)
            m = _model()
            m.fit(x, y, batch_size=16, nb_epoch=2)
        finally:
            set_registry(prev)
        losses[k] = [h["loss"] for h in m._estimator.history]
        dispatches[k] = registry.histogram(
            "zoo_train_step_dispatch_seconds", "").summary()["count"]
    assert losses[4] == losses[1] and losses[16] == losses[1]
    assert dispatches == {1: 256, 4: 64, 16: 16}
