"""Precision plane (ISSUE 16): ``dtype_rules`` as the FOURTH rule table
on :class:`ShardingPlan` — bf16 compute + f32 masters/accumulation
(``mixed_precision()``), the int8 weight-only serving role, the dtype-
aware cost-model ceilings behind ``plan="auto"``, the generalized
``hlo-dtype-policy`` lint, the checkpoint's dtype-policy contract, and
what the lowered step and the served weights show of the policy.

The core claims pinned here:

- masters stay f32 and the bf16 trajectory tracks f32 within tolerance
  (the cast is in-graph, so grads/collectives/optimizer stay f32);
- elastic resume of the f32 masters across world sizes under
  ``mixed_precision()`` is BIT-exact (same contract as the sharding
  plans' resume tests);
- resuming under a DIFFERENT dtype policy fails loudly
  (``ZOO_DTYPE_RESUME=cast`` is the deliberate escape hatch);
- ``dtype_rules`` participate in the plan cache key, so a bf16 program
  never collides with its f32 twin in the compiled-step cache.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Rule table / plan vocabulary units
# ---------------------------------------------------------------------------


class TestDtypeRules:
    def test_first_match_wins_and_scalars_exempt(self):
        from analytics_zoo_tpu.parallel.plan import ShardingPlan

        plan = ShardingPlan(
            name="t",
            dtype_rules=((r"dense_0/kernel", "f32"), (r".*", "bf16")))
        tree = {"dense_0": {"kernel": np.zeros((4, 4), np.float32),
                            "bias": np.zeros((4,), np.float32)},
                "step": np.zeros((), np.float32)}
        roles = plan.dtype_roles(tree)
        assert roles["dense_0/kernel"] == "f32"
        assert roles["dense_0/bias"] == "bf16"
        # scalar leaves never appear in the role map (never down-cast)
        assert "step" not in roles

    def test_invalid_role_raises_at_construction(self):
        from analytics_zoo_tpu.parallel.plan import ShardingPlan

        with pytest.raises(ValueError, match="role"):
            ShardingPlan(name="t", dtype_rules=((".*", "f8"),))

    def test_cast_params_for_compute_keeps_masters(self):
        from analytics_zoo_tpu.parallel.plan import mixed_precision

        plan = mixed_precision()
        params = {"dense_0": {"kernel": jnp.ones((4, 4), jnp.float32),
                              "bias": jnp.ones((4,), jnp.float32)},
                  "scale": jnp.ones((), jnp.float32)}
        compute = plan.cast_params_for_compute(params)
        assert compute["dense_0"]["kernel"].dtype == jnp.bfloat16
        assert compute["dense_0"]["bias"].dtype == jnp.bfloat16
        # scalar exemption: a loss scale keeps its width
        assert compute["scale"].dtype == jnp.float32
        # masters untouched
        assert params["dense_0"]["kernel"].dtype == jnp.float32

    def test_cache_key_participation(self):
        from analytics_zoo_tpu.parallel.plan import (
            data_parallel,
            mixed_precision,
            with_dtype,
        )

        dp = data_parallel()
        mp = mixed_precision()
        assert dp.cache_key() != mp.cache_key()
        assert with_dtype(dp, "f16").cache_key() != mp.cache_key()

    def test_policy_round_trip_and_names(self):
        from analytics_zoo_tpu.parallel.plan import (
            fsdp,
            int8_serving,
            mixed_precision,
            resolve_dtype_rules,
            resolve_plan,
            with_dtype_policy,
        )

        mp = mixed_precision()
        assert mp.name == "dp+bf16"
        assert mp.dtype_policy_str() == ".*=bf16"
        assert resolve_dtype_rules(mp.dtype_policy_str()) == mp.dtype_rules
        assert resolve_dtype_rules("bf16_mixed") == mp.dtype_rules
        assert int8_serving().dtype_rules == ((".*", "int8"),)
        assert with_dtype_policy(fsdp(), "int8_serving").name == "fsdp+int8"
        # name suffix resolution composes with +overlap
        p = resolve_plan("zero1+overlap+bf16")
        assert p.name == "zero1+overlap+bf16"
        assert p.dtype_rules == ((".*", "bf16"),)
        # "auto" is the oracle's job, not a rule string
        with pytest.raises(ValueError, match="auto"):
            resolve_dtype_rules("auto")

    def test_zoo_dtype_policy_env_validated_eagerly(self, monkeypatch):
        from analytics_zoo_tpu.common.engine import ZooConfig

        monkeypatch.setenv("ZOO_DTYPE_POLICY", "bf17")
        with pytest.raises(ValueError, match="ZOO_DTYPE_POLICY"):
            ZooConfig()
        monkeypatch.setenv("ZOO_DTYPE_POLICY", "bf16_mixed")
        assert ZooConfig().dtype_policy == "bf16_mixed"
        monkeypatch.setenv("ZOO_DTYPE_POLICY", "auto")
        assert ZooConfig().dtype_policy == "auto"

    def test_sharding_plan_env_accepts_dtype_suffix(self, monkeypatch):
        from analytics_zoo_tpu.common.engine import ZooConfig

        monkeypatch.setenv("ZOO_SHARDING_PLAN", "zero1+overlap+bf16")
        assert ZooConfig().sharding_plan == "zero1+overlap+bf16"
        monkeypatch.setenv("ZOO_SHARDING_PLAN", "zero1+bf17")
        with pytest.raises(ValueError, match="ZOO_SHARDING_PLAN"):
            ZooConfig()


# ---------------------------------------------------------------------------
# Training: trajectory tolerance, masters, resume contracts
# ---------------------------------------------------------------------------


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(8, 4))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return x, y


def _fit(mesh_size, ckpt_dir, epochs, plan=None):
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    zoo.init_zoo_context(seed=3, mesh_shape={"data": mesh_size})
    x, y = _data()
    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    if ckpt_dir:
        m.set_checkpoint(ckpt_dir)
    m.fit(x, y, batch_size=32, nb_epoch=epochs, plan=plan)
    return m


class TestMixedPrecisionTraining:
    def test_bf16_trajectory_tracks_f32_with_f32_masters(self):
        from analytics_zoo_tpu.parallel.plan import mixed_precision

        f32 = _fit(2, None, 2)
        mp = _fit(2, None, 2, plan=mixed_precision())
        l32 = [h["loss"] for h in f32._estimator.history]
        lmp = [h["loss"] for h in mp._estimator.history]
        for a, b in zip(l32, lmp):
            assert abs(a - b) / max(abs(a), 1e-9) < 0.05, (l32, lmp)
        # masters (and optimizer moments) stay f32 — the bitwise-stable
        # optimizer state contract
        for leaf in jax.tree_util.tree_leaves(mp._estimator.model.params):
            assert leaf.dtype == jnp.float32
        for leaf in jax.tree_util.tree_leaves(mp._estimator._opt_state):
            if hasattr(leaf, "dtype") and jnp.issubdtype(
                    leaf.dtype, jnp.floating):
                assert leaf.dtype == jnp.float32
        rec = mp._estimator._plan_record
        assert rec["name"] == "dp+bf16"
        assert rec["dtype_policy"] == ".*=bf16"

    def test_elastic_resume_bit_exact_across_world_sizes(self, tmp_path):
        """f32 masters reshard bit-exact 8 → 4 under mixed_precision():
        the precision plane composes with the elastic-resume contract
        (same shape as the fsdp/zeroN resume tests)."""
        from analytics_zoo_tpu.parallel.plan import mixed_precision

        ckdir = str(tmp_path / "ck_mp")
        full = _fit(8, None, 4, plan=mixed_precision())
        losses_full = [h["loss"] for h in full._estimator.history]

        first = _fit(8, ckdir, 2, plan=mixed_precision())
        assert [h["loss"] for h in first._estimator.history] \
            == losses_full[:2]  # bitwise

        resumed = _fit(4, ckdir, 4, plan=mixed_precision())
        losses_resumed = [h["loss"] for h in resumed._estimator.history]
        assert len(losses_resumed) == 2, losses_resumed
        assert losses_resumed == losses_full[2:]  # bitwise

    def test_resume_under_different_policy_fails_loudly(
            self, tmp_path, monkeypatch):
        from analytics_zoo_tpu.parallel.plan import mixed_precision

        ckdir = str(tmp_path / "ck_policy")
        _fit(2, ckdir, 1, plan=mixed_precision())
        with pytest.raises(ValueError, match="dtype policy"):
            _fit(2, ckdir, 2, plan=None)
        # the deliberate escape hatch
        monkeypatch.setenv("ZOO_DTYPE_RESUME", "cast")
        m = _fit(2, ckdir, 2, plan=None)
        assert len(m._estimator.history) == 1  # epoch 2 only: resumed

    def test_auto_plan_sweeps_dtype_under_auto_policy(self, monkeypatch):
        monkeypatch.setenv("ZOO_DTYPE_POLICY", "auto")
        m = _fit(2, None, 1, plan="auto")
        rec = m._estimator._plan_record
        assert rec["auto"]["chosen_dtype"] == "bf16"
        assert any(c["dtype"] == "bf16" for c in rec["auto"]["candidates"])
        assert rec["name"].endswith("+bf16")
        assert rec["dtype_policy"] == ".*=bf16"


# ---------------------------------------------------------------------------
# Cost model: dtype ceilings + collective accounting
# ---------------------------------------------------------------------------


class TestDtypeCostModel:
    def test_dtype_peaks_scale_flops_only(self):
        from analytics_zoo_tpu.analysis.costmodel import (
            PeakTable,
            dtype_peaks,
        )

        peaks = PeakTable(flops=1e12, hbm_bytes_per_s=1e11,
                          link_bytes_per_s=1e10,
                          dispatch_overhead_s=1e-4,
                          hbm_bytes=16e9, source="test")
        b = dtype_peaks(peaks, "bf16")
        assert b.flops == 2e12
        assert b.hbm_bytes_per_s == peaks.hbm_bytes_per_s
        assert dtype_peaks(peaks, None) is peaks
        with pytest.raises(ValueError):
            dtype_peaks(peaks, "f8")

    def test_gather_bytes_shrink_grad_bytes_do_not(self):
        """fsdp at bf16: only the param-gather 2P scales by 0.5 — the
        reduce-scatter P stays f32 per the accumulation contract, so
        the predicted ratio is exactly (1 + 2·0.5)/3 = 2/3."""
        from analytics_zoo_tpu.analysis.costmodel import (
            plan_collective_bytes,
        )

        pb = 1 << 20
        f32 = plan_collective_bytes(pb, "fsdp", 8)
        bf16 = plan_collective_bytes(pb, "fsdp", 8, dtype="bf16")
        assert abs(bf16 / f32 - 2 / 3) < 1e-6
        # dp has no param gather: nothing shrinks
        assert plan_collective_bytes(pb, "dp", 8, dtype="bf16") \
            == plan_collective_bytes(pb, "dp", 8)

    def test_choose_plan_dtype_sweep_prefers_bf16_under_tight_slo(self):
        from analytics_zoo_tpu.analysis.costmodel import PeakTable
        from analytics_zoo_tpu.analysis.oracle import ConfigOracle

        peaks = PeakTable(flops=1e12, hbm_bytes_per_s=1e11,
                          link_bytes_per_s=1e10,
                          dispatch_overhead_s=1e-5,
                          hbm_bytes=64 << 30, source="test")
        oracle = ConfigOracle(peaks=peaks)
        # a compute-bound program: 10 TFLOP per step over the 1 TFLOP/s
        # ceiling dominates the collective seconds, so the doubled bf16
        # matmul rate is the decisive term
        feats = {"matmul_flops": 1e13, "bytes_accessed": 1e9}
        # default: no dtype options — behavior (and the pinned oracle
        # tests' expectations) unchanged
        name, doc = oracle.choose_plan(1 << 30, 2 << 30, 8,
                                       features=feats,
                                       activation_bytes=1 << 30)
        assert doc.get("chosen_dtype") is None
        # with the sweep: the candidates carry the dtype dimension and
        # bf16 wins on the halved compute term
        name2, doc2 = oracle.choose_plan(
            1 << 30, 2 << 30, 8, features=feats,
            activation_bytes=1 << 30,
            dtype_options=(None, "bf16"))
        assert doc2["chosen_dtype"] == "bf16"
        assert any(c["config"].endswith("+bf16")
                   for c in doc2["candidates"])
        assert {c["dtype"] for c in doc2["candidates"]} == {None, "bf16"}

    def test_histogram_compute_dtype(self):
        from analytics_zoo_tpu.analysis.costmodel import (
            histogram_compute_dtype,
        )

        assert histogram_compute_dtype({"f32": 10, "bf16": 40}) == "bf16"
        assert histogram_compute_dtype({"f32": 10, "i32": 99}) == "f32"
        assert histogram_compute_dtype({}) is None
        assert histogram_compute_dtype(None) is None


# ---------------------------------------------------------------------------
# hlo-dtype-policy lint fixtures
# ---------------------------------------------------------------------------


class TestDtypePolicyLint:
    def test_f32_matmul_under_bf16_policy_flagged(self):
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        text = jax.jit(lambda a, b: a @ b).lower(
            np.zeros((8, 16), np.float32),
            np.zeros((16, 4), np.float32)).as_text()
        rpt = analyze_hlo_text(text, "mm", dtype_policy=".*=bf16")
        assert "hlo-dtype-policy" in {f.rule for f in rpt.findings}
        assert rpt.dtype_policy == ".*=bf16"

    def test_bf16_matmul_under_bf16_policy_clean(self):
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        text = jax.jit(lambda a, b: a @ b).lower(
            np.zeros((8, 16), np.dtype("bfloat16")),
            np.zeros((16, 4), np.dtype("bfloat16"))).as_text()
        rpt = analyze_hlo_text(text, "mm16", dtype_policy=".*=bf16")
        assert "hlo-dtype-policy" not in {f.rule for f in rpt.findings}

    def test_low_precision_all_reduce_breaks_accum_contract(self):
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        devices = jax.devices()[:2]
        f = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i",
                     devices=devices)
        text = f.lower(
            np.zeros((2, 8), np.dtype("bfloat16"))).as_text()
        rpt = analyze_hlo_text(text, "psum16", dtype_policy=".*=bf16")
        msgs = [f.message for f in rpt.findings
                if f.rule == "hlo-dtype-policy"]
        assert any("f32-accumulation" in m for m in msgs), msgs

    def test_suppressed_without_policy(self):
        """The same f32 matmul is CLEAN with no policy declared (None)
        or under a pure-f32 policy — the lint only checks a declared
        low-precision contract."""
        from analytics_zoo_tpu.analysis import analyze_hlo_text

        text = jax.jit(lambda a, b: a @ b).lower(
            np.zeros((8, 16), np.float32),
            np.zeros((16, 4), np.float32)).as_text()
        for policy in (None, "", ".*=f32"):
            rpt = analyze_hlo_text(text, "mm", dtype_policy=policy)
            assert "hlo-dtype-policy" not in {
                f.rule for f in rpt.findings}, policy


# ---------------------------------------------------------------------------
# int8 serving + explicit zero1 policy carry
# ---------------------------------------------------------------------------


class TestInt8Serving:
    def test_plan_aware_quantization_respects_roles_and_heuristic(self):
        from analytics_zoo_tpu.parallel.plan import ShardingPlan, int8_serving
        from analytics_zoo_tpu.pipeline.inference.quantize import (
            QuantizedTensor,
            quantize_params_for_plan,
        )

        params = {
            "dense_0": {"kernel": jnp.ones((64, 64), jnp.float32),
                        "bias": jnp.ones((64,), jnp.float32)},
            "norm": {"scale": jnp.ones((64,), jnp.float32)},
        }
        q = quantize_params_for_plan(params, int8_serving())
        assert isinstance(q["dense_0"]["kernel"], QuantizedTensor)
        # 1-D leaves fail the structural heuristic even under .*=int8
        assert not isinstance(q["dense_0"]["bias"], QuantizedTensor)
        assert not isinstance(q["norm"]["scale"], QuantizedTensor)
        # a rule that marks nothing int8 is a no-op tree
        noop = ShardingPlan(name="t", dtype_rules=((".*", "bf16"),))
        q2 = quantize_params_for_plan(params, noop)
        assert q2 is params

    def test_predict_parity_and_bytes_ratio(self):
        from analytics_zoo_tpu.parallel.plan import int8_serving
        from analytics_zoo_tpu.pipeline.inference.quantize import (
            dequantize_params,
            quantize_params_for_plan,
            quantized_bytes_ratio,
        )

        rng = np.random.default_rng(5)
        params = {"k": jnp.asarray(
            rng.normal(size=(64, 64)).astype(np.float32))}
        q = quantize_params_for_plan(params, int8_serving())
        ratio = quantized_bytes_ratio(params, q)
        # int8 values + per-channel f32 scales ≈ 0.266x of f32
        assert ratio < 0.3, ratio
        x = rng.normal(size=(8, 64)).astype(np.float32)
        base = np.asarray(x @ params["k"])
        served = np.asarray(x @ dequantize_params(q)["k"])
        denom = np.linalg.norm(base)
        assert np.linalg.norm(base - served) / denom < 0.01

    def test_reshard_zero1_carries_dtype_policy(self):
        """The explicit zero1 reshard path records the dtype policy on
        its placement plan, so the resharded state keeps the precision
        contract it was trained under."""
        from analytics_zoo_tpu.parallel.plan import ShardingPlan
        from analytics_zoo_tpu.parallel.strategies import (
            reshard_zero1_opt_state,
        )

        import analytics_zoo_tpu as zoo

        zoo.init_zoo_context(seed=0, mesh_shape={"data": 4})
        params = {"w": np.zeros((8, 4), np.float32)}
        n_old = 8
        size = 32
        pad = (-size) % n_old
        flat = np.arange(size + pad, dtype=np.float32)
        opt_state = {"mu": flat.copy(), "nu": flat.copy(),
                     "count": np.zeros((), np.float32)}
        out = reshard_zero1_opt_state(opt_state, params, n_old=n_old,
                                      dtype_policy=".*=bf16")
        # values re-padded for the new axis and still intact
        np.testing.assert_array_equal(
            np.asarray(out["mu"])[:size], flat[:size])
        # and the policy string round-trips through a plan
        probe = ShardingPlan(name="t", dtype_rules=((".*", "bf16"),))
        assert probe.dtype_policy_str() == ".*=bf16"


# ---------------------------------------------------------------------------
# What the lowered programs and the served weights show of the policy
# ---------------------------------------------------------------------------


def _wide_fit(plan, report_dir, monkeypatch):
    """Two epochs of a 32 -> 256 -> 256 -> 10 net under ``plan`` on the
    8-device mesh; returns the model, its losses, the compile plane's
    features of the step and the report's dtype histogram of it."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.analysis.costmodel import load_report_rows
    from analytics_zoo_tpu.analysis.hlo import last_features
    from analytics_zoo_tpu.parallel.plan import resolve_plan
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    monkeypatch.setenv("ZOO_HLO_REPORT_DIR", report_dir)
    zoo.init_zoo_context(seed=11, mesh_shape={"data": 8}, platform="cpu")
    plan = resolve_plan(plan)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(512, 32)).astype(np.float32)
    y = np.argmax(x @ rng.normal(size=(32, 10)), axis=1).astype(np.int32)
    m = Sequential()
    m.add(Dense(256, activation="relu", input_shape=(32,)))
    m.add(Dense(256, activation="relu"))
    m.add(Dense(10, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    m.fit(x, y, batch_size=64, nb_epoch=2, plan=plan)
    label = "train_step" if plan.name == "dp" \
        else f"train_step_{plan.name}"
    row = next(r for r in load_report_rows(report_dir)
               if r["label"] == label)
    return {"model": m, "x": x, "hlo": last_features(label),
            "losses": [h["loss"] for h in m._estimator.history],
            "dtype_histogram": row["dtype_histogram"] or {}}


def test_bf16_shift_shows_in_the_lowered_step_and_int8_in_served_bytes(
        tmp_path, monkeypatch):
    """f32 against ``mixed_precision()``: the trajectory within
    tolerance, bf16 ops in the bf16 step's report and none in the f32
    one's, fewer bytes through the lowered program.  Then the f32 leg's
    trained weights quantized under ``int8_serving()``: under half the
    bytes, and ``predict`` within 0.05 of the f32 answers."""
    from analytics_zoo_tpu.parallel.plan import int8_serving, mixed_precision
    from analytics_zoo_tpu.pipeline.inference.quantize import (
        dequantize_params,
        quantize_params_for_plan,
        quantized_bytes_ratio,
    )

    f32 = _wide_fit("dp", str(tmp_path / "f32"), monkeypatch)
    bf16 = _wide_fit(mixed_precision(), str(tmp_path / "bf16"), monkeypatch)
    assert max(abs(a - b) / max(abs(a), 1e-9)
               for a, b in zip(f32["losses"], bf16["losses"])) <= 0.05
    assert f32["dtype_histogram"].get("bf16", 0) == 0
    assert bf16["dtype_histogram"].get("bf16", 0) > 0
    # per-plan labels, distinct cache keys: each leg has its own features
    assert bf16["hlo"]["bytes_accessed"] < f32["hlo"]["bytes_accessed"]

    m, x = f32["model"], f32["x"]
    params = m.params
    qparams = quantize_params_for_plan(params, int8_serving())
    assert quantized_bytes_ratio(params, qparams) < 0.5
    base = np.asarray(m.predict(x[:64]))
    m._estimator.model.params = dequantize_params(qparams)
    served = np.asarray(m.predict(x[:64]))
    assert float(np.max(np.abs(base - served))) < 0.05
