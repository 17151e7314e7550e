"""FeatureSet data-layer tests: sharded iteration, O(1)-IO resume,
process-shard slicing (multi-host locality), padding contracts.

Reference semantics: FeatureSet.scala:240-289 (iterator), :332-409
(DiskFeatureSet slice residency); tf_dataset.py:136-143 (batch contract).
"""

import glob
import os

import numpy as np
import pytest

from analytics_zoo_tpu.feature.dataset import (
    ArrayFeatureSet,
    FeatureSet,
    ShardedFeatureSet,
)


@pytest.fixture
def shard_dir(tmp_path):
    """6 shards with uneven sizes (including tiny ones < batch_size)."""
    rng = np.random.default_rng(0)
    sizes = [17, 5, 23, 11, 3, 19]
    start = 0
    for i, n in enumerate(sizes):
        x = np.arange(start, start + n, dtype=np.float32)[:, None] * [1.0, 2.0]
        y = np.arange(start, start + n, dtype=np.int32)
        np.savez(tmp_path / f"shard{i}.npz", x=x, y=y)
        start += n
    return str(tmp_path)


def _collect(fs, batch_size, **kw):
    return list(fs.batches(batch_size, shuffle=True, seed=5, epoch=2, **kw))


def test_npz_header_sizer(shard_dir):
    paths = sorted(glob.glob(os.path.join(shard_dir, "*.npz")))
    fs = ShardedFeatureSet(paths, n_slices=3)
    assert fs.num_samples == 17 + 5 + 23 + 11 + 3 + 19
    # sizing must not have populated the data cache
    assert not fs._cache


def test_sharded_resume_matches_full_iteration(shard_dir):
    paths = sorted(glob.glob(os.path.join(shard_dir, "*.npz")))
    full = _collect(ShardedFeatureSet(paths, n_slices=2), 8)
    for start in (1, 3, 5, len(full) - 1):
        tail = _collect(ShardedFeatureSet(paths, n_slices=2), 8,
                        start_batch=start)
        assert len(tail) == len(full) - start
        for a, b in zip(full[start:], tail):
            np.testing.assert_array_equal(a["x"], b["x"])
            np.testing.assert_array_equal(a["y"], b["y"])


def test_sharded_resume_skips_shard_io(shard_dir):
    paths = sorted(glob.glob(os.path.join(shard_dir, "*.npz")))
    loads = []

    def counting_loader(path):
        loads.append(path)
        data = np.load(path, allow_pickle=False)
        return {k: data[k] for k in data.files}

    fs = ShardedFeatureSet(paths, n_slices=2, loader=counting_loader)
    full = _collect(ShardedFeatureSet(paths, n_slices=2), 8)
    # size discovery for a custom loader loads each shard once
    fs._shard_sizes()
    n_size_loads = len(loads)
    fs._cache.clear()
    loads.clear()

    tail = _collect(fs, 8, start_batch=len(full) - 1)
    assert len(tail) == 1
    # only the shards contributing rows to the last batch are re-loaded
    assert 0 < len(loads) < len(paths), loads
    assert n_size_loads == len(paths)


def test_sharded_process_shard_reassembles(shard_dir):
    paths = sorted(glob.glob(os.path.join(shard_dir, "*.npz")))
    full = _collect(ShardedFeatureSet(paths, n_slices=2), 8)
    parts = [
        _collect(ShardedFeatureSet(paths, n_slices=2), 8,
                 process_shard=(pid, 2))
        for pid in range(2)
    ]
    for bi, batch in enumerate(full):
        rebuilt = np.concatenate([parts[0][bi]["x"], parts[1][bi]["x"]])
        np.testing.assert_array_equal(batch["x"], rebuilt)


def test_array_process_shard_and_padding():
    x = np.arange(22, dtype=np.float32)[:, None]
    y = np.arange(22, dtype=np.int32)
    fs = ArrayFeatureSet(x, y)
    full = list(fs.batches(8, shuffle=False, drop_last=False,
                           pad_to_batch=4))
    # last batch: 6 valid rows padded to 8
    assert len(full[-1]["x"]) == 8 and int(full[-1]["n_valid"]) == 6
    parts = [
        list(fs.batches(8, shuffle=False, drop_last=False, pad_to_batch=4,
                        process_shard=(pid, 2)))
        for pid in range(2)
    ]
    for bi, batch in enumerate(full):
        rebuilt = np.concatenate([parts[0][bi]["x"], parts[1][bi]["x"]])
        np.testing.assert_array_equal(batch["x"], rebuilt)
        # n_valid stays the GLOBAL count on every process
        for pid in range(2):
            assert parts[pid][bi].get("n_valid") == batch.get("n_valid")


@pytest.mark.parametrize("batch_size", [2, 3, 8, 12, 32])
def test_predict_drops_the_padding_of_every_batch(zoo_ctx, batch_size):
    """A batch the mesh does not divide is padded to it — each one, not
    only the last — and ``predict`` must return row i for record i
    whatever the batch size (found by chip_smoke's serve phase: rows 2-7
    of a batch-2 predict on eight devices were copies of row 1)."""
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    x = np.random.default_rng(0).normal(size=(21, 6)).astype(np.float32)
    net = Sequential()
    net.add(Dense(3, input_shape=(6,)))
    want = net.predict(x, batch_size=24)   # one batch, divisible by 8
    assert len({tuple(row) for row in want.round(5).tolist()}) == 21
    np.testing.assert_allclose(net.predict(x, batch_size=batch_size), want,
                               rtol=1e-6, atol=1e-6)


def test_resume_past_end_yields_nothing(shard_dir):
    paths = sorted(glob.glob(os.path.join(shard_dir, "*.npz")))
    fs = ShardedFeatureSet(paths, n_slices=2)
    n = len(_collect(ShardedFeatureSet(paths, n_slices=2), 8))
    assert _collect(fs, 8, start_batch=n + 3) == []


class TestPmemTier:
    """PMEM memory tier (reference FeatureSet.scala Optane tier): arrays
    spill to memory-mapped spool files; iteration, exact resume and fit()
    behave identically to DRAM while resident memory stays O(pages)."""

    def test_spill_produces_memmaps_with_identical_batches(self):
        from analytics_zoo_tpu.feature.dataset import FeatureSet

        rng = np.random.default_rng(0)
        x = rng.normal(size=(256, 12)).astype(np.float32)
        y = rng.integers(0, 3, size=(256,)).astype(np.int32)
        dram = FeatureSet.array(x, y)
        pmem = FeatureSet.array(x, y, memory_type="PMEM")
        assert isinstance(pmem.xs[0], np.memmap)
        assert not isinstance(dram.xs[0], np.memmap)
        for bd, bp in zip(dram.batches(32, seed=5, epoch=2),
                          pmem.batches(32, seed=5, epoch=2)):
            np.testing.assert_array_equal(bd["x"], bp["x"])
            np.testing.assert_array_equal(bd["y"], bp["y"])

    def test_resume_contract_survives_spill(self):
        from analytics_zoo_tpu.feature.dataset import FeatureSet

        rng = np.random.default_rng(1)
        x = rng.normal(size=(128, 4)).astype(np.float32)
        fs = FeatureSet.array(x, memory_type="PMEM")
        full = list(fs.batches(16, seed=3, epoch=1))
        resumed = list(fs.batches(16, seed=3, epoch=1, start_batch=4))
        for a, b in zip(full[4:], resumed):
            np.testing.assert_array_equal(a["x"], b["x"])

    def test_fit_through_pmem_tier(self):
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.feature.dataset import FeatureSet
        from analytics_zoo_tpu.pipeline.api.keras import Sequential
        from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

        zoo.init_zoo_context(seed=0)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(256, 8)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32)
        accs = {}
        for tier in ("DRAM", "PMEM"):
            fs = FeatureSet.array(x, y, memory_type=tier)
            m = Sequential()
            m.add(Dense(16, activation="relu", input_shape=(8,)))
            m.add(Dense(2, activation="softmax"))
            m.compile(optimizer="adam",
                      loss="sparse_categorical_crossentropy",
                      metrics=["accuracy"])
            m.fit(fs, batch_size=32, nb_epoch=30)
            accs[tier] = m.evaluate(x, y)["accuracy"]
        # the tier changes WHERE bytes live, not a single training bit
        assert accs["PMEM"] == accs["DRAM"], accs
        assert accs["PMEM"] > 0.9, accs


def test_npz_sizer_handles_v3_headers_and_falls_back(tmp_path):
    """npy header version (3,0) (numpy emits it for long utf-8 field
    names) must size from the header, and an unparseable member must fall
    back to a full load instead of raising."""
    import zipfile

    arr = np.arange(42, dtype=np.float32)[:, None] * [1.0, 2.0]
    p3 = str(tmp_path / "v3.npz")
    with zipfile.ZipFile(p3, "w") as z:
        with z.open("x.npy", "w") as f:
            np.lib.format.write_array(f, arr, version=(3, 0))
    assert ShardedFeatureSet._npz_first_dim(p3) == 42

    # header parse fails -> full-load fallback (np.load's own reader is
    # untouched: only the public per-version wrapper our sizer calls is
    # broken here)
    pbad = str(tmp_path / "bad.npz")
    np.savez(pbad, x=arr)
    import unittest.mock as mock
    with mock.patch("numpy.lib.format.read_array_header_1_0",
                    side_effect=ValueError("bad header")):
        assert ShardedFeatureSet._npz_first_dim(pbad) == 42

    # and num_samples uses it end-to-end
    fs = ShardedFeatureSet([p3], n_slices=1)
    assert fs.num_samples == 42
