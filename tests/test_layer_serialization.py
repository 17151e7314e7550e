"""Registry-enforced save/load round-trip for EVERY public keras layer —
the serialization half of the reference's SerializerSpec
(zoo/src/test/.../serializer/SerializerSpec.scala:32: every module class
must round-trip through serialization or CI fails; the oracle half lives
in tests/test_layer_oracle_enforcement.py).

Each spec builds a small net containing the layer, materializes weights,
saves with ``KerasNet.save`` (the whitelisting-unpickler path) and
reloads; forward outputs must be IDENTICAL (predict = inference mode, so
stochastic layers are deterministic).  The enforcement test fails for
any public layer class with no spec — a new layer cannot ship without
round-trip coverage.
"""

import os
import tempfile

import numpy as np
import pytest

from analytics_zoo_tpu import init_zoo_context
from analytics_zoo_tpu.pipeline.api.keras import Input, Model, Sequential
from analytics_zoo_tpu.pipeline.api.keras.topology import KerasNet


@pytest.fixture(autouse=True)
def _ctx():
    init_zoo_context("layer-serialization-test", seed=0)


def _x(shape, seed=0, scale=1.0):
    return (np.random.default_rng(seed).normal(size=shape)
            * scale).astype(np.float32)


def _ints(shape, hi, seed=0):
    return np.random.default_rng(seed).integers(
        0, hi, size=shape).astype(np.int32)


def _seq(layer_fn, in_shape, ints=None):
    """Single-input spec: Sequential([layer]) + input maker."""
    def build():
        net = Sequential()
        net.add(layer_fn())
        x = (_ints((2,) + in_shape[:1], ints) if ints
             else _x((2,) + in_shape))
        return net, x
    return build


def _glove_file():
    d = tempfile.mkdtemp()
    path = os.path.join(d, "glove.txt")
    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for w in ("alpha", "beta", "gamma"):
            vec = " ".join(f"{v:.4f}" for v in rng.normal(size=4))
            f.write(f"{w} {vec}\n")
    return path


def _specs():
    from analytics_zoo_tpu.pipeline.api.keras import layers as L

    S = {}

    def seq(name, fn, shape, ints=None):
        S[name] = _seq(fn, shape, ints)

    # ---- core / activations / elementwise ------------------------------
    seq("Dense", lambda: L.Dense(5, input_shape=(4,)), (4,))
    seq("Activation",
        lambda: L.Activation("tanh", input_shape=(4,)), (4,))
    seq("Dropout", lambda: L.Dropout(0.4, input_shape=(4,)), (4,))
    seq("Flatten", lambda: L.Flatten(input_shape=(2, 3)), (2, 3))
    seq("Reshape", lambda: L.Reshape((3, 2), input_shape=(2, 3)), (2, 3))
    seq("Permute",
        lambda: L.Permute((2, 1), input_shape=(2, 3)), (2, 3))
    seq("RepeatVector",
        lambda: L.RepeatVector(3, input_shape=(4,)), (4,))
    seq("Masking", lambda: L.Masking(0.0, input_shape=(3, 4)), (3, 4))
    seq("Highway", lambda: L.Highway(input_shape=(4,)), (4,))
    seq("MaxoutDense",
        lambda: L.MaxoutDense(5, input_shape=(4,)), (4,))
    seq("SparseDense",
        lambda: L.SparseDense(5, input_shape=(4,)), (4,))
    seq("Identity", lambda: L.Identity(input_shape=(4,)), (4,))
    seq("GaussianNoise",
        lambda: L.GaussianNoise(0.2, input_shape=(4,)), (4,))
    seq("GaussianDropout",
        lambda: L.GaussianDropout(0.2, input_shape=(4,)), (4,))
    seq("SpatialDropout1D",
        lambda: L.SpatialDropout1D(0.3, input_shape=(4, 3)), (4, 3))
    seq("SpatialDropout2D",
        lambda: L.SpatialDropout2D(0.3, input_shape=(4, 4, 3)), (4, 4, 3))
    seq("SpatialDropout3D",
        lambda: L.SpatialDropout3D(0.3, input_shape=(2, 4, 4, 3)),
        (2, 4, 4, 3))
    seq("ELU", lambda: L.ELU(input_shape=(4,)), (4,))
    seq("LeakyReLU", lambda: L.LeakyReLU(input_shape=(4,)), (4,))
    seq("PReLU", lambda: L.PReLU(input_shape=(4,)), (4,))
    seq("RReLU", lambda: L.RReLU(input_shape=(4,)), (4,))
    seq("SReLU", lambda: L.SReLU(input_shape=(4,)), (4,))
    seq("ParametricSoftPlus",
        lambda: L.ParametricSoftPlus(input_shape=(4,)), (4,))
    seq("ThresholdedReLU",
        lambda: L.ThresholdedReLU(0.5, input_shape=(4,)), (4,))
    seq("Threshold",
        lambda: L.Threshold(0.3, input_shape=(4,)), (4,))
    seq("BinaryThreshold",
        lambda: L.BinaryThreshold(0.1, input_shape=(4,)), (4,))
    seq("HardShrink", lambda: L.HardShrink(input_shape=(4,)), (4,))
    seq("SoftShrink", lambda: L.SoftShrink(input_shape=(4,)), (4,))
    seq("HardTanh", lambda: L.HardTanh(input_shape=(4,)), (4,))
    seq("Softmax", lambda: L.Softmax(input_shape=(4,)), (4,))
    seq("AddConstant",
        lambda: L.AddConstant(1.5, input_shape=(4,)), (4,))
    seq("MulConstant",
        lambda: L.MulConstant(2.0, input_shape=(4,)), (4,))
    seq("Negative", lambda: L.Negative(input_shape=(4,)), (4,))
    seq("Exp", lambda: L.Exp(input_shape=(4,)), (4,))
    seq("Log", lambda: L.Log(input_shape=(4,)), (4,))
    seq("Sqrt", lambda: L.Sqrt(input_shape=(4,)), (4,))
    seq("Square", lambda: L.Square(input_shape=(4,)), (4,))
    seq("Power", lambda: L.Power(2.0, input_shape=(4,)), (4,))
    seq("CAdd", lambda: L.CAdd((4,), input_shape=(4,)), (4,))
    seq("CMul", lambda: L.CMul((4,), input_shape=(4,)), (4,))
    seq("Scale", lambda: L.Scale((4,), input_shape=(4,)), (4,))
    seq("Mul", lambda: L.Mul(input_shape=(4,)), (4,))
    seq("Select", lambda: L.Select(1, 2, input_shape=(4, 3)), (4, 3))
    seq("Squeeze", lambda: L.Squeeze(1, input_shape=(1, 4)), (1, 4))
    seq("ExpandDim", lambda: L.ExpandDim(1, input_shape=(4,)), (4,))
    seq("Expand",
        lambda: L.Expand((3, 4), input_shape=(1, 4)), (1, 4))
    seq("Narrow",
        lambda: L.Narrow(1, 1, 2, input_shape=(4, 3)), (4, 3))
    seq("Max", lambda: L.Max(1, input_shape=(4, 3)), (4, 3))
    seq("GetShape", lambda: L.GetShape(input_shape=(4, 3)), (4, 3))
    seq("SpaceToDepth",
        lambda: L.SpaceToDepth(2, input_shape=(4, 4, 3)), (4, 4, 3))
    seq("ResizeBilinear",
        lambda: L.ResizeBilinear(6, 6, input_shape=(4, 4, 3)), (4, 4, 3))

    # ---- conv / pooling / padding / upsampling -------------------------
    seq("Convolution1D",
        lambda: L.Convolution1D(4, 3, input_shape=(8, 3)), (8, 3))
    seq("Convolution2D",
        lambda: L.Convolution2D(4, 3, 3, input_shape=(8, 8, 3)),
        (8, 8, 3))
    seq("Convolution3D",
        lambda: L.Convolution3D(4, 3, 3, 3, input_shape=(6, 6, 6, 2)),
        (6, 6, 6, 2))
    seq("AtrousConvolution1D",
        lambda: L.AtrousConvolution1D(4, 3, atrous_rate=2,
                                      input_shape=(10, 3)), (10, 3))
    seq("AtrousConvolution2D",
        lambda: L.AtrousConvolution2D(4, 3, 3, atrous_rate=(2, 2),
                                      input_shape=(10, 10, 3)),
        (10, 10, 3))
    seq("SeparableConvolution2D",
        lambda: L.SeparableConvolution2D(4, 3, input_shape=(8, 8, 3)),
        (8, 8, 3))
    seq("DepthwiseConvolution2D",
        lambda: L.DepthwiseConvolution2D(3, input_shape=(8, 8, 3)),
        (8, 8, 3))
    seq("Deconvolution2D",
        lambda: L.Deconvolution2D(4, 3, 3, input_shape=(6, 6, 3)),
        (6, 6, 3))
    seq("ShareConvolution2D",
        lambda: L.ShareConvolution2D(4, 3, 3, input_shape=(8, 8, 3)),
        (8, 8, 3))
    seq("LocallyConnected1D",
        lambda: L.LocallyConnected1D(4, 3, input_shape=(8, 3)), (8, 3))
    seq("LocallyConnected2D",
        lambda: L.LocallyConnected2D(4, 3, 3, input_shape=(6, 6, 2)),
        (6, 6, 2))
    for rank, shape in ((1, (8, 3)), (2, (8, 8, 3)), (3, (4, 4, 4, 2))):
        seq(f"MaxPooling{rank}D",
            lambda rank=rank, shape=shape: getattr(
                L, f"MaxPooling{rank}D")(input_shape=shape), shape)
        seq(f"AveragePooling{rank}D",
            lambda rank=rank, shape=shape: getattr(
                L, f"AveragePooling{rank}D")(input_shape=shape), shape)
        seq(f"GlobalMaxPooling{rank}D",
            lambda rank=rank, shape=shape: getattr(
                L, f"GlobalMaxPooling{rank}D")(input_shape=shape), shape)
        seq(f"GlobalAveragePooling{rank}D",
            lambda rank=rank, shape=shape: getattr(
                L, f"GlobalAveragePooling{rank}D")(input_shape=shape),
            shape)
        # Cropping1D takes (left, right); 2D/3D take per-dim pairs
        crop_arg = (1, 1) if rank == 1 else [1] * rank
        seq(f"Cropping{rank}D",
            lambda rank=rank, shape=shape, crop_arg=crop_arg: getattr(
                L, f"Cropping{rank}D")(crop_arg, input_shape=shape),
            shape)
        seq(f"ZeroPadding{rank}D",
            lambda rank=rank, shape=shape: getattr(
                L, f"ZeroPadding{rank}D")(1, input_shape=shape), shape)
        seq(f"UpSampling{rank}D",
            lambda rank=rank, shape=shape: getattr(
                L, f"UpSampling{rank}D")(input_shape=shape), shape)
    seq("LRN2D", lambda: L.LRN2D(input_shape=(6, 6, 4)), (6, 6, 4))
    seq("WithinChannelLRN2D",
        lambda: L.WithinChannelLRN2D(input_shape=(6, 6, 4)), (6, 6, 4))

    # ---- normalization -------------------------------------------------
    seq("BatchNormalization",
        lambda: L.BatchNormalization(input_shape=(6, 6, 4)), (6, 6, 4))
    seq("LayerNormalization",
        lambda: L.LayerNormalization(input_shape=(6,)), (6,))

    # ---- recurrent -----------------------------------------------------
    seq("SimpleRNN",
        lambda: L.SimpleRNN(5, input_shape=(4, 3)), (4, 3))
    seq("LSTM", lambda: L.LSTM(5, input_shape=(4, 3)), (4, 3))
    seq("GRU", lambda: L.GRU(5, input_shape=(4, 3)), (4, 3))
    seq("ConvLSTM2D",
        lambda: L.ConvLSTM2D(4, 3, input_shape=(3, 6, 6, 2)),
        (3, 6, 6, 2))
    seq("ConvLSTM3D",
        lambda: L.ConvLSTM3D(2, 3, input_shape=(2, 4, 4, 4, 2)),
        (2, 4, 4, 4, 2))
    seq("Bidirectional",
        lambda: L.Bidirectional(L.LSTM(4, return_sequences=True),
                                input_shape=(4, 3)), (4, 3))
    seq("TimeDistributed",
        lambda: L.TimeDistributed(L.Dense(5), input_shape=(4, 3)),
        (4, 3))

    # ---- embeddings / attention ----------------------------------------
    seq("Embedding",
        lambda: L.Embedding(11, 6, input_shape=(5,)), (5,), ints=11)
    seq("SparseEmbedding",
        lambda: L.SparseEmbedding(11, 6, input_shape=(5,)), (5,),
        ints=11)
    seq("WordEmbedding",
        lambda: L.WordEmbedding(_glove_file(), input_length=5), (5,),
        ints=3)
    seq("TransformerLayer",
        lambda: L.TransformerLayer(vocab=17, seq_len=6, n_block=1,
                                   n_head=2, hidden_size=8,
                                   input_shape=(6,)), (6,), ints=17)

    seq("LoopedDecoder",
        lambda: L.LoopedDecoder(vocab=17, n_block=1, n_head=2,
                                hidden_size=8, intermediate_size=12,
                                passes=2, input_shape=(6,)), (6,), ints=17)

    seq("LatentMoEDecoder",
        lambda: L.LatentMoEDecoder(
            vocab=17, n_block=2, n_head=2, hidden_size=8,
            intermediate_size=12, kv_latent_rank=4, qk_nope_dim=4,
            qk_rope_dim=2, v_head_dim=4, routed_experts=4, experts_held=2,
            experts_per_token=2, expert_size=6, shared_experts=1,
            input_shape=(6,)), (6,), ints=17)

    # ---- multi-input / multi-output graphs -----------------------------
    def merge_spec():
        a, b = Input(shape=(4,)), Input(shape=(4,))
        out = L.Merge(mode="sum")([a, b])
        net = Model([a, b], out)
        return net, [_x((2, 4), 1), _x((2, 4), 2)]
    S["Merge"] = merge_spec

    def select_table_spec():
        a, b = Input(shape=(4,)), Input(shape=(3,))
        out = L.SelectTable(1)([a, b])
        net = Model([a, b], out)
        return net, [_x((2, 4), 1), _x((2, 3), 2)]
    S["SelectTable"] = select_table_spec

    def split_tensor_spec():
        a = Input(shape=(4, 6))
        parts = L.SplitTensor(2, 2)(a)
        net = Model(a, parts)
        return net, _x((2, 4, 6))
    S["SplitTensor"] = split_tensor_spec

    def sampler_spec():
        mean, logv = Input(shape=(4,)), Input(shape=(4,))
        out = L.GaussianSampler()([mean, logv])
        net = Model([mean, logv], out)
        return net, [_x((2, 4), 1), _x((2, 4), 2)]
    S["GaussianSampler"] = sampler_spec

    def bert_spec():
        bert = L.BERT(vocab=17, hidden_size=8, n_block=1, n_head=2,
                      seq_len=6, intermediate_size=16)
        ids = Input(shape=(6,))
        types = Input(shape=(6,))
        pos = Input(shape=(6,))
        mask = Input(shape=(6,))   # (B, L) 1/0 — the reference contract
        seq_out, pooled = bert([ids, types, pos, mask])
        net = Model([ids, types, pos, mask], [seq_out, pooled])
        rng = np.random.default_rng(0)
        return net, [
            rng.integers(0, 17, (2, 6)).astype(np.int32),
            np.zeros((2, 6), np.int32),
            np.tile(np.arange(6, dtype=np.int32), (2, 1)),
            np.ones((2, 6), np.float32),
        ]
    S["BERT"] = bert_spec

    return S


# Symbolic/abstract surface with no concrete serialization story of its
# own (Input returns a Variable; InputLayer/Layer are plumbing).
SKIP = {"Input", "InputLayer", "Layer"}


def _public_classes():
    import inspect

    import analytics_zoo_tpu.pipeline.api.keras.layers as L

    out = {}
    for n in dir(L):
        if n.startswith("_"):
            continue
        obj = getattr(L, n)
        if inspect.ismodule(obj):
            continue
        out[n] = obj
    return out


def test_every_public_layer_has_a_serialization_spec():
    """The SerializerSpec enforcement: a public layer class with neither a
    spec nor an alias sharing one fails CI."""
    public = _public_classes()
    specs = _specs()
    covered_objs = {id(public[n]) for n in specs if n in public}
    missing = [
        n for n, obj in public.items()
        if n not in SKIP and n not in specs and id(obj) not in covered_objs
    ]
    assert not missing, (
        f"{len(missing)} public layers lack a save/load round-trip spec "
        f"in test_layer_serialization.py: {sorted(missing)}")
    stale = [n for n in specs if n not in public]
    assert not stale, f"specs for nonexistent layers: {stale}"


@pytest.mark.parametrize("name", sorted(_specs()))
def test_layer_roundtrip(name, tmp_path):
    net, x = _specs()[name]()
    before = net.predict(x, batch_size=2)
    path = str(tmp_path / f"{name}.zoo")
    net.save(path)
    loaded = KerasNet.load(path)
    after = loaded.predict(x, batch_size=2)
    if isinstance(before, list):
        assert isinstance(after, list) and len(after) == len(before)
        for b, a in zip(before, after):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
    else:
        np.testing.assert_array_equal(np.asarray(before),
                                      np.asarray(after))
