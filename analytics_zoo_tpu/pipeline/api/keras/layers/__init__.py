"""Layer namespace — mirrors reference
pyzoo/zoo/pipeline/api/keras/layers/__init__.py (120 Keras-1 layers)."""

from analytics_zoo_tpu.pipeline.api.keras.engine import (  # noqa: F401
    Input,
    InputLayer,
    Layer,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.core import (  # noqa: F401
    Activation,
    Dense,
    Dropout,
    ExpandDim,
    Flatten,
    GaussianDropout,
    GaussianNoise,
    Highway,
    Identity,
    Masking,
    MaxoutDense,
    Permute,
    RepeatVector,
    Reshape,
    Select,
    SparseDense,
    SpatialDropout1D,
    SpatialDropout2D,
    SpatialDropout3D,
    Squeeze,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.conv import (  # noqa: F401
    AtrousConvolution1D,
    AtrousConvolution2D,
    Convolution1D,
    Convolution2D,
    Convolution3D,
    Cropping1D,
    Cropping2D,
    Cropping3D,
    Deconvolution2D,
    DepthwiseConvolution2D,
    LocallyConnected1D,
    LocallyConnected2D,
    SeparableConvolution2D,
    ShareConvolution2D,
    UpSampling1D,
    UpSampling2D,
    UpSampling3D,
    ZeroPadding1D,
    ZeroPadding2D,
    ZeroPadding3D,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.embedding import (  # noqa: F401
    Embedding,
    SparseEmbedding,
    WordEmbedding,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.merge import (  # noqa: F401
    Merge,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (  # noqa: F401
    BatchNormalization,
    LayerNormalization,
    WithinChannelLRN2D,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.advanced import (  # noqa: F401
    ELU,
    LeakyReLU,
    ParametricSoftPlus,
    PReLU,
    SReLU,
    ThresholdedReLU,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.recurrent import (  # noqa: F401
    GRU,
    LSTM,
    Bidirectional,
    ConvLSTM2D,
    ConvLSTM3D,
    SimpleRNN,
    TimeDistributed,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import (  # noqa: F401
    BERT,
    LatentMoEDecoder,
    LoopedDecoder,
    TransformerLayer,
)
from analytics_zoo_tpu.pipeline.api.keras.layers.pooling import (  # noqa: F401
    AveragePooling1D,
    AveragePooling2D,
    AveragePooling3D,
    GlobalAveragePooling1D,
    GlobalAveragePooling2D,
    GlobalAveragePooling3D,
    GlobalMaxPooling1D,
    GlobalMaxPooling2D,
    GlobalMaxPooling3D,
    MaxPooling1D,
    MaxPooling2D,
    MaxPooling3D,
)

from analytics_zoo_tpu.pipeline.api.keras.layers.tensor_ops import (  # noqa: F401
    LRN2D,
    AddConstant,
    BinaryThreshold,
    CAdd,
    CMul,
    Exp,
    Expand,
    GaussianSampler,
    GetShape,
    HardShrink,
    HardTanh,
    Log,
    Max,
    Mul,
    MulConstant,
    Narrow,
    Negative,
    Power,
    ResizeBilinear,
    RReLU,
    Scale,
    SelectTable,
    Softmax,
    SoftShrink,
    SpaceToDepth,
    SplitTensor,
    Sqrt,
    Square,
    Threshold,
)

# Keras-2-style aliases (reference keras2 package provides these names).
Conv1D = Convolution1D
Conv2D = Convolution2D
Conv3D = Convolution3D
SeparableConv2D = SeparableConvolution2D
DepthwiseConv2D = DepthwiseConvolution2D
Conv2DTranspose = Deconvolution2D
