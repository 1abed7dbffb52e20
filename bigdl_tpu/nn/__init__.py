"""bigdl_tpu.nn — layer library (reference spark/dl nn/, 151 files).

TPU-first: every layer is a pure ``apply_fn`` over param/buffer pytrees;
the Torch-style mutable API (forward/backward/getParameters) is a shell
(see module.py).
"""
from .module import AbstractModule, Container, TensorModule, to_array
from .initialization import (
    BilinearFiller, ConstInitMethod, InitializationMethod, MsraFiller, Ones,
    RandomNormal, RandomUniform, VariableFormat, Xavier, Zeros,
)
from .containers import (
    Bottle, Concat, ConcatTable, Echo, Identity, MapTable, ParallelTable,
    Sequential,
)
from .graph import Graph, Input, Model, ModuleNode
from .linear import (
    Add, AddConstant, Bilinear, CAdd, CMul, Cosine, Euclidean, Linear,
    LookupTable, MM, MV, Mul, MulConstant,
)
from .embedding import ShardedEmbedding
from .embedding_store import (
    EmbeddingStore, HotRowCache, MigrationCorrupt, StoreMigrating,
    table_checksum,
)
from .activations import (
    Abs, Clamp, ELU, Exp, HardShrink, HardTanh, LeakyReLU, Log, LogSigmoid,
    LogSoftMax, Max, Mean, Min, Power, PReLU, ReLU, ReLU6, RReLU, Sigmoid,
    SoftMax, SoftMin, SoftPlus, SoftShrink, SoftSign, Sqrt, Square, Sum,
    Tanh, TanhShrink, Threshold,
)
from .conv import (
    SpatialConvolution, SpatialConvolutionMap, SpatialDilatedConvolution,
    SpatialFullConvolution, SpatialShareConvolution, TemporalConvolution,
    VolumetricConvolution,
)
from .pooling import (
    RoiPooling, SpatialAveragePooling, SpatialMaxPooling, VolumetricMaxPooling,
)
from .normalization import (
    LayerNorm, RMSNorm,
    BatchNormalization, ImageNormalize, L1Penalty, Normalize,
    SpatialBatchNormalization,
    SpatialContrastiveNormalization, SpatialCrossMapLRN,
    SpatialDivisiveNormalization, SpatialSubtractiveNormalization,
)
from .shape_ops import (
    Contiguous, CosineDistance, DotProduct, FlattenTable, GradientReversal,
    Index, InferReshape, JoinTable, MaskedSelect, MixtureTable, Narrow,
    NarrowTable, Pack, Padding, PairwiseDistance, Replicate, Reshape, Reverse,
    Scale, Select, SelectTable, SpatialZeroPadding, SplitTable, Squeeze,
    Transpose, Unsqueeze, View,
)
from .table_ops import (
    CAddTable, CDivTable, CMaxTable, CMinTable, CMulTable, CSubTable,
)
from .dropout import Dropout
from .criterion import (
    AbsCriterion, AbstractCriterion, BCECriterion, ClassNLLCriterion,
    ClassSimplexCriterion, CosineDistanceCriterion, CosineEmbeddingCriterion,
    CrossEntropyCriterion, DiceCoefficientCriterion, DistKLDivCriterion,
    HingeEmbeddingCriterion, L1Cost, L1HingeEmbeddingCriterion,
    MarginCriterion, MarginRankingCriterion, MSECriterion, MultiCriterion,
    MultiLabelMarginCriterion, MultiLabelSoftMarginCriterion,
    MultiMarginCriterion, ParallelCriterion, SmoothL1Criterion,
    SmoothL1CriterionWithWeights, SoftMarginCriterion, SoftmaxWithCriterion,
    TimeDistributedCriterion,
)
from .attention import LatentAttention, MultiHeadAttention
from .mamba import HybridMambaBlock, Mamba2Mixer
from .short_conv import GatedShortConv
from .hyper_connection import HyperConnection
from .recurrent import (
    BiRecurrent, Cell, ConvLSTMPeephole, GRU, LSTM, LSTMPeephole, Recurrent,
    RnnCell, TimeDistributed,
)
from .tree import BinaryTreeLSTM, TensorTree, TreeLSTM
from .tf_ops import Const, Fill, Nms, Shape, SplitAndSelect, StrideSlice
