from .basic_layers import (
    Sequential, HybridSequential, Dense, Dropout, Embedding, BatchNorm,
    InstanceNorm, LayerNorm, GroupNorm, Flatten, Lambda, HybridLambda,
    Activation, LeakyReLU, PReLU, ELU, SELU, Swish, GELU, SiLU, Identity,
    HybridBlock, Block,
)
from .transformer import (
    MultiHeadAttention, PositionwiseFFN, TransformerEncoderCell,
    TransformerEncoder,
)
from .decoder import (
    RMSNorm, GatedMLP, CausalConv1D, KDAMixer, MLAMixer, HeldExperts,
    MambaMixer, DiffAttention, GatedMemoryUnit, SparseGQAttention,
)
from .conv_layers import (
    Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose, Conv3DTranspose,
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    GlobalMaxPool1D, GlobalMaxPool2D, GlobalMaxPool3D,
    GlobalAvgPool1D, GlobalAvgPool2D, GlobalAvgPool3D, ReflectionPad2D,
)
