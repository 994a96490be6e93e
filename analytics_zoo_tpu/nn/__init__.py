"""Keras-style NN layer API on a minimal JAX module system (reference L5)."""

from . import activations, initializers, losses, metrics
from .attention import (LatentAttention, MultiHeadAttention,
                        TransformerLayer, dot_product_attention)
from .layers import (Activation, Add, AveragePooling2D, BatchNormalization,
                     CausalConv1D, Concatenate, Conv1D, Conv2D, Dense,
                     Dropout, Embedding,
                     Flatten, GlobalAveragePooling1D, GlobalAveragePooling2D,
                     GlobalMaxPooling1D, GlobalMaxPooling2D, Lambda,
                     LayerNormalization, MaxPooling2D, Multiply, Reshape,
                     RMSNorm, ScaledWSConv2D, SwiGLU, Sequential, ZeroPadding2D)
from .layers_extra import (AveragePooling1D, AveragePooling3D, Average,
                           Conv2DTranspose, Conv3D, Cropping1D, Cropping2D,
                           Cropping3D, DepthwiseConv2D, Dot, ELU,
                           GaussianDropout,
                           GaussianNoise, GlobalAveragePooling3D,
                           GlobalMaxPooling3D, Highway, LeakyReLU,
                           LocallyConnected1D, Masking, MaxoutDense,
                           MaxPooling1D, MaxPooling3D, Maximum, Minimum,
                           Narrow, Permute, PReLU, Remat, RepeatVector,
                           Select, SeparableConv2D, SReLU, Squeeze,
                           SpatialDropout1D, SpatialDropout2D,
                           SpatialDropout3D, Subtract, ThresholdedReLU,
                           UpSampling1D, UpSampling2D, UpSampling3D,
                           ZeroPadding1D, ZeroPadding3D)
from .layers_zoo import (ActivityRegularization, AddConstant, AlphaDropout,
                         CAdd, CMul, Conv1DTranspose, Conv3DTranspose,
                         ConvLSTM2D, ConvLSTM3D, Cos, Exp, GaussianSampler,
                         HardShrink, HardTanh, Identity, LocallyConnected2D,
                         Log, LRN2D, MulConstant, Negative, Power,
                         ResizeBilinear, Scale, SeparableConv1D, Softmax,
                         SoftShrink, Sqrt, Square, Threshold, WordEmbedding,
                         Merge, merge)
from .functional import Input, Model, SymbolicTensor
from .linear_attention import GatedDeltaNet
from .module import Module, Scope, param_count
from .recurrent import (GRU, LSTM, Bidirectional, SimpleRNN, TimeDistributed)
from .state_space import Mamba2

# keras-1 naming aliases (reference: zoo keras-1.2 class names) so ported
# scripts keep their spellings
Convolution1D = Conv1D
Convolution2D = Conv2D
Convolution3D = Conv3D
Deconvolution2D = Conv2DTranspose
Deconvolution3D = Conv3DTranspose
AtrousConvolution1D = Conv1D   # dilation= covers the atrous variants
AtrousConvolution2D = Conv2D
# BigDL ShareConvolution was a memory-sharing twin of SpatialConvolution;
# functionally identical, and XLA owns buffer reuse here
ShareConvolution2D = Conv2D
SeparableConvolution2D = SeparableConv2D
# zoo's Sparse* layers existed for sparse-gradient CPU training; XLA's
# scatter/gather handles the same access pattern on dense TPU arrays
SparseEmbedding = Embedding
SparseDense = Dense

__all__ = [
    "activations", "initializers", "losses", "metrics",
    "Module", "Scope", "param_count",
    "Dense", "Embedding", "Dropout", "Flatten", "Reshape", "Activation",
    "Lambda", "Conv1D", "Conv2D", "MaxPooling2D", "AveragePooling2D",
    "GlobalAveragePooling2D", "GlobalMaxPooling2D", "GlobalAveragePooling1D",
    "GlobalMaxPooling1D", "ZeroPadding2D", "BatchNormalization",
    "LayerNormalization", "Concatenate", "Add", "Multiply", "Sequential",
    "RMSNorm", "SwiGLU", "CausalConv1D", "GatedDeltaNet", "Mamba2",
    "LSTM", "GRU", "SimpleRNN", "Bidirectional", "TimeDistributed",
    "MultiHeadAttention", "LatentAttention", "TransformerLayer",
    "dot_product_attention",
    # extended Keras-1.2 zoo (layers_extra)
    "Conv3D", "Conv2DTranspose", "DepthwiseConv2D", "SeparableConv2D",
    "LocallyConnected1D", "MaxPooling1D", "AveragePooling1D",
    "MaxPooling3D", "AveragePooling3D", "GlobalAveragePooling3D",
    "GlobalMaxPooling3D", "UpSampling1D", "UpSampling2D", "UpSampling3D",
    "ZeroPadding1D", "ZeroPadding3D", "Cropping1D", "Cropping2D",
    "RepeatVector", "Permute", "Masking", "SpatialDropout1D",
    "SpatialDropout2D", "SpatialDropout3D", "GaussianNoise",
    "GaussianDropout", "LeakyReLU", "ELU", "ThresholdedReLU", "PReLU",
    "Average", "Maximum", "Minimum", "Subtract", "Dot", "Highway",
    "MaxoutDense",
    # functional graph API
    "Input", "Model", "SymbolicTensor",
    "Remat",
    "Cropping3D", "SReLU", "Select", "Narrow", "Squeeze",
    # layer-zoo backfill (layers_zoo)
    "ConvLSTM2D", "LocallyConnected2D", "Conv3DTranspose", "Conv1DTranspose",
    "SeparableConv1D", "AlphaDropout", "Softmax", "ActivityRegularization",
    "LRN2D", "Cos", "Identity", "Exp", "Log", "Sqrt", "Square", "Power",
    "Negative", "AddConstant", "MulConstant", "Scale", "Threshold",
    "HardShrink", "SoftShrink", "WordEmbedding", "Merge", "merge",
    "ConvLSTM3D", "CAdd", "CMul", "HardTanh", "GaussianSampler",
    "ResizeBilinear",
    # keras-1 naming aliases
    "Convolution1D", "Convolution2D", "Convolution3D", "Deconvolution2D",
    "Deconvolution3D", "AtrousConvolution1D", "AtrousConvolution2D",
    "ShareConvolution2D", "SeparableConvolution2D", "SparseEmbedding",
    "SparseDense",
]
