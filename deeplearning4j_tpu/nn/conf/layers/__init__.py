"""Layer configuration classes.

One config class per reference layer type (nn/conf/layers/*.java, 32
files). Unlike the reference — where a config class and a separate
impl class exist per layer (nn/conf/layers/DenseLayer.java vs
nn/layers/feedforward/dense/DenseLayer.java) — each config here *owns*
its functional implementation: ``initialize`` builds the param/state
pytrees and ``apply`` is the pure forward function. Backprop is
``jax.grad`` of the composed network; there is no per-layer
``backpropGradient``.
"""

from deeplearning4j_tpu.nn.conf.layers.base import (
    Layer, BaseLayer, FeedForwardLayer, register_layer, layer_from_dict,
)
from deeplearning4j_tpu.nn.conf.layers.core import (
    DenseLayer, ActivationLayer, DropoutLayer, EmbeddingLayer,
    EmbeddingSequenceLayer, AutoEncoder, RBM, RecursiveAutoEncoder,
)
from deeplearning4j_tpu.nn.conf.layers.output import (
    OutputLayer, RnnOutputLayer, LossLayer, CenterLossOutputLayer,
)
from deeplearning4j_tpu.nn.conf.layers.convolutional import (
    ConvolutionLayer, Convolution1DLayer, Deconvolution2DLayer,
    SeparableConvolution2DLayer, DepthwiseConvolution2DLayer,
    ZeroPaddingLayer, ZeroPadding1DLayer, UpsamplingLayer, CroppingLayer,
    SpaceToDepthLayer, SpaceToBatchLayer,
)
from deeplearning4j_tpu.nn.conf.layers.pooling import (
    SubsamplingLayer, Subsampling1DLayer, GlobalPoolingLayer, PoolingType,
)
from deeplearning4j_tpu.nn.conf.layers.normalization import (
    BatchNormalization, LayerNormalization, RMSNormalization,
    LocalResponseNormalization,
)
from deeplearning4j_tpu.nn.conf.layers.recurrent import (
    LSTM, GravesLSTM, GravesBidirectionalLSTM, Bidirectional, SimpleRnn,
    LastTimeStep, RnnLossLayer,
)
from deeplearning4j_tpu.nn.conf.layers.special import (
    FrozenLayer, VariationalAutoencoder, Yolo2OutputLayer,
)
from deeplearning4j_tpu.nn.conf.layers.attention import (
    SelfAttentionLayer, TransformerEncoderLayer,
    GroupedQueryAttentionLayer,
)
from deeplearning4j_tpu.nn.conf.layers.latent_attention import (
    LatentAttentionLayer,
)
from deeplearning4j_tpu.nn.conf.layers.state_space import (
    Mamba2MixerLayer,
)
from deeplearning4j_tpu.nn.conf.layers.short_conv import (
    ShortConvMixerLayer,
)
from deeplearning4j_tpu.nn.conf.layers.delta_rule import (
    GatedDeltaMixerLayer,
)
from deeplearning4j_tpu.nn.conf.layers.moe import SparseExpertsLayer
from deeplearning4j_tpu.nn.conf.layers.decoder_blocks import (
    LatentDecoderBlock, ShortcutExpertBlock, GroupedQueryDecoderBlock,
    StateSpaceDecoderBlock, ShortConvDecoderBlock, DeltaRuleDecoderBlock,
)

__all__ = [
    "Layer", "BaseLayer", "FeedForwardLayer", "register_layer",
    "layer_from_dict",
    "DenseLayer", "ActivationLayer", "DropoutLayer", "EmbeddingLayer",
    "EmbeddingSequenceLayer", "AutoEncoder", "RBM", "RecursiveAutoEncoder",
    "OutputLayer", "RnnOutputLayer", "LossLayer", "CenterLossOutputLayer",
    "ConvolutionLayer", "Convolution1DLayer", "Deconvolution2DLayer",
    "SeparableConvolution2DLayer", "DepthwiseConvolution2DLayer",
    "ZeroPaddingLayer", "ZeroPadding1DLayer", "UpsamplingLayer",
    "CroppingLayer", "SpaceToDepthLayer", "SpaceToBatchLayer",
    "SubsamplingLayer", "Subsampling1DLayer", "GlobalPoolingLayer",
    "PoolingType",
    "BatchNormalization", "LayerNormalization", "RMSNormalization",
    "LocalResponseNormalization",
    "LSTM", "GravesLSTM", "GravesBidirectionalLSTM", "Bidirectional",
    "SimpleRnn", "LastTimeStep", "RnnLossLayer",
    "FrozenLayer", "VariationalAutoencoder", "Yolo2OutputLayer",
    "SelfAttentionLayer", "TransformerEncoderLayer",
    "GroupedQueryAttentionLayer",
    "LatentAttentionLayer", "SparseExpertsLayer", "LatentDecoderBlock",
    "ShortcutExpertBlock", "GroupedQueryDecoderBlock",
    "Mamba2MixerLayer", "StateSpaceDecoderBlock",
    "ShortConvMixerLayer", "ShortConvDecoderBlock",
    "GatedDeltaMixerLayer", "DeltaRuleDecoderBlock",
]
