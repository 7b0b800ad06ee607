"""Configuration DSL (the TPU-native equivalent of nn/conf in the reference:
NeuralNetConfiguration.java, MultiLayerConfiguration.java and the 28 layer
config classes — SURVEY.md §2.1). Configs are pure data with JSON round-trip."""

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.core import (
    NeuralNetConfiguration,
    MultiLayerConfiguration,
    ListBuilder,
)
from deeplearning4j_tpu.nn.conf import layers
from deeplearning4j_tpu.nn.conf import layers_conv
from deeplearning4j_tpu.nn.conf import layers_recurrent
from deeplearning4j_tpu.nn.conf import layers_attention
from deeplearning4j_tpu.nn.conf import layers_decoder
from deeplearning4j_tpu.nn.conf import layers_pretrain
