"""Model zoo: canonical network builders (reference parity:
TrainedModels.java / ModelGuesser.java model-zoo hooks, and the configs
BASELINE.md measures — LeNet-MNIST, ResNet-50, GravesLSTM char-RNN)."""

from deeplearning4j_tpu.zoo.models import (
    BF16,
    F32,
    VGG16_MEAN_RGB,
    char_rnn,
    glm4_moe_lite,
    gpt_mini,
    gpt_mini_draft,
    gpt_mini_tp_rules,
    keye_vl2_moe,
    lenet,
    lfm2_moe,
    mnist_mlp,
    nemotron_h,
    resnet18,
    resnet50,
    sdar_moe,
    vgg16,
    vgg16_preprocess,
)

__all__ = ["BF16", "F32", "VGG16_MEAN_RGB", "char_rnn", "glm4_moe_lite",
           "gpt_mini", "gpt_mini_draft", "gpt_mini_tp_rules", "keye_vl2_moe",
           "lenet", "lfm2_moe", "mnist_mlp",
           "nemotron_h", "resnet18", "resnet50", "sdar_moe", "vgg16", "vgg16_preprocess"]
