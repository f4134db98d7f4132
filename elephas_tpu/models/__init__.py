"""Model zoo covering the reference's example/benchmark configurations.

The reference ships *examples*, not a model zoo — users hand
``SparkModel`` an arbitrary compiled Keras model, and the example scripts
(``[U] elephas examples/``: MNIST MLP, CIFAR-style convnets, IMDB LSTM)
build those models inline. Here the same architectures are first-class
builders so the benchmark suite (the five configurations listed in
BASELINE.json) and the examples share one definition. All builders return *compiled* Keras-3 (jax backend)
models ready to wrap in ``SparkModel``.

The five sparse LMs (``qwen3_next_lm``, ``deepseek_v3_lm``,
``smallthinker_lm``, ``nemotron_h_lm``, ``laguna_lm``) are built from
blocks that this package also exports: the norms ``ZeroCentredRMSNorm`` and ``RMSNorm``;
the feed-forwards ``SwiGLU``, ``DenseMLP`` and ``UngatedMLP``; the
mixers ``GatedAttention``, ``LatentAttention``, ``BandedAttention``,
``GatedDeltaNet`` (the gated delta rule) and ``Mamba2Mixer`` (the
state-space scan); and ``SparseMoeBlock``, the one sparse block of all
five (gated or ungated experts, with or without a shared expert).
"""

from elephas_tpu.models.mlp import mnist_mlp
from elephas_tpu.models.convnet import cifar10_cnn
from elephas_tpu.models.lstm import imdb_lstm
from elephas_tpu.models.resnet import resnet50, resnet
from elephas_tpu.models.transformer import (
    generate,
    transformer_classifier,
    transformer_lm,
)
from elephas_tpu.models.switch import (
    switch_transformer_classifier,
    switch_transformer_lm,
)
from elephas_tpu.models.qwen3_next import qwen3_next_lm
from elephas_tpu.models.deepseek_v3 import deepseek_v3_lm
from elephas_tpu.models.smallthinker import smallthinker_lm
from elephas_tpu.models.nemotron_h import nemotron_h_lm
from elephas_tpu.models.laguna import laguna_lm

__all__ = [
    "mnist_mlp",
    "cifar10_cnn",
    "imdb_lstm",
    "resnet50",
    "resnet",
    "transformer_classifier",
    "transformer_lm",
    "generate",
    "switch_transformer_classifier",
    "switch_transformer_lm",
    "qwen3_next_lm",
    "deepseek_v3_lm",
    "smallthinker_lm",
    "nemotron_h_lm",
    "laguna_lm",
    "MoeFFN",
    "FlashMHA",
    "FusedLayerNorm",
    "ZeroCentredRMSNorm",
    "SwiGLU",
    "UngatedMLP",
    "GatedAttention",
    "GatedDeltaNet",
    "SparseMoeBlock",
    "RMSNorm",
    "LatentAttention",
    "DenseMLP",
    "BandedAttention",
    "Mamba2Mixer",
]


def __getattr__(name):
    # lazily resolve layer classes that require keras at definition time
    if name == "FlashMHA":
        from elephas_tpu.models.transformer import _flash_mha_layer

        return _flash_mha_layer()
    if name == "FusedLayerNorm":
        from elephas_tpu.models.transformer import _fused_ln_layer

        return _fused_ln_layer()
    if name == "MoeFFN":
        from elephas_tpu.models.switch import MoeFFN

        return MoeFFN
    from elephas_tpu.models import (
        deepseek_v3, nemotron_h, qwen3_next, smallthinker)

    for module in (qwen3_next, deepseek_v3, smallthinker, nemotron_h):
        if name in module.LAYER_NAMES:
            return getattr(module, name)
    raise AttributeError(name)
