"""Model zoo covering the reference's example/benchmark configurations.

The reference ships *examples*, not a model zoo — users hand
``SparkModel`` an arbitrary compiled Keras model, and the example scripts
(``[U] elephas examples/``: MNIST MLP, CIFAR-style convnets, IMDB LSTM)
build those models inline. Here the same architectures are first-class
builders so the benchmark suite (the five configurations listed in
BASELINE.json) and the examples share one definition. All builders return *compiled* Keras-3 (jax backend)
models ready to wrap in ``SparkModel``.

The five sparse LMs (``qwen3_next_lm``, ``deepseek_v3_lm``,
``smallthinker_lm``, ``nemotron_h_lm``, ``laguna_lm``) and the dense
state-space hybrid ``granite_hybrid_lm`` each have a file
that turns the published config's argument names into a list of layers
and imports no other model's; the layers, which this package also
exports (``LM_NAMES``), live in two modules that import keras and are
therefore loaded on first use, not with the package:
``lm_blocks`` has the norms ``ZeroCentredRMSNorm`` and ``RMSNorm``, the
feed-forwards ``SwiGLU``, ``DenseMLP`` and ``UngatedMLP``,
``SparseMoeBlock``, the one sparse block of all five (gated or ungated
experts, with or without a shared expert), ``LMHead``,
``TiedEmbedding`` (an embedding whose table is the head) and the loop that
stacks a list of layers into a compiled model (``decoder_lm``);
``lm_mixers`` has the attention layers ``GatedAttention``,
``LatentAttention`` and ``BandedAttention`` with their one path into the
flash kernels, ``GatedDeltaNet`` (the gated delta rule) and
``Mamba2Mixer`` (the state-space scan).
"""

import importlib

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
from elephas_tpu.models.granite_hybrid import granite_hybrid_lm

# the sparse LMs' layer classes, and the loss they are compiled with, by
# the module that defines them
LM_NAMES = {
    "lm_blocks": ("ZeroCentredRMSNorm", "RMSNorm", "SwiGLU", "DenseMLP",
                  "UngatedMLP", "SparseMoeBlock", "LMHead", "TiedEmbedding",
                  "next_token_loss"),
    "lm_mixers": ("BandedAttention", "GatedAttention", "LatentAttention",
                  "GatedDeltaNet", "Mamba2Mixer"),
}

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
    "granite_hybrid_lm",
    "MoeFFN",
    "FlashMHA",
    "FusedLayerNorm",
    *LM_NAMES["lm_blocks"],
    *LM_NAMES["lm_mixers"],
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
    for module, names in LM_NAMES.items():
        if name in names:
            return getattr(
                importlib.import_module(f"{__name__}.{module}"), name)
    raise AttributeError(name)
