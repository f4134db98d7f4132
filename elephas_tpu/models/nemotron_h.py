"""State-space / sparse / attention hybrid LM (the Nemotron-H block, as
``NVIDIA-Nemotron-3-Nano-30B-A3B`` publishes it under ``model_type:
nemotron_h``).

Every decoder layer is ONE mixer under one pre-norm and one residual
add, ``h = h + mixer_i(norm(h))``, and character ``i`` of
``hybrid_override_pattern`` says which: ``M`` a Mamba-2 mixer, ``E`` a
sparse block, ``*`` attention. The block is made of layers of
:mod:`elephas_tpu.models.lm_blocks` and
:mod:`elephas_tpu.models.lm_mixers`, set the published way:

- ``Mamba2Mixer``: one input projection split into a gate ``z``,
  the convolved channels ``x | B | C`` and a step ``dt`` a head; a causal
  depthwise convolution with bias and a silu over those channels; the
  selective scan (:func:`elephas_tpu.ops.ssd.ssd_chunked`: a scalar
  decay ``exp(dt A)`` a head, ``B`` and ``C`` shared by the heads of a
  group, a ``D`` skip); a gated RMS norm in groups (``norm(y *
  silu(z))``: the gate first, then the norm over each group of
  ``inner / n_groups`` channels); the output projection. Its inner
  width is ``mamba_num_heads * mamba_head_dim``.
- ``SparseMoeBlock`` in its ungated form: routed experts
  ``down_e(relu(up_e x)^2)``, a shared expert of the same form
  (``UngatedMLP``) added unweighted, behind the sigmoid router with a
  selection bias and ``routed_scaling_factor``.
- attention with no position term at all: ``BandedAttention`` with
  neither a window nor a rotation (the Mamba-2 layers carry order).

``fit`` only: the state cache and the one-token step that serving a
recurrent layer needs are not here, and a sequence made of several
documents resets no state at their boundaries.
"""

from __future__ import annotations

import math
from functools import partial

LAYER_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def nemotron_h_lm(
    vocab_size: int = 1024,
    maxlen: int = 128,
    hidden_size: int = 64,
    hybrid_override_pattern: str = "MEM*E",
    num_hidden_layers: int | None = None,
    mamba_num_heads: int = 8,
    mamba_head_dim: int = 8,
    ssm_state_size: int = 16,
    n_groups: int = 2,
    conv_kernel: int = 4,
    chunk_size: int = 128,
    time_step_min: float = 0.001,
    time_step_max: float = 0.1,
    time_step_floor: float = 1e-4,
    num_attention_heads: int = 4,
    num_key_value_heads: int = 2,
    head_dim: int = 32,
    n_routed_experts: int = 16,
    num_experts_per_tok: int = 2,
    moe_intermediate_size: int = 32,
    moe_shared_expert_intermediate_size: int = 64,
    routed_scaling_factor: float = 2.5,
    mlp_hidden_act: str = "relu2",
    experts_held=None,
    layer_norm_epsilon: float = 1e-5,
    rescale_prenorm_residual: bool = True,
    init_std: float = 0.02,
    lr: float = 0.01,
    momentum: float = 0.9,
    seed: int = 0,
    dtype_policy: str | None = None,
    remat: bool = False,
):
    """Decoder-only hybrid LM whose layer ``i`` is the one mixer that
    character ``i`` of ``hybrid_override_pattern`` names (``M``: a
    Mamba-2 mixer; ``E``: a sparse block of ungated ``mlp_hidden_act``
    experts with a shared expert, behind a sigmoid router with a
    selection bias; ``*``: grouped-query causal attention with no
    position term), under one pre-norm and one residual add; the
    argument names are the published config's. ``num_hidden_layers``
    reads that many leading characters of the pattern (all of them when
    None); with ``rescale_prenorm_residual`` the mixers' output
    projections are drawn ``sqrt(num_hidden_layers)`` times narrower.

    ``experts_held = (first, stop)`` and ``remat`` as for
    :func:`elephas_tpu.models.qwen3_next.qwen3_next_lm`: this chip's
    share of the routed experts, and every layer keeping its input for
    the backward pass (an attention layer also what the flash kernels
    read and give: q, k and v, the result and its log-sum-exp).
    Compiled with SGD (``lr``, ``momentum``)
    and next-token cross-entropy over float32 logits."""
    n = len(hybrid_override_pattern) if num_hidden_layers is None else (
        num_hidden_layers)
    pattern = hybrid_override_pattern[:n]
    if len(pattern) < n or set(pattern) - set(LAYER_KINDS):
        raise ValueError(
            f"{n} layers of hybrid_override_pattern "
            f"{hybrid_override_pattern!r}: its characters are "
            f"{sorted(LAYER_KINDS)}"
        )
    from elephas_tpu.models import lm_blocks, lm_mixers

    def mixer(kind, name):
        if kind == "M":
            return partial(
                lm_mixers.Mamba2Mixer, mamba_num_heads, mamba_head_dim,
                ssm_state_size, n_groups, conv_kernel, chunk_size,
                layer_norm_epsilon, time_step_min, time_step_max,
                time_step_floor, init_std,
                init_std / math.sqrt(n) if rescale_prenorm_residual
                else None, remat=remat, name=name)
        if kind == "E":
            return partial(
                lm_blocks.SparseMoeBlock, n_routed_experts,
                num_experts_per_tok, moe_intermediate_size,
                moe_shared_expert_intermediate_size, experts_held, init_std,
                scoring_func="sigmoid", selection_bias=True,
                routed_scaling_factor=routed_scaling_factor,
                gated_shared_expert=False, hidden_act=mlp_hidden_act,
                gated_experts=False, remat=remat, name=name)
        return partial(
            lm_mixers.BandedAttention, num_attention_heads,
            num_key_value_heads, head_dim, None, False, init_std=init_std,
            remat=remat, name=name)

    return lm_blocks.decoder_lm(
        "nemotron_h_lm",
        [[lm_blocks.SubLayer(
            "norm", mixer(kind, f"layer{i}_{LAYER_KINDS[kind]}"))]
         for i, kind in enumerate(pattern)],
        partial(lm_blocks.RMSNorm, layer_norm_epsilon),
        vocab_size=vocab_size, maxlen=maxlen, hidden_size=hidden_size,
        init_std=init_std, lr=lr, momentum=momentum, seed=seed,
        dtype_policy=dtype_policy)
