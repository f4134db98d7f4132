"""Hybrid linear-attention mixture-of-experts LM (the Qwen3-Next block).

The zoo's dense LM (:mod:`elephas_tpu.models.transformer`) is a GPT-2
shaped block; this builder is the hybrid block of today's sparse models,
from the layers of :mod:`elephas_tpu.models.lm_blocks` and
:mod:`elephas_tpu.models.lm_mixers`, set the published way:

- ``ZeroCentredRMSNorm``: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``,
  ``w`` from zeros, statistics in float32.
- ``GatedAttention``: grouped-query causal attention with per-head
  q/k norms, rotary embedding on a part of each head
  (``partial_rotary_factor``), and a sigmoid output gate that ``q_proj``
  computes beside the query.
- ``GatedDeltaNet``: the recurrent-state mixer: fused q/k/v/z and
  beta/decay projections, a causal depthwise convolution, the chunked
  gated delta rule (:mod:`elephas_tpu.ops.gated_delta`), a gated
  per-head norm and the output projection.
- ``SparseMoeBlock`` with the rule this family states: a softmax router
  over ALL experts, the routed part that the experts HELD here give
  (``experts_held``, a range; no token dropped), SwiGLU experts and a
  SwiGLU shared expert under its sigmoid gate.

Decoder layer ``i``: ``x += mixer_i(norm(x)); x += moe(norm(x))``, the
mixer gated attention where ``(i + 1) % full_attention_interval == 0``
and Gated DeltaNet elsewhere; then a final norm and an untied head.
:func:`qwen3_next_lm` returns the compiled model, ready for
``SparkModel``. Recomputation in the backward pass is set here
(``remat``): every mixer and sparse block then keeps its input and
the few results its class names (``kept``).
"""

from __future__ import annotations

from functools import partial


def qwen3_next_lm(
    vocab_size: int = 1024,
    maxlen: int = 128,
    hidden_size: int = 64,
    num_layers: int = 4,
    full_attention_interval: int = 4,
    num_attention_heads: int = 4,
    num_key_value_heads: int = 2,
    head_dim: int = 32,
    partial_rotary_factor: float = 0.25,
    rope_theta: float = 1e7,
    linear_num_key_heads: int = 2,
    linear_num_value_heads: int = 4,
    linear_key_head_dim: int = 16,
    linear_value_head_dim: int = 16,
    linear_conv_kernel_dim: int = 4,
    num_experts: int = 16,
    num_experts_per_tok: int = 2,
    moe_intermediate_size: int = 32,
    shared_expert_intermediate_size: int = 32,
    experts_held=None,
    rms_norm_eps: float = 1e-6,
    chunk_size: int = 64,
    init_std: float = 0.02,
    lr: float = 0.01,
    momentum: float = 0.9,
    seed: int = 0,
    dtype_policy: str | None = None,
    remat: bool = False,
):
    """Decoder-only hybrid LM: Gated DeltaNet layers with a gated
    attention layer every ``full_attention_interval``-th, each followed
    by a sparse block; the argument names are the published config's.

    ``experts_held = (first, stop)`` gives this chip's share of a
    deployment that spreads ``num_experts`` over several: the router
    keeps all its outputs, the block computes its own experts' part,
    and the rest is left out (no exchange on one chip, and nothing in
    its place). With ``remat`` every mixer and sparse block keeps its
    input (an attention layer also the flash kernel's result and
    log-sum-exp, a Gated DeltaNet layer its triangular inverses), and
    the backward pass computes the rest again.
    Compiled with SGD (``lr``, ``momentum``)
    and next-token cross-entropy over float32 logits."""
    from elephas_tpu.models import lm_blocks, lm_mixers

    def mixer(i):
        if (i + 1) % full_attention_interval == 0:
            return partial(
                lm_mixers.GatedAttention, num_attention_heads,
                num_key_value_heads, head_dim,
                int(head_dim * partial_rotary_factor), rope_theta,
                rms_norm_eps, init_std, remat=remat, name=f"layer{i}_attn")
        return partial(
            lm_mixers.GatedDeltaNet, linear_num_key_heads,
            linear_num_value_heads, linear_key_head_dim,
            linear_value_head_dim, linear_conv_kernel_dim, chunk_size,
            rms_norm_eps, init_std, remat=remat, name=f"layer{i}_gdn")

    def moe(i):
        return partial(
            lm_blocks.SparseMoeBlock, num_experts, num_experts_per_tok,
            moe_intermediate_size, shared_expert_intermediate_size,
            experts_held, init_std, remat=remat, name=f"layer{i}_moe")

    return lm_blocks.decoder_lm(
        "qwen3_next_lm",
        [[lm_blocks.SubLayer("input_norm", mixer(i)),
          lm_blocks.SubLayer("post_norm", moe(i))]
         for i in range(num_layers)],
        partial(lm_blocks.ZeroCentredRMSNorm, rms_norm_eps),
        vocab_size=vocab_size, maxlen=maxlen, hidden_size=hidden_size,
        init_std=init_std, lr=lr, momentum=momentum, seed=seed,
        dtype_policy=dtype_policy)
