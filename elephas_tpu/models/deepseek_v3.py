"""Latent-attention mixture-of-experts LM (the DeepSeek-V3 block, as
``kanana-2-30b-a3b`` publishes it under ``model_type: deepseek_v3``).

The block is made of layers of :mod:`elephas_tpu.models.lm_blocks` and
:mod:`elephas_tpu.models.lm_mixers`, set the published way:

- ``RMSNorm``: ``w * x * rsqrt(mean(x^2) + eps)``, ``w`` from ones,
  statistics in float32.
- ``LatentAttention`` (MLA, the form without a query latent):
  ``q_proj`` gives a head a ``qk_nope_head_dim + qk_rope_head_dim``
  wide query; ``kv_a_proj_with_mqa`` gives a token one
  ``kv_lora_rank`` wide latent and one rotated key part that all heads
  share; ``kv_b_proj`` raises the normed latent to a head's unrotated
  key part and its ``v_head_dim`` wide value. Scores are over the
  whole query and key width, the sum over the narrower values
  (:func:`elephas_tpu.ops.flash_attention.flash_attention` takes the
  two widths). The rotation turns the pairs ``(2i, 2i + 1)``
  (``rope_interleave``).
- ``DenseMLP``: the leading layers' SwiGLU under its own scope.
- ``SparseMoeBlock`` with this family's rule: sigmoid scores, a
  non-trainable selection bias (``e_score_correction_bias``) added for
  the choice alone, the chosen scores renormalised and scaled by
  ``routed_scaling_factor``; the ``n_shared_experts`` shared experts
  are one ungated SwiGLU of their joint width.

Decoder layer ``i``: ``x += attn(norm(x)); x += ffn(norm(x))``, the
feed-forward dense below ``first_k_dense_replace`` and sparse from
there on; a final norm and an untied head. ``fit`` only: the latent
cache that serving would hold is not here.
"""

from __future__ import annotations

from functools import partial


def deepseek_v3_lm(
    vocab_size: int = 1024,
    maxlen: int = 128,
    hidden_size: int = 64,
    num_hidden_layers: int = 3,
    first_k_dense_replace: int = 1,
    intermediate_size: int = 192,
    num_attention_heads: int = 4,
    qk_nope_head_dim: int = 16,
    qk_rope_head_dim: int = 8,
    v_head_dim: int = 16,
    kv_lora_rank: int = 32,
    rope_theta: float = 1e6,
    n_routed_experts: int = 16,
    num_experts_per_tok: int = 2,
    moe_intermediate_size: int = 16,
    n_shared_experts: int = 2,
    routed_scaling_factor: float = 2.448,
    scoring_func: str = "sigmoid",
    experts_held=None,
    rms_norm_eps: float = 1e-6,
    init_std: float = 0.02,
    lr: float = 0.01,
    momentum: float = 0.9,
    seed: int = 0,
    dtype_policy: str | None = None,
    remat: bool = False,
):
    """Decoder-only latent-attention LM: ``first_k_dense_replace`` layers
    with a dense SwiGLU, then sparse layers (a router over
    ``n_routed_experts`` with a selection bias, ``num_experts_per_tok``
    a token, ``n_shared_experts`` ungated shared experts as one SwiGLU);
    the argument names are the published config's. The published
    ``q_lora_rank: null`` form (queries straight from the hidden state)
    and one expert group (``n_group`` 1) are what is built.

    ``experts_held = (first, stop)`` and ``remat`` as for
    :func:`elephas_tpu.models.qwen3_next.qwen3_next_lm`: this chip's
    share of the routed experts, and every attention and feed-forward
    layer keeping its input for the backward pass (an attention layer
    also the flash kernel's result and log-sum-exp). Compiled with
    SGD (``lr``, ``momentum``) and next-token cross-entropy over
    float32 logits."""
    from elephas_tpu.models import lm_blocks, lm_mixers

    def attention(i):
        return partial(
            lm_mixers.LatentAttention, num_attention_heads, qk_nope_head_dim,
            qk_rope_head_dim, v_head_dim, kv_lora_rank, rope_theta,
            rms_norm_eps, init_std, remat=remat, name=f"layer{i}_attn")

    def feed_forward(i):
        if i < first_k_dense_replace:
            return partial(
                lm_blocks.DenseMLP, intermediate_size, init_std, remat=remat,
                name=f"layer{i}_mlp")
        return partial(
            lm_blocks.SparseMoeBlock, n_routed_experts, num_experts_per_tok,
            moe_intermediate_size, n_shared_experts * moe_intermediate_size,
            experts_held, init_std, scoring_func=scoring_func,
            selection_bias=True, routed_scaling_factor=routed_scaling_factor,
            gated_shared_expert=False, remat=remat, name=f"layer{i}_moe")

    return lm_blocks.decoder_lm(
        "deepseek_v3_lm",
        [[lm_blocks.SubLayer("input_norm", attention(i)),
          lm_blocks.SubLayer("post_norm", feed_forward(i))]
         for i in range(num_hidden_layers)],
        partial(lm_blocks.RMSNorm, rms_norm_eps),
        vocab_size=vocab_size, maxlen=maxlen, hidden_size=hidden_size,
        init_std=init_std, lr=lr, momentum=momentum, seed=seed,
        dtype_policy=dtype_policy)
