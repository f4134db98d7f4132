"""Window-and-full-attention mixture-of-experts LM (the SmallThinker
block, as ``SmallThinker-21BA3B-Instruct`` publishes it).

The block is made of layers of :mod:`elephas_tpu.models.lm_blocks` and
:mod:`elephas_tpu.models.lm_mixers`, set the published way:

- ``BandedAttention``: grouped-query causal attention with no gate, no
  bias and no q/k norm, whose two per-layer settings are the model's
  own: ``window`` (a query sees itself and the ``window - 1`` keys
  before it; None is full causal attention) and ``rotary`` (the rotary
  embedding over the whole head, pairs ``(i, i + head_dim / 2)``;
  without it the layer has no position term at all). The flash
  kernels' grids hold the band's block pairs alone
  (:func:`elephas_tpu.ops.flash_attention.flash_attention`,
  ``window``), under the scope ``attn.window``; a full layer runs under
  ``attn.full``.
- a decoder layer whose router stands before attention: it scores the
  layer's input ``h`` (the residual stream, un-normed), while the
  experts take the normed attention result; the ``SparseMoeBlock`` is
  called with both tensors (``SubLayer.takes_stream``), holds no shared
  expert (``shared_width`` 0) and its experts are ReGLU (``hidden_act``
  ``relu``).

Decoder layer ``l``: ``h1 = h + attn_l(norm(h)); h' = h1 +
moe(norm(h1), route_from=h)``, with ``sliding_window_layout[l]`` and
``rope_layout[l]`` choosing the attention's window and rotation; a
final norm and an untied head. ``fit`` only: a cache whose layers
differ (serving) is not here.
"""

from __future__ import annotations

from functools import partial


def smallthinker_lm(
    vocab_size: int = 1024,
    maxlen: int = 128,
    hidden_size: int = 64,
    num_hidden_layers: int = 4,
    num_attention_heads: int = 4,
    num_key_value_heads: int = 2,
    head_dim: int = 32,
    sliding_window_size: int = 32,
    sliding_window_layout=(0, 1, 1, 1),
    rope_layout=(0, 1, 1, 1),
    rope_theta: float = 1.5e6,
    moe_num_primary_experts: int = 16,
    moe_num_active_primary_experts: int = 2,
    moe_ffn_hidden_size: int = 32,
    experts_held=None,
    rms_norm_eps: float = 1e-6,
    init_std: float = 0.02,
    lr: float = 0.01,
    momentum: float = 0.9,
    seed: int = 0,
    dtype_policy: str | None = None,
    remat: bool = False,
):
    """Decoder-only LM that mixes window and full attention: layer
    ``l`` attends within ``sliding_window_size`` keys where
    ``sliding_window_layout[l]`` is 1 and over the whole causal past
    where it is 0, and rotates its queries and keys where
    ``rope_layout[l]`` is 1 (a layer with 0 has no position term);
    every layer ends in a sparse block of ReGLU experts with no shared
    expert, whose softmax router scores the layer's input, before
    attention. The argument names are the published config's; the two
    layouts give at least ``num_hidden_layers`` entries.

    ``experts_held = (first, stop)`` and ``remat`` as for
    :func:`elephas_tpu.models.qwen3_next.qwen3_next_lm`: this chip's
    share of the routed experts, and every attention layer and sparse
    block keeping its inputs for the backward pass (an attention layer
    also what the flash kernels read and give: q, k and v as projected
    and rotated, the result and its log-sum-exp). Compiled with
    SGD (``lr``, ``momentum``) and next-token cross-entropy over float32
    logits."""
    if min(len(sliding_window_layout), len(rope_layout)) < num_hidden_layers:
        raise ValueError(
            f"{num_hidden_layers} layers need as many entries of "
            f"sliding_window_layout and rope_layout"
        )
    from elephas_tpu.models import lm_blocks, lm_mixers

    def attention(i):
        return partial(
            lm_mixers.BandedAttention, num_attention_heads,
            num_key_value_heads, head_dim,
            sliding_window_size if sliding_window_layout[i] else None,
            bool(rope_layout[i]), rope_theta, init_std, remat=remat,
            name=f"layer{i}_attn")

    def moe(i):
        return partial(
            lm_blocks.SparseMoeBlock, moe_num_primary_experts,
            moe_num_active_primary_experts, moe_ffn_hidden_size, 0,
            experts_held, init_std, hidden_act="relu", remat=remat,
            name=f"layer{i}_moe")

    return lm_blocks.decoder_lm(
        "smallthinker_lm",
        # the router reads the layer's input, the experts the normed
        # attention result
        [[lm_blocks.SubLayer("input_norm", attention(i)),
          lm_blocks.SubLayer("post_norm", moe(i), takes_stream=True)]
         for i in range(num_hidden_layers)],
        partial(lm_blocks.RMSNorm, rms_norm_eps),
        vocab_size=vocab_size, maxlen=maxlen, hidden_size=hidden_size,
        init_std=init_std, lr=lr, momentum=momentum, seed=seed,
        dtype_policy=dtype_policy)
