"""Window-and-full-attention mixture-of-experts LM whose layers differ
in their head counts (the Laguna block, as ``Laguna-S-2.1`` publishes
it under ``model_type: laguna``).

The block is made of layers of :mod:`elephas_tpu.models.lm_blocks` and
:mod:`elephas_tpu.models.lm_mixers`, set the published way:

- ``BandedAttention``, a layer at a time:
  ``num_attention_heads_per_layer[l]`` query heads (more behind a
  ``sliding_window`` than behind full attention) over the same
  ``num_key_value_heads``; a sigmoid output gate, one scalar a head and
  token from a projection of the layer's normed input
  (``gating: "per-head"``); and a rotation by the layer's kind
  (``rope_parameters[layer_types[l]]``): plain rotary embedding over
  the whole head in a sliding layer, YaRN-scaled frequencies over the
  first ``partial_rotary_factor`` of the head in a full one.
- ``DenseMLP`` where ``mlp_layer_types[l]`` is ``dense``, else
  ``SparseMoeBlock`` with the rule this family states: sigmoid scores
  (:data:`SCORING_FUNC`) over all ``num_experts``, the ``num_experts_per_tok`` largest renormalised
  and scaled by ``moe_routed_scaling_factor``, SwiGLU experts, one
  ungated SwiGLU shared expert.

Decoder layer ``l``: ``h1 = h + attn_l(norm(h)); h' = h1 +
ffn_l(norm(h1))``; a final norm and an untied head. ``fit`` only: a
cache whose layers differ in head count and window (serving) is not
here.
"""

from __future__ import annotations

from functools import partial

LAYER_TYPES = ("full_attention", "sliding_attention")
MLP_LAYER_TYPES = ("dense", "sparse")
# the router's score: the published config has no key for it, so it is
# no argument here either (why sigmoid: ``assumed.scoring_func_why`` of
# ``benchmarks/configs/laguna-s-2.1-ep32.json``)
SCORING_FUNC = "sigmoid"


def rotation_of(rope_parameters: dict, head_dim: int) -> dict:
    """One published ``rope_parameters`` group as ``BandedAttention``'s
    arguments: the base, the rotated width, and YaRN's settings where
    ``rope_type`` says ``yarn`` (``attention_factor`` None where the
    group leaves it to the standard rule)."""
    from elephas_tpu.models.lm_mixers import YARN_KEYS

    kind = rope_parameters.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"rope_type {kind!r} is neither default nor yarn")
    rotary_dim = int(head_dim * rope_parameters.get(
        "partial_rotary_factor", 1))
    yarn = None
    if kind == "yarn":
        yarn = {k: rope_parameters.get(k) for k in YARN_KEYS}
    return {"rope_theta": rope_parameters["rope_theta"],
            "rotary_dim": rotary_dim, "yarn": yarn}


def laguna_lm(
    vocab_size: int = 1024,
    maxlen: int = 128,
    hidden_size: int = 64,
    intermediate_size: int = 192,
    num_hidden_layers: int = 5,
    num_attention_heads_per_layer=(4, 6, 6, 6, 4),
    num_key_value_heads: int = 2,
    head_dim: int = 32,
    layer_types=("full_attention", "sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"),
    mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
    sliding_window: int = 32,
    rope_parameters=None,
    gating: str | None = "per-head",
    num_experts: int = 16,
    num_experts_per_tok: int = 2,
    moe_intermediate_size: int = 32,
    shared_expert_intermediate_size: int = 32,
    moe_routed_scaling_factor: float = 2.5,
    experts_held=None,
    rms_norm_eps: float = 1e-6,
    init_std: float = 0.02,
    lr: float = 0.01,
    momentum: float = 0.9,
    seed: int = 0,
    dtype_policy: str | None = None,
    remat: bool = False,
):
    """Decoder-only LM whose layer ``l`` attends with
    ``num_attention_heads_per_layer[l]`` query heads, within
    ``sliding_window`` keys where ``layer_types[l]`` is
    ``sliding_attention`` and over the whole causal past where it is
    ``full_attention``, rotated as ``rope_parameters[layer_types[l]]``
    says (None: plain rotary embedding at 10000 over the whole head in
    both kinds), each head's result under a sigmoid gate; the
    feed-forward is a dense SwiGLU of ``intermediate_size`` or the
    sparse block by ``mlp_layer_types[l]``. The argument names are the
    published config's; the three per-layer lists give at least
    ``num_hidden_layers`` entries.

    ``experts_held = (first, stop)`` and ``remat`` as for
    :func:`elephas_tpu.models.qwen3_next.qwen3_next_lm`: this chip's
    share of the routed experts, and every attention layer, dense
    feed-forward and sparse block keeping its input for the backward
    pass (an attention layer also what the flash kernels read and give:
    q, k and v as projected and rotated, the result and its
    log-sum-exp). Compiled with SGD (``lr``, ``momentum``) and
    next-token cross-entropy over float32 logits."""
    lists = (layer_types, mlp_layer_types, num_attention_heads_per_layer)
    if min(len(t) for t in lists) < num_hidden_layers:
        raise ValueError(
            f"{num_hidden_layers} layers need as many entries of "
            f"layer_types, mlp_layer_types and num_attention_heads_per_layer"
        )
    unknown = (set(layer_types) - set(LAYER_TYPES)) | (
        set(mlp_layer_types) - set(MLP_LAYER_TYPES))
    if unknown:
        raise ValueError(f"layer types {sorted(unknown)}")
    rope_parameters = rope_parameters or {
        kind: {"rope_theta": 10000.0} for kind in LAYER_TYPES}
    from elephas_tpu.models import lm_blocks, lm_mixers

    def attention(i):
        kind = layer_types[i]
        return partial(
            lm_mixers.BandedAttention, num_attention_heads_per_layer[i],
            num_key_value_heads, head_dim,
            sliding_window if kind == "sliding_attention" else None,
            init_std=init_std, gating=gating, remat=remat,
            name=f"layer{i}_attn",
            **rotation_of(rope_parameters[kind], head_dim))

    def feed_forward(i):
        if mlp_layer_types[i] == "dense":
            return partial(
                lm_blocks.DenseMLP, intermediate_size, init_std, remat=remat,
                name=f"layer{i}_mlp")
        return partial(
            lm_blocks.SparseMoeBlock, num_experts, num_experts_per_tok,
            moe_intermediate_size, shared_expert_intermediate_size,
            experts_held, init_std, scoring_func=SCORING_FUNC,
            routed_scaling_factor=moe_routed_scaling_factor,
            gated_shared_expert=False, remat=remat, name=f"layer{i}_moe")

    return lm_blocks.decoder_lm(
        "laguna_lm",
        [[lm_blocks.SubLayer("input_norm", attention(i)),
          lm_blocks.SubLayer("post_norm", feed_forward(i))]
         for i in range(num_hidden_layers)],
        partial(lm_blocks.RMSNorm, rms_norm_eps),
        vocab_size=vocab_size, maxlen=maxlen, hidden_size=hidden_size,
        init_std=init_std, lr=lr, momentum=momentum, seed=seed,
        dtype_policy=dtype_policy)
