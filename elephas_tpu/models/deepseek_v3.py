"""Latent-attention mixture-of-experts LM (the DeepSeek-V3 block, as
``kanana-2-30b-a3b`` publishes it under ``model_type: deepseek_v3``).

What the block adds to the zoo, beside the layers it shares with
:mod:`elephas_tpu.models.qwen3_next` (``SwiGLU``, ``SparseMoeBlock``,
``LMHead``, ``next_token_loss``):

- :class:`RMSNorm`: ``w * x * rsqrt(mean(x^2) + eps)``, ``w`` from
  ones, statistics in float32.
- :class:`LatentAttention` (MLA, the form without a query latent):
  ``q_proj`` gives a head a ``qk_nope_head_dim + qk_rope_head_dim``
  wide query; ``kv_a_proj_with_mqa`` gives a token one
  ``kv_lora_rank`` wide latent and one rotated key part that all heads
  share; ``kv_b_proj`` raises the normed latent to a head's unrotated
  key part and its ``v_head_dim`` wide value. Scores are over the
  whole query and key width, the sum over the narrower values
  (:func:`elephas_tpu.ops.flash_attention.flash_attention` takes the
  two widths). The rotation turns the pairs ``(2i, 2i + 1)``
  (``rope_interleave``).
- :class:`DenseMLP`: the leading layers' SwiGLU under its own scope.
- the sparse block's other rule, set here on the shared
  ``SparseMoeBlock``: sigmoid scores, a non-trainable selection bias
  (``e_score_correction_bias``) added for the choice alone, the chosen
  scores renormalised and scaled by ``routed_scaling_factor``; the
  ``n_shared_experts`` shared experts are one ungated SwiGLU of their
  joint width.

Decoder layer ``i``: ``x += attn(norm(x)); x += ffn(norm(x))``, the
feed-forward dense below ``first_k_dense_replace`` and sparse from
there on; a final norm and an untied head. ``fit`` only: the latent
cache that serving would hold is not here.
"""

from __future__ import annotations

from elephas_tpu.models import qwen3_next
from elephas_tpu.models.qwen3_next import _rms, next_token_loss
from elephas_tpu.models.transformer import (
    _dtype_policy_scope,
    _keras,
    _rope_tables,
)

_LAYERS = None
LAYER_NAMES = ("RMSNorm", "LatentAttention", "DenseMLP")


def _layers():
    """This module's layer classes beside those it shares with
    ``qwen3_next``, created lazily (keras under the jax backend first)
    and registered with Keras's serializer."""
    global _LAYERS
    if _LAYERS is not None:
        return _LAYERS
    import jax
    import jax.numpy as jnp
    import keras

    from elephas_tpu.ops.flash_attention import LSE_NAME, OUT_NAME

    shared = qwen3_next._layers()
    _SameShape, _Remat = shared["_SameShape"], shared["_Remat"]
    register = keras.saving.register_keras_serializable(package="elephas_tpu")
    f32 = jnp.float32

    @register
    class RMSNorm(_SameShape):
        def __init__(self, epsilon: float = 1e-6, **kwargs):
            super().__init__(**kwargs)
            self.epsilon = epsilon

        def build(self, input_shape):
            self.weight = self._weight(
                "weight", (int(input_shape[-1]),), "ones", autocast=False
            )

        def call(self, x):
            y = _rms(x, self.epsilon) * self.weight.value.astype(f32)
            return y.astype(x.dtype)

        def get_config(self):
            return {**super().get_config(), "epsilon": self.epsilon}

    @register
    class DenseMLP(shared["SwiGLU"]):
        def _forward(self, x):
            with jax.named_scope("mlp.dense"):
                return super()._forward(x)

    @register
    class LatentAttention(_Remat):
        """Latent attention (MLA): queries and keys of ``qk_nope_head_dim
        + qk_rope_head_dim``, values of ``v_head_dim``, keys and values
        expanded from a normed latent of ``kv_lora_rank``. Under
        ``remat`` the backward pass runs the projections, the norm and
        the rotation again and keeps the flash kernel's result and
        log-sum-exp (a head's ``[S, v_head_dim]`` in the compute dtype
        and ``[S]`` in float32)."""

        kept = (OUT_NAME, LSE_NAME)

        def __init__(self, num_heads: int, qk_nope_head_dim: int,
                     qk_rope_head_dim: int, v_head_dim: int,
                     kv_lora_rank: int, rope_theta: float = 10000.0,
                     epsilon: float = 1e-6, init_std: float = 0.02,
                     **kwargs):
            super().__init__(**kwargs)
            if qk_rope_head_dim % 2:
                raise ValueError(
                    f"qk_rope_head_dim {qk_rope_head_dim} is rotated in "
                    f"pairs"
                )
            self.num_heads, self.kv_lora_rank = num_heads, kv_lora_rank
            self.qk_nope_head_dim, self.qk_rope_head_dim = (
                qk_nope_head_dim, qk_rope_head_dim)
            self.v_head_dim = v_head_dim
            self.rope_theta, self.epsilon = rope_theta, epsilon
            self.init_std = init_std

        def build(self, input_shape):
            d, h = int(input_shape[-1]), self.num_heads
            nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                              self.v_head_dim)
            init = keras.initializers.RandomNormal(stddev=self.init_std)
            self.q_proj = self._weight("q_proj", (d, h * (nope + rope)), init)
            self.kv_a_proj_with_mqa = self._weight(
                "kv_a_proj_with_mqa", (d, self.kv_lora_rank + rope), init)
            self.kv_a_layernorm = self._weight(
                "kv_a_layernorm", (self.kv_lora_rank,), "ones", False)
            self.kv_b_proj = self._weight(
                "kv_b_proj", (self.kv_lora_rank, h * (nope + dv)), init)
            self.o_proj = self._weight("o_proj", (h * dv, d), init)

        def _forward(self, x):
            from elephas_tpu.ops.flash_attention import flash_attention

            b, s = jnp.shape(x)[0], x.shape[1]
            h, rank = self.num_heads, self.kv_lora_rank
            nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                              self.v_head_dim)
            with jax.named_scope("mla.proj"):
                cos, sin = _rope_tables(s, rope, float(self.rope_theta))
                # one angle a pair: the tables repeat their first half
                cos, sin = cos[:, :rope // 2], sin[:, :rope // 2]

                def rotate(t):  # [B, S, heads, rope], pairs (2i, 2i + 1)
                    pairs = t.astype(f32).reshape(t.shape[:-1] + (rope // 2, 2))
                    even, odd = pairs[..., 0], pairs[..., 1]
                    c, sn = cos[None, :, None], sin[None, :, None]
                    turned = jnp.stack(
                        [even * c - odd * sn, even * sn + odd * c], axis=-1)
                    return turned.reshape(t.shape).astype(x.dtype)

                q = jnp.matmul(x, self.q_proj.value).reshape(
                    b, s, h, nope + rope)
                q = jnp.concatenate(
                    [q[..., :nope], rotate(q[..., nope:])], axis=-1)
                latent = jnp.matmul(x, self.kv_a_proj_with_mqa.value)
                k_rope = rotate(latent[..., None, rank:])  # one head
                normed = (_rms(latent[..., :rank], self.epsilon)
                          * self.kv_a_layernorm.value.astype(f32))
                kv = jnp.matmul(
                    normed.astype(x.dtype), self.kv_b_proj.value
                ).reshape(b, s, h, nope + dv)
                k = jnp.concatenate([
                    kv[..., :nope],
                    jnp.broadcast_to(k_rope, (b, s, h, rope)),
                ], axis=-1)
                v = kv[..., nope:]
            with jax.named_scope("attn.mla"):
                heads_first = lambda t: jnp.transpose(t, (0, 2, 1, 3))  # noqa: E731
                out = flash_attention(
                    heads_first(q), heads_first(k), heads_first(v),
                    causal=True, scale=(nope + rope) ** -0.5,
                )
                out = heads_first(out).reshape(b, s, h * dv)
            with jax.named_scope("mla.proj"):
                return jnp.matmul(out, self.o_proj.value)

        def get_config(self):
            return {**super().get_config(), "num_heads": self.num_heads,
                    "qk_nope_head_dim": self.qk_nope_head_dim,
                    "qk_rope_head_dim": self.qk_rope_head_dim,
                    "v_head_dim": self.v_head_dim,
                    "kv_lora_rank": self.kv_lora_rank,
                    "rope_theta": self.rope_theta, "epsilon": self.epsilon,
                    "init_std": self.init_std, "remat": self.remat}

    _LAYERS = {cls.__name__: cls
               for cls in (RMSNorm, LatentAttention, DenseMLP)}
    assert set(_LAYERS) == set(LAYER_NAMES)
    return _LAYERS


def __getattr__(name):
    if name in LAYER_NAMES:
        return _layers()[name]
    raise AttributeError(name)


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
    keras = _keras()
    keras.utils.set_random_seed(seed)
    with _dtype_policy_scope(keras, dtype_policy):
        shared, L = qwen3_next._layers(), _layers()
        Norm = L["RMSNorm"]
        inputs = keras.Input((maxlen,), dtype="int32")
        x = keras.layers.Embedding(
            vocab_size, hidden_size, name="embed_tokens",
            embeddings_initializer=keras.initializers.RandomNormal(
                stddev=init_std),
        )(inputs)
        for i in range(num_hidden_layers):
            h = Norm(rms_norm_eps, name=f"layer{i}_input_norm")(x)
            x = x + L["LatentAttention"](
                num_attention_heads, qk_nope_head_dim, qk_rope_head_dim,
                v_head_dim, kv_lora_rank, rope_theta, rms_norm_eps, init_std,
                remat=remat, name=f"layer{i}_attn",
            )(h)
            h = Norm(rms_norm_eps, name=f"layer{i}_post_norm")(x)
            if i < first_k_dense_replace:
                h = L["DenseMLP"](
                    intermediate_size, init_std, remat=remat,
                    name=f"layer{i}_mlp",
                )(h)
            else:
                h = shared["SparseMoeBlock"](
                    n_routed_experts, num_experts_per_tok,
                    moe_intermediate_size,
                    n_shared_experts * moe_intermediate_size, experts_held,
                    init_std, scoring_func=scoring_func, selection_bias=True,
                    routed_scaling_factor=routed_scaling_factor,
                    gated_shared_expert=False, remat=remat,
                    name=f"layer{i}_moe",
                )(h)
            x = x + h
        x = Norm(rms_norm_eps, name="final_norm")(x)
        outputs = shared["LMHead"](vocab_size, init_std, name="lm_head")(x)
        model = keras.Model(inputs, outputs, name="deepseek_v3_lm")
    model.compile(
        optimizer=keras.optimizers.SGD(lr, momentum=momentum),
        loss=next_token_loss,
    )
    return model
