"""Window-and-full-attention mixture-of-experts LM (the SmallThinker
block, as ``SmallThinker-21BA3B-Instruct`` publishes it).

What the block adds to the zoo, beside the layers it shares with
:mod:`elephas_tpu.models.qwen3_next` (``SparseMoeBlock``, ``LMHead``,
``next_token_loss``) and :mod:`elephas_tpu.models.deepseek_v3`
(``RMSNorm``):

- :class:`BandedAttention`: grouped-query causal attention with no
  gate, no bias and no q/k norm, whose two per-layer settings are the
  model's own: ``window`` (a query sees itself and the ``window - 1``
  keys before it; None is full causal attention) and ``rotary`` (the
  rotary embedding over the whole head, pairs ``(i, i + head_dim /
  2)``; without it the layer has no position term at all). The flash
  kernels' grids hold the band's block pairs alone
  (:func:`elephas_tpu.ops.flash_attention.flash_attention`,
  ``window``), under the scope ``attn.window``; a full layer runs under
  ``attn.full``.
- a decoder layer whose router stands before attention: it scores the
  layer's input ``h`` (the residual stream, un-normed), while the
  experts take the normed attention result; the shared
  ``SparseMoeBlock`` is called with both tensors, holds no shared
  expert (``shared_width`` 0) and its experts are ReGLU
  (``hidden_act`` ``relu``).

Decoder layer ``l``: ``h1 = h + attn_l(norm(h)); h' = h1 +
moe(norm(h1), route_from=h)``, with ``sliding_window_layout[l]`` and
``rope_layout[l]`` choosing the attention's window and rotation; a
final norm and an untied head. ``fit`` only: a cache whose layers
differ (serving) is not here.
"""

from __future__ import annotations

from elephas_tpu.models import deepseek_v3, qwen3_next
from elephas_tpu.models.qwen3_next import next_token_loss
from elephas_tpu.models.transformer import (
    _apply_rope,
    _dtype_policy_scope,
    _keras,
    _rope_tables,
)

_LAYERS = None
LAYER_NAMES = ("BandedAttention",)
# the keys of a published ``rope_parameters`` group that YaRN reads, in
# the order ``transformer._rope_tables`` takes them
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")
# the names (``jax.ad_checkpoint.checkpoint_name``) of what the layer
# hands the flash kernels, heads first: ``[B, H, S, D]`` queries (rotated
# where the layer rotates) and ``[B, Hk, S, D]`` keys and values at
# their own head count
Q_NAME, K_NAME, V_NAME = "attn_q", "attn_k", "attn_v"


def _layers():
    """This module's layer class, created lazily (keras under the jax
    backend first) and registered with Keras's serializer."""
    global _LAYERS
    if _LAYERS is not None:
        return _LAYERS
    import jax
    import jax.numpy as jnp
    import keras
    from jax.ad_checkpoint import checkpoint_name

    from elephas_tpu import telemetry
    from elephas_tpu.ops.flash_attention import LSE_NAME, OUT_NAME

    _Remat = qwen3_next._layers()["_Remat"]
    register = keras.saving.register_keras_serializable(package="elephas_tpu")
    f32 = jnp.float32

    @register
    class BandedAttention(_Remat):
        """Grouped-query causal attention, banded (``window``) or full,
        rotated or not: the module's docstring has the settings. Under
        ``remat`` the backward pass keeps, beside the layer's input,
        everything the flash kernels read: q, k and v as the layer hands
        them over (projected, rotated, heads first; k and v at their own
        head count) and the forward kernel's result and log-sum-exp (a
        head's ``[S, D]`` in the compute dtype and ``[S]`` in float32),
        whatever the band. So it projects, rotates and transposes once
        a step; only the gate's small product runs again. Tracing such
        a layer emits one ``remat.kept`` event: the layer's name, the
        names kept and the bytes that q, k and v hold, from their
        shapes."""

        kept = (Q_NAME, K_NAME, V_NAME, OUT_NAME, LSE_NAME)

        def __init__(self, num_heads: int, num_kv_heads: int, head_dim: int,
                     window: int | None = None, rotary: bool = True,
                     rope_theta: float = 10000.0, init_std: float = 0.02,
                     gating: str | None = None,
                     rotary_dim: int | None = None,
                     yarn: dict | None = None, **kwargs):
            super().__init__(**kwargs)
            rotary_dim = head_dim if rotary_dim is None else rotary_dim
            if num_heads % num_kv_heads or rotary_dim % 2 or not (
                    0 < rotary_dim <= head_dim):
                raise ValueError(
                    f"{num_heads} query heads over {num_kv_heads} key/value "
                    f"heads of width {head_dim}, {rotary_dim} of it rotated"
                )
            if window is not None and window < 1:
                raise ValueError(f"window {window!r} holds no key")
            if gating not in (None, "per-head"):
                raise ValueError(
                    f"gating {gating!r} is neither None nor 'per-head'")
            if yarn is not None and set(yarn) != set(YARN_KEYS):
                raise ValueError(
                    f"yarn names {sorted(yarn)}, not {sorted(YARN_KEYS)}")
            self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
            self.head_dim, self.window = head_dim, window
            self.rotary, self.rope_theta = bool(rotary), rope_theta
            self.init_std, self.gating = init_std, gating
            self.rotary_dim = rotary_dim
            self.yarn = None if yarn is None else dict(yarn)

        def build(self, input_shape):
            d, hd = int(input_shape[-1]), self.head_dim
            init = keras.initializers.RandomNormal(stddev=self.init_std)
            self.q_proj = self._weight("q_proj", (d, self.num_heads * hd), init)
            self.k_proj = self._weight(
                "k_proj", (d, self.num_kv_heads * hd), init)
            self.v_proj = self._weight(
                "v_proj", (d, self.num_kv_heads * hd), init)
            self.o_proj = self._weight("o_proj", (self.num_heads * hd, d), init)
            if self.gating:
                self.g_proj = self._weight("g_proj", (d, self.num_heads), init)

        def _rotate(self, t, cos, sin, dtype):
            """The first ``rotary_dim`` of each head of ``t [B, S, heads,
            D]`` turned in float32, the rest passed on as it is."""
            rot = self.rotary_dim
            if rot == self.head_dim:
                return _apply_rope(t.astype(f32), cos, sin).astype(dtype)
            turned = _apply_rope(t[..., :rot].astype(f32), cos, sin)
            return jnp.concatenate(
                [turned, t[..., rot:].astype(f32)], axis=-1).astype(dtype)

        def _forward(self, x):
            from elephas_tpu.ops.flash_attention import flash_attention

            b, s = jnp.shape(x)[0], x.shape[1]
            h, hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
            with jax.named_scope("attn.proj"):
                q = jnp.matmul(x, self.q_proj.value).reshape(b, s, h, hd)
                k = jnp.matmul(x, self.k_proj.value).reshape(b, s, hk, hd)
                v = jnp.matmul(x, self.v_proj.value).reshape(b, s, hk, hd)
                if self.gating:  # one logit a head and token
                    gate = jnp.matmul(x, self.g_proj.value)
                if self.rotary:
                    cos, sin = _rope_tables(
                        s, self.rotary_dim, float(self.rope_theta),
                        self.yarn and tuple(self.yarn[k] for k in YARN_KEYS))
                    cos, sin = cos[None, :, None], sin[None, :, None]
                    q, k = (self._rotate(t, cos, sin, x.dtype) for t in (q, k))
            with jax.named_scope(
                    "attn.full" if self.window is None else "attn.window"):
                heads_first = lambda t: jnp.transpose(t, (0, 2, 1, 3))  # noqa: E731
                q, k, v = (
                    checkpoint_name(heads_first(t), name)
                    for t, name in ((q, Q_NAME), (k, K_NAME), (v, V_NAME)))
                out = flash_attention(
                    q, k, v, causal=True, scale=hd ** -0.5,
                    window=self.window,
                )
                if self.remat:
                    telemetry.emit(
                        "remat.kept", layer=self.name, kept=list(self.kept),
                        bytes={name: t.size * t.dtype.itemsize for name, t
                               in ((Q_NAME, q), (K_NAME, k), (V_NAME, v))})
                out = heads_first(out)
                if not self.gating:
                    out = out.reshape(b, s, h * hd)
            if self.gating:
                with jax.named_scope("attn.gate"):
                    out = out.astype(f32) * jax.nn.sigmoid(
                        gate.astype(f32))[..., None]
                    out = out.astype(x.dtype).reshape(b, s, h * hd)
            with jax.named_scope("attn.proj"):
                return jnp.matmul(out, self.o_proj.value)

        def get_config(self):
            return {**super().get_config(), "num_heads": self.num_heads,
                    "num_kv_heads": self.num_kv_heads,
                    "head_dim": self.head_dim, "window": self.window,
                    "rotary": self.rotary, "rope_theta": self.rope_theta,
                    "init_std": self.init_std, "gating": self.gating,
                    "rotary_dim": self.rotary_dim, "yarn": self.yarn,
                    "remat": self.remat}

    _LAYERS = {"BandedAttention": BandedAttention}
    return _LAYERS


def __getattr__(name):
    if name in LAYER_NAMES:
        return _layers()[name]
    raise AttributeError(name)


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
    keras = _keras()
    keras.utils.set_random_seed(seed)
    with _dtype_policy_scope(keras, dtype_policy):
        shared, L = qwen3_next._layers(), _layers()
        Norm = deepseek_v3._layers()["RMSNorm"]
        inputs = keras.Input((maxlen,), dtype="int32")
        x = keras.layers.Embedding(
            vocab_size, hidden_size, name="embed_tokens",
            embeddings_initializer=keras.initializers.RandomNormal(
                stddev=init_std),
        )(inputs)
        for i in range(num_hidden_layers):
            h = Norm(rms_norm_eps, name=f"layer{i}_input_norm")(x)
            attended = x + L["BandedAttention"](
                num_attention_heads, num_key_value_heads, head_dim,
                sliding_window_size if sliding_window_layout[i] else None,
                bool(rope_layout[i]), rope_theta, init_std, remat=remat,
                name=f"layer{i}_attn",
            )(h)
            h = Norm(rms_norm_eps, name=f"layer{i}_post_norm")(attended)
            # the router reads the layer's input, the experts ``h``
            x = attended + shared["SparseMoeBlock"](
                moe_num_primary_experts, moe_num_active_primary_experts,
                moe_ffn_hidden_size, 0, experts_held, init_std,
                hidden_act="relu", remat=remat, name=f"layer{i}_moe",
            )(h, x)
        x = Norm(rms_norm_eps, name="final_norm")(x)
        outputs = shared["LMHead"](vocab_size, init_std, name="lm_head")(x)
        model = keras.Model(inputs, outputs, name="smallthinker_lm")
    model.compile(
        optimizer=keras.optimizers.SGD(lr, momentum=momentum),
        loss=next_token_loss,
    )
    return model
