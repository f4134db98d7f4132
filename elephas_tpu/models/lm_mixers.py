"""The sparse LMs' sequence mixers: three attention layers over the flash
kernels, the gated delta rule and the state-space scan.

Like :mod:`elephas_tpu.models.lm_blocks`, whose bases these build on,
this module imports keras and is imported by the model files inside
their ``*_lm`` functions.

- :func:`causal_flash_attention`: the one path of the three attention
  layers into :func:`elephas_tpu.ops.flash_attention.flash_attention`:
  heads first, q, k and v named for the layer that keeps them, the
  kernels, heads last. What a layer projects, norms, rotates and gates,
  and under which scope, is its own.
- :class:`BandedAttention`: grouped-query causal attention with no bias
  and no q/k norm, set a layer at a time: ``window`` (a query sees
  itself and the ``window - 1`` keys before it; None is full causal
  attention), ``rotary`` (the rotary embedding, pairs ``(i, i +
  rotary_dim / 2)``, over ``rotary_dim`` of each head, with ``yarn``
  scaled frequencies; without it the layer has no position term at
  all), ``gating`` (a sigmoid output gate, one scalar a head and
  token) and ``scale`` (the scores' multiplier; None is ``head_dim **
  -0.5``). The flash kernels' grids hold the band's block pairs alone,
  under the scope ``attn.window``; a full layer runs under
  ``attn.full``.
- :class:`GatedAttention`: grouped-query causal attention with per-head
  q/k norms, rotary embedding on a part of each head, and a sigmoid
  output gate that ``q_proj`` computes beside the query.
- :class:`LatentAttention` (MLA, the form without a query latent):
  ``q_proj`` gives a head a ``qk_nope_head_dim + qk_rope_head_dim``
  wide query; ``kv_a_proj_with_mqa`` gives a token one
  ``kv_lora_rank`` wide latent and one rotated key part that all heads
  share; ``kv_b_proj`` raises the normed latent to a head's unrotated
  key part and its ``v_head_dim`` wide value. Scores are over the
  whole query and key width, the sum over the narrower values. The
  rotation turns the pairs ``(2i, 2i + 1)`` (``rope_interleave``).
- :class:`GatedDeltaNet`: the recurrent-state mixer: fused q/k/v/z and
  beta/decay projections, a causal depthwise convolution, the chunked
  gated delta rule (:mod:`elephas_tpu.ops.gated_delta`), a gated
  per-head norm and the output projection.
- :class:`Mamba2Mixer`: one input projection split into a gate ``z``,
  the convolved channels ``x | B | C`` and a step ``dt`` a head; a causal
  depthwise convolution with bias and a silu over those channels; the
  selective scan (:func:`elephas_tpu.ops.ssd.ssd_chunked`: a scalar
  decay ``exp(dt A)`` a head, ``B`` and ``C`` shared by the heads of a
  group, a ``D`` skip); a gated RMS norm in groups (``norm(y *
  silu(z))``: the gate first, then the norm over each group of
  ``inner / n_groups`` channels); the output projection. Its inner
  width is ``mamba_num_heads * mamba_head_dim``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import keras
from jax.ad_checkpoint import checkpoint_name

from elephas_tpu import telemetry
from elephas_tpu.models.lm_blocks import Remat, f32, normal, register, rms
from elephas_tpu.models.transformer import _apply_rope, _rope_tables
from elephas_tpu.ops.flash_attention import LSE_NAME, OUT_NAME
from elephas_tpu.ops.gated_delta import RESOLVE_NAME, gated_delta_rule
from elephas_tpu.ops.ssd import ssd_chunked

# the keys of a published ``rope_parameters`` group that YaRN reads, in
# the order ``transformer._rope_tables`` takes them
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")
# the names (``jax.ad_checkpoint.checkpoint_name``) of what an attention
# layer hands the flash kernels, heads first: ``[B, H, S, D]`` queries
# (rotated where the layer rotates) and ``[B, Hk, S, D]`` keys and values
# at their own head count
Q_NAME, K_NAME, V_NAME = "attn_q", "attn_k", "attn_v"


def causal_flash_attention(layer, q, k, v, scale, window=None):
    """Causal attention of ``q [B, S, H, D]`` over ``k [B, S, Hk, D]``
    and ``v [B, S, Hk, Dv]`` through the flash kernels, ``[B, S, H, Dv]``
    out, for the attention layer ``layer``, inside the scope the layer
    has opened. Of q, k and v as the kernels take them (heads first),
    those that ``layer.kept`` lists are named, so that a recomputed
    layer keeps them; one that keeps any of them says so once a trace
    in a ``remat.kept`` event (the layer's name, the names kept and the
    bytes that the kept ones of the three hold, from their shapes)."""
    # looked up at each call: tests watch the kernels' one entry there
    from elephas_tpu.ops.flash_attention import flash_attention

    def heads_first(t, name=None):
        t = jnp.transpose(t, (0, 2, 1, 3))
        return checkpoint_name(t, name) if name in layer.kept else t

    q, k, v = (heads_first(t, name) for t, name in (
        (q, Q_NAME), (k, K_NAME), (v, V_NAME)))
    out = flash_attention(q, k, v, causal=True, scale=scale, window=window)
    held = {name: t.size * t.dtype.itemsize for name, t in (
        (Q_NAME, q), (K_NAME, k), (V_NAME, v)) if name in layer.kept}
    if layer.remat and held:
        telemetry.emit("remat.kept", layer=layer.name,
                       kept=list(layer.kept), bytes=held)
    return heads_first(out)


@register
class BandedAttention(Remat):
    """Grouped-query causal attention, banded (``window``) or full,
    rotated or not, gated or not: the module's docstring has the
    settings. Under ``remat`` the backward pass keeps, beside the
    layer's input, everything the flash kernels read: q, k and v as the
    layer hands them over (projected, rotated, heads first; k and v at
    their own head count) and the forward kernel's result and
    log-sum-exp (a head's ``[S, D]`` in the compute dtype and ``[S]``
    in float32), whatever the band. So it projects, rotates and
    transposes once a step; only the gate's small product runs
    again."""

    kept = (Q_NAME, K_NAME, V_NAME, OUT_NAME, LSE_NAME)

    def __init__(self, num_heads: int, num_kv_heads: int, head_dim: int,
                 window: int | None = None, rotary: bool = True,
                 rope_theta: float = 10000.0, init_std: float = 0.02,
                 gating: str | None = None,
                 rotary_dim: int | None = None,
                 yarn: dict | None = None, scale: float | None = None,
                 **kwargs):
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
        self.scale = scale

    def build(self, input_shape):
        d, hd = int(input_shape[-1]), self.head_dim
        init = normal(self.init_std)
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
            out = causal_flash_attention(
                self, q, k, v,
                hd ** -0.5 if self.scale is None else self.scale,
                self.window)
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
                "scale": self.scale, "remat": self.remat}


@register
class GatedAttention(Remat):
    """Grouped-query causal attention with q/k norms, a partial
    rotation and a sigmoid output gate. Under ``remat`` the backward
    pass projects, norms and rotates again and keeps the flash
    kernel's result and log-sum-exp (a head's ``[S, D]`` in the
    compute dtype and ``[S]`` in float32), so that the forward kernel
    runs once a layer."""

    kept = (OUT_NAME, LSE_NAME)

    def __init__(self, num_heads: int, num_kv_heads: int, head_dim: int,
                 rotary_dim: int, rope_theta: float = 10000.0,
                 epsilon: float = 1e-6, init_std: float = 0.02,
                 **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads or rotary_dim % 2:
            raise ValueError(
                f"{num_heads} query heads over {num_kv_heads} key/value "
                f"heads, rotary_dim {rotary_dim}"
            )
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.rotary_dim = head_dim, rotary_dim
        self.rope_theta, self.epsilon = rope_theta, epsilon
        self.init_std = init_std

    def build(self, input_shape):
        d, hd = int(input_shape[-1]), self.head_dim
        init = normal(self.init_std)
        self.q_proj = self._weight(
            "q_proj", (d, self.num_heads * 2 * hd), init)
        self.k_proj = self._weight(
            "k_proj", (d, self.num_kv_heads * hd), init)
        self.v_proj = self._weight(
            "v_proj", (d, self.num_kv_heads * hd), init)
        self.o_proj = self._weight(
            "o_proj", (self.num_heads * hd, d), init)
        self.q_norm = self._weight("q_norm", (hd,), "zeros", False)
        self.k_norm = self._weight("k_norm", (hd,), "zeros", False)

    def _forward(self, x):
        b, s = jnp.shape(x)[0], x.shape[1]
        h, hk, hd, rot = (self.num_heads, self.num_kv_heads,
                          self.head_dim, self.rotary_dim)
        with jax.named_scope("attn.proj"):
            q, gate = jnp.split(
                jnp.matmul(x, self.q_proj.value).reshape(b, s, h, 2 * hd),
                2, axis=-1,
            )
            k = jnp.matmul(x, self.k_proj.value).reshape(b, s, hk, hd)
            v = jnp.matmul(x, self.v_proj.value).reshape(b, s, hk, hd)
            q = rms(q, self.epsilon) * (1.0 + self.q_norm.value.astype(f32))
            k = rms(k, self.epsilon) * (1.0 + self.k_norm.value.astype(f32))
            cos, sin = _rope_tables(s, rot, float(self.rope_theta))
            cos, sin = cos[None, :, None], sin[None, :, None]

            def rotate(t):  # on the first ``rot`` of each head
                turned = _apply_rope(t[..., :rot], cos, sin)
                return jnp.concatenate(
                    [turned, t[..., rot:]], axis=-1).astype(x.dtype)

            q, k = rotate(q), rotate(k)
        with jax.named_scope("attn.full"):
            out = causal_flash_attention(self, q, k, v, hd ** -0.5)
            out = out.astype(f32) * jax.nn.sigmoid(gate.astype(f32))
            out = out.astype(x.dtype).reshape(b, s, h * hd)
        with jax.named_scope("attn.proj"):
            return jnp.matmul(out, self.o_proj.value)

    def get_config(self):
        return {**super().get_config(), "num_heads": self.num_heads,
                "num_kv_heads": self.num_kv_heads,
                "head_dim": self.head_dim, "rotary_dim": self.rotary_dim,
                "rope_theta": self.rope_theta, "epsilon": self.epsilon,
                "init_std": self.init_std, "remat": self.remat}


@register
class LatentAttention(Remat):
    """Latent attention (MLA): queries and keys of ``qk_nope_head_dim
    + qk_rope_head_dim``, values of ``v_head_dim``, keys and values
    expanded from a normed latent of ``kv_lora_rank``. Under
    ``remat`` the backward pass runs the projections, the norm and
    the rotation again and keeps the flash kernel's result and
    log-sum-exp (a head's ``[S, v_head_dim]`` in the compute dtype
    and ``[S]`` in float32), so that the forward kernel runs once a
    layer."""

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
        init = normal(self.init_std)
        self.q_proj = self._weight("q_proj", (d, h * (nope + rope)), init)
        self.kv_a_proj_with_mqa = self._weight(
            "kv_a_proj_with_mqa", (d, self.kv_lora_rank + rope), init)
        self.kv_a_layernorm = self._weight(
            "kv_a_layernorm", (self.kv_lora_rank,), "ones", False)
        self.kv_b_proj = self._weight(
            "kv_b_proj", (self.kv_lora_rank, h * (nope + dv)), init)
        self.o_proj = self._weight("o_proj", (h * dv, d), init)

    def _forward(self, x):
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
            normed = (rms(latent[..., :rank], self.epsilon)
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
            out = causal_flash_attention(
                self, q, k, v, (nope + rope) ** -0.5)
            out = out.reshape(b, s, h * dv)
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


@register
class GatedDeltaNet(Remat):
    """The Gated DeltaNet mixer; the module's docstring has its parts.
    Under ``remat`` it keeps, beside its input, the chunks' triangular
    inverses, 64 KiB a head a chunk: the dearest product of the scan's
    chunk-parallel part by far."""

    kept = (RESOLVE_NAME,)

    def __init__(self, num_key_heads: int, num_value_heads: int,
                 key_head_dim: int, value_head_dim: int,
                 conv_kernel: int = 4, chunk_size: int = 64,
                 epsilon: float = 1e-6, init_std: float = 0.02,
                 **kwargs):
        super().__init__(**kwargs)
        if num_value_heads % num_key_heads:
            raise ValueError(
                f"{num_value_heads} value heads over {num_key_heads} "
                f"key heads"
            )
        self.num_key_heads, self.num_value_heads = (
            num_key_heads, num_value_heads)
        self.key_head_dim, self.value_head_dim = (
            key_head_dim, value_head_dim)
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.epsilon, self.init_std = epsilon, init_std

    def build(self, input_shape):
        d = int(input_shape[-1])
        hk, hv = self.num_key_heads, self.num_value_heads
        key_dim, value_dim = hk * self.key_head_dim, hv * self.value_head_dim
        init = normal(self.init_std)
        self.in_proj_qkvz = self._weight(
            "in_proj_qkvz", (d, 2 * key_dim + 2 * value_dim), init)
        self.in_proj_ba = self._weight("in_proj_ba", (d, 2 * hv), init)
        bound = self.conv_kernel ** -0.5
        self.conv = self._weight(
            "conv", (self.conv_kernel, 2 * key_dim + value_dim),
            keras.initializers.RandomUniform(-bound, bound), False)
        self.dt_bias = self._weight("dt_bias", (hv,), "ones", False)
        self.A_log = self._weight(
            "A_log", (hv,),
            lambda shape, dtype=None: keras.ops.log(
                keras.random.uniform(shape, 1.0, 16.0, dtype=dtype)),
            False)
        self.norm = self._weight(
            "norm", (self.value_head_dim,), "ones", False)
        self.out_proj = self._weight("out_proj", (value_dim, d), init)

    def _forward(self, x):
        b, s = jnp.shape(x)[0], x.shape[1]
        hk, hv = self.num_key_heads, self.num_value_heads
        dk, dv = self.key_head_dim, self.value_head_dim
        per = hv // hk  # value heads a key head
        with jax.named_scope("gdn.proj"):
            # the published layout: one group a key head, holding its
            # q, k and its value heads' v, z (and b, a)
            qkvz = jnp.matmul(x, self.in_proj_qkvz.value).reshape(
                b, s, hk, 2 * dk + 2 * per * dv)
            q, k, v, z = jnp.split(
                qkvz, (dk, 2 * dk, 2 * dk + per * dv), axis=-1)
            ba = jnp.matmul(x, self.in_proj_ba.value).reshape(
                b, s, hk, 2 * per)
            beta_in, a = (t.reshape(b, s, hv) for t in
                          jnp.split(ba, 2, axis=-1))
            z = z.reshape(b, s, hv, dv)
        with jax.named_scope("gdn.conv"):
            mixed = jnp.concatenate([
                q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
                v.reshape(b, s, hv * dv)], axis=-1)
            width = self.conv_kernel
            padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
            taps = self.conv.value.astype(f32)
            mixed = sum(
                padded[:, j:j + s].astype(f32) * taps[j]
                for j in range(width)
            )
            mixed = jax.nn.silu(mixed).astype(x.dtype)
            q, k, v = jnp.split(mixed, (hk * dk, 2 * hk * dk), axis=-1)
        with jax.named_scope("gdn.scan"):
            def unit(t):  # L2-normalised over the head, in float32
                t = t.reshape(b, s, hk, dk).astype(f32)
                return t * jax.lax.rsqrt(
                    jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

            q = (unit(q) * dk ** -0.5).astype(x.dtype)
            k = unit(k).astype(x.dtype)
            beta = jax.nn.sigmoid(beta_in.astype(f32))
            decay = -jnp.exp(self.A_log.value.astype(f32)) * jax.nn.softplus(
                a.astype(f32) + self.dt_bias.value.astype(f32))
            out, _state = gated_delta_rule(
                jnp.repeat(q, per, axis=2), jnp.repeat(k, per, axis=2),
                v.reshape(b, s, hv, dv), decay, beta,
                chunk_size=self.chunk_size,
            )
        with jax.named_scope("gdn.out"):
            out = (rms(out, self.epsilon) * self.norm.value.astype(f32)
                   * jax.nn.silu(z.astype(f32))).astype(x.dtype)
            return jnp.matmul(
                out.reshape(b, s, hv * dv), self.out_proj.value)

    def get_config(self):
        return {**super().get_config(),
                "num_key_heads": self.num_key_heads,
                "num_value_heads": self.num_value_heads,
                "key_head_dim": self.key_head_dim,
                "value_head_dim": self.value_head_dim,
                "conv_kernel": self.conv_kernel,
                "chunk_size": self.chunk_size, "epsilon": self.epsilon,
                "init_std": self.init_std, "remat": self.remat}


@register
class Mamba2Mixer(Remat):
    """The Mamba-2 mixer; the module's docstring has its parts.
    Under ``remat`` it keeps its input alone: the backward pass
    projects, convolves and scans again (no product of the scan is
    dear, and what it would keep is large: a layer's decay factors
    alone are ``heads x S x chunk`` float32)."""

    def __init__(self, num_heads: int, head_dim: int, state_size: int,
                 n_groups: int, conv_kernel: int = 4,
                 chunk_size: int = 128, epsilon: float = 1e-5,
                 time_step_min: float = 0.001,
                 time_step_max: float = 0.1,
                 time_step_floor: float = 1e-4, init_std: float = 0.02,
                 out_proj_std: float | None = None, **kwargs):
        super().__init__(**kwargs)
        inner = num_heads * head_dim
        if num_heads % n_groups or inner % n_groups:
            raise ValueError(
                f"{num_heads} heads of {head_dim} over {n_groups} groups"
            )
        self.num_heads, self.head_dim = num_heads, head_dim
        self.state_size, self.n_groups = state_size, n_groups
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.epsilon = epsilon
        self.time_step_min, self.time_step_max = (
            time_step_min, time_step_max)
        self.time_step_floor = time_step_floor
        self.init_std = init_std
        self.out_proj_std = init_std if out_proj_std is None else (
            out_proj_std)

    def build(self, input_shape):
        d, h = int(input_shape[-1]), self.num_heads
        inner = h * self.head_dim
        conv_dim = inner + 2 * self.n_groups * self.state_size
        self.in_proj = self._weight(
            "in_proj", (d, inner + conv_dim + h), normal(self.init_std))
        bound = self.conv_kernel ** -0.5
        uniform = keras.initializers.RandomUniform(-bound, bound)
        self.conv = self._weight(
            "conv", (self.conv_kernel, conv_dim), uniform, False)
        self.conv_bias = self._weight(
            "conv_bias", (conv_dim,), uniform, False)

        def dt_bias(shape, dtype=None):
            # the inverse softplus of a log-uniform step
            lo, hi = math.log(self.time_step_min), math.log(
                self.time_step_max)
            dt = keras.ops.maximum(keras.ops.exp(keras.random.uniform(
                shape, lo, hi, dtype=dtype)), self.time_step_floor)
            return dt + keras.ops.log(-keras.ops.expm1(-dt))

        self.dt_bias = self._weight("dt_bias", (h,), dt_bias, False)
        self.A_log = self._weight(
            "A_log", (h,), lambda shape, dtype=None: keras.ops.log(
                keras.ops.arange(1, shape[0] + 1, dtype=dtype)), False)
        self.D = self._weight("D", (h,), "ones", False)
        self.norm = self._weight("norm", (inner,), "ones", False)
        self.out_proj = self._weight(
            "out_proj", (inner, d), normal(self.out_proj_std))

    def _forward(self, x):
        b, s = jnp.shape(x)[0], x.shape[1]
        h, p = self.num_heads, self.head_dim
        g, n = self.n_groups, self.state_size
        inner = h * p
        with jax.named_scope("ssm.proj"):
            z, mixed, dt = jnp.split(
                jnp.matmul(x, self.in_proj.value),
                (inner, 2 * inner + 2 * g * n), axis=-1)
        with jax.named_scope("ssm.conv"):
            width = self.conv_kernel
            padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
            taps = self.conv.value.astype(f32)
            mixed = sum(
                padded[:, j:j + s].astype(f32) * taps[j]
                for j in range(width)
            ) + self.conv_bias.value.astype(f32)
            mixed = jax.nn.silu(mixed).astype(x.dtype)
            u, b_in, c_in = jnp.split(
                mixed, (inner, inner + g * n), axis=-1)
        chunks = -(-s // self.chunk_size)
        # once a trace: the chunk the program runs, and the bytes of one
        # float32 [B, H, S / Q, Q, Q] factor of the masked product
        telemetry.emit(
            "ssd.chunks", layer=self.name, chunk=self.chunk_size,
            chunks=chunks, heads=h, groups=g,
            bytes=4 * b * h * chunks * self.chunk_size ** 2)
        with jax.named_scope("ssm.scan"):
            step = jax.nn.softplus(
                dt.astype(f32) + self.dt_bias.value.astype(f32))
            y, _state = ssd_chunked(
                u.reshape(b, s, h, p), step,
                -jnp.exp(self.A_log.value.astype(f32)),
                b_in.reshape(b, s, g, n), c_in.reshape(b, s, g, n),
                self.D.value, chunk_size=self.chunk_size,
            )
        with jax.named_scope("ssm.norm"):
            # the gate first, then the norm over each group
            gated = (y.reshape(b, s, inner).astype(f32)
                     * jax.nn.silu(z.astype(f32)))
            normed = rms(
                gated.reshape(b, s, g, inner // g), self.epsilon
            ).reshape(b, s, inner) * self.norm.value.astype(f32)
        with jax.named_scope("ssm.proj"):
            return jnp.matmul(normed.astype(x.dtype), self.out_proj.value)

    def get_config(self):
        return {**super().get_config(), "num_heads": self.num_heads,
                "head_dim": self.head_dim,
                "state_size": self.state_size, "n_groups": self.n_groups,
                "conv_kernel": self.conv_kernel,
                "chunk_size": self.chunk_size, "epsilon": self.epsilon,
                "time_step_min": self.time_step_min,
                "time_step_max": self.time_step_max,
                "time_step_floor": self.time_step_floor,
                "init_std": self.init_std,
                "out_proj_std": self.out_proj_std, "remat": self.remat}
