"""Hybrid linear-attention mixture-of-experts LM (the Qwen3-Next block).

The zoo's dense LM (:mod:`elephas_tpu.models.transformer`) is a GPT-2
shaped block; this builder is the hybrid block of today's sparse models,
from Keras layers the zoo lacked:

- :class:`ZeroCentredRMSNorm`: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``,
  ``w`` from zeros, statistics in float32.
- :class:`SwiGLU`: ``down(silu(gate(x)) * up(x))``, no biases.
- :class:`GatedAttention`: grouped-query causal attention with per-head
  q/k norms, rotary embedding on a part of each head, and a sigmoid
  output gate that ``q_proj`` computes beside the query.
- :class:`GatedDeltaNet`: the recurrent-state mixer: fused q/k/v/z and
  beta/decay projections, a causal depthwise convolution, the chunked
  gated delta rule (:mod:`elephas_tpu.ops.gated_delta`), a gated
  per-head norm and the output projection.
- :class:`UngatedMLP`: ``down(act(up(x)))``, no biases (``relu2``: the
  squared ReLU), the ungated sparse block's shared expert.
- :class:`SparseMoeBlock`: a router over ALL experts, the routed part
  that the experts HELD here give (``experts_held``, a range; stacked
  weights; no token dropped; :func:`elephas_tpu.ops.moe.held_experts_ffn`)
  and a shared expert (here under its sigmoid gate). Experts and shared
  expert are gated, ``down(act(gate(x)) * up(x))``, or with
  ``gated_experts`` false ungated, ``down(act(up(x)))``. It counts what
  it routes in a non-trainable ``route_counts`` variable that the epoch
  runner reads with the loss.

Decoder layer ``i``: ``x += mixer_i(norm(x)); x += moe(norm(x))``, the
mixer gated attention where ``(i + 1) % full_attention_interval == 0``
and Gated DeltaNet elsewhere; then a final norm and an untied head.
:func:`qwen3_next_lm` returns the compiled model, ready for
``SparkModel``. Recomputation in the backward pass is set here
(``remat``): every mixer and sparse block then keeps its input and
the few results its class names (``_Remat.kept``).
"""

from __future__ import annotations

from elephas_tpu.models.transformer import (
    _apply_rope,
    _dtype_policy_scope,
    _keras,
    _rope_tables,
)

_LAYERS = None
COUNTER_NAMES = ("held_slots", "slots", "max_expert_tokens", "calls",
                 "blocked_calls")
LAYER_NAMES = ("ZeroCentredRMSNorm", "SwiGLU", "UngatedMLP", "GatedAttention",
               "GatedDeltaNet", "SparseMoeBlock", "LMHead")


def _rms(x, eps):
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def next_token_loss(y_true, logits):
    """Per-token cross-entropy of float32 logits against integer
    targets, as ``logsumexp - picked`` (no second ``[.., V]`` tensor)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("lm.head_loss"):
        logits = logits.astype(jnp.float32)
        picked = jnp.take_along_axis(
            logits, y_true.astype(jnp.int32)[..., None], axis=-1
        )[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked


def _layers():
    """The layer classes, created lazily (keras must be imported under
    the jax backend first) and registered with Keras's serializer."""
    global _LAYERS
    if _LAYERS is not None:
        return _LAYERS
    import jax
    import jax.numpy as jnp
    import keras

    from elephas_tpu.ops.flash_attention import LSE_NAME, OUT_NAME
    from elephas_tpu.ops.gated_delta import RESOLVE_NAME, gated_delta_rule
    from elephas_tpu.ops.moe import ROUTE_NAME

    register = keras.saving.register_keras_serializable(package="elephas_tpu")
    f32 = jnp.float32

    class _SameShape(keras.layers.Layer):
        """A layer of plain ``jax.numpy`` whose result has its input's
        shape and the layer's compute dtype (so Keras need not trace
        ``call`` with a symbolic batch to learn it)."""

        def compute_output_spec(self, x, *args, **kwargs):
            return keras.KerasTensor(x.shape, dtype=self.compute_dtype)

        def _weight(self, name, shape, init, autocast=True):
            return self.add_weight(
                name=name, shape=shape, initializer=init, autocast=autocast
            )

    def normal(stddev):
        return keras.initializers.RandomNormal(stddev=stddev)

    @register
    class ZeroCentredRMSNorm(_SameShape):
        def __init__(self, epsilon: float = 1e-6, **kwargs):
            super().__init__(**kwargs)
            self.epsilon = epsilon

        def build(self, input_shape):
            self.weight = self._weight(
                "weight", (int(input_shape[-1]),), "zeros", autocast=False
            )

        def call(self, x):
            y = _rms(x, self.epsilon) * (1.0 + self.weight.value.astype(f32))
            return y.astype(x.dtype)

        def get_config(self):
            return {**super().get_config(), "epsilon": self.epsilon}

    class _Remat(_SameShape):
        """``call`` is ``_forward``, under ``jax.checkpoint`` where the
        builder asked for it: the backward pass then keeps the layer's
        input, what ``_forward`` names with one of ``kept``
        (``jax.ad_checkpoint.checkpoint_name``), and computes the rest
        again. The attention layers keep the flash forward kernel's
        result and log-sum-exp (its backward kernels' residuals beside
        q, k and v), so that kernel runs once a layer;
        ``smallthinker.BandedAttention`` names and keeps q, k and v too
        and projects once, the other two project again; a Gated
        DeltaNet layer its chunks' triangular
        inverses; a sparse block what its routing decided (the chosen
        experts and the slot buffer's plan, a few integers a token
        slot), so that top-k and the ordering run once a layer;
        the dense feed-forward layers nothing."""

        kept: tuple = ()

        def __init__(self, remat: bool = False, **kwargs):
            super().__init__(**kwargs)
            self.remat = remat

        def _rematted(self):
            if not self.remat:
                return self._forward
            policy = jax.checkpoint_policies.save_only_these_names(
                *self.kept) if self.kept else None
            return jax.checkpoint(self._forward, policy=policy)

        def call(self, x):
            return self._rematted()(x)

    @register
    class SwiGLU(_Remat):
        def __init__(self, width: int, init_std: float = 0.02, **kwargs):
            super().__init__(**kwargs)
            self.width, self.init_std = width, init_std

        def build(self, input_shape):
            d = int(input_shape[-1])
            self.gate_up = self._weight(
                "gate_up", (d, 2 * self.width), normal(self.init_std))
            self.down = self._weight(
                "down", (self.width, d), normal(self.init_std))

        def _forward(self, x):
            gate, up = jnp.split(jnp.matmul(x, self.gate_up.value), 2, -1)
            hidden = jax.nn.silu(gate.astype(f32)) * up.astype(f32)
            return jnp.matmul(hidden.astype(x.dtype), self.down.value)

        def get_config(self):
            return {**super().get_config(), "width": self.width,
                    "init_std": self.init_std, "remat": self.remat}

    @register
    class UngatedMLP(_Remat):
        def __init__(self, width: int, init_std: float = 0.02,
                     hidden_act: str = "relu2", **kwargs):
            super().__init__(**kwargs)
            self.width, self.init_std = width, init_std
            self.hidden_act = hidden_act

        def build(self, input_shape):
            d = int(input_shape[-1])
            self.up = self._weight(
                "up", (d, self.width), normal(self.init_std))
            self.down = self._weight(
                "down", (self.width, d), normal(self.init_std))

        def _forward(self, x):
            from elephas_tpu.ops.moe import EXPERT_ACTIVATIONS

            hidden = EXPERT_ACTIVATIONS[self.hidden_act](
                jnp.matmul(x, self.up.value).astype(f32))
            return jnp.matmul(hidden.astype(x.dtype), self.down.value)

        def get_config(self):
            return {**super().get_config(), "width": self.width,
                    "init_std": self.init_std,
                    "hidden_act": self.hidden_act, "remat": self.remat}

    @register
    class GatedAttention(_Remat):
        """Grouped-query causal attention with q/k norms, a partial
        rotation and a sigmoid output gate. Under ``remat`` the backward
        pass projects, norms and rotates again and keeps the flash
        kernel's result and log-sum-exp (a head's ``[S, D]`` in the
        compute dtype and ``[S]`` in float32)."""

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
            from elephas_tpu.ops.flash_attention import flash_attention

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
                q = _rms(q, self.epsilon) * (1.0 + self.q_norm.value.astype(f32))
                k = _rms(k, self.epsilon) * (1.0 + self.k_norm.value.astype(f32))
                cos, sin = _rope_tables(s, rot, float(self.rope_theta))
                cos, sin = cos[None, :, None], sin[None, :, None]

                def rotate(t):  # on the first ``rot`` of each head
                    turned = _apply_rope(t[..., :rot], cos, sin)
                    return jnp.concatenate(
                        [turned, t[..., rot:]], axis=-1).astype(x.dtype)

                q, k = rotate(q), rotate(k)
            with jax.named_scope("attn.full"):
                heads_first = lambda t: jnp.transpose(t, (0, 2, 1, 3))  # noqa: E731
                out = flash_attention(
                    heads_first(q), heads_first(k), heads_first(v),
                    causal=True, scale=hd ** -0.5,
                )
                out = heads_first(out).astype(f32) * jax.nn.sigmoid(
                    gate.astype(f32))
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
    class GatedDeltaNet(_Remat):
        # the chunks' triangular inverses, 64 KiB a head a chunk: the
        # dearest product of the scan's chunk-parallel part by far
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
                out = (_rms(out, self.epsilon) * self.norm.value.astype(f32)
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
    class SparseMoeBlock(_Remat):
        """Router over ``num_experts``, the routed part of the experts
        in ``experts_held`` (a ``(first, stop)`` range; all of them when
        None), and a shared expert. What differs between the models
        that use it is the builder's to set: the router's rule
        (``scoring_func``, ``routed_scaling_factor`` and, with
        ``selection_bias``, a non-trainable ``e_score_correction_bias``
        added for the choice alone; :func:`elephas_tpu.ops.moe.route_top_k`),
        whether the shared expert lies under a sigmoid gate
        (``gated_shared_expert``) or is absent (``shared_width`` 0: the
        block is its routed part alone and has no ``shared_expert``
        variables), the experts' activation (``hidden_act``: ``silu``
        for SwiGLU experts, ``relu`` for ReGLU, ``relu2`` the squared
        ReLU) and whether an expert has a gate at all
        (``gated_experts``; without one the routed experts are
        ``down_e(act(up_e x))`` over an ``experts_up`` stack and the
        shared expert an :class:`UngatedMLP` of the same activation).
        ``layer(x, route_from)`` hands the router a tensor of
        its own (a router that stands before attention scores the
        decoder layer's input; the experts take ``x``); ``layer(x)``
        routes from ``x``. ``epoch_counters`` tells the epoch runner
        which variable adds up, call by call, what the block routed,
        and what its entries are."""

        epoch_counters = {"route_counts": COUNTER_NAMES}
        kept = (ROUTE_NAME,)

        def __init__(self, num_experts: int, experts_per_token: int,
                     expert_width: int, shared_width: int,
                     experts_held=None, init_std: float = 0.02,
                     scoring_func: str = "softmax",
                     selection_bias: bool = False,
                     routed_scaling_factor: float = 1.0,
                     gated_shared_expert: bool = True,
                     hidden_act: str = "silu", gated_experts: bool = True,
                     **kwargs):
            super().__init__(**kwargs)
            from elephas_tpu.ops.moe import EXPERT_ACTIVATIONS, ROUTER_SCORES

            if scoring_func not in ROUTER_SCORES:
                raise ValueError(
                    f"scoring_func {scoring_func!r} is none of "
                    f"{sorted(ROUTER_SCORES)}"
                )
            if hidden_act not in EXPERT_ACTIVATIONS:
                raise ValueError(
                    f"hidden_act {hidden_act!r} is none of "
                    f"{sorted(EXPERT_ACTIVATIONS)}"
                )
            self.hidden_act, self.gated_experts = hidden_act, bool(gated_experts)
            self.scoring_func, self.selection_bias = (
                scoring_func, bool(selection_bias))
            self.routed_scaling_factor = float(routed_scaling_factor)
            self.gated_shared_expert = bool(
                gated_shared_expert and shared_width)
            first, stop = experts_held or (0, num_experts)
            if not 0 <= first < stop <= num_experts:
                raise ValueError(
                    f"experts_held {experts_held!r} is no range of the "
                    f"{num_experts} experts"
                )
            if experts_per_token > num_experts:
                raise ValueError(
                    f"{experts_per_token} experts a token of {num_experts}"
                )
            self.num_experts, self.experts_per_token = (
                num_experts, experts_per_token)
            self.expert_width, self.shared_width = expert_width, shared_width
            self.experts_held = (int(first), int(stop))
            self.init_std = init_std

        def build(self, input_shape):
            d = int(input_shape[-1])
            held = self.experts_held[1] - self.experts_held[0]
            init = normal(self.init_std)
            self.router = self._weight(
                "router", (d, self.num_experts), init, False)
            # a gated expert's gate and up side by side, or its up alone
            name, columns = (("experts_gate_up", 2) if self.gated_experts
                             else ("experts_up", 1))
            self.experts_in = self._weight(
                name, (held, d, columns * self.expert_width), init)
            self.experts_down = self._weight(
                "experts_down", (held, self.expert_width, d), init)
            if self.gated_shared_expert:
                self.shared_gate = self._weight("shared_gate", (d, 1), init)
            if self.selection_bias:
                self.e_score_correction_bias = self.add_weight(
                    name="e_score_correction_bias",
                    shape=(self.num_experts,), dtype="float32",
                    initializer="zeros", trainable=False, autocast=False,
                )
            if self.shared_width:
                self.shared_expert = SwiGLU(
                    self.shared_width, self.init_std, name="shared_expert"
                ) if self.gated_experts else UngatedMLP(
                    self.shared_width, self.init_std, self.hidden_act,
                    name="shared_expert")
                self.shared_expert.build(input_shape)
            self.route_counts = self.add_weight(
                name="route_counts", shape=(len(COUNTER_NAMES),),
                dtype="int32", initializer="zeros", trainable=False,
                autocast=False,
            )

        def _forward(self, x, route_from=None):
            from elephas_tpu.ops.moe import held_experts_ffn

            b, s, d = jnp.shape(x)[0], x.shape[1], x.shape[2]
            flat = x.reshape(b * s, d)
            routing = {"score": self.scoring_func,
                       "scale": self.routed_scaling_factor}
            if self.selection_bias:
                routing["select_bias"] = self.e_score_correction_bias.value
            if route_from is not None:
                routing["route_from"] = route_from.reshape(b * s, d)
            routed, counts = held_experts_ffn(
                flat, self.router.value, self.experts_in.value,
                self.experts_down.value, self.experts_held,
                self.experts_per_token, activation=self.hidden_act,
                gated=self.gated_experts, **routing,
            )
            if not self.shared_width:
                return routed.reshape(b, s, d), counts
            with jax.named_scope("moe.shared"):
                shared = self.shared_expert(flat).astype(f32)
                if self.gated_shared_expert:
                    shared = shared * jax.nn.sigmoid(jnp.matmul(
                        flat, self.shared_gate.value).astype(f32))
            y = (routed.astype(f32) + shared).astype(x.dtype)
            return y.reshape(b, s, d), counts

        def call(self, x, route_from=None):
            inputs = (x,) if route_from is None else (x, route_from)
            y, counts = self._rematted()(*inputs)
            # outside the rematerialised part: a variable is written once
            self.route_counts.assign(self.route_counts.value + counts)
            return y

        def get_config(self):
            return {**super().get_config(), "num_experts": self.num_experts,
                    "experts_per_token": self.experts_per_token,
                    "expert_width": self.expert_width,
                    "shared_width": self.shared_width,
                    "experts_held": list(self.experts_held),
                    "init_std": self.init_std,
                    "scoring_func": self.scoring_func,
                    "selection_bias": self.selection_bias,
                    "routed_scaling_factor": self.routed_scaling_factor,
                    "gated_shared_expert": self.gated_shared_expert,
                    "hidden_act": self.hidden_act,
                    "gated_experts": self.gated_experts,
                    "remat": self.remat}

    @register
    class LMHead(keras.layers.Layer):
        """Untied float32 output projection (no bias)."""

        def __init__(self, vocab_size: int, init_std: float = 0.02, **kwargs):
            kwargs.setdefault("dtype", "float32")
            super().__init__(**kwargs)
            self.vocab_size, self.init_std = vocab_size, init_std

        def build(self, input_shape):
            self.kernel = self.add_weight(
                name="kernel", shape=(int(input_shape[-1]), self.vocab_size),
                initializer=normal(self.init_std),
            )

        def compute_output_spec(self, x, *args, **kwargs):
            return keras.KerasTensor(
                x.shape[:-1] + (self.vocab_size,), dtype="float32")

        def call(self, x):
            with jax.named_scope("lm.head_loss"):
                return jnp.matmul(x.astype(f32), self.kernel.value)

        def get_config(self):
            return {**super().get_config(), "vocab_size": self.vocab_size,
                    "init_std": self.init_std}

    keras.saving.register_keras_serializable(package="elephas_tpu")(
        next_token_loss)
    # the two bases ride along for the models that build on these
    # layers (``models/deepseek_v3.py``); they are no public names
    _LAYERS = {
        cls.__name__: cls for cls in (
            ZeroCentredRMSNorm, SwiGLU, UngatedMLP, GatedAttention,
            GatedDeltaNet,
            SparseMoeBlock, LMHead, _SameShape, _Remat,
        )
    }
    assert set(LAYER_NAMES) == {n for n in _LAYERS if n[0] != "_"}
    return _LAYERS


def __getattr__(name):
    # `from elephas_tpu.models.qwen3_next import GatedDeltaNet` resolves
    # to the real (lazily created) layer class
    if name in LAYER_NAMES:
        return _layers()[name]
    raise AttributeError(name)


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
    keras = _keras()
    keras.utils.set_random_seed(seed)
    with _dtype_policy_scope(keras, dtype_policy):
        L = _layers()
        Norm = L["ZeroCentredRMSNorm"]
        inputs = keras.Input((maxlen,), dtype="int32")
        x = keras.layers.Embedding(
            vocab_size, hidden_size, name="embed_tokens",
            embeddings_initializer=keras.initializers.RandomNormal(
                stddev=init_std),
        )(inputs)
        for i in range(num_layers):
            h = Norm(rms_norm_eps, name=f"layer{i}_input_norm")(x)
            if (i + 1) % full_attention_interval == 0:
                h = L["GatedAttention"](
                    num_attention_heads, num_key_value_heads, head_dim,
                    int(head_dim * partial_rotary_factor), rope_theta,
                    rms_norm_eps, init_std, remat=remat,
                    name=f"layer{i}_attn",
                )(h)
            else:
                h = L["GatedDeltaNet"](
                    linear_num_key_heads, linear_num_value_heads,
                    linear_key_head_dim, linear_value_head_dim,
                    linear_conv_kernel_dim, chunk_size, rms_norm_eps,
                    init_std, remat=remat, name=f"layer{i}_gdn",
                )(h)
            x = x + h
            h = Norm(rms_norm_eps, name=f"layer{i}_post_norm")(x)
            h = L["SparseMoeBlock"](
                num_experts, num_experts_per_tok, moe_intermediate_size,
                shared_expert_intermediate_size, experts_held, init_std,
                remat=remat, name=f"layer{i}_moe",
            )(h)
            x = x + h
        x = Norm(rms_norm_eps, name="final_norm")(x)
        outputs = L["LMHead"](vocab_size, init_std, name="lm_head")(x)
        model = keras.Model(inputs, outputs, name="qwen3_next_lm")
    model.compile(
        optimizer=keras.optimizers.SGD(lr, momentum=momentum),
        loss=next_token_loss,
    )
    return model
