"""State-space / sparse / attention hybrid LM (the Nemotron-H block, as
``NVIDIA-Nemotron-3-Nano-30B-A3B`` publishes it under ``model_type:
nemotron_h``).

Every decoder layer is ONE mixer under one pre-norm and one residual
add, ``h = h + mixer_i(norm(h))``, and character ``i`` of
``hybrid_override_pattern`` says which: ``M`` a Mamba-2 mixer, ``E`` a
sparse block, ``*`` attention. What the block adds to the zoo, beside
the layers it shares with :mod:`elephas_tpu.models.qwen3_next`
(``SparseMoeBlock``, ``UngatedMLP``, ``LMHead``, ``next_token_loss``),
:mod:`elephas_tpu.models.deepseek_v3` (``RMSNorm``) and
:mod:`elephas_tpu.models.smallthinker` (``BandedAttention``):

- :class:`Mamba2Mixer`: one input projection split into a gate ``z``,
  the convolved channels ``x | B | C`` and a step ``dt`` a head; a causal
  depthwise convolution with bias and a silu over those channels; the
  selective scan (:func:`elephas_tpu.ops.ssd.ssd_chunked`: a scalar
  decay ``exp(dt A)`` a head, ``B`` and ``C`` shared by the heads of a
  group, a ``D`` skip); a gated RMS norm in groups (``norm(y *
  silu(z))``: the gate first, then the norm over each group of
  ``inner / n_groups`` channels); the output projection. Its inner
  width is ``mamba_num_heads * mamba_head_dim``.
- the sparse block's ungated form, set here on the shared
  ``SparseMoeBlock``: routed experts ``down_e(relu(up_e x)^2)``, a
  shared expert of the same form added unweighted, behind the sigmoid
  router with a selection bias and ``routed_scaling_factor``.
- attention with no position term at all: ``BandedAttention`` with
  neither a window nor a rotation (the Mamba-2 layers carry order).

``fit`` only: the state cache and the one-token step that serving a
recurrent layer needs are not here, and a sequence made of several
documents resets no state at their boundaries.
"""

from __future__ import annotations

import math

from elephas_tpu.models import deepseek_v3, qwen3_next, smallthinker
from elephas_tpu.models.qwen3_next import _rms, next_token_loss
from elephas_tpu.models.transformer import _dtype_policy_scope, _keras

_LAYERS = None
LAYER_NAMES = ("Mamba2Mixer",)
LAYER_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def _layers():
    """This module's layer class, created lazily (keras under the jax
    backend first) and registered with Keras's serializer."""
    global _LAYERS
    if _LAYERS is not None:
        return _LAYERS
    import jax
    import jax.numpy as jnp
    import keras

    from elephas_tpu.ops.ssd import ssd_chunked

    _Remat = qwen3_next._layers()["_Remat"]
    register = keras.saving.register_keras_serializable(package="elephas_tpu")
    f32 = jnp.float32

    @register
    class Mamba2Mixer(_Remat):
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
            normal = lambda std: keras.initializers.RandomNormal(  # noqa: E731
                stddev=std)
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
                normed = _rms(
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

    _LAYERS = {"Mamba2Mixer": Mamba2Mixer}
    return _LAYERS


def __getattr__(name):
    if name in LAYER_NAMES:
        return _layers()[name]
    raise AttributeError(name)


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
        for i, kind in enumerate(pattern):
            name = f"layer{i}_{LAYER_KINDS[kind]}"
            if kind == "M":
                mixer = L["Mamba2Mixer"](
                    mamba_num_heads, mamba_head_dim, ssm_state_size,
                    n_groups, conv_kernel, chunk_size, layer_norm_epsilon,
                    time_step_min, time_step_max, time_step_floor, init_std,
                    init_std / math.sqrt(n) if rescale_prenorm_residual
                    else None, remat=remat, name=name)
            elif kind == "E":
                mixer = shared["SparseMoeBlock"](
                    n_routed_experts, num_experts_per_tok,
                    moe_intermediate_size,
                    moe_shared_expert_intermediate_size, experts_held,
                    init_std, scoring_func="sigmoid", selection_bias=True,
                    routed_scaling_factor=routed_scaling_factor,
                    gated_shared_expert=False, hidden_act=mlp_hidden_act,
                    gated_experts=False, remat=remat, name=name)
            else:
                mixer = smallthinker._layers()["BandedAttention"](
                    num_attention_heads, num_key_value_heads, head_dim,
                    None, False, init_std=init_std, remat=remat, name=name)
            x = x + mixer(
                Norm(layer_norm_epsilon, name=f"layer{i}_norm")(x))
        x = Norm(layer_norm_epsilon, name="final_norm")(x)
        outputs = shared["LMHead"](vocab_size, init_std, name="lm_head")(x)
        model = keras.Model(inputs, outputs, name="nemotron_h_lm")
    model.compile(
        optimizer=keras.optimizers.SGD(lr, momentum=momentum),
        loss=next_token_loss,
    )
    return model
