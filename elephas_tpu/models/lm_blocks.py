"""What every sparse LM of the zoo is made of beside its sequence mixer,
and the one loop that stacks them into a model.

The model files (``qwen3_next``, ``deepseek_v3``, ``smallthinker``,
``nemotron_h``, ``laguna``, ``granite_hybrid``) each turn a published config's argument
names into a list of layers from this module and from
:mod:`elephas_tpu.models.lm_mixers`, and hand it to :func:`decoder_lm`;
none of them defines a layer, and none imports another. This module
imports keras (the package has set ``KERAS_BACKEND=jax`` by then), so
the model files import it inside their ``*_lm`` function: importing them
loads neither keras nor jax.

- :class:`SameShape`, :class:`Remat`: the bases. A layer of plain
  ``jax.numpy`` whose result has its input's shape; and one whose
  ``call`` runs under ``jax.checkpoint`` where the builder asked for it
  (``remat``), keeping its input and what its class names in ``kept``.
- :class:`ZeroCentredRMSNorm`: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``,
  ``w`` from zeros; :class:`RMSNorm`: ``w * x * rsqrt(mean(x^2) +
  eps)``, ``w`` from ones. Statistics in float32.
- :class:`SwiGLU`: ``down(silu(gate(x)) * up(x))``, no biases;
  :class:`DenseMLP`: the same as a decoder layer's dense feed-forward,
  under the scope ``mlp.dense``; :class:`UngatedMLP`:
  ``down(act(up(x)))``.
- :class:`SparseMoeBlock`: a router over ALL experts, the routed part
  that the experts HELD here give (``experts_held``, a range; stacked
  weights; no token dropped; :func:`elephas_tpu.ops.moe.held_experts_ffn`,
  under the scopes ``moe.route`` and ``moe.experts``) and a shared
  expert (``moe.shared``). It counts what it routes in a non-trainable
  ``route_counts`` variable (:data:`COUNTER_NAMES`) that the epoch
  runner reads with the loss.
- :class:`LMHead` and :func:`next_token_loss`: the untied float32 head
  and the per-token cross-entropy, both under ``lm.head_loss``;
  :class:`TiedEmbedding`: an embedding whose table is the head too.
- :func:`decoder_lm`: embedding, ``x = x + layer(norm(x))`` for every
  :class:`SubLayer` of every decoder layer, final norm, head, compiled
  with SGD; a family's constant multipliers on the embedding, the
  sub-layers' results and the logits where the builder names them. A
  new architecture is a new model file that lists its layers; where it
  needs a layer the zoo lacks, the layer goes here or into
  ``lm_mixers``, under its own name.
"""

from __future__ import annotations

from collections.abc import Callable
from typing import NamedTuple

import jax
import jax.numpy as jnp
import keras

from elephas_tpu.models.transformer import _dtype_policy_scope
from elephas_tpu.ops.moe import (
    EXPERT_ACTIVATIONS,
    ROUTE_NAME,
    ROUTER_SCORES,
    held_experts_ffn,
)

register = keras.saving.register_keras_serializable(package="elephas_tpu")
f32 = jnp.float32
# what a sparse block counts, call by call, in ``route_counts``
COUNTER_NAMES = ("held_slots", "slots", "max_expert_tokens", "calls",
                 "blocked_calls")


def rms(x, eps):
    """``x`` over the root of its last axis's mean square, in float32."""
    xf = x.astype(f32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def normal(stddev):
    return keras.initializers.RandomNormal(stddev=stddev)


@register
def next_token_loss(y_true, logits):
    """Per-token cross-entropy of float32 logits against integer
    targets, as ``logsumexp - picked`` (no second ``[.., V]`` tensor)."""
    with jax.named_scope("lm.head_loss"):
        logits = logits.astype(f32)
        picked = jnp.take_along_axis(
            logits, y_true.astype(jnp.int32)[..., None], axis=-1
        )[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked


class SameShape(keras.layers.Layer):
    """A layer of plain ``jax.numpy`` whose result has its input's
    shape and the layer's compute dtype (so Keras need not trace
    ``call`` with a symbolic batch to learn it)."""

    def compute_output_spec(self, x, *args, **kwargs):
        return keras.KerasTensor(x.shape, dtype=self.compute_dtype)

    def _weight(self, name, shape, init, autocast=True):
        return self.add_weight(
            name=name, shape=shape, initializer=init, autocast=autocast
        )


class Remat(SameShape):
    """``call`` is ``_forward``, under ``jax.checkpoint`` where the
    builder asked for it (``remat``): the backward pass then keeps the
    layer's input and what ``_forward`` names with one of ``kept``
    (``jax.ad_checkpoint.checkpoint_name``), and computes the rest
    again. ``kept`` is the subclass's to set and to account for: the
    results that are dear to compute and small to hold. Empty, as
    here, the layer keeps its input alone."""

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
class ZeroCentredRMSNorm(SameShape):
    def __init__(self, epsilon: float = 1e-6, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = epsilon

    def build(self, input_shape):
        self.weight = self._weight(
            "weight", (int(input_shape[-1]),), "zeros", autocast=False
        )

    def call(self, x):
        y = rms(x, self.epsilon) * (1.0 + self.weight.value.astype(f32))
        return y.astype(x.dtype)

    def get_config(self):
        return {**super().get_config(), "epsilon": self.epsilon}


@register
class RMSNorm(SameShape):
    def __init__(self, epsilon: float = 1e-6, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = epsilon

    def build(self, input_shape):
        self.weight = self._weight(
            "weight", (int(input_shape[-1]),), "ones", autocast=False
        )

    def call(self, x):
        y = rms(x, self.epsilon) * self.weight.value.astype(f32)
        return y.astype(x.dtype)

    def get_config(self):
        return {**super().get_config(), "epsilon": self.epsilon}


@register
class SwiGLU(Remat):
    """``down(silu(gate(x)) * up(x))``; under ``remat`` it keeps its
    input alone."""

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
class DenseMLP(SwiGLU):
    """A decoder layer's dense SwiGLU, under its own scope."""

    def _forward(self, x):
        with jax.named_scope("mlp.dense"):
            return super()._forward(x)


@register
class UngatedMLP(Remat):
    """``down(act(up(x)))``, no biases (``relu2``: the squared ReLU);
    under ``remat`` it keeps its input alone."""

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
        hidden = EXPERT_ACTIVATIONS[self.hidden_act](
            jnp.matmul(x, self.up.value).astype(f32))
        return jnp.matmul(hidden.astype(x.dtype), self.down.value)

    def get_config(self):
        return {**super().get_config(), "width": self.width,
                "init_std": self.init_std,
                "hidden_act": self.hidden_act, "remat": self.remat}


@register
class SparseMoeBlock(Remat):
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
    and what its entries are. Under ``remat`` it keeps, beside its
    inputs, what its routing decided (``kept``: the chosen experts
    and the slot buffer's plan, a few integers a token slot), so that
    top-k and the ordering run once a layer."""

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


@register
class TiedEmbedding(keras.layers.Embedding):
    """A token embedding whose table is the output projection too
    (``tie_word_embeddings``): ``layer(ids)`` looks rows up, in the
    compute dtype; ``layer(x, reverse=True)`` is the float32 head,
    ``x E^T`` (over ``logits_scaling`` where given) under
    ``lm.head_loss``, as :class:`LMHead` multiplies. One float32
    variable, ``<name>/embeddings``, with both paths' gradients and
    one momentum; the model has no head of its own. Built without
    autocast, so that the head reads the table as it is stored."""

    def __init__(self, input_dim: int, output_dim: int,
                 logits_scaling: float | None = None, **kwargs):
        kwargs["autocast"] = False
        super().__init__(input_dim, output_dim, **kwargs)
        self.logits_scaling = logits_scaling

    def compute_output_spec(self, x, reverse=False):
        if reverse:
            return keras.KerasTensor(
                x.shape[:-1] + (self.input_dim,), dtype="float32")
        return keras.KerasTensor(
            x.shape + (self.output_dim,), dtype=self.compute_dtype)

    def call(self, x, reverse=False):
        table = self.embeddings.value
        if not reverse:
            return jnp.take(table, x, axis=0).astype(self.compute_dtype)
        with jax.named_scope("lm.head_loss"):
            logits = jnp.matmul(x.astype(f32), table.T)
            if self.logits_scaling is not None:
                logits = logits / self.logits_scaling
            return logits

    def get_config(self):
        return {**super().get_config(),
                "logits_scaling": self.logits_scaling}


class SubLayer(NamedTuple):
    """One ``x = x + layer(norm(x))`` of a decoder layer."""

    norm: str  # its pre-norm is named ``layer{i}_{norm}``
    make: Callable  # the layer's constructor with its arguments bound
    # ``layer(norm(x), stream)``: the layer also takes the decoder
    # layer's input, un-normed (a router that stands before attention)
    takes_stream: bool = False


def decoder_lm(name: str, layers, norm: Callable, *, vocab_size: int,
               maxlen: int, hidden_size: int, init_std: float, lr: float,
               momentum: float, seed: int, dtype_policy: str | None,
               embedding_multiplier: float | None = None,
               residual_multiplier: float | None = None,
               logits_scaling: float | None = None,
               tie_word_embeddings: bool = False):
    """The compiled decoder-only LM ``name``: token embedding; for
    decoder layer ``i`` each :class:`SubLayer` of ``layers[i]`` in turn as
    ``x = x + layer(norm(x))``; ``final_norm``; an untied float32 head.
    ``norm(name=...)`` makes a norm layer. Every layer is made at its
    turn in the stack, under ``dtype_policy`` and after the seed is set,
    so a model's variables and their seeded initial values do not depend
    on how its builder arrived at the list. Compiled with SGD (``lr``,
    ``momentum``) and :func:`next_token_loss`.

    A family's constants, each left out of the program where None: the
    embedding times ``embedding_multiplier``, ``x = x +
    residual_multiplier * layer(norm(x))`` and the logits over
    ``logits_scaling``. With ``tie_word_embeddings`` the head is the
    embedding's table (:class:`TiedEmbedding`) and the model has no
    ``lm_head``; only that head divides its logits, so
    ``logits_scaling`` without it is refused."""
    if logits_scaling is not None and not tie_word_embeddings:
        raise ValueError(
            "logits_scaling is the tied head's (tie_word_embeddings): "
            "the untied LMHead divides nothing")
    keras.utils.set_random_seed(seed)
    with _dtype_policy_scope(keras, dtype_policy):
        inputs = keras.Input((maxlen,), dtype="int32")
        named = dict(name="embed_tokens",
                     embeddings_initializer=normal(init_std))
        embed = TiedEmbedding(
            vocab_size, hidden_size, logits_scaling, **named
        ) if tie_word_embeddings else keras.layers.Embedding(
            vocab_size, hidden_size, **named)
        x = embed(inputs)
        if embedding_multiplier is not None:
            x = x * embedding_multiplier
        for i, sub_layers in enumerate(layers):
            stream = x
            for sub in sub_layers:
                h = norm(name=f"layer{i}_{sub.norm}")(x)
                layer = sub.make()
                y = layer(h, stream) if sub.takes_stream else layer(h)
                if residual_multiplier is not None:
                    y = y * residual_multiplier
                x = x + y
        x = norm(name="final_norm")(x)
        outputs = embed(x, reverse=True) if tie_word_embeddings else LMHead(
            vocab_size, init_std, name="lm_head")(x)
        model = keras.Model(inputs, outputs, name=name)
    model.compile(
        optimizer=keras.optimizers.SGD(lr, momentum=momentum),
        loss=next_token_loss,
    )
    return model
