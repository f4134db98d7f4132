"""Transformer model family — flash-attention-backed, TPU-first.

The reference's deepest sequence model is a single LSTM (its IMDB
example); transformers are the modern load-bearing family, so this module
provides them as first-class zoo members:

- :class:`FlashMHA` — Keras multi-head attention layer whose core runs
  the Pallas flash kernel (:mod:`elephas_tpu.ops.flash_attention`);
  O(S) memory, MXU-tiled.
- :func:`transformer_classifier` — encoder stack + pooled head (the
  IMDB-class task at transformer quality).
- :func:`transformer_lm` — causal decoder-only language model.

Both builders return compiled models that drop straight into
``SparkModel`` for data-parallel training; with
``elephas_tpu.ops.ring_attention`` the same attention math extends to
sequence-parallel long-context training (SURVEY.md §5 lists all of this
as absent upstream — TPU-native extension, not a port).
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np


def _keras():
    import keras

    return keras


@contextlib.contextmanager
def _dtype_policy_scope(keras, policy: str | None):
    """Temporarily set Keras's global dtype policy while building layers
    (restored even on build failure — the global must not leak)."""
    prev = keras.config.dtype_policy()
    if policy is not None:
        keras.config.set_dtype_policy(policy)
    try:
        yield
    finally:
        keras.config.set_dtype_policy(prev)


_FLASH_MHA_CLS = None


def _flash_mha_layer():
    """The FlashMHA layer class, created lazily (keras must be imported
    under the jax backend first) and registered with Keras's serializer
    so save/load/checkpoint-resume need no custom_objects."""
    global _FLASH_MHA_CLS
    if _FLASH_MHA_CLS is not None:
        return _FLASH_MHA_CLS
    import keras

    @keras.saving.register_keras_serializable(package="elephas_tpu")
    class FlashMHA(keras.layers.Layer):
        """Multi-head self-attention over the Pallas flash kernel.

        Equivalent math to ``keras.layers.MultiHeadAttention`` (fused
        qkv projection, per-head scaled dot-product, output projection)
        but the attention core never materializes the [S, S] matrix.
        """

        def __init__(self, num_heads: int, head_dim: int, causal: bool = False,
                     rope: bool = False, **kwargs):
            super().__init__(**kwargs)
            self.num_heads = num_heads
            self.head_dim = head_dim
            self.causal = causal
            self.rope = rope
            if rope and head_dim % 2:
                raise ValueError(
                    f"rope needs an even head_dim, got {head_dim}"
                )

        def build(self, input_shape):
            d_model = int(input_shape[-1])
            self.qkv = keras.layers.Dense(
                3 * self.num_heads * self.head_dim, use_bias=False, name="qkv"
            )
            self.proj = keras.layers.Dense(d_model, name="proj")
            self.qkv.build(input_shape)
            self.proj.build(
                tuple(input_shape[:-1]) + (self.num_heads * self.head_dim,)
            )
            super().build(input_shape)

        def call(self, x):
            import jax.numpy as jnp

            from elephas_tpu.parallel.sequence import (
                active_sequence_scope, ring_mha,
            )

            from elephas_tpu.ops.flash_attention import (
                flash_attention,
                flash_attention_qkv,
            )

            B = jnp.shape(x)[0]
            S = x.shape[1]
            H, D = self.num_heads, self.head_dim
            qkv = self.qkv(x)  # [B, S, 3*H*D]
            qkv = jnp.reshape(qkv, (B, S, 3, H, D))
            scope = active_sequence_scope()
            if scope is not None or self.rope:
                # transposed path: the SP ring wants separate q/k/v, and
                # rope must rotate q/k between the projection and the
                # kernel (which forfeits the packed kernel's zero-copy
                # read — one layout copy, the price of rotation)
                qkv_t = jnp.transpose(qkv, (2, 0, 3, 1, 4))  # [3,B,H,S,D]
                q, k, v = qkv_t[0], qkv_t[1], qkv_t[2]
                if self.rope:
                    cos, sin = _rope_tables(S, D)
                    cos = jnp.asarray(cos, x.dtype)[None, None]
                    sin = jnp.asarray(sin, x.dtype)[None, None]
                    # positionwise over the GLOBAL sequence, so under a
                    # sequence scope GSPMD shards the rotation with the
                    # activations — ring semantics are unchanged
                    q = _apply_rope(q, cos, sin)
                    k = _apply_rope(k, cos, sin)
                if scope is not None:
                    out = ring_mha(q, k, v, causal=self.causal, scope=scope)
                else:
                    out = flash_attention(q, k, v, causal=self.causal)
                out = jnp.reshape(
                    jnp.transpose(out, (0, 2, 1, 3)), (B, S, H * D)
                )
            else:
                # packed-layout kernel (r4): q/k/v are read straight
                # from the fused projection and the output lands
                # sequence-major — the bhsd transposes (the top copy
                # kernels in the r4 transformer trace, fwd AND their
                # bwd counterparts) never materialize
                out = flash_attention_qkv(qkv, causal=self.causal)
                out = jnp.reshape(out, (B, S, H * D))
            return self.proj(out)

        def get_config(self):
            config = super().get_config()
            config.update(
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                causal=self.causal,
                rope=self.rope,
            )
            return config

    _FLASH_MHA_CLS = FlashMHA
    return FlashMHA


_FUSED_LN_CLS = None


def _fused_ln_layer():
    """The FusedLayerNorm layer class (lazy, serializer-registered —
    same pattern as :func:`_flash_mha_layer`). Normalization runs the
    one-pass Pallas kernel (:mod:`elephas_tpu.ops.layer_norm`): the r4
    trace billed ~20% of transformer device time to XLA's multi-pass
    layernorm fusions + their bf16↔f32 converts (VERDICT r4 #3a).
    Under a sequence-parallel scope the math falls back to plain jnp
    ops so GSPMD shards the normalization with the seq-sharded
    activations instead of forcing the kernel replicated."""
    global _FUSED_LN_CLS
    if _FUSED_LN_CLS is not None:
        return _FUSED_LN_CLS
    import keras

    @keras.saving.register_keras_serializable(package="elephas_tpu")
    class FusedLayerNorm(keras.layers.Layer):
        """LayerNormalization (last axis, keras-equivalent math: f32
        statistics, affine gamma/beta) over one fused Pallas pass."""

        def __init__(self, epsilon: float = 1e-6, **kwargs):
            super().__init__(**kwargs)
            self.epsilon = float(epsilon)

        def build(self, input_shape):
            d = int(input_shape[-1])
            self.gamma = self.add_weight(
                name="gamma", shape=(d,), initializer="ones"
            )
            self.beta = self.add_weight(
                name="beta", shape=(d,), initializer="zeros"
            )
            super().build(input_shape)

        def call(self, x):
            import jax
            import jax.numpy as jnp

            from elephas_tpu.parallel.sequence import (
                active_sequence_scope,
            )

            gamma, beta = self.gamma.value, self.beta.value
            if active_sequence_scope() is not None:
                x32 = jnp.asarray(x, jnp.float32)
                mean = jnp.mean(x32, axis=-1, keepdims=True)
                xc = x32 - mean
                var = jnp.mean(xc * xc, axis=-1, keepdims=True)
                y = xc * jax.lax.rsqrt(var + self.epsilon)
                return (y * gamma + beta).astype(x.dtype)

            from elephas_tpu.ops.layer_norm import layer_norm

            return layer_norm(x, gamma, beta, eps=self.epsilon)

        def compute_output_shape(self, input_shape):
            # keras's symbolic build traces call() with a polymorphic
            # batch dim otherwise — the kernel's row flatten needs
            # concrete rows (shape is identity anyway)
            return input_shape

        def get_config(self):
            config = super().get_config()
            config.update(epsilon=self.epsilon)
            return config

    _FUSED_LN_CLS = FusedLayerNorm
    return FusedLayerNorm


def __getattr__(name):
    # `from elephas_tpu.models.transformer import FlashMHA` resolves to
    # the real (lazily created) layer class
    if name == "FlashMHA":
        return _flash_mha_layer()
    if name == "FusedLayerNorm":
        return _fused_ln_layer()
    raise AttributeError(name)


def _block(x, num_heads, head_dim, mlp_ratio, dropout, causal, name, L,
           FlashMHA, rope=False):
    # keras LayerNormalization on purpose, A/B-measured (r5): the
    # in-tree Pallas FusedLayerNorm (one-pass fwd, one-pass bwd with
    # in-kernel dgamma/dbeta) reaches only PARITY end-to-end on v5e
    # (220.4-221.4k tok/s fused vs 221.9-223.0k keras-LN, same
    # session) — both run at the platform's realized elementwise
    # bandwidth, so the simpler stock layer wins on compatibility.
    # FusedLayerNorm stays available (elephas_tpu.models) for shapes
    # where a single fused pass wins.
    h = L.LayerNormalization(epsilon=1e-6, name=f"{name}_ln1")(x)
    h = FlashMHA(num_heads, head_dim, causal=causal, rope=rope,
                 name=f"{name}_attn")(h)
    if dropout > 0:
        # rate-0 Dropout layers are elided entirely: dead ops, and their
        # python `if training` branch breaks keras.RematScope (jax.remat
        # traces the training flag)
        h = L.Dropout(dropout, name=f"{name}_drop1")(h)
    x = L.Add(name=f"{name}_res1")([x, h])
    h = L.LayerNormalization(epsilon=1e-6, name=f"{name}_ln2")(x)
    d_model = x.shape[-1]
    h = L.Dense(int(d_model * mlp_ratio), activation="gelu", name=f"{name}_mlp1")(h)
    h = L.Dense(d_model, name=f"{name}_mlp2")(h)
    if dropout > 0:
        h = L.Dropout(dropout, name=f"{name}_drop2")(h)
    return L.Add(name=f"{name}_res2")([x, h])


def _positions(maxlen: int, d_model: int) -> np.ndarray:
    """Sinusoidal position table (fixed, not learned — no extra state)."""
    pos = np.arange(maxlen)[:, None]
    i = np.arange(d_model)[None, :]
    angle = pos / np.power(10000.0, (2 * (i // 2)) / d_model)
    table = np.where(i % 2 == 0, np.sin(angle), np.cos(angle))
    return table.astype(np.float32)


@functools.lru_cache(maxsize=8)
def _rope_tables(maxlen: int, head_dim: int, theta: float = 10000.0,
                 yarn: tuple | None = None):
    """cos/sin tables ``[S, D]`` for rotary position embeddings
    (half-split / GPT-NeoX convention; ``head_dim`` is the rotated
    width, the head's own or the part of it a caller rotates, and must
    be even; ``theta`` is the base of the frequencies).
    Cached so every attention layer shares ONE host table (and jax sees
    one constant object) instead of L identical copies — at long-context
    sequence lengths the table is large (code-review r4).

    ``yarn = (factor, original_max_position_embeddings, beta_fast,
    beta_slow, attention_factor)`` scales the frequencies as YaRN does
    (arXiv:2309.00071, "NTK-by-parts"): a pair that turns more than
    ``beta_fast`` times over the original context keeps its frequency,
    one that turns less than ``beta_slow`` times is slowed ``factor``
    times, a linear ramp between them; cos and sin are multiplied by
    ``attention_factor`` (None: ``0.1 ln(factor) + 1``). None is the
    plain table."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    scale = 1.0
    if yarn is not None:
        factor, original, beta_fast, beta_slow, scale = yarn

        def pair_turning(n):  # the pair that turns n times in ``original``
            return (head_dim * np.log(original / (2 * np.pi * n))
                    / (2 * np.log(theta)))

        low = max(np.floor(pair_turning(beta_fast)), 0)
        high = min(np.ceil(pair_turning(beta_slow)), head_dim - 1)
        ramp = np.clip(
            (np.arange(head_dim // 2) - low) / max(high - low, 1e-3), 0, 1)
        inv = ramp * inv / factor + (1 - ramp) * inv
        scale = 0.1 * np.log(factor) + 1.0 if scale is None else scale
    ang = np.arange(maxlen)[:, None] * inv[None, :]  # [S, D/2]
    cos = scale * np.concatenate([np.cos(ang), np.cos(ang)], axis=-1)
    sin = scale * np.concatenate([np.sin(ang), np.sin(ang)], axis=-1)
    return cos.astype(np.float32), sin.astype(np.float32)


def _apply_rope(x, cos, sin):
    """Rotate ``[..., S, D]`` (or ``[..., D]`` single-position) heads:
    ``x·cos + rotate_half(x)·sin`` with broadcastable tables."""
    import jax.numpy as jnp

    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rot * sin


def transformer_classifier(
    vocab_size: int = 20000,
    maxlen: int = 128,
    num_classes: int = 2,
    d_model: int = 128,
    num_heads: int = 4,
    num_layers: int = 2,
    mlp_ratio: float = 4.0,
    dropout: float = 0.1,
    lr: float = 1e-3,
    seed: int = 0,
    dtype_policy: str | None = None,
):
    """Encoder-stack text classifier (IMDB-class tasks, BASELINE #4+).

    ``dtype_policy='mixed_bfloat16'`` keeps the matmuls (and the flash
    attention kernel) in bf16 on the MXU with float32 variables."""
    keras = _keras()
    keras.utils.set_random_seed(seed)
    with _dtype_policy_scope(keras, dtype_policy):
        L = keras.layers
        FlashMHA = _flash_mha_layer()
        head_dim = d_model // num_heads

        inputs = keras.Input((maxlen,), dtype="int32")
        x = L.Embedding(vocab_size, d_model, name="tok_embed")(inputs)
        x = x + _positions(maxlen, d_model)[None]
        for b in range(num_layers):
            x = _block(
                x, num_heads, head_dim, mlp_ratio, dropout, False,
                f"blk{b}", L, FlashMHA,
            )
        x = L.LayerNormalization(epsilon=1e-6, name="final_ln")(x)
        x = L.GlobalAveragePooling1D(name="pool")(x)
        activation = "sigmoid" if num_classes == 1 else "softmax"
        outputs = L.Dense(
            num_classes, activation=activation, name="head", dtype="float32"
        )(x)
        model = keras.Model(inputs, outputs, name="transformer_classifier")
    loss = (
        "binary_crossentropy"
        if num_classes == 1
        else "sparse_categorical_crossentropy"
    )
    model.compile(
        optimizer=keras.optimizers.Adam(lr), loss=loss, metrics=["accuracy"]
    )
    return model


def transformer_lm(
    vocab_size: int = 32000,
    maxlen: int = 256,
    d_model: int = 256,
    num_heads: int = 4,
    num_layers: int = 4,
    mlp_ratio: float = 4.0,
    dropout: float = 0.0,
    lr: float = 3e-4,
    seed: int = 0,
    dtype_policy: str | None = None,
    rope: bool = False,
):
    """Decoder-only causal LM (next-token prediction).

    ``dtype_policy='mixed_bfloat16'`` keeps the matmuls (and the flash
    attention kernel) in bf16 on the MXU; the lm_head logits stay f32.
    ``rope=True`` (r4) uses rotary position embeddings in every
    attention layer instead of the additive sinusoidal table — the
    modern-LLM positional scheme; composes with the sequence-parallel
    ring (rotation is positionwise over the global sequence) and with
    KV-cache decode."""
    keras = _keras()
    keras.utils.set_random_seed(seed)
    with _dtype_policy_scope(keras, dtype_policy):
        L = keras.layers
        FlashMHA = _flash_mha_layer()
        head_dim = d_model // num_heads

        inputs = keras.Input((maxlen,), dtype="int32")
        x = L.Embedding(vocab_size, d_model, name="tok_embed")(inputs)
        if not rope:
            x = x + _positions(maxlen, d_model)[None]
        for b in range(num_layers):
            x = _block(
                x, num_heads, head_dim, mlp_ratio, dropout, True,
                f"blk{b}", L, FlashMHA, rope=rope,
            )
        x = L.LayerNormalization(epsilon=1e-6, name="final_ln")(x)
        outputs = L.Dense(vocab_size, name="lm_head", dtype="float32")(x)
        model = keras.Model(inputs, outputs, name="transformer_lm")
    model.compile(
        optimizer=keras.optimizers.Adam(lr),
        loss=keras.losses.SparseCategoricalCrossentropy(from_logits=True),
        metrics=["accuracy"],
    )
    return model


def _sample_logits(logits, key, temperature: float, top_k, top_p=None):
    """Greedy argmax at temperature 0; else temperature-scaled
    categorical sampling, optionally truncated to the top_k logits
    and/or the top_p (nucleus) probability mass.
    Shared by the full-recompute and KV-cache decode paths."""
    import jax
    import jax.numpy as jnp

    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = _filter_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def _filter_logits(scaled, top_k, top_p):
    """top-k / top-p (nucleus) truncation of ``[B, V]`` scaled logits —
    ONE implementation shared by the scalar-temperature sampler above
    and the serving engine's vector-temperature sampler, so the two
    paths cannot drift apart (their parity is a documented contract)."""
    import jax
    import jax.numpy as jnp

    if top_k is not None:
        kth = jnp.sort(scaled, axis=-1)[:, -int(top_k)][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p is not None:
        # nucleus: keep the smallest set of tokens whose cumulative
        # probability reaches top_p (the first token past the threshold
        # is kept so the nucleus is never empty)
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < float(top_p)  # prev-cumulative below mass
        # threshold = smallest kept logit per row
        kept_min = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
        )
        scaled = jnp.where(scaled < kept_min, -jnp.inf, scaled)
    return scaled


def _mesh_fingerprint(mesh, batch_axes, model_axis):
    """Hashable identity of a decode mesh for the jit cache — axis
    layout plus the concrete device set (hyperparam trials lease many
    distinct submeshes over the same process lifetime)."""
    if mesh is None:
        return None
    return (
        tuple(mesh.shape.items()),
        tuple(d.id for d in mesh.devices.flat),
        batch_axes,
        model_axis,
    )


def _decode_jit_cache(model) -> dict:
    """The per-model compiled-decode cache, BOUNDED via
    :func:`_cache_insert`: mesh-fingerprinted keys would otherwise pin
    every leased submesh (devices + compiled executables) alive for the
    model's lifetime (hyperparam trials lease many)."""
    return model.__dict__.setdefault("_elephas_generate_jit", {})


def _cache_insert(cache: dict, key, value, bound: int = 16):
    """Insert then evict oldest entries past ``bound`` — eviction AFTER
    insertion so the entry being served is never the one popped
    (code-review r5: pre-insert eviction recompiled the round-robin
    17th config on every call)."""
    cache[key] = value
    while len(cache) > bound:
        cache.pop(next(iter(cache)))


def _cache_get(cache: dict, key):
    """Fetch AND refresh recency: the hit re-inserts at the dict's end,
    so :func:`_cache_insert`'s evict-oldest approximates LRU instead of
    FIFO — a hot decode config inserted early is no longer silently
    evicted (and recompiled) once 16 newer configs appear (ADVICE r5)."""
    value = cache.get(key)
    if value is not None:
        cache[key] = cache.pop(key)
    return value


def _finish_decode(model, run, wargs, tokens0, key, mesh, batch_axes,
                   n_rows, n_cols):
    """Shared decode epilogue: stage the tokens/key (sharded under
    ``mesh`` if given), execute the compiled loop, record the
    out-sharding introspection hook, and host-read the real rows."""
    import jax.numpy as jnp

    if mesh is None:
        out = run(*wargs, jnp.asarray(tokens0), key)
        model.__dict__["_elephas_generate_out_sharding"] = getattr(
            out, "sharding", None
        )
        return np.asarray(out[:n_rows, :n_cols])

    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu.parallel.mesh import host_read, put_global

    tokens = put_global(tokens0, NamedSharding(mesh, P(batch_axes)))
    out = run(
        *wargs, tokens,
        put_global(np.asarray(key), NamedSharding(mesh, P())),
    )
    # introspection hook: tests (and curious users) can check the decode
    # really ran batch-sharded rather than replicated
    model.__dict__["_elephas_generate_out_sharding"] = out.sharding
    return host_read(out, mesh)[:n_rows, :n_cols]


def _validate_decode_args(model, prompt, steps, top_k, top_p):
    """Shared decode-argument validation (also used by the pipeline
    ring decode): normalizes the prompt to ``[B, P]`` and checks the
    length/sampling bounds against the model. Returns
    ``(prompt, b, p, maxlen, vocab)``."""
    prompt = np.asarray(prompt)
    if prompt.ndim == 1:
        prompt = prompt[None]
    b, p = prompt.shape
    maxlen = int(model.inputs[0].shape[1])
    vocab = int(model.outputs[0].shape[-1])
    if p + steps > maxlen:
        raise ValueError(
            f"prompt ({p}) + steps ({steps}) exceeds the model's "
            f"maxlen ({maxlen})"
        )
    if top_k is not None and not 0 < int(top_k) <= vocab:
        raise ValueError(
            f"top_k={top_k} outside (0, vocab={vocab}]"
        )
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError(f"top_p={top_p} outside (0, 1]")
    return prompt, b, p, maxlen, vocab


def _decode_shardings(variables, mesh, model_axis, rules):
    """Per-variable NamedShardings for decoding under ``mesh``: the TP
    planner's layouts when a >1 ``model_axis`` exists, replicated
    otherwise (data/seq/stage axes shard the batch, never weights)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if model_axis is not None and mesh.shape.get(model_axis, 1) > 1:
        from elephas_tpu.parallel.tensor import plan_sharding

        return plan_sharding(
            variables, mesh, model_axis=model_axis, rules=rules
        )
    return [NamedSharding(mesh, P())] * len(variables)


def generate(
    model,
    prompt,
    steps: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    seed: int = 0,
    kv_cache: bool = False,
    mesh=None,
    batch_axes=("data",),
    model_axis: str | None = None,
    rules=None,
):
    """Autoregressive sampling from a :func:`transformer_lm` model.

    ``prompt``: ``[B, P]`` int tokens (``P + steps`` must fit the
    model's ``maxlen``). Returns ``[B, P + steps]`` tokens.
    ``temperature=0`` is greedy argmax; otherwise softmax sampling at
    that temperature, optionally truncated to the ``top_k`` most likely
    tokens and/or the ``top_p`` nucleus (the smallest set of tokens
    whose cumulative probability reaches ``top_p``).

    TPU-shaped: ONE jitted program — the sequence stays at the model's
    fixed ``maxlen`` (causal attention makes positions ``>= t`` inert),
    and ``lax.fori_loop`` advances a token at a time writing in place.
    The default path recomputes the prefix each step (O(S²·L) per
    token, exactly the training math); ``kv_cache=True`` switches to a
    cached decode — per-layer K/V caches, one token's compute per step
    (O(S·L) total) — same greedy outputs, built for
    :func:`transformer_lm`'s architecture specifically.

    Mesh-aware decode (r5, VERDICT r4 #1 — the LM analogue of the
    reference's distributed ``predict``, SURVEY.md §3.4): pass ``mesh``
    and the decode runs as ONE GSPMD program over it — the batch shards
    over ``batch_axes`` (padded up to their product and sliced back),
    and with a >1 ``model_axis`` the weights stay sharded through
    ``stateless_call`` under the TP planner's layouts (qkv
    column-split, proj row-split, vocab-sharded head — ``rules``
    overrides), so models that only fit sharded can decode at all.
    Under ``kv_cache=True`` the per-layer K/V caches shard batch over
    ``batch_axes`` and heads over ``model_axis``. Weights ride as jit
    arguments (host→mesh upload per call — decode loops dominate, the
    upload does not). Every gang process must make the identical call
    (SPMD contract); all return the full tokens.
    """
    import jax
    import jax.numpy as jnp

    prompt, b, p, maxlen, _vocab = _validate_decode_args(
        model, prompt, steps, top_k, top_p
    )

    pad = 0
    if mesh is not None:
        if isinstance(batch_axes, str):
            batch_axes = (batch_axes,)
        batch_axes = tuple(batch_axes)
        missing = [a for a in batch_axes if a not in mesh.shape]
        if missing:
            raise ValueError(
                f"batch_axes {missing} not in mesh axes "
                f"{tuple(mesh.shape)}"
            )
        dp = int(np.prod([mesh.shape[a] for a in batch_axes]))
        pad = (-b) % dp
    bt = b + pad
    tokens0 = np.zeros((bt, maxlen), np.int32)
    tokens0[:b, :p] = prompt
    if pad:
        # padded lanes decode real math on a copy of the last prompt row
        # (any in-vocab content works — they are sliced off below)
        tokens0[b:, :p] = prompt[-1]

    if kv_cache:
        return _generate_cached(
            model, tokens0, bt, p, steps, temperature, top_k, top_p, seed,
            mesh=mesh, batch_axes=batch_axes, model_axis=model_axis,
            rules=rules, n_real=b,
        )

    tv = [v.value for v in model.trainable_variables]
    ntv = [v.value for v in model.non_trainable_variables]

    # the compiled loop is cached ON the model, keyed by everything its
    # program shape depends on — repeat calls (same prompt shape and
    # sampling config) hit the cache, and weights ride as ARGUMENTS so
    # further training never serves stale baked-in constants
    cache = _decode_jit_cache(model)
    cache_key = (
        bt, p, steps, float(temperature), top_k, top_p,
        _mesh_fingerprint(mesh, batch_axes, model_axis),
    )
    run = _cache_get(cache, cache_key)
    if run is None:

        @jax.jit
        def run(tv, ntv, tokens, key):
            def step(t, carry):
                tokens, key = carry
                logits, _ = model.stateless_call(
                    tv, ntv, tokens, training=False
                )
                key, sub = jax.random.split(key)
                nxt = _sample_logits(
                    logits[:, t - 1], sub, temperature, top_k, top_p
                )
                return tokens.at[:, t].set(nxt), key

            tokens, _ = jax.lax.fori_loop(p, p + steps, step, (tokens, key))
            return tokens

        _cache_insert(cache, cache_key, run)

    if mesh is not None:
        from elephas_tpu.parallel.mesh import put_global

        tv_sh = _decode_shardings(
            model.trainable_variables, mesh, model_axis, rules
        )
        ntv_sh = _decode_shardings(
            model.non_trainable_variables, mesh, model_axis, rules
        )
        tv = [put_global(np.asarray(v), s) for v, s in zip(tv, tv_sh)]
        ntv = [put_global(np.asarray(v), s) for v, s in zip(ntv, ntv_sh)]
    return _finish_decode(
        model, run, (tv, ntv), tokens0, jax.random.PRNGKey(seed),
        mesh, batch_axes, b, p + steps,
    )



def validate_token_decode_model(model, what: str = "kv_cache decode",
                                hint: str = "use kv_cache=False",
                                allow_stock: bool = True):
    """Compatibility gate for token-at-a-time cached decode, shared by
    ``generate(kv_cache=True)`` and the serving engine
    (:mod:`elephas_tpu.serving`): the model must be a single-input
    functional graph of causal attention layers plus token-local
    layers, computed in float32, with no weight-tied or nested
    attention call sites. Returns ``(flash_layers, stock_mha_layers,
    gqa_layers)``; raises ``ValueError`` (messages prefixed ``what``,
    suffixed ``hint``) otherwise. ``allow_stock=False`` additionally
    rejects stock keras MultiHeadAttention/GQA layers (callers whose
    decode handlers only replay ``FlashMHA`` math)."""
    import keras

    FlashMHA = _flash_mha_layer()

    if not hasattr(model, "_run_through_graph") or len(model.inputs) != 1:
        raise ValueError(
            f"{what} needs a single-input functional model; {hint} "
            f"for this architecture"
        )
    flash_layers = [
        l for l in model._flatten_layers() if isinstance(l, FlashMHA)
    ]
    gqa_cls = getattr(
        keras.layers, "GroupQueryAttention", None
    ) or getattr(keras.layers, "GroupedQueryAttention", None)

    def _stock_layers_of(base):
        if base is None:
            return []
        found = []
        for l in model._flatten_layers():
            if not isinstance(l, base):
                continue
            if not allow_stock:
                raise ValueError(
                    f"{what} replays FlashMHA attention only, but "
                    f"{l.name!r} is a stock {base.__name__}; {hint}"
                )
            # the decode handler recomputes STOCK attention math from
            # the EinsumDense kernels; a subclass overriding call /
            # _compute_attention (RoPE, ALiBi, soft-caps...) would
            # silently decode different tokens — reject with guidance
            # (code-review r4)
            if (
                type(l).call is not base.call
                or type(l)._compute_attention is not base._compute_attention
            ):
                raise ValueError(
                    f"{what} replays stock {base.__name__} math, "
                    f"but {l.name!r} is a customized subclass "
                    f"({type(l).__name__}); {hint}"
                )
            if len(l._output_dense.kernel.shape) != 3:
                raise ValueError(
                    f"{what}: {l.name!r} has a non-default "
                    f"output_shape (rank-"
                    f"{len(l._output_dense.kernel.shape)} output "
                    f"kernel); {hint}"
                )
            found.append(l)
        return found

    stock_mha_layers = _stock_layers_of(keras.layers.MultiHeadAttention)
    gqa_layers = _stock_layers_of(gqa_cls)
    if not flash_layers and not stock_mha_layers and not gqa_layers:
        raise ValueError(
            f"{what} needs at least one attention layer (FlashMHA"
            + (", keras MultiHeadAttention, or GroupQueryAttention"
               if allow_stock else "")
            + f" — the cache lives there); {hint}"
        )
    for l in flash_layers:
        if not l.causal:
            raise ValueError(
                f"{what} is causal by construction, but FlashMHA "
                f"layer {l.name!r} has causal=False; {hint}"
            )
    # count call sites within THIS model's graph only — inbound nodes
    # accumulate across every symbolic call a layer ever received, so a
    # layer also referenced by some other Model would be spuriously
    # rejected by a global count (code-review r4)
    calls_here: dict[int, int] = {}
    nodes_by_depth = getattr(model, "_nodes_by_depth", None)
    if nodes_by_depth is None:  # fall back to the (global) node count
        for l in flash_layers + stock_mha_layers + gqa_layers:
            calls_here[id(l)] = len(l._inbound_nodes)
    else:
        for depth_nodes in nodes_by_depth.values():
            for node in depth_nodes:
                op = getattr(node, "operation", None)
                if op is not None:
                    calls_here[id(op)] = calls_here.get(id(op), 0) + 1
    for l in flash_layers + stock_mha_layers + gqa_layers:
        n_calls = calls_here.get(id(l), 0)
        if n_calls > 1:
            # weight-tied reuse (ALBERT-style): every call site would
            # share ONE name-keyed cache and clobber the others' K/V
            raise ValueError(
                f"{what} keys K/V caches by layer, but "
                f"{l.name!r} is called at {n_calls} graph "
                f"nodes (weight tying) — the call sites would corrupt "
                f"each other's cache; {hint}"
            )
        if n_calls == 0 and nodes_by_depth is not None:
            # reachable only through a NESTED sub-Model's graph: the
            # decode handler would never intercept it (the replay calls
            # the inner Model as one opaque layer) — reject with
            # guidance instead of dying mid-trace (code-review r4)
            raise ValueError(
                f"{what}: attention layer {l.name!r} lives "
                f"inside a nested sub-Model — the token-by-token replay "
                f"only walks the top-level graph; flatten the model or "
                f"{hint}"
            )
    _SEQ_MIXING = (
        keras.layers.GlobalAveragePooling1D, keras.layers.AveragePooling1D,
        keras.layers.MaxPooling1D, keras.layers.Conv1D, keras.layers.RNN,
        keras.layers.Flatten,
    )
    for l in model._flatten_layers():
        if isinstance(l, _SEQ_MIXING):
            raise ValueError(
                f"{what} replays the graph one token at a time; "
                f"layer {l.name!r} ({type(l).__name__}) mixes the "
                f"sequence axis — {hint}"
            )
    compute_dtype = getattr(model.dtype_policy, "compute_dtype", "float32")
    if compute_dtype != "float32":
        raise ValueError(
            f"{what} computes in float32, which would diverge "
            f"from this model's {compute_dtype} forward (argmax flips "
            f"where top logits are close) — {hint} for "
            f"mixed-precision models"
        )
    return flash_layers, stock_mha_layers, gqa_layers


def _generate_cached(model, tokens0, b, p, steps, temperature, top_k,
                     top_p, seed, mesh=None, batch_axes=("data",),
                     model_axis=None, rules=None, n_real=None):
    """KV-cache decode for ANY single-input causal LM assembled from
    ``FlashMHA`` attention plus token-local keras layers.

    r4 (VERDICT r3 weak #3): instead of requiring ``transformer_lm``'s
    exact variable paths, the model's functional graph is replayed one
    TOKEN at a time through keras' own node traversal
    (``Function._run_through_graph``), each node's operation swapped for
    a single-token decode handler:

    - ``FlashMHA`` — and stock ``keras.layers.MultiHeadAttention``
      called self-attentively with ``use_causal_mask=True`` (r4) —
      become cached-attention read/writes: per-layer ``[B, S, H, Dh]``
      K/V caches keyed by layer name, one token's q/k/v computed and
      attention taken over the cache (O(S·L) for the whole generation
      vs the default path's O(S²·L));
    - layers with weights run ``stateless_call`` on the ``[B, D]`` token
      activations, weights riding as jit ARGUMENTS so further training
      never serves stale baked-in constants;
    - ``Dropout`` is elided (inference);
    - weightless ops (residual ``Add``s, the positional-table add) run
      as recorded, with any concrete array argument spanning the
      sequence axis sliced at ``t`` (that is how the fixed sinusoidal
      table follows the decode position).

    One jitted ``fori_loop`` runs prefill and sampling alike (prompt
    positions keep their ground-truth token; sampled positions write in
    place), the compiled loop caching on the model like the default
    path. Graph shapes the token-local replay cannot honor — no causal
    ``FlashMHA``, mixed precision, sequence-mixing layers (pooling,
    conv, RNNs) — raise with a pointer to ``kv_cache=False``.
    """
    import jax
    import jax.numpy as jnp

    import keras

    FlashMHA = _flash_mha_layer()

    flash_layers, stock_mha_layers, gqa_layers = validate_token_decode_model(
        model, what="kv_cache decode", hint="use kv_cache=False"
    )
    gqa_cls = getattr(
        keras.layers, "GroupQueryAttention", None
    ) or getattr(keras.layers, "GroupedQueryAttention", None)

    maxlen = tokens0.shape[1]
    total = p + steps

    if mesh is None:
        weights = {v.path: v.value for v in model.variables}

        def _constrain_cache(z, heads):
            return z
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from elephas_tpu.parallel.mesh import put_global

        var_sh = _decode_shardings(
            list(model.variables), mesh, model_axis, rules
        )
        weights = {
            v.path: put_global(np.asarray(v.value), s)
            for v, s in zip(model.variables, var_sh)
        }

        def _constrain_cache(z, heads):
            # [B, S, H, Dh] K/V cache: batch over the batch axes, heads
            # over the model axis when they tile (GQA kv-head counts may
            # not divide — those caches stay head-replicated)
            ax = (
                model_axis
                if model_axis is not None
                and mesh.shape.get(model_axis, 1) > 1
                and heads % mesh.shape[model_axis] == 0
                else None
            )
            return jax.lax.with_sharding_constraint(
                z, NamedSharding(mesh, P(batch_axes, None, ax, None))
            )

    cache = _decode_jit_cache(model)
    cache_key = (
        "kv", b, p, steps, float(temperature), top_k, top_p,
        _mesh_fingerprint(mesh, batch_axes, model_axis),
    )
    run = _cache_get(cache, cache_key)
    if run is None:

        def _slice_seq(a):
            # CONCRETE array arguments recorded in the graph that span
            # the sequence axis follow the decode position: a
            # [..., maxlen, D] table (sinusoidal positions) slices to
            # [..., D]; a [maxlen] index vector (arange feeding a
            # learned positional Embedding) slices to the scalar t.
            # Traced tensors are never touched — their dims can
            # coincide with maxlen without meaning "sequence".
            concrete = isinstance(a, np.ndarray) or (
                isinstance(a, jax.Array)
                and not isinstance(a, jax.core.Tracer)
            )
            if not concrete:
                return a
            if a.ndim >= 2 and a.shape[-2] == maxlen:
                return jnp.asarray(a)[..., t_ref[0], :]
            if a.ndim == 1 and a.shape[0] == maxlen:
                return jnp.asarray(a)[t_ref[0]]
            return a

        t_ref = [None]  # current decode position, set per decode_step

        def decode_step(w, tok, t, caches):
            t_ref[0] = t
            ctx_new = {}

            def handler(op):
                if isinstance(op, FlashMHA):
                    def attn(x, *_a, **_k):
                        ck, cv = caches[op.name]
                        H, Dh = op.num_heads, op.head_dim
                        qkv = x @ w[op.qkv.kernel.path]  # [B, 3·H·Dh]
                        q, k, v = jnp.split(
                            qkv.reshape(x.shape[0], 3, H, Dh), 3, axis=1
                        )
                        q, k, v = q[:, 0], k[:, 0], v[:, 0]  # [B, H, Dh]
                        if getattr(op, "rope", False):
                            # rotate THIS position's q and k before they
                            # enter the cache/attend — cached k stay
                            # rotated, matching the full forward
                            cos_np, sin_np = _rope_tables(maxlen, Dh)
                            cos_t = jnp.asarray(cos_np)[t]
                            sin_t = jnp.asarray(sin_np)[t]
                            q = _apply_rope(q, cos_t, sin_t)
                            k = _apply_rope(k, cos_t, sin_t)
                        ck = ck.at[:, t].set(k)
                        cv = cv.at[:, t].set(v)
                        att = jnp.einsum("bhd,bshd->bhs", q, ck) * (
                            Dh**-0.5
                        )
                        visible = jnp.arange(maxlen)[None, None, :] <= t
                        att = jax.nn.softmax(
                            jnp.where(visible, att, -jnp.inf), axis=-1
                        )
                        o = jnp.einsum("bhs,bshd->bhd", att, cv).reshape(
                            x.shape[0], H * Dh
                        )
                        ctx_new[op.name] = (ck, cv)
                        return (
                            o @ w[op.proj.kernel.path]
                            + w[op.proj.bias.path]
                        )

                    return attn
                if isinstance(op, keras.layers.MultiHeadAttention) or (
                    gqa_cls is not None and isinstance(op, gqa_cls)
                ):
                    def attn_stock(query, *pos, _op=op, **kwargs):
                        if not kwargs.get("use_causal_mask"):
                            raise ValueError(
                                f"kv_cache decode: stock attention layer "
                                f"{_op.name!r} is called without "
                                f"use_causal_mask=True — non-causal "
                                f"attention cannot decode token-by-"
                                f"token; use kv_cache=False"
                            )
                        value = pos[0] if pos else kwargs.get("value")
                        key_in = (
                            pos[1] if len(pos) > 1 else kwargs.get("key")
                        )
                        if value is not query or (
                            key_in is not None and key_in is not query
                        ):
                            raise ValueError(
                                f"kv_cache decode: {_op.name!r} is used "
                                f"as cross-attention; use kv_cache=False"
                            )
                        for bad in ("attention_mask", "query_mask",
                                    "value_mask", "key_mask"):
                            if kwargs.get(bad) is not None:
                                raise ValueError(
                                    f"kv_cache decode: {_op.name!r} "
                                    f"carries an explicit {bad}; use "
                                    f"kv_cache=False"
                                )
                        if kwargs.get("return_attention_scores"):
                            raise ValueError(
                                f"kv_cache decode: {_op.name!r} returns "
                                f"attention scores; use kv_cache=False"
                            )

                        def dense(sub, x_, eq_in, eq_out):
                            y = jnp.einsum(
                                f"{eq_in}->{eq_out}", x_,
                                w[sub.kernel.path],
                            )
                            if sub.bias is not None:
                                y = y + w[sub.bias.path]
                            return y

                        x = query  # [B, D]
                        q = dense(_op._query_dense, x, "bd,dhk", "bhk")
                        k = dense(_op._key_dense, x, "bd,dhk", "bhk")
                        v = dense(_op._value_dense, x, "bd,dhv", "bhv")
                        ck, cv = caches[_op.name]
                        ck = ck.at[:, t].set(k)
                        cv = cv.at[:, t].set(v)
                        inv = getattr(_op, "_inverse_sqrt_key_dim", None)
                        if inv is None:  # GQA names it by head_dim
                            inv = _op._inverse_sqrt_head_dim
                        # one grouped attend covers both: the cache holds
                        # UN-repeated kv heads and query heads attend in
                        # groups of rep (rep == 1 for plain MHA). keras
                        # multiplies the QUERY by the inverse-sqrt factor
                        # BEFORE the dot — matching that operation order
                        # keeps the float reduction identical to the
                        # full-recompute path (code-review r4)
                        hq, hkv = q.shape[1], k.shape[1]
                        rep = hq // hkv
                        qg = (q * float(inv)).reshape(
                            q.shape[0], hkv, rep, q.shape[-1]
                        )
                        att = jnp.einsum("bgrk,bsgk->bgrs", qg, ck)
                        visible = (
                            jnp.arange(maxlen)[None, None, None, :] <= t
                        )
                        att = jax.nn.softmax(
                            jnp.where(visible, att, -jnp.inf), axis=-1
                        )
                        ctx = jnp.einsum(
                            "bgrs,bsgv->bgrv", att, cv
                        ).reshape(q.shape[0], hq, cv.shape[-1])
                        ctx_new[_op.name] = (ck, cv)
                        return dense(
                            _op._output_dense, ctx, "bhv,hvd", "bd"
                        )

                    return attn_stock
                if isinstance(op, keras.layers.Dropout):
                    return lambda x, *a, **k: x
                if isinstance(op, keras.Layer) and op.variables:
                    def stateless(*args, _op=op, **kwargs):
                        if kwargs.get("training"):
                            kwargs["training"] = False
                        args = [_slice_seq(a) for a in args]
                        tv = [w[v.path] for v in _op.trainable_variables]
                        ntv = [
                            w[v.path]
                            for v in _op.non_trainable_variables
                        ]
                        out, _ = _op.stateless_call(tv, ntv, *args, **kwargs)
                        return out

                    return stateless

                def weightless(*args, _op=op, **kwargs):
                    args = [_slice_seq(a) for a in args]
                    kwargs = {kk: _slice_seq(vv) for kk, vv in kwargs.items()}
                    return _op(*args, **kwargs)

                return weightless

            logits = model._run_through_graph(tok, operation_fn=handler)
            return logits, {
                name: ctx_new.get(name, caches[name]) for name in caches
            }

        @jax.jit
        def run(w, tokens, key):
            caches = {
                l.name: (
                    _constrain_cache(
                        jnp.zeros(
                            (b, maxlen, l.num_heads, l.head_dim),
                            jnp.float32,
                        ),
                        l.num_heads,
                    ),
                    _constrain_cache(
                        jnp.zeros(
                            (b, maxlen, l.num_heads, l.head_dim),
                            jnp.float32,
                        ),
                        l.num_heads,
                    ),
                )
                for l in flash_layers
            }
            for l in stock_mha_layers:
                caches[l.name] = (
                    _constrain_cache(
                        jnp.zeros(
                            (b, maxlen, l._num_heads, l._key_dim),
                            jnp.float32,
                        ),
                        l._num_heads,
                    ),
                    _constrain_cache(
                        jnp.zeros(
                            (b, maxlen, l._num_heads,
                             l._value_dim or l._key_dim),
                            jnp.float32,
                        ),
                        l._num_heads,
                    ),
                )
            for l in gqa_layers:
                caches[l.name] = (
                    _constrain_cache(
                        jnp.zeros(
                            (b, maxlen, l.num_key_value_heads, l.head_dim),
                            jnp.float32,
                        ),
                        l.num_key_value_heads,
                    ),
                    _constrain_cache(
                        jnp.zeros(
                            (b, maxlen, l.num_key_value_heads, l.head_dim),
                            jnp.float32,
                        ),
                        l.num_key_value_heads,
                    ),
                )

            def step(t, carry):
                tokens, caches, key = carry
                logits, caches = decode_step(w, tokens[:, t], t, caches)
                # prompt positions keep their ground-truth token; only
                # the continuation writes
                write = t + 1 >= p
                # advance the PRNG stream only on sampling steps — the
                # default (kv_cache=False) path splits once per GENERATED
                # token, so consuming splits during prefill would make
                # sampled output at the same seed differ between the two
                # paths (r3 advisor finding)
                key2, sub = jax.random.split(key)
                key = jnp.where(write, key2, key)
                nxt = _sample_logits(logits, sub, temperature, top_k, top_p)
                tokens = jnp.where(
                    write,
                    tokens.at[:, jnp.minimum(t + 1, maxlen - 1)].set(nxt),
                    tokens,
                )
                return tokens, caches, key

            tokens, _, _ = jax.lax.fori_loop(
                0, total - 1, step, (tokens, caches, key)
            )
            return tokens

        _cache_insert(cache, cache_key, run)

    return _finish_decode(
        model, run, (weights,), tokens0, jax.random.PRNGKey(seed),
        mesh, batch_axes, b if n_real is None else n_real, total,
    )
