"""Sequence/context parallelism behind the parity API.

The reference trains sequence models whole-sequence per worker — its
sequence length is bounded by one worker's memory (SURVEY.md §5
"long-context: entirely absent"). This module removes that ceiling the
TPU way: the sequence axis of every activation is sharded over a
``('data', 'seq')`` mesh, attention runs as a **ring** —
:func:`elephas_tpu.ops.ring_attention.ring_attention` rotates KV shards
via ``ppermute`` over ICI while queries stay put — and every other op
(layernorm, MLP, embedding lookup) is token-local, so GSPMD runs it on
the sequence shards with no communication at all.

Design: weights replicate (``rules=[]`` under the
:class:`~elephas_tpu.parallel.tensor.ShardedTrainer` machinery — the
planner is told to shard *nothing*), activations shard. The only manual
region is the attention core: :class:`~elephas_tpu.models.transformer`'s
``FlashMHA`` layer consults :func:`active_sequence_scope` at trace time
and, inside a sequence-parallel region, routes through a ``shard_map``
ring instead of the single-chip Pallas flash kernel. Everything else —
fit/evaluate/predict/history metrics/sharded checkpoints — is inherited
from the tensor-parallel trainer unchanged.

``SparkModel(model, sequence_parallel=N)`` routes here via
:class:`SequenceParallelRunner`; data-parallel replicas occupy the
remaining ``devices // N`` mesh rows, so DP×SP composes on one mesh.

No counterpart exists upstream (TPU-native extension, not a port).
"""

from __future__ import annotations

import functools
import logging
import threading

import jax
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from elephas_tpu.parallel.tensor import ShardedTrainer, TensorParallelRunner

logger = logging.getLogger(__name__)

# (mesh, data_axis, seq_axis) while a sequence-parallel trainer is
# tracing/running — read by FlashMHA.call. Thread-local so concurrent
# trainers (hyperparam trials run threads) can't see each other's mesh.
_SCOPE = threading.local()


class _SequenceScope:
    __slots__ = ("mesh", "data_axis", "seq_axis", "mechanism")

    def __init__(self, mesh: Mesh, data_axis: str, seq_axis: str,
                 mechanism: str = "ring"):
        self.mesh = mesh
        self.data_axis = data_axis
        self.seq_axis = seq_axis
        self.mechanism = mechanism


def active_sequence_scope() -> _SequenceScope | None:
    """The innermost active sequence-parallel scope, or None."""
    stack = getattr(_SCOPE, "stack", None)
    return stack[-1] if stack else None


class sequence_parallel_scope:
    """Context manager: route sequence-aware ops (``FlashMHA``) through
    the sharded attention over ``mesh[seq_axis]`` for the duration.
    ``mechanism``: ``'ring'`` (ppermute KV rotation, O(S/W) activations,
    any head count) or ``'ulysses'`` (two all-to-alls around full
    attention; needs ``num_heads % W == 0``)."""

    def __init__(self, mesh: Mesh, data_axis: str = "data",
                 seq_axis: str = "seq", mechanism: str = "ring"):
        if mechanism not in ("ring", "ulysses"):
            raise ValueError(
                f"mechanism must be 'ring' or 'ulysses', got {mechanism!r}"
            )
        self._scope = _SequenceScope(mesh, data_axis, seq_axis, mechanism)

    def __enter__(self):
        if not hasattr(_SCOPE, "stack"):
            _SCOPE.stack = []
        _SCOPE.stack.append(self._scope)
        return self._scope

    def __exit__(self, *exc):
        _SCOPE.stack.pop()
        return False


def dp_sp_mesh(sequence_parallel: int, data_parallel: int | None = None) -> Mesh:
    """2-D ``('data', 'seq')`` mesh — see
    :func:`~elephas_tpu.parallel.tensor.second_axis_mesh`."""
    from elephas_tpu.parallel.tensor import second_axis_mesh

    return second_axis_mesh(
        sequence_parallel, "seq", data_parallel, label="sequence_parallel"
    )


def dp_sp_tp_mesh(
    sequence_parallel: int,
    model_parallel: int,
    data_parallel: int | None = None,
) -> Mesh:
    """3-D ``('data', 'seq', 'model')`` mesh: Megatron weight sharding
    and sequence sharding compose, data replicas fill the rest. Device
    budget/divisibility rules live in
    :func:`~elephas_tpu.parallel.tensor.second_axis_mesh` (one copy)."""
    from elephas_tpu.parallel.tensor import second_axis_mesh

    sp, mp = int(sequence_parallel), int(model_parallel)
    if sp <= 0 or mp <= 0:
        raise ValueError(
            f"sequence_parallel={sequence_parallel} and "
            f"model_parallel={model_parallel} must be positive"
        )
    flat = second_axis_mesh(
        sp * mp, "cell", data_parallel,
        label="sequence_parallel×model_parallel",
    )
    arr = np.asarray(flat.devices).reshape(flat.shape["data"], sp, mp)
    return Mesh(arr, ("data", "seq", "model"))


def ring_mha(q, k, v, causal: bool = False, scale: float | None = None,
             scope: _SequenceScope | None = None):
    """Ring attention on ``[B, H, S, D]`` heads under the active scope.

    Batch shards over the data axis, heads over the model axis (under
    TP×SP), sequence over the seq axis; KV shards rotate the ring
    (``ops/ring_attention.py``). When the batch alone does not tile
    over the data axis (1-row predicts, tiny introspection calls) the
    HEAD dim absorbs the data axis too, recovering the utilization the
    old merged batch·heads layout had. Gradients flow (the ring op
    carries a custom VJP)."""
    from elephas_tpu.ops.ring_attention import ring_attention

    scope = scope or active_sequence_scope()
    if scope is None:
        raise RuntimeError(
            "ring_mha called outside a sequence_parallel_scope"
        )
    b, h, s, d = q.shape
    sp = scope.mesh.shape[scope.seq_axis]
    dp = scope.mesh.shape[scope.data_axis]
    # under TP×SP the 'model' axis shards the HEAD dimension of the
    # attention core too (heads are independent, so splitting them over
    # TP ranks changes the layout, not the math) — without this, q/k/v
    # replicate across TP ranks inside the shard_map and model_parallel
    # buys no attention speedup (r3 advisor finding)
    mp_axis = "model" if "model" in scope.mesh.shape else None
    mp = scope.mesh.shape.get("model", 1)
    if s % sp:
        raise ValueError(
            f"sequence length {s} must divide over sequence_parallel={sp}"
        )
    if scope.mechanism == "ulysses":
        from elephas_tpu.ops.ulysses import ulysses_attention

        # batch shards over 'data' when it tiles (tiny introspection
        # batches replicate — a layout choice, not a limit); heads shard
        # over 'model' when each TP rank's slice still tiles over seq
        data_axis = scope.data_axis if b % dp == 0 else None
        head_axis = (
            mp_axis if mp > 1 and h % mp == 0 and (h // mp) % sp == 0
            else None
        )
        if data_axis is None and dp > 1:
            logger.info(
                "ulysses: batch %d does not tile over data=%d — "
                "activations replicate across the data axis for this "
                "call (correct, but a multi-x memory/throughput cost)",
                b, dp,
            )
        spec4 = P(data_axis, head_axis, scope.seq_axis, None)
        fn4 = functools.partial(
            ulysses_attention, axis_name=scope.seq_axis, causal=causal,
            scale=scale,
        )
        return jax.shard_map(
            fn4, mesh=scope.mesh, in_specs=(spec4,) * 3, out_specs=spec4,
            check_vma=False,
        )(q, k, v)
    # batch shards over 'data' and heads over 'model' when they tile.
    # The q/k/v stay 4-D [B, H, S, D] through the shard_map boundary
    # and merge batch·heads LOCALLY inside: a global reshape merging a
    # data-sharded B with a model-sharded H produced an unsplittable
    # merged sharding whose backward cotangent hit XLA's "involuntary
    # full rematerialization" path (spmd_partitioner.cc:652, seen in
    # an 8-device dry run of 2026-07). When B alone does not tile
    # over 'data' (1-row predicts, tiny introspection batches) the
    # head dim absorbs the data axis too — the old merged layout's
    # joint tiling, expressed per-axis; only when neither dim tiles do
    # activations replicate (a layout choice, not a limit).
    data_axis = scope.data_axis if b % dp == 0 else None
    if mp > 1 and h % mp == 0:
        head_axis = mp_axis
        if data_axis is None and h % (dp * mp) == 0:
            head_axis = (scope.data_axis, mp_axis)
    elif data_axis is None and dp > 1 and h % dp == 0:
        head_axis = scope.data_axis
    else:
        head_axis = None
    if (
        data_axis is None and head_axis is None and mp == 1
        and dp > 1 and (b * h) % dp == 0
    ):
        # neither dim tiles alone but their product does (r4's merged
        # layout; code-review r5 round sweep) — WITHOUT a model axis
        # the merged reshape is cliff-free, so keep that tiling rather
        # than replicate
        spec = P(scope.data_axis, scope.seq_axis, None)
        fn3 = functools.partial(
            ring_attention, axis_name=scope.seq_axis, causal=causal,
            scale=scale,
        )
        sharded3 = jax.shard_map(
            fn3, mesh=scope.mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False,
        )
        out = sharded3(
            q.reshape(b * h, s, d), k.reshape(b * h, s, d),
            v.reshape(b * h, s, d),
        )
        return out.reshape(b, h, s, d)
    head_axes = (
        head_axis if isinstance(head_axis, tuple)
        else () if head_axis is None else (head_axis,)
    )
    if data_axis is None and dp > 1 and scope.data_axis not in head_axes:
        logger.info(
            "ring: neither batch %d nor heads %d tile over data=%d — "
            "activations replicate across the data axis for this call "
            "(correct, but a multi-x memory/throughput cost)",
            b, h, dp,
        )
    if mp > 1 and mp_axis not in head_axes:
        logger.info(
            "ring: heads %d do not tile over model=%d — attention "
            "activations replicate across the model axis for this call "
            "(correct, but model_parallel buys no attention speedup "
            "here)",
            h, mp,
        )
    spec = P(data_axis, head_axis, scope.seq_axis, None)

    def fn(q4, k4, v4):
        bl, hl, sl, dl = q4.shape
        out = ring_attention(
            q4.reshape(bl * hl, sl, dl),
            k4.reshape(bl * hl, sl, dl),
            v4.reshape(bl * hl, sl, dl),
            axis_name=scope.seq_axis, causal=causal, scale=scale,
        )
        return out.reshape(bl, hl, sl, dl)

    sharded = jax.shard_map(
        fn, mesh=scope.mesh, in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False,
    )
    return sharded(q, k, v)


def patch_stock_attention(model) -> int:
    """Make keras' stock attention layers sequence-parallel-aware.

    The reference's promise is "bring any compiled Keras model"
    (SURVEY.md §2, `[U] elephas/spark_model.py`); round 3 kept it under
    SP only for the in-tree ``FlashMHA``. This routes the attention core
    of stock ``keras.layers.MultiHeadAttention`` /
    ``GroupedQueryAttention`` through :func:`ring_mha` whenever a
    sequence scope is active, by patching two instance methods:

    - ``_compute_attention_mask``: under the scope, ``use_causal_mask``
      is absorbed into the sharded kernel's analytic causal handling
      instead of densifying a ``[T, S]`` mask across seq shards;
    - ``_compute_attention``: under the scope, the projected
      ``[B, S, N, H]`` heads run through the ring / Ulysses
      ``shard_map`` (keras' own einsum attention otherwise).

    Outside a scope the layers behave exactly as stock keras (the
    original methods are called), so patched models remain ordinary
    Keras models — save/summary/inference all unchanged. Falls back to
    the stock path (replicated attention; training still correct) for
    explicit attention masks, attention dropout, returned scores, or
    non-4D heads, logging once per layer.

    Returns the number of stock attention layers now sequence-aware.
    """
    import keras

    targets = [keras.layers.MultiHeadAttention]
    for name in ("GroupQueryAttention", "GroupedQueryAttention"):
        if hasattr(keras.layers, name):  # renamed across keras versions
            targets.append(getattr(keras.layers, name))
    targets = tuple(targets)
    n = 0
    for layer in model._flatten_layers():
        if not isinstance(layer, targets):
            continue
        n += 1
        if getattr(layer, "_elephas_sp_patched", False):
            continue
        _patch_attention_layer(layer)
    return n


def _patch_attention_layer(layer):
    import inspect

    import jax.numpy as jnp

    orig_mask = layer._compute_attention_mask
    orig_compute = layer._compute_attention
    # MHA's _compute_attention takes return_attention_scores
    # positionally; GQA's reads self._return_attention_scores instead
    orig_takes_scores = (
        "return_attention_scores"
        in inspect.signature(orig_compute).parameters
    )

    def patched_mask(query, value, query_mask=None, value_mask=None,
                     key_mask=None, attention_mask=None,
                     use_causal_mask=False):
        if (active_sequence_scope() is not None and use_causal_mask
                and query_mask is None and value_mask is None
                and key_mask is None and attention_mask is None):
            layer._elephas_sp_causal = True
            return None
        layer._elephas_sp_causal = False
        return orig_mask(
            query, value, query_mask=query_mask, value_mask=value_mask,
            key_mask=key_mask, attention_mask=attention_mask,
            use_causal_mask=use_causal_mask,
        )

    def patched_compute(query, key, value, attention_mask=None,
                        training=None, return_attention_scores=False):
        scope = active_sequence_scope()
        wants_scores = return_attention_scores or getattr(
            layer, "_return_attention_scores", False
        )
        dropout = getattr(layer, "_dropout", None)
        if dropout is None:
            dropout = getattr(layer, "dropout", 0.0)
        if (scope is None or attention_mask is not None or wants_scores
                or dropout > 0.0 or len(query.shape) != 4
                # ring/ulysses assume a self-attention-shaped core:
                # equal q/kv sequence lengths, one head dim throughout
                or query.shape[1] != key.shape[1]
                or query.shape[-1] != value.shape[-1]):
            if (attention_mask is None
                    and getattr(layer, "_elephas_sp_causal", False)):
                # patched_mask absorbed use_causal_mask expecting the
                # sharded kernel to apply causality analytically; on
                # fallback the stock path MUST get the mask back or it
                # silently attends bidirectionally (code-review r4)
                attention_mask = jnp.tril(
                    jnp.ones(
                        (query.shape[1], key.shape[1]), dtype="bool"
                    )
                )
            if scope is not None and not getattr(
                layer, "_elephas_sp_fallback_logged", False
            ):
                layer._elephas_sp_fallback_logged = True
                logger.info(
                    "%s: stock attention path under sequence parallelism "
                    "(explicit mask, attention dropout, or returned "
                    "scores) — attention replicates across seq shards "
                    "for this layer; training stays correct",
                    layer.name,
                )
            if orig_takes_scores:
                return orig_compute(query, key, value, attention_mask,
                                    training, return_attention_scores)
            return orig_compute(query, key, value,
                                attention_mask=attention_mask,
                                training=training)
        inv_scale = getattr(layer, "_inverse_sqrt_key_dim", None)
        if inv_scale is None:
            inv_scale = layer._inverse_sqrt_head_dim
        out = ring_mha(
            jnp.moveaxis(query, 1, 2),  # [B, T, N, H] -> [B, N, T, H]
            jnp.moveaxis(key, 1, 2),
            jnp.moveaxis(value, 1, 2),
            causal=bool(getattr(layer, "_elephas_sp_causal", False)),
            scale=float(inv_scale),
            scope=scope,
        )
        return jnp.moveaxis(out, 1, 2), None

    layer._compute_attention_mask = patched_mask
    layer._compute_attention = patched_compute
    layer._elephas_sp_patched = True


class SequenceShardedTrainer(ShardedTrainer):
    """DP×SP trainer for a compiled Keras model whose attention layers
    are sequence-aware (``FlashMHA``).

    Weights replicate; the sequence axis of activations shards over the
    ``seq`` mesh axis (GSPMD propagates the layout out of the attention
    ``shard_map`` through the token-local ops). Training is synchronous
    — the ``seq`` shards jointly compute ONE model's step, and the
    ``data`` axis all-reduces gradients per step; async/hogwild describe
    diverging data replicas and do not apply to a sequence split.
    """

    MODEL_AXIS = "seq"

    def __init__(
        self,
        model,
        sequence_parallel: int = 1,
        mesh: Mesh | None = None,
        data_parallel: int | None = None,
        attention: str = "ring",
        model_parallel: int = 1,
    ):
        self.model_parallel = int(model_parallel)
        if mesh is None:
            mesh = (
                dp_sp_tp_mesh(
                    sequence_parallel, self.model_parallel, data_parallel
                )
                if self.model_parallel > 1
                else dp_sp_mesh(sequence_parallel, data_parallel)
            )
        if "seq" not in mesh.shape:
            raise ValueError(
                "SequenceShardedTrainer needs a mesh with a 'seq' axis; "
                f"got axes {tuple(mesh.shape)} — build one with "
                "dp_sp_mesh()/dp_sp_tp_mesh() or add a 'seq' axis"
            )
        if attention not in ("ring", "ulysses"):
            raise ValueError(
                f"attention must be 'ring' or 'ulysses', got {attention!r}"
            )
        self.attention = attention
        if self.model_parallel > 1 or "model" in mesh.shape:
            # TP×SP: plan Megatron shardings over the 'model' axis while
            # the scope shards activations over 'seq' — GSPMD reshards
            # around the attention shard_map, keeping the composition
            # exact (asserted against the unsharded oracle in tests)
            self.MODEL_AXIS = "model"  # instance override
            rules = None  # DEFAULT_RULES
        else:
            rules = []  # weights replicate; SP shards activations only
        super().__init__(
            model, mesh=mesh, rules=rules, mode="synchronous",
            frequency="epoch",
        )
        self.sp = self.mesh.shape["seq"]
        n_stock = patch_stock_attention(model)
        if not self._has_sequence_aware_layer(model) and not n_stock:
            logger.warning(
                "sequence_parallel=%d but the model has no sequence-aware "
                "attention layer (FlashMHA or stock keras MHA/GQA) — "
                "training stays correct, but nothing rings over the seq "
                "axis; activations may simply replicate across it",
                self.sp,
            )

    @staticmethod
    def _has_sequence_aware_layer(model) -> bool:
        from elephas_tpu.models.transformer import _flash_mha_layer

        cls = _flash_mha_layer()
        return any(isinstance(l, cls) for l in model._flatten_layers())

    def _scope(self):
        return sequence_parallel_scope(
            self.mesh, "data", "seq", mechanism=self.attention
        )

    # every public entry point runs (and, on first call, TRACES) inside
    # the scope, so FlashMHA sees the mesh whenever jit retraces
    def fit(self, *args, **kwargs):
        with self._scope():
            return super().fit(*args, **kwargs)

    def fit_stream(self, *args, **kwargs):
        with self._scope():
            return super().fit_stream(*args, **kwargs)

    def evaluate(self, *args, **kwargs):
        with self._scope():
            return super().evaluate(*args, **kwargs)

    def predict(self, *args, **kwargs):
        with self._scope():
            return super().predict(*args, **kwargs)


class SequenceParallelRunner(TensorParallelRunner):
    """``MeshRunner``-shaped facade so ``SparkModel(model,
    sequence_parallel=N)`` drives the whole L5 surface
    (fit/evaluate/predict/checkpoint/streaming) over the DP×SP mesh."""

    def __init__(self, model, mesh: Mesh, attention: str = "ring"):
        self.model = model
        self.mode = "synchronous"
        self.frequency = "epoch"
        self.mesh = mesh
        self.num_workers = mesh.shape["data"]
        self.trainer = SequenceShardedTrainer(
            model, mesh=mesh, attention=attention,
            model_parallel=mesh.shape.get("model", 1),
        )
