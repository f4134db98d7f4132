"""Expert parallelism — Mixture-of-Experts FFN over a mesh axis.

Absent from the reference (SURVEY.md §2a lists EP as not-implemented);
provided here as the TPU-native construction: experts are sharded over a
mesh axis (each device owns ``E/W`` experts' weights), tokens are routed
top-1 (Switch) or top-k (GShard) with a capacity bound and a
load-balance auxiliary loss, and the token↔expert exchange is
``lax.all_to_all`` over ICI — the canonical EP data path. The Keras
layer form (:class:`elephas_tpu.models.MoeFFN`) and the Switch
transformer builder live in :mod:`elephas_tpu.models.switch`.

Everything is dense and statically shaped (one-hot dispatch/combine
einsums, fixed capacity with overflow dropping) so the whole op lowers
through XLA with no ragged shapes; autodiff works end-to-end (all_to_all
is linear).

Call :func:`expert_parallel_ffn` INSIDE ``shard_map`` with tokens sharded
over the same axis as the experts. :func:`moe_ffn_reference` is the
single-device oracle used by the tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _topk_dispatch(x, gate_w, num_experts: int, capacity: int, k: int = 1):
    """Token → expert routing tensors (top-k, capacity-bounded).

    Returns ``(dispatch [T, E, C], combine [T, E, C], aux)``:

    - ``k=1``: Switch routing — each token goes to its argmax expert,
      combine-weighted by that expert's raw softmax prob.
    - ``k>1``: GShard-style — the top-k experts each process the token,
      combine weights are the top-k probs renormalized to sum to 1;
      first choices claim capacity slots before second choices.
    - ``aux``: the Switch §2.2 load-balance loss ``E · Σ_e f_e · P_e``
      (``f_e`` = fraction of tokens whose FIRST choice is ``e``, ``P_e``
      = mean router prob for ``e``) — differentiable through ``P``,
      minimized by a uniform router. Scale it and add to the task loss.

    Tokens beyond an expert's capacity are dropped (output zero — the
    residual connection around the MoE layer carries them, as in Switch).
    """
    if k > num_experts:
        raise ValueError(
            f"k={k} routing choices exceed num_experts={num_experts}"
        )
    logits = x @ gate_w  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # iterative top-k (k is tiny): argmax, mask, repeat
    choices = []  # [T] expert index per choice
    gates = []  # [T] raw prob per choice
    masked = probs
    for _ in range(k):
        expert = jnp.argmax(masked, axis=-1)
        choices.append(expert)
        gates.append(jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0])
        masked = masked * (1.0 - jax.nn.one_hot(expert, num_experts, dtype=probs.dtype))
    if k > 1:
        denom = sum(gates)
        gates = [g / jnp.maximum(denom, 1e-9) for g in gates]

    # routing math runs in int32 regardless of activation dtype: a
    # bfloat16 cumsum goes inexact past 256 tokens, silently corrupting
    # the capacity mask; only the final dispatch/combine cast to x.dtype
    dispatch = jnp.zeros((x.shape[0], num_experts, capacity), x.dtype)
    combine = jnp.zeros_like(dispatch)
    counts = jnp.zeros((num_experts,), jnp.int32)  # slots claimed so far
    for expert, gate in zip(choices, gates):
        onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)  # [T, E]
        # 0-based position of each token within its expert's queue (only
        # the token's own expert column is nonzero-capable), offset by
        # the slots earlier choices already claimed
        position = (
            jnp.cumsum(onehot, axis=0) * onehot - onehot + counts[None, :] * onehot
        )
        kept = (position < capacity) & (onehot > 0)
        rank = jnp.sum(jnp.where(kept, position, 0), axis=-1)  # [T] int32
        pos_onehot = jax.nn.one_hot(rank, capacity, dtype=x.dtype)  # [T, C]
        keep_mask = jnp.any(kept, axis=-1).astype(x.dtype)  # [T]
        d = (
            onehot.astype(x.dtype)[:, :, None]
            * pos_onehot[:, None, :]
            * keep_mask[:, None, None]
        )
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        counts = counts + jnp.sum(onehot, axis=0)

    first = jax.nn.one_hot(choices[0], num_experts, dtype=probs.dtype)
    f = jnp.mean(first, axis=0)  # fraction routed (first choice)
    p = jnp.mean(probs, axis=0)  # mean router prob
    aux = num_experts * jnp.sum(f * p)
    return dispatch, combine, aux


def _top1_dispatch(x, gate_w, num_experts: int, capacity: int):
    """Back-compat Switch top-1 routing: ``(dispatch, combine)``."""
    dispatch, combine, _ = _topk_dispatch(x, gate_w, num_experts, capacity, k=1)
    return dispatch, combine


def expert_parallel_ffn(
    x,
    gate_w,
    w1,
    b1,
    w2,
    b2,
    axis_name: str,
    capacity_factor: float = 1.25,
    activation=jax.nn.gelu,
    k: int = 1,
    return_aux: bool = False,
):
    """Top-k MoE FFN; call INSIDE ``shard_map``.

    Shapes (per device): ``x [T_local, D]``; ``gate_w [D, E_total]``
    (replicated); expert weights are the local shard —
    ``w1 [E_local, D, H]``, ``b1 [E_local, H]``, ``w2 [E_local, H, D]``,
    ``b2 [E_local, D]`` with ``E_total = W · E_local``. With
    ``return_aux`` also returns the load-balance loss (this shard's —
    ``pmean`` it across the axis if training on it).
    """
    w = jax.lax.axis_size(axis_name)
    t_local, d = x.shape
    e_local = w1.shape[0]
    e_total = w * e_local
    # per-expert per-source-device slot budget (k assignments per token)
    capacity = max(1, int(k * t_local * capacity_factor / e_total))

    dispatch, combine, aux = _topk_dispatch(x, gate_w, e_total, capacity, k=k)

    # gather expert inputs locally, then all-to-all so each device
    # receives its own experts' tokens from every device
    expert_inputs = jnp.einsum("td,tec->ecd", x, dispatch)  # [E_total, C, D]
    expert_inputs = expert_inputs.reshape(w, e_local, capacity, d)
    expert_inputs = jax.lax.all_to_all(
        expert_inputs, axis_name, split_axis=0, concat_axis=0, tiled=False
    )  # [W_src, E_local, C, D]
    expert_inputs = jnp.moveaxis(expert_inputs, 0, 1).reshape(
        e_local, w * capacity, d
    )

    h = activation(
        jnp.einsum("ecd,edh->ech", expert_inputs, w1) + b1[:, None, :]
    )
    out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]

    # route results back to the devices that own the tokens
    out = jnp.moveaxis(
        out.reshape(e_local, w, capacity, d), 1, 0
    )  # [W_src, E_local, C, D]
    out = jax.lax.all_to_all(
        out, axis_name, split_axis=0, concat_axis=0, tiled=False
    )
    out = out.reshape(e_total, capacity, d)
    result = jnp.einsum("ecd,tec->td", out, combine)
    return (result, aux) if return_aux else result


def moe_ffn_reference(
    x,
    gate_w,
    w1,
    b1,
    w2,
    b2,
    capacity_factor: float = 1.25,
    activation=jax.nn.gelu,
    num_shards: int = 1,
    k: int = 1,
    return_aux: bool = False,
):
    """Single-device oracle with identical routing/capacity semantics.

    ``num_shards`` mirrors the EP run's token sharding: routing capacity
    is computed per shard, so with the same sharding factor the outputs
    of :func:`expert_parallel_ffn` match exactly. With ``return_aux``
    also returns the load-balance loss averaged over shards.
    """
    e_total = gate_w.shape[-1]
    shards = jnp.split(x, num_shards, axis=0)
    outs = []
    auxes = []
    for xs in shards:
        t_local = xs.shape[0]
        capacity = max(1, int(k * t_local * capacity_factor / e_total))
        dispatch, combine, aux = _topk_dispatch(xs, gate_w, e_total, capacity, k=k)
        auxes.append(aux)
        expert_inputs = jnp.einsum("td,tec->ecd", xs, dispatch)
        h = activation(
            jnp.einsum("ecd,edh->ech", expert_inputs, w1) + b1[:, None, :]
        )
        out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
        outs.append(jnp.einsum("ecd,tec->td", out, combine))
    result = jnp.concatenate(outs, axis=0)
    if return_aux:
        return result, sum(auxes) / len(auxes)
    return result


def init_moe_params(
    key, d_model: int, d_hidden: int, num_experts: int, dtype=jnp.float32
):
    """Convenience initializer: (gate_w, w1, b1, w2, b2) for E experts."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale1 = (2.0 / d_model) ** 0.5
    scale2 = (2.0 / d_hidden) ** 0.5
    return (
        jax.random.normal(k1, (d_model, num_experts), dtype) * scale1,
        jax.random.normal(k2, (num_experts, d_model, d_hidden), dtype) * scale1,
        jnp.zeros((num_experts, d_hidden), dtype),
        jax.random.normal(k3, (num_experts, d_hidden, d_model), dtype) * scale2,
        jnp.zeros((num_experts, d_model), dtype),
    )
