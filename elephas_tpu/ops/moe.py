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

:func:`held_experts_ffn` is the other construction, for experts as they
are deployed today (hundreds of small gated experts, many a token): the
router scores ALL experts, the chip computes the part of the result
that the experts it HOLDS give, and no token is ever dropped: the token
slots routed to a held expert (a tenth of them, say) are placed by
expert in a buffer of twice what uniform routing would send
(:class:`RoutePlan`: one sort of the slots, one of the buffer's rows,
no scatter, and nothing the size of every slot times a row), go
through one grouped matrix product (:func:`grouped_matmul`) whose work
follows the rows that are really there, and come back to their tokens
as one sum over the buffer's rows on the MXU
(:func:`_sum_rows_by_token`). Past that buffer the same program takes
the tokens in blocks.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from elephas_tpu.utils import backend_guard


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


# -- dropless routing over the experts held ------------------------------


# the name (``jax.ad_checkpoint.checkpoint_name``) of what a sparse
# block's routing decided: the experts the router chose and the leaves
# of the one-buffer :class:`RoutePlan`. A caller whose own
# ``jax.checkpoint`` keeps it (``save_only_these_names``) runs top-k and
# the ordering once a layer, not again in the backward pass
ROUTE_NAME = "moe_route"

ROUTER_SCORES = {"softmax": jax.nn.softmax, "sigmoid": jax.nn.sigmoid}
# a held expert's activation: on the gate of a gated expert (SwiGLU's,
# ReGLU's), on the one product of an ungated one (squared ReLU)
EXPERT_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
                      "relu2": lambda t: jnp.square(jax.nn.relu(t))}


@jax.custom_vjp
def _chosen(scores, experts):
    """``scores [T, E]`` at ``experts [T, k]``. Its transpose is ``k``
    compares and sums over ``[T, E]``, where JAX's own is a scatter-add
    of every slot, which costs the chip twice that."""
    return jnp.take_along_axis(scores, experts, axis=-1)


def _chosen_fwd(scores, experts):
    return _chosen(scores, experts), (experts, scores.shape[-1])


def _chosen_bwd(residuals, g):
    experts, count = residuals
    every = jnp.arange(count, dtype=experts.dtype)
    # slot by slot, so that nothing of [T, k, E] is ever written
    return sum(jnp.where(experts[:, j, None] == every, g[:, j, None], 0)
               for j in range(experts.shape[1])), None


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def route_top_k(x, router_w, k: int, score: str = "softmax",
                select_bias=None, scale: float = 1.0):
    """``(weights [T, k] float32, experts [T, k] int32)``: every one of
    the router's outputs scored in float32 (``score``: a ``softmax``
    over all of them, or a ``sigmoid`` each), the ``k`` largest chosen,
    their scores renormalised to sum to 1 and multiplied by ``scale``.
    ``select_bias [E]`` is added to the scores for the choice alone
    (a load-balancing term that is no parameter): the weights are the
    scores without it."""
    logits = jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = ROUTER_SCORES[score](logits)
    chooses = scores if select_bias is None else (
        scores + select_bias.astype(jnp.float32))
    # the choice carries a name, and the weights are read through it
    # (top-k's own values are these, bit for bit): a rematerialising
    # caller that keeps the name does not choose again
    experts = checkpoint_name(jax.lax.top_k(chooses, k)[1], ROUTE_NAME)
    weights = _chosen(scores, experts)
    # the published sigmoid router's guard against a zero sum; below
    # float32's last bit of any sum of softmax's k largest
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * scale, experts


def _tile(size: int, wanted: int) -> int:
    """The largest power-of-two fraction of ``wanted`` that divides
    ``size`` (the kernel's tiles must divide its operands)."""
    while wanted > 1 and size % wanted:
        wanted //= 2
    return wanted


def _lane_tile(size: int, wanted: int) -> int:
    """A tile of a contracted or output width: :func:`_tile`'s where
    that is at least two lanes' worth (256). Else a multiple of 128
    (the backward products lay each tile over the other width too, so
    "the whole width" will not do): the largest up to ``wanted`` that
    divides ``size`` (2688 = 3 x 896, where the power of two would be
    128 and a grid step hardly any work), or for a width that is no
    multiple of 128 the one that pads it least (1856 = 3 x 640 - 64;
    the kernel masks what lies past the edge)."""
    tile = _tile(size, wanted)
    if tile >= 256:
        return tile
    lanes = range(wanted // 128, 0, -1)
    return 128 * min(lanes, key=lambda t: (-size % (128 * t), -t))


def grouped_matmul(lhs, rhs, group_sizes, kernel: bool | None = None):
    """``out[r] = lhs[r] @ rhs[group of r]`` for rows sorted by group:
    ``lhs [M, K]``, ``rhs [G, K, N]``, ``group_sizes [G]`` int32. Rows
    past ``sum(group_sizes)`` belong to no group and come out zero, and
    cost nothing but their zeroing. It is the Pallas grouped product
    (megablox) wherever Pallas compiles, and ``lax.ragged_dot`` on the
    ``cpu`` backend; ``kernel`` overrides that for a program compiled
    for another platform than the process's own. Differentiable either
    way."""
    if kernel is None:
        kernel = not backend_guard.pallas_interpret()
    group_sizes = group_sizes.astype(jnp.int32)
    if not kernel:
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes, preferred_element_type=jnp.float32
        ).astype(lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k_dim = lhs.shape
    n_dim = rhs.shape[-1]
    tiling = (_tile(m, 256), _lane_tile(k_dim, 1024), _lane_tile(n_dim, 1024))
    # the kernel visits only the tiles that hold a group's rows: what it
    # leaves of the output (and, on the way back, of lhs's gradient) is
    # not written at all, so both sides are selected, never multiplied
    held = (jnp.arange(m, dtype=jnp.int32) < jnp.sum(group_sizes))[:, None]
    out = gmm(jnp.where(held, lhs, 0), rhs, group_sizes, lhs.dtype, tiling)
    return jnp.where(held, out, 0)


# tokens a tile of the row-to-token sum (one MXU pass is 128 deep)
_TOKEN_TILE = 128


class RoutePlan(NamedTuple):
    """Which token slots sit where in a buffer of ``R`` rows sorted by
    expert (``grouped_matmul``'s order), and in which order the
    buffer's rows follow the tokens: rank ``q`` is the ``q``-th held
    slot counted token by token. A row or a rank past the slots held
    has weight 0."""

    token_of_row: jax.Array    # [R] the token a buffer row came from
    weight_of_row: jax.Array   # [R] float32, the router's weight of it
    group_sizes: jax.Array     # [held] rows an expert
    row_of_rank: jax.Array     # [R] a rank's buffer row, R past the held
    token_of_rank: jax.Array   # [R] its token, T past the held
    choice_of_rank: jax.Array  # [R] which of its token's k slots it is
    weight_of_rank: jax.Array  # [R] float32, its weight
    tile_ranks: jax.Array      # [tiles] ranks a tile of _TOKEN_TILE tokens


@functools.partial(jax.jit, static_argnames=("rows", "held"))
def _route_plan(local, weights, rows: int, held: int) -> RoutePlan:
    """The plan for ``local [T, k]`` (a slot's expert among the held,
    ``held`` for an absent one) and a buffer of ``rows`` rows, which is
    right where the buffer holds every held slot. One stable sort of
    the slots by expert, weights riding along, says what each row
    holds; one sort of the buffer's ``rows`` slot numbers brings the
    rows to token order; the experts' rows are counted by compares (a
    ``bincount`` of every slot is a scatter, which costs the chip more
    than sorting them). Nothing is gathered at ``T * k`` and nothing
    has a row's width."""
    tokens, k = local.shape
    every = tokens * k
    weights = jax.lax.stop_gradient(weights).astype(jnp.float32)
    in_held = local < held
    group_sizes = sum(
        jnp.sum(local[:, j, None] == jnp.arange(held, dtype=jnp.int32),
                axis=0, dtype=jnp.int32) for j in range(k))
    # absent experts sort behind the held: the buffer's rows come first
    by_expert, slot_of_row, weight_of_row = (a[:rows] for a in jax.lax.sort(
        (local.reshape(every), jnp.arange(every, dtype=jnp.int32),
         weights.reshape(every)), num_keys=1, is_stable=True))
    is_held = by_expert < held
    weight_of_row = jnp.where(is_held, weight_of_row, 0.0)
    # token order is the order of the slots' numbers
    slot_of_rank, row_of_rank, weight_of_rank = jax.lax.sort(
        (jnp.where(is_held, slot_of_row, every),
         jnp.where(is_held, jnp.arange(rows, dtype=jnp.int32), rows),
         weight_of_row), num_keys=1)
    tiles = -(-tokens // _TOKEN_TILE)
    tile_ranks = jnp.sum(jnp.pad(
        jnp.sum(in_held, axis=1, dtype=jnp.int32),
        (0, tiles * _TOKEN_TILE - tokens)).reshape(tiles, _TOKEN_TILE), axis=1)
    return RoutePlan(slot_of_row // k, weight_of_row, group_sizes,
                     row_of_rank, slot_of_rank // k, slot_of_rank % k,
                     weight_of_rank, tile_ranks)


def _split_bf16(values):
    """``values`` float32 as three bfloat16 terms that add up to it
    exactly (8 + 8 + 8 bits of significand), on a new last axis.
    ``reduce_precision`` and not a cast there and back, which a
    compiler may take for the identity."""
    terms, rest = [], values
    for _ in range(3):
        term = jax.lax.reduce_precision(rest, exponent_bits=8,
                                        mantissa_bits=7)
        terms.append(term.astype(jnp.bfloat16))
        rest = rest - term
    return jnp.stack(terms, axis=-1)


def _sum_ranks_kernel(metadata, marks, values, out, acc, *, terms: int,
                      row_tile: int, lane_tile: int):
    """One grid step of :func:`_sum_rows_by_token`: the ranks of one
    row tile that belong to one tile of tokens, ``marks^T @ values``
    added to the accumulator; the ``terms`` slabs of the accumulator are
    added up as the token tile is left (megablox ``tgmm``'s scheme, the
    terms besides)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        _get_group_size, _get_store_mask)

    step = pl.program_id(1)
    tile_of_step = metadata[1]
    tile = tile_of_step[step]
    last = pl.num_programs(1) - 1
    before = tile_of_step[jnp.where(step > 0, step - 1, 0)]
    after = tile_of_step[jnp.where(step < last, step + 1, last)]

    @pl.when((step == 0) | (before != tile))
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(_get_group_size(grid_id=step, group_metadata=metadata) > 0)
    def _add():
        mine = functools.partial(
            _get_store_mask, grid_id=step, group_metadata=metadata,
            tm=row_tile)
        marked = jnp.where(mine(tn=marks.shape[1]), marks[...], 0)
        rows = jnp.where(mine(tn=lane_tile), values[...], 0)
        acc[...] += jax.lax.dot(marked.swapaxes(0, 1), rows,
                                preferred_element_type=jnp.float32)

    @pl.when((step == last) | (after != tile))
    def _store():
        total = acc[:_TOKEN_TILE]
        for term in range(1, terms):
            total += acc[term * _TOKEN_TILE:(term + 1) * _TOKEN_TILE]
        out[...] = total.astype(out.dtype)


def _sum_rows_by_token(values, plan: RoutePlan, tokens: int, weights=None,
                       dtype=jnp.float32, kernel: bool | None = None):
    """``y[t] = sum of weights[q] * values[q] over the ranks q of token
    t``, added up in float32 and given in ``dtype``: ``values [R, N]``
    in token order (``plan.row_of_rank``'s), ``weights [R]`` float32 or
    None for ones. A token's ranks lie
    together, so the sum is the grouped product's transposed form
    (megablox ``tgmm``'s scheme) over tiles of ``_TOKEN_TILE`` tokens:
    on the other side of the product stands a one-hot of the token
    within its tile that carries the weight, as three bfloat16 terms
    that add up to it exactly, whose three products the kernel adds in
    its accumulator. The MXU adds, nothing is scattered, and the rows
    are read once. On the ``cpu`` backend it is a segment sum;
    ``kernel`` as in :func:`grouped_matmul`."""
    if kernel is None:
        kernel = not backend_guard.pallas_interpret()
    if not kernel:
        values = values.astype(jnp.float32)
        if weights is not None:
            values = weights[:, None] * values
        return jax.ops.segment_sum(
            values, plan.token_of_rank, num_segments=tokens,
            indices_are_sorted=True).astype(dtype)
    return _sum_rows_on_the_mxu(values, plan, weights, tokens=tokens,
                                dtype=jnp.dtype(dtype))


# jitted like megablox's ``gmm``: a model's layers call it at the same
# shapes, and are traced and lowered (the kernel with them) once, not
# once a call: that is seconds of every process's set-up
@functools.partial(jax.jit, static_argnames=("tokens", "dtype"))
def _sum_rows_on_the_mxu(values, plan: RoutePlan, weights, *, tokens: int,
                         dtype):
    """:func:`_sum_rows_by_token` as a Pallas kernel."""
    rows, width = values.shape
    if values.dtype != jnp.bfloat16:
        # a float32 model: the weighted rows themselves as three terms
        # side by side, and the three sums added
        if weights is not None:
            values = weights[:, None] * values
        y = _sum_rows_on_the_mxu(
            _split_bf16(values.astype(jnp.float32)).swapaxes(1, 2).reshape(
                rows, 3 * width), plan, None, tokens=tokens,
            dtype=jnp.dtype(jnp.float32))
        return sum(jnp.split(y, 3, axis=-1)).astype(dtype)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    onehot = (plan.token_of_rank[:, None] % _TOKEN_TILE
              == jnp.arange(_TOKEN_TILE, dtype=jnp.int32))       # [R, tile]
    if weights is None:
        terms, marks = 1, onehot.astype(jnp.bfloat16)
    else:
        terms = 3
        marks = jnp.where(onehot[:, None, :],
                          _split_bf16(weights)[:, :, None], 0
                          ).reshape(rows, terms * _TOKEN_TILE)
    lanes = _round_up(width, 128)
    if lanes != width:
        values = jnp.pad(values, ((0, 0), (0, lanes - width)))
    row_tile, lane_tile = _tile(rows, 256), _lane_tile(lanes, 1024)
    tiles = plan.tile_ranks.shape[0]
    metadata, steps = make_group_metadata(
        group_sizes=plan.tile_ranks, m=rows, tm=row_tile,
        start_group=jnp.int32(0), num_nonzero_groups=tiles,
        visit_empty_groups=True)
    y = pl.pallas_call(
        functools.partial(_sum_ranks_kernel, terms=terms, row_tile=row_tile,
                          lane_tile=lane_tile),
        out_shape=jax.ShapeDtypeStruct((tiles, _TOKEN_TILE, lanes), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((row_tile, terms * _TOKEN_TILE),
                             lambda n, s, meta: (meta[2][s], 0)),
                pl.BlockSpec((row_tile, lane_tile),
                             lambda n, s, meta: (meta[2][s], n)),
            ],
            out_specs=pl.BlockSpec((None, _TOKEN_TILE, lane_tile),
                                   lambda n, s, meta: (meta[1][s], 0, n)),
            grid=(pl.cdiv(lanes, lane_tile), steps),
            scratch_shapes=[pltpu.VMEM(
                (terms * _TOKEN_TILE, lane_tile), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="moe_sum_rows_by_token",
    )(metadata, marks, values)
    return y.reshape(tiles * _TOKEN_TILE, lanes)[:tokens, :width]


@jax.custom_vjp
def _take_slots(x, plan: RoutePlan):
    """``x[plan.token_of_row]``: the rows of the slot buffer, ``[R, D]``.
    The transpose sums each token's rows (:func:`_sum_rows_by_token`),
    never a scatter."""
    return x[plan.token_of_row]


def _take_slots_fwd(x, plan):
    return x[plan.token_of_row], (plan, x.shape[0])


def _take_slots_bwd(residuals, g):
    plan, tokens = residuals
    by_rank = jnp.take(g, plan.row_of_rank, axis=0, mode="clip")
    return _sum_rows_by_token(by_rank, plan, tokens, dtype=g.dtype), None


_take_slots.defvjp(_take_slots_fwd, _take_slots_bwd)


@jax.custom_vjp
def _combine_slots(out, weights, plan: RoutePlan):
    """``y[t] = sum_j weights[t, j] * out[row of slot (t, j)]`` summed
    in float32 (a slot outside the buffer adds nothing), in ``out``'s
    dtype: ``out``'s rows brought to token order and summed a token
    under their weights. ``weights``' gradient is a dot a row, brought
    to ``[T, k]`` by the same sum over a one-hot of the slot's ``j``."""
    by_rank = jnp.take(out, plan.row_of_rank, axis=0, mode="clip")
    return _sum_rows_by_token(by_rank, plan, weights.shape[0],
                              weights=plan.weight_of_rank, dtype=out.dtype)


def _combine_slots_fwd(out, weights, plan):
    return _combine_slots(out, weights, plan), (out, plan, weights.shape)


def _combine_slots_bwd(residuals, g):
    out, plan, (tokens, k) = residuals
    g_rows = g[plan.token_of_row].astype(jnp.float32)
    d_out = (plan.weight_of_row[:, None] * g_rows).astype(out.dtype)
    dot = jnp.sum(g_rows * out.astype(jnp.float32), axis=-1)
    dot = jnp.take(dot, plan.row_of_rank, mode="fill", fill_value=0.0)
    which = (plan.choice_of_rank[:, None]
             == jnp.arange(k, dtype=jnp.int32)).astype(jnp.bfloat16)
    d_weights = _sum_rows_by_token(which, plan, tokens, weights=dot)
    return d_out, d_weights, None


_combine_slots.defvjp(_combine_slots_fwd, _combine_slots_bwd)


def _held_part(x, weights, plan: RoutePlan, w_gate_up, w_down,
               activation=jax.nn.silu, gated: bool = True):
    """The held experts' part for tokens ``x [T, D]`` through the slot
    buffer that ``plan`` lays out, which must hold every slot routed to
    a held expert. An ungated expert's first product is its ``up``
    alone."""
    with jax.named_scope("moe.route"):
        slots = _take_slots(x, plan)
    with jax.named_scope("moe.experts"):
        gate_up = grouped_matmul(slots, w_gate_up, plan.group_sizes)
        if gated:
            gate, up = jnp.split(gate_up, 2, axis=-1)
            hidden = (activation(gate.astype(jnp.float32))
                      * up.astype(jnp.float32)).astype(x.dtype)
        else:
            hidden = activation(gate_up.astype(jnp.float32)).astype(x.dtype)
        out = grouped_matmul(hidden, w_down, plan.group_sizes)
    with jax.named_scope("moe.route"):
        return _combine_slots(out, weights, plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _compute_copy(weights, dtype):
    """``weights`` in the compute ``dtype``, for the branches of
    ``held_experts_ffn``'s ``lax.cond`` to close over. Its gradient
    crosses an ``optimization_barrier`` before it is widened again:
    without one the compiler may move that cast into the branches, and
    a layer's expert gradients then wait for the optimizer in float32
    (in the hybrid LM's epoch program 0.2 GB a layer more, PR 43)."""
    return weights.astype(dtype)


def _compute_copy_fwd(weights, dtype):
    return weights.astype(dtype), jnp.zeros((), weights.dtype)


def _compute_copy_bwd(dtype, like, g):
    return (jax.lax.optimization_barrier(g).astype(like.dtype),)


_compute_copy.defvjp(_compute_copy_fwd, _compute_copy_bwd)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def held_experts_ffn(x, router_w, w_gate_up, w_down, experts_held, k: int,
                     route_from=None, activation: str = "silu",
                     gated: bool = True, **routing):
    """The routed part of a sparse block that the experts held here
    give: ``sum_e p_e * expert_e(x)`` over the token's ``k`` chosen
    experts that lie in ``experts_held``, an expert being gated,
    ``down_e(act(gate_e(x)) * up_e(x))``, or with ``gated`` false
    ungated, ``down_e(act(up_e(x)))``; ``routing`` is the router's
    rule, as :func:`route_top_k` takes it (``score``, ``select_bias``,
    ``scale``). ``activation`` names ``act`` (``silu``: SwiGLU experts;
    ``relu``: ReGLU; ``relu2``: the squared ReLU of an ungated expert).
    The router reads ``route_from [T, D]`` where one is given (a model
    whose router stands before attention scores the layer's input while
    its experts take the normed attention result ``x``), else ``x``.

    ``x [T, D]``; ``router_w [D, E]`` scores all ``E`` experts;
    ``experts_held = (first, stop)`` is the range of them whose weights
    are here, stacked: ``w_gate_up [E_held, D, 2 * I]`` (gate columns
    first; an ungated expert's ``up`` alone, ``[E_held, D, I]``) and
    ``w_down [E_held, I, D]``. What the absent experts would add is
    left out; nothing stands in for it.

    No token is dropped whatever the imbalance. Up to twice what uniform
    routing would send, the slots routed here go through one buffer of
    that size, laid out by a :class:`RoutePlan` that is built from the
    held slots alone, before the choice of path, and carries
    :data:`ROUTE_NAME` as the router's choice does: a caller that
    rematerialises the block and keeps that name orders the slots once.
    Past that size (every token may choose ``k`` held experts) the
    tokens are taken in blocks, each through a buffer that holds all of
    its ``k`` slots a token, under a plan of its own. Both are the same
    program at two sizes, chosen by a ``lax.cond`` on the count, and
    each is rematerialised in the backward pass, so that neither keeps
    the buffer, the experts' products or the hidden rows.

    Returns ``(y [T, D] in x's dtype, counts int32 [5])``; ``counts``
    is (token slots routed to held experts, token slots in all, the
    fullest held expert's tokens, 1 for the call, 1 where the call went
    in blocks)."""
    first, stop = experts_held
    held = stop - first
    tokens = x.shape[0]
    every = tokens * k
    usual = min(_round_up(2 * every * held // router_w.shape[-1], 8), every)
    named = functools.partial(
        jax.tree.map, lambda leaf: checkpoint_name(leaf, ROUTE_NAME))
    with jax.named_scope("moe.route"):
        weights, experts = route_top_k(
            x if route_from is None else route_from, router_w, k, **routing)
        local = jnp.where(
            (experts >= first) & (experts < stop), experts - first, held
        )
        plan = named(_route_plan(local, weights, usual, held))
        routed_here = jnp.sum(plan.group_sizes)
    w_gate_up = _compute_copy(w_gate_up, x.dtype)
    w_down = _compute_copy(w_down, x.dtype)
    part = jax.checkpoint(functools.partial(
        _held_part, gated=gated, activation=EXPERT_ACTIVATIONS[activation]))

    def in_blocks(x, weights, local, plan):
        blocks = max(1, every // max(usual, 1))
        while tokens % blocks:
            blocks -= 1
        size = tokens // blocks
        split = lambda t: t.reshape((blocks, size) + t.shape[1:])  # noqa: E731

        def block(b):
            with jax.named_scope("moe.route"):
                own = _route_plan(b[2], b[1], size * k, held)
            return part(b[0], b[1], own, w_gate_up, w_down)

        y = jax.lax.map(block, (split(x), split(weights), split(local)))
        return y.reshape(tokens, -1)

    if usual == every:
        y = part(x, weights, plan, w_gate_up, w_down)
        blocked = jnp.int32(0)
    else:
        blocked = (routed_here > usual).astype(jnp.int32)
        y = jax.lax.cond(
            blocked,
            in_blocks,
            lambda x, weights, local, plan: part(
                x, weights, plan, w_gate_up, w_down),
            x, weights, local, plan,
        )
    counts = jnp.stack([
        routed_here, jnp.int32(every), jnp.max(plan.group_sizes),
        jnp.int32(1), blocked,
    ]).astype(jnp.int32)
    return y, counts
