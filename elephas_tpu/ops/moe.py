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
that the experts it HOLDS give, and no token is ever dropped: token
slots are sorted by expert into a buffer sized for the worst case and
go through one grouped matrix product (:func:`grouped_matmul`) whose
work follows the rows that are really there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


ROUTER_SCORES = {"softmax": jax.nn.softmax, "sigmoid": jax.nn.sigmoid}
# a held expert's activation: on the gate of a gated expert (SwiGLU's,
# ReGLU's), on the one product of an ungated one (squared ReLU)
EXPERT_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
                      "relu2": lambda t: jnp.square(jax.nn.relu(t))}


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
    if select_bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(
            scores + select_bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
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


def _slot_rows(rows, position):
    """``[rows[position[:, j]] for j]`` as float32, with a zero row
    where ``position == len(rows)`` (a slot outside the buffer):
    ``position [T, k]`` -> ``k`` arrays ``[T, D]``."""
    padded = jnp.concatenate([rows, jnp.zeros_like(rows[:1])], axis=0)
    return [padded[position[:, j]].astype(jnp.float32)
            for j in range(position.shape[1])]


@jax.custom_vjp
def _take_slots(x, token_of_row, position):
    """``x[token_of_row]``: the rows of the slot buffer, ``[R, D]``.
    ``position [T, k]`` says where each token slot sits in the buffer
    (``R`` where it does not), so that the transpose is ``k`` gathers
    and a sum, never a scatter."""
    return x[token_of_row]


def _take_slots_fwd(x, token_of_row, position):
    return x[token_of_row], position


def _take_slots_bwd(position, g):
    return sum(_slot_rows(g, position)).astype(g.dtype), None, None


_take_slots.defvjp(_take_slots_fwd, _take_slots_bwd)


@jax.custom_vjp
def _combine_slots(out, weights, position, token_of_row, weight_of_row):
    """``y[t] = sum_j weights[t, j] * out[position[t, j]]`` in float32
    (a slot outside the buffer adds nothing); gathers both ways."""
    return sum(weights[:, j, None] * rows
               for j, rows in enumerate(_slot_rows(out, position)))


def _combine_slots_fwd(out, weights, position, token_of_row, weight_of_row):
    y = _combine_slots(out, weights, position, token_of_row, weight_of_row)
    return y, (out, position, token_of_row, weight_of_row)


def _combine_slots_bwd(residuals, g):
    out, position, token_of_row, weight_of_row = residuals
    d_out = (weight_of_row[:, None] * g[token_of_row]).astype(out.dtype)
    d_weights = jnp.stack(
        [jnp.sum(g * rows, axis=-1) for rows in _slot_rows(out, position)],
        axis=1)
    return d_out, d_weights, None, None, None


_combine_slots.defvjp(_combine_slots_fwd, _combine_slots_bwd)


def _held_part(x, weights, local, w_gate_up, w_down, rows: int, held: int,
               activation=jax.nn.silu, gated: bool = True):
    """The held experts' part for tokens ``x [T, D]`` through a slot
    buffer of ``rows`` rows, which must hold every slot routed to a
    held expert (``local < held``; ``local [T, k]`` is the expert's
    index among the held, ``held`` for an absent one). An ungated
    expert's first product is its ``up`` alone."""
    tokens, k = local.shape
    with jax.named_scope("moe.route"):
        flat = local.reshape(tokens * k)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        # absent experts sort behind the held: the buffer's rows are the
        # first of the order
        in_buffer = order[:rows]
        token_of_row = in_buffer // k
        position = jnp.minimum(
            jnp.argsort(order).astype(jnp.int32), rows
        ).reshape(tokens, k)
        weight_of_row = jnp.where(
            flat[in_buffer] < held, weights.reshape(tokens * k)[in_buffer],
            0.0,
        )
        group_sizes = jnp.bincount(flat, length=held + 1)[:held]
        slots = _take_slots(x, token_of_row, position)
    with jax.named_scope("moe.experts"):
        gate_up = grouped_matmul(slots, w_gate_up, group_sizes)
        if gated:
            gate, up = jnp.split(gate_up, 2, axis=-1)
            hidden = (activation(gate.astype(jnp.float32))
                      * up.astype(jnp.float32)).astype(x.dtype)
        else:
            hidden = activation(gate_up.astype(jnp.float32)).astype(x.dtype)
        out = grouped_matmul(hidden, w_down, group_sizes)
    with jax.named_scope("moe.route"):
        y = _combine_slots(out, weights, position, token_of_row,
                           weight_of_row)
    return y.astype(x.dtype)


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

    No token is dropped whatever the imbalance. The slots routed here
    are counted first. Up to twice what uniform routing would send,
    they go through one buffer of that size; past it (every token may
    choose ``k`` held experts) the tokens are taken in blocks, each
    through a buffer that holds all of its ``k`` slots a token. Both
    are the same program at two sizes, chosen by a ``lax.cond`` on the
    count, and each is rematerialised in the backward pass, so that
    neither keeps more than its inputs.

    Returns ``(y [T, D] in x's dtype, counts int32 [3])``; ``counts``
    is (token slots routed to held experts, token slots in all, the
    fullest held expert's tokens)."""
    first, stop = experts_held
    held = stop - first
    tokens = x.shape[0]
    with jax.named_scope("moe.route"):
        weights, experts = route_top_k(
            x if route_from is None else route_from, router_w, k, **routing)
        local = jnp.where(
            (experts >= first) & (experts < stop), experts - first, held
        )
        group_sizes = jnp.bincount(
            local.reshape(-1), length=held + 1)[:held].astype(jnp.int32)
        routed_here = jnp.sum(group_sizes)
    w_gate_up, w_down = w_gate_up.astype(x.dtype), w_down.astype(x.dtype)
    part = jax.checkpoint(
        functools.partial(_held_part, held=held, gated=gated,
                          activation=EXPERT_ACTIVATIONS[activation]),
        static_argnums=(5,)
    )
    every = tokens * k
    usual = _round_up(2 * every * held // router_w.shape[-1], 8)

    def in_blocks(x, weights, local):
        blocks = max(1, every // max(usual, 1))
        while tokens % blocks:
            blocks -= 1
        size = tokens // blocks
        split = lambda t: t.reshape((blocks, size) + t.shape[1:])  # noqa: E731
        y = jax.lax.map(
            lambda b: part(b[0], b[1], b[2], w_gate_up, w_down, size * k),
            (split(x), split(weights), split(local)),
        )
        return y.reshape(tokens, -1)

    if usual >= every:
        y = part(x, weights, local, w_gate_up, w_down, every)
    else:
        y = jax.lax.cond(
            routed_here <= usual,
            lambda *a: part(*a, w_gate_up, w_down, usual),
            in_blocks, x, weights, local,
        )
    counts = jnp.stack([
        routed_here, jnp.int32(every), jnp.max(group_sizes),
    ]).astype(jnp.int32)
    return y, counts
