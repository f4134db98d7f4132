#!/usr/bin/env python3
"""The sparse block's ``moe.route`` scope in parts, on the chip, at the
four LM cells' shapes (ISSUE 43): the parent's forms (two stable sorts of
every slot, ``k`` gathers of ``[T, D]`` each way) beside the forms the
routing plan uses, and the candidate forms of the row-to-token sum
beside each other.

    chiprun --timeout 1800 -- python3 scripts/moe_route_micro.py \
        [--parent .chipcheck/parent] [--cells qwen3next-fit-seq8k,...]

Writes ``chiprun_out/moe-route-split-PR43.json`` (kept as
``benchmarks/results/moe-route-split-PR43.json``, whose ``first_round``
holds the forms this PR tried first and dropped: a plan by binary
searches, megablox ``tgmm`` with float32 operands, and with three
bfloat16 terms side by side). The parent's forms are copied here from
``ops/moe.py`` at 65ffe8c, because the op itself holds only what was
kept; with ``--parent`` (a ``git archive`` of the parent) one whole
block's gradient under the layer's ``jax.checkpoint`` is timed from
both trees, experts and all. Milliseconds are host-clock
medians of calls that end in ``block_until_ready``; ``--cpu`` rehearses
at a toy size and says nothing about time.
"""

import argparse
import functools
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu.ops import moe
from elephas_tpu.utils import backend_guard

TOKENS = 16384
# k, experts, held, hidden, expert width, gated, score: each file under
# benchmarks/configs/
CELLS = {
    "qwen3next-fit-seq8k": (10, 512, 32, 2048, 512, True, "softmax"),
    "kanana2-fit-seq8k": (6, 128, 16, 2048, 768, True, "sigmoid"),
    "smallthinker-fit-seq16k": (6, 64, 8, 2560, 768, True, "softmax"),
    "nemotron3nano-fit-seq8k": (6, 128, 8, 2688, 1856, False, "sigmoid"),
}
TIMED_CALLS = 7
f32, bf16 = jnp.float32, jnp.bfloat16


def timed(fn, *args):
    """Median milliseconds of ``fn(*args)``, compiled and warmed first;
    None for a form the compiler refuses."""
    fn = jax.jit(fn)
    try:
        for _ in range(2):
            jax.block_until_ready(fn(*args))
    except Exception as error:
        print("refused:", repr(error)[:400], flush=True)
        return None
    times = []
    for _ in range(TIMED_CALLS):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    return round(statistics.median(times), 4)


# -- the parent's forms (ops/moe.py at 65ffe8c) ----------------------------


def parent_order(local, weights, rows, held):
    tokens, k = local.shape
    flat = local.reshape(tokens * k)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    in_buffer = order[:rows]
    token_of_row = in_buffer // k
    position = jnp.minimum(
        jnp.argsort(order).astype(jnp.int32), rows).reshape(tokens, k)
    weight_of_row = jnp.where(
        flat[in_buffer] < held, weights.reshape(tokens * k)[in_buffer], 0.0)
    group_sizes = jnp.bincount(flat, length=held + 1)[:held]
    return position, token_of_row, weight_of_row, group_sizes


def parent_slot_rows(rows, position):
    padded = jnp.concatenate([rows, jnp.zeros_like(rows[:1])], axis=0)
    return [padded[position[:, j]].astype(f32)
            for j in range(position.shape[1])]


def parent_combine(out, weights, position):
    return sum(weights[:, j, None] * rows
               for j, rows in enumerate(parent_slot_rows(out, position)))


def parent_combine_bwd(out, position, token_of_row, weight_of_row, g):
    d_out = (weight_of_row[:, None] * g[token_of_row]).astype(out.dtype)
    d_weights = jnp.stack(
        [jnp.sum(g * rows, axis=-1)
         for rows in parent_slot_rows(out, position)], axis=1)
    return d_out, d_weights


def parent_take_bwd(position, g):
    return sum(parent_slot_rows(g, position)).astype(g.dtype)


# -- candidate forms of the row-to-token sum -------------------------------


def sum_first_and_rest(out, weights, plan):
    """Form (b): one ``[T, D]`` gather for each token's first held slot,
    the other ranks by a scatter-add (what "a second pass that is exact
    at any imbalance" comes to without the MXU)."""
    rows = plan.row_of_rank.shape[0]
    tokens = weights.shape[0]
    token_of_rank = plan.token_of_rank
    held_here = jnp.zeros(tokens, jnp.int32).at[token_of_rank].add(
        1, mode="drop")
    first = jnp.cumsum(held_here) - held_here
    padded = jnp.concatenate([out, jnp.zeros_like(out[:1])], axis=0)
    first_row = jnp.where(held_here > 0, plan.row_of_rank[
        jnp.minimum(first, rows - 1)], rows)
    first_weight = plan.weight_of_rank[jnp.minimum(first, rows - 1)]
    y = first_weight[:, None] * padded[first_row].astype(f32)
    rank = jnp.arange(rows, dtype=jnp.int32)
    is_first = rank == first[jnp.minimum(token_of_rank, tokens - 1)]
    rest = jnp.where(is_first, 0.0, plan.weight_of_rank)[:, None] * jnp.take(
        out, plan.row_of_rank, axis=0, mode="clip").astype(f32)
    return y.at[token_of_rank].add(
        rest, indices_are_sorted=True, mode="drop")


def sum_scatter_sorted(out, plan, tokens):
    """Form (c): the rows in token order, scatter-added."""
    values = plan.weight_of_rank[:, None] * jnp.take(
        out, plan.row_of_rank, axis=0, mode="clip").astype(f32)
    return jnp.zeros((tokens, out.shape[1]), f32).at[
        plan.token_of_rank].add(
            values, indices_are_sorted=True, mode="drop")


def sum_scatter_buffer(out, plan, tokens):
    """Form (c) from the buffer's own order, no gather first."""
    return jnp.zeros((tokens, out.shape[1]), f32).at[plan.token_of_row].add(
        plan.weight_of_row[:, None] * out.astype(f32))


def choice_grad_onehot(d_chosen, experts, num_experts):
    """The transpose of reading the chosen scores, as compares and a sum
    over ``[T, k, E]`` where JAX's own is a scatter-add."""
    hit = experts[:, :, None] == jnp.arange(num_experts, dtype=jnp.int32)
    return jnp.sum(jnp.where(hit, d_chosen[:, :, None], 0.0), axis=1)


# -- one cell ----------------------------------------------------------------


def load_parent(tree):
    spec = importlib.util.spec_from_file_location(
        "parent_moe", os.path.join(tree, "elephas_tpu", "ops", "moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def block_gradient(module, held_range, k, gated, score, act):
    """One block's routed part, forward and backward, under the layer's
    ``jax.checkpoint`` as ``SparseMoeBlock`` puts it."""
    name = getattr(module, "ROUTE_NAME", None)
    policy = (jax.checkpoint_policies.save_only_these_names(name)
              if name else None)

    def loss(x, router, w_in, w_down):
        def forward(x, router, w_in, w_down):
            return module.held_experts_ffn(
                x, router, w_in, w_down, held_range, k, activation=act,
                gated=gated, score=score)[0]
        y = jax.checkpoint(forward, policy=policy)(x, router, w_in, w_down)
        return jnp.sum(jnp.sin(y.astype(f32)))

    return jax.grad(loss, (0, 1, 2, 3))


def run_cell(name, tokens, shape, parent, alternatives):
    k, experts, held, d, width, gated, score = shape
    rows = min(moe._round_up(2 * tokens * k * held // experts, 8),
               tokens * k)
    keys = jax.random.split(jax.random.key(43), 8)
    x = jax.random.normal(keys[0], (tokens, d), f32).astype(bf16)
    router = jax.random.normal(keys[1], (d, experts), f32) * 0.02
    out = jax.random.normal(keys[2], (rows, d), f32).astype(bf16)
    g_y = jax.random.normal(keys[3], (tokens, d), f32).astype(bf16)
    g_rows = jax.random.normal(keys[4], (rows, d), f32).astype(bf16)
    weights, chosen = jax.jit(functools.partial(
        moe.route_top_k, k=k, score=score))(x, router)
    local = jnp.where(chosen < held, chosen, held)
    make_plan = jax.jit(functools.partial(
        moe._route_plan, rows=rows, held=held))
    plan = make_plan(local, weights)
    order = jax.jit(functools.partial(parent_order, rows=rows, held=held))
    position, token_of_row, weight_of_row, group_sizes = order(local, weights)
    routed = int(jnp.sum(group_sizes))
    # as the grouped product leaves them: nothing past the rows held
    unheld = (jnp.arange(rows) >= routed)[:, None]
    out, g_rows = jnp.where(unheld, 0, out), jnp.where(unheld, 0, g_rows)
    result = {
        "tokens": tokens, "k": k, "experts": experts, "held": held,
        "hidden": d, "buffer_rows": rows, "slots": tokens * k,
        "slots_held": routed,
        "tokens_with_a_held_slot": int(jnp.sum(jnp.any(local < held, 1))),
    }
    # the two plans agree
    assert (plan.token_of_row[:routed] == token_of_row[:routed]).all()
    assert (plan.weight_of_row == weight_of_row).all(), "weight_of_row"
    assert (plan.group_sizes == group_sizes).all(), "group_sizes"
    assert (position.reshape(-1)[
        plan.token_of_rank[:routed] * k + plan.choice_of_rank[:routed]]
        == plan.row_of_rank[:routed]).all(), "row_of_rank"

    ms = result["ms"] = {}

    def scores_of(x, router):
        logits = jnp.matmul(x.astype(f32), router,
                            precision=jax.lax.Precision.HIGHEST)
        return moe.ROUTER_SCORES[score](logits)

    ms["router_product_and_score"] = timed(scores_of, x, router)
    scores = jax.jit(scores_of)(x, router)
    d_scores = jax.random.normal(keys[5], scores.shape, f32)
    ms["router_transposes"] = timed(
        lambda x, router, ct: jax.vjp(scores_of, x, router)[1](ct),
        x, router, d_scores)
    ms["top_k"] = timed(lambda s: jax.lax.top_k(s, k), scores)
    d_chosen = jax.random.normal(keys[6], weights.shape, f32)
    ms["choice_transpose_scatter"] = timed(
        lambda s, e, ct: jax.vjp(
            lambda s: jnp.take_along_axis(s, e, axis=-1), s)[1](ct),
        scores, chosen, d_chosen)
    ms["choice_transpose_onehot"] = timed(
        functools.partial(choice_grad_onehot, num_experts=experts),
        d_chosen, chosen)
    ms["parent_order_two_sorts_of_all_slots"] = timed(order, local, weights)
    ms["parent_bincount_of_all_slots"] = timed(
        lambda l: jnp.bincount(l.reshape(-1), length=held + 1), local)
    ms["plan"] = timed(make_plan, local, weights)
    flat = local.reshape(-1)
    ms["plan_sort_of_all_slots_with_weights"] = timed(
        lambda f, w: jax.lax.sort(
            (f, jnp.arange(f.shape[0], dtype=jnp.int32), w.reshape(-1)),
            num_keys=1, is_stable=True), flat, weights)
    ms["plan_sort_of_buffer_rows"] = timed(
        lambda a, b, c: jax.lax.sort((a, b, c), num_keys=1),
        plan.token_of_rank, plan.row_of_rank, plan.weight_of_rank)
    ms["plan_count_by_compares"] = timed(
        lambda l: jnp.sum(l[:, :, None] == jnp.arange(held), axis=(0, 1)),
        local)
    ms["dispatch_gather_R_rows"] = timed(lambda x, t: x[t], x, token_of_row)
    ms["gather_R_rows_to_token_order"] = timed(
        lambda o, r: jnp.take(o, r, axis=0, mode="clip"),
        out, plan.row_of_rank)
    ms["one_gather_T_rows_float32"] = timed(
        lambda o, p: jnp.concatenate([o, jnp.zeros_like(o[:1])])[
            p[:, 0]].astype(f32), out, position)

    ms["parent_combine_k_gathers"] = timed(
        parent_combine, out, weights, position)
    ms["parent_combine_transposes"] = timed(
        parent_combine_bwd, out, position, token_of_row, weight_of_row,
        g_y.astype(f32))
    ms["parent_take_slots_transpose"] = timed(
        parent_take_bwd, position, g_rows)

    combines = {
        "a_kept_rows_summed_on_the_mxu": lambda o, w, p: (
            moe._sum_rows_by_token(
                jnp.take(o, p.row_of_rank, axis=0, mode="clip"), p, tokens,
                weights=p.weight_of_rank)),
        "b_first_slot_gather_and_scatter_rest": sum_first_and_rest,
        "c_scatter_add_token_order": lambda o, w, p: sum_scatter_sorted(
            o, p, tokens),
        "c_scatter_add_buffer_order": lambda o, w, p: sum_scatter_buffer(
            o, p, tokens),
    }
    want = np.asarray(jax.jit(parent_combine)(out, weights, position))
    # float64 on the host for a few tokens
    some = np.arange(0, tokens, max(1, tokens // 512))
    rows64 = np.concatenate(
        [np.asarray(out.astype(f32)), np.zeros((1, d), np.float32)]
    ).astype(np.float64)
    exact = np.einsum("tj,tjd->td", np.asarray(weights)[some].astype(
        np.float64), rows64[np.asarray(position)[some]])
    scale = float(np.abs(exact).max())
    result["combine_error_of_max"] = {
        "parent_k_gathers": float(np.abs(want[some] - exact).max() / scale)}
    for form, fn in combines.items():
        if form[0] != "a" and not alternatives:
            continue  # round 1 has them at this cell's shapes
        ms["combine_" + form] = timed(fn, out, weights, plan)
        if ms["combine_" + form] is not None:
            got = np.asarray(jax.jit(fn)(out, weights, plan))
            result["combine_error_of_max"][form] = float(
                np.abs(got[some] - exact).max() / scale)
    ms["combine_transposes"] = timed(
        lambda o, p, g: moe._combine_slots_bwd((o, p, (tokens, k)), g)[:2],
        out, plan, g_y)
    ms["take_slots_transpose"] = timed(
        lambda p, g: moe._take_slots_bwd((p, tokens), g)[0], plan, g_rows)
    got = jax.jit(lambda p, g: moe._take_slots_bwd((p, tokens), g)[0])(
        plan, g_rows)
    result["take_slots_transpose_equals_parent"] = bool(
        (got == jax.jit(parent_take_bwd)(position, g_rows)).all())

    # one block's gradient, experts and all, from both trees
    n_in = (2 if gated else 1) * width
    w_in = jax.random.normal(keys[7], (held, d, n_in), f32) * 0.02
    w_down = jax.random.normal(keys[5], (held, width, d), f32) * 0.02
    act = "silu" if gated else "relu2"
    sides = {"change": moe}
    if parent:
        sides["parent"] = load_parent(parent)
    grads = {}
    for side, module in sides.items():
        fn = block_gradient(module, (0, held), k, gated, score, act)
        ms["block_gradient_" + side] = timed(fn, x, router, w_in, w_down)
        grads[side] = jax.jit(fn)(x, router, w_in, w_down)
    if parent:
        result["block_gradient_largest_difference_of_max"] = [
            float(jnp.abs(a.astype(f32) - b.astype(f32)).max()
                  / jnp.abs(b.astype(f32)).max())
            for a, b in zip(grads["change"], grads["parent"])]
    return result


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cells", default=",".join(CELLS))
    parser.add_argument("--parent", default=None)
    parser.add_argument("--alternatives", default="qwen3next-fit-seq8k",
                        help="cells that also time the forms not kept")
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--out", default="moe-route-split-PR43.json")
    args = parser.parse_args()
    if args.cpu:
        from elephas_tpu.utils.backend_guard import force_cpu_devices

        force_cpu_devices(1)
    else:
        backend_guard.require_accelerator("tpu")
    tokens = 256 if args.cpu else TOKENS
    device = jax.devices()[0]
    record = {
        "pr": 43, "device": {"platform": device.platform,
                             "kind": device.device_kind},
        "timed_calls": TIMED_CALLS, "cells": {},
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for name in args.cells.split(","):
        record["cells"][name] = run_cell(
            name, tokens, CELLS[name], args.parent,
            alternatives=name in args.alternatives.split(","))
        print(name, json.dumps(record["cells"][name]), flush=True)
        with open(os.path.join(ROOT, "chiprun_out", args.out), "w") as f:
            json.dump(record, f, indent=1)


if __name__ == "__main__":
    main()
