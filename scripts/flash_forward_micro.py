#!/usr/bin/env python3
"""The flash kernels alone on the two LM cells' attention tensors, on the
chip: what blocks, clamped key/value maps and the cast of ``p`` are each
worth (ISSUE 36, step 0).

    chiprun --timeout 1800 -- python3 scripts/flash_forward_micro.py

Writes ``chiprun_out/flash-forward-micro-PR36.json`` (kept as
``benchmarks/results/flash-forward-micro-PR36.json``). The forward
variants are built here, from a copy of the op's kernel with two flags,
because the op itself holds only what was kept (``c1p0``); ``op_fwd``
times the op's own ``_flash_forward`` and must agree with that variant
bit for bit.
The backward kernels are the op's, dK/dV and dQ timed apart (a jitted
function that returns one of them keeps only that kernel). Needs the
TPU; milliseconds are host-clock medians of calls that end in
``block_until_ready``.
"""

import functools
import importlib
import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elephas_tpu.utils import backend_guard

fa = importlib.import_module("elephas_tpu.ops.flash_attention")
NEG_INF = fa.NEG_INF
BLOCKS = (256, 512, 1024)
CELLS = {
    # q, k, v of one layer and step: [heads x sequences, positions, width]
    "kanana2-fit-seq8k": ((64, 8192, 192), (64, 8192, 192), (64, 8192, 128)),
    "qwen3next-fit-seq8k": ((32, 8192, 256), (4, 8192, 256), (4, 8192, 256)),
}
TIMED_CALLS = 5
INTERPRET = backend_guard.pallas_interpret()  # on the CPU alone: a rehearsal
REFERENCE_HEADS = 2


def fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
               *, scale, block_q, block_k, cast):
    """``fa._fwd_kernel`` (causal) with the cast of ``p`` as a flag."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_k < (i + 1) * block_q)
    def _accumulate():
        s = jax.lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(fa._causal_mask(i, j, block_q, block_k), s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new <= NEG_INF * 0.5, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[:]
        if cast:
            p = p.astype(v.dtype)
        else:
            v = v.astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        safe_l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[:] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, :] = (m_ref[:] + jnp.log(safe_l))[:, 0]


def fwd_variant(q, k, v, scale, block_q, block_k, clamp, cast):
    bh, s_q, d = q.shape
    s_k, dv = k.shape[1], v.shape[-1]
    group = bh // k.shape[0]
    nq = s_q // block_q
    _, last_j = fa._visible_maps(bool(clamp), block_q, block_k, nq)
    kv_side = lambda b, i, j: (b // group, last_j(i, j), 0)  # noqa: E731
    out, lse = pl.pallas_call(
        functools.partial(fwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, cast=bool(cast)),
        grid=(bh, nq, s_k // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), kv_side),
            pl.BlockSpec((None, block_k, dv), kv_side),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        cost_estimate=fa._cost(bh, s_q, s_k, d, q.dtype.itemsize, dv),
        compiler_params=fa._MOSAIC,
        interpret=INTERPRET,
    )(q, k, v)
    return out, lse[:, 0, :]


def timed(fn, *args) -> dict:
    """Compile and warm up with one call, then the median of the timed
    ones. A refusal (Mosaic, VMEM) is the reading."""
    try:
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn(*args))
        first = time.perf_counter() - t0
        laps = []
        for _ in range(TIMED_CALLS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            laps.append((time.perf_counter() - t0) * 1e3)
    except Exception as e:  # noqa: BLE001 - the compiler's refusal is recorded
        return {"refused": f"{type(e).__name__}: {str(e)[:300]}"}, None
    return {"ms": statistics.median(laps), "min_ms": min(laps),
            "first_call_s": round(first, 3)}, got


def error(got, want) -> dict:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    diff = got - want
    return {
        "rel_l2": float(jnp.linalg.norm(diff) / jnp.linalg.norm(want)),
        "max_over_max": float(jnp.max(jnp.abs(diff)) / jnp.max(jnp.abs(want))),
        "finite": bool(jnp.all(jnp.isfinite(got))),
    }


def measure(cell, shapes, seed) -> dict:
    qs, ks, vs = shapes
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], qs, jnp.bfloat16)
    k = jax.random.normal(keys[1], ks, jnp.bfloat16)
    v = jax.random.normal(keys[2], vs, jnp.bfloat16)
    g = jax.random.normal(keys[3], qs[:2] + vs[-1:], jnp.bfloat16)
    scale = qs[-1] ** -0.5
    group = qs[0] // ks[0]
    readings = {}

    # the float32 answer for the first heads, every product at highest
    heads = REFERENCE_HEADS
    kv_heads = -(-heads // group)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(
            fa.attention_reference, causal=True, scale=scale))(
            f32(q[:heads]),
            jnp.repeat(f32(k[:kv_heads]), group, axis=0)[:heads],
            jnp.repeat(f32(v[:kv_heads]), group, axis=0)[:heads])
    want = jax.block_until_ready(want)
    readings["round_err"] = error(want.astype(jnp.bfloat16), want)

    residuals = None
    for bq, bk in itertools.product(BLOCKS, BLOCKS):
        outs = {}
        for clamp, cast in itertools.product((0, 1), (0, 1)):
            name = f"fwd({bq}, {bk})c{clamp}p{cast}"
            fn = jax.jit(functools.partial(
                fwd_variant, scale=scale, block_q=bq, block_k=bk,
                clamp=clamp, cast=cast))
            row, got = timed(fn, q, k, v)
            if got is not None:
                row["err"] = error(got[0][:heads], want)
                outs[clamp, cast] = got
                if residuals is None and (clamp, cast) == (1, 1):
                    residuals = got
            readings[name] = row
            print(cell, name, row, flush=True)
        if len(outs) == 4:
            same = lambda a, b: all(  # noqa: E731
                bool(jnp.array_equal(x, y)) for x, y in zip(outs[a], outs[b]))
            readings[f"fwd({bq}, {bk}) outputs"] = {
                "p0_equals_p1": same((1, 0), (1, 1)),
                "c0_equals_c1": same((0, 0), (1, 0))}
        del outs
        # the op itself at these blocks: the committed kernel
        fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: fa._flash_forward(
            q, k, v, scale, True, bq, bk, INTERPRET))
        row, got = timed(fn, q, k, v)
        if got is not None:
            twin = fwd_variant(q, k, v, scale, bq, bk, 1, 0)
            row["equals_c1p0"] = bool(
                jnp.array_equal(got[0], twin[0])
                and jnp.array_equal(got[1], twin[1]))
        readings[f"op_fwd({bq}, {bk})"] = row
        print(cell, f"op_fwd({bq}, {bk})", row, flush=True)

    # the backward kernels at the same blocks, each alone
    out, lse = residuals
    for bq, bk in itertools.product(BLOCKS, BLOCKS):
        for name, pick in (("dkv", slice(1, 3)), ("dq", slice(0, 1))):
            fn = jax.jit(lambda q, k, v, out, lse, g, bq=bq, bk=bk,
                         pick=pick: fa._flash_backward_kernels(
                scale, True, bq, bk, INTERPRET, (q, k, v, out, lse), g)[pick])
            row, _ = timed(fn, q, k, v, out, lse, g)
            readings[f"{name}({bq}, {bk})"] = row
            print(cell, f"{name}({bq}, {bk})", row, flush=True)

    # what the op takes where no block is named: forward, then the
    # gradient through the public op (forward once more, dK/dV, dQ)
    fn = jax.jit(lambda q, k, v: fa._flash_forward(
        q, k, v, scale, True, None, None, INTERPRET))
    readings["op_fwd(rule)"], _ = timed(fn, q, k, v)
    fn = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        fa._flash_attention_bhsd(q, k, v, scale, True, None, None, INTERPRET)
        .astype(jnp.float32) * g.astype(jnp.float32)), (0, 1, 2)))
    readings["op_fwd_and_bwd(rule)"], _ = timed(fn, q, k, v)
    readings["rule_blocks"] = {
        kernel: fa._resolve_blocks(
            None, None, qs[1], ks[1], qs[2], vs[2], 2, kernel)
        for kernel in ("fwd", "bwd")}
    print(cell, {n: readings[n] for n in (
        "op_fwd(rule)", "op_fwd_and_bwd(rule)", "rule_blocks")}, flush=True)
    return readings


def main() -> int:
    device = backend_guard.require_accelerator("tpu")
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 3636000001
    result = {
        "what": (
            "PR 36, my chip run (one TPU v5 lite chip): the flash kernels "
            "alone on the two LM cells' attention tensors, bfloat16, "
            "causal, 8192 positions; milliseconds a call, the median of "
            f"{TIMED_CALLS} timed calls after one that compiles. "
            "fwd(bq, bk)cXpY: a copy of the forward kernel at those "
            "blocks; c1 = key/value index maps that stay on the last "
            "block the causal mask leaves something of, c0 = maps that "
            "follow the grid; p1 = p cast to v's dtype for p x v "
            "(float32 sum), p0 = v cast to float32. op_fwd: the op's own "
            "_flash_forward (equals_c1p0: bit for bit the c1p0 variant, the "
            "one kept); outputs: whether the variants' out and lse are "
            "equal bit for bit. "
            "dkv, dq: the op's backward kernels, each alone, at those "
            "blocks. err: against float32 attention at highest "
            f"precision on the first {REFERENCE_HEADS} heads; round_err: "
            "that answer rounded to bfloat16, the floor of any bfloat16 "
            "output. rule: no block named."),
        "device": device, "seed": seed, "vmem_limit_bytes": fa._VMEM_LIMIT,
        "cells": {},
    }
    for cell, shapes in CELLS.items():
        result["cells"][cell] = {
            "q_k_v": shapes, "readings": measure(cell, shapes, seed)}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash-forward-micro-PR36.json", "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
