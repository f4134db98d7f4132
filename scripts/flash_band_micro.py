#!/usr/bin/env python3
"""The flash kernels alone under a sliding window, on the chip: what
each pair of blocks costs where the band is short (ISSUE 44: 72 query
heads over 8 key/value heads of 128, two sequences of 8192, a 512-key
band) and where it is long (the SmallThinker cell's 28 over 4, one
sequence of 16384, a 4096-key band), forward, dK/dV and dQ each alone.

    chiprun --timeout 1500 -- python3 scripts/flash_band_micro.py

Writes ``chiprun_out/flash-band-micro-PR44.json`` (kept as
``benchmarks/results/flash-band-micro-PR44.json``); ``--cpu`` rehearses
at a toy size in interpret mode. Milliseconds are host-clock medians
of calls that end in ``block_until_ready``; ``rule`` is what the op
resolves to where no block is named, and ``pairs`` the block pairs a
head's grid visits of all it steps over.
"""

import importlib
import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CPU = "--cpu" in sys.argv
if CPU:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp

from elephas_tpu.utils import backend_guard

fa = importlib.import_module("elephas_tpu.ops.flash_attention")
BLOCKS = (128, 256, 512, 1024)
CASES = {
    # [heads x sequences, positions, width] of q and of k and v; the
    # window; the blocks tried
    "laguna-fit-seq8k sliding": (
        (144, 8192, 128), (16, 8192, 128), 512, BLOCKS),
    "smallthinker-fit-seq16k sliding": (
        (28, 16384, 128), (4, 16384, 128), 4096, (512, 1024)),
}
if CPU:
    CASES = {"toy": ((6, 512, 128), (2, 512, 128), 128, (128, 256))}
TIMED_CALLS = 5
INTERPRET = backend_guard.pallas_interpret()


def timed(fn, *args):
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


def pairs(s, bq, bk, window) -> dict:
    seen = sum(
        1 for i in range(s // bq) for j in range(s // bk)
        if j * bk < (i + 1) * bq and (j + 1) * bk + window - 1 > i * bq)
    return {"seen": seen, "grid": (s // bq) * (s // bk),
            "scores_seen": seen * bq * bk}


def measure(name, qs, ks, window, blocks, seed) -> dict:
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], qs, jnp.bfloat16)
    k = jax.random.normal(keys[1], ks, jnp.bfloat16)
    v = jax.random.normal(keys[2], ks, jnp.bfloat16)
    g = jax.random.normal(keys[3], qs, jnp.bfloat16)
    scale = qs[-1] ** -0.5
    readings = {}
    out, lse = jax.jit(lambda q, k, v: fa._flash_forward(
        q, k, v, scale, True, blocks[0], blocks[0], INTERPRET, window))(
        q, k, v)
    for bq, bk in itertools.product(blocks, blocks):
        row = {"pairs": pairs(qs[1], bq, bk, window)}
        fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: fa._flash_forward(
            q, k, v, scale, True, bq, bk, INTERPRET, window))
        row["fwd"], got = timed(fn, q, k, v)
        if got is not None:
            diff = got[0].astype(jnp.float32) - out.astype(jnp.float32)
            row["fwd"]["rel_l2_to_first"] = float(
                jnp.linalg.norm(diff)
                / jnp.linalg.norm(out.astype(jnp.float32)))
        for kernel, pick in (("dkv", slice(1, 3)), ("dq", slice(0, 1))):
            fn = jax.jit(lambda q, k, v, out, lse, g, bq=bq, bk=bk,
                         pick=pick: fa._flash_backward_kernels(
                scale, True, bq, bk, INTERPRET, (q, k, v, out, lse), g,
                window)[pick])
            row[kernel], _ = timed(fn, q, k, v, out, lse, g)
        readings[f"({bq}, {bk})"] = row
        print(name, (bq, bk), json.dumps(row), flush=True)
    # what the op takes where no block is named
    readings["rule"] = {"blocks": {
        kernel: fa._resolve_blocks(
            None, None, qs[1], ks[1], qs[2], ks[2], 2, kernel, window)
        for kernel in ("fwd", "bwd")}}
    fn = jax.jit(lambda q, k, v: fa._flash_forward(
        q, k, v, scale, True, None, None, INTERPRET, window))
    readings["rule"]["fwd"], _ = timed(fn, q, k, v)
    fn = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        fa._flash_attention_bhsd(
            q, k, v, scale, True, None, None, INTERPRET, window)
        .astype(jnp.float32) * g.astype(jnp.float32)), (0, 1, 2)))
    readings["rule"]["fwd_and_bwd"], _ = timed(fn, q, k, v)
    print(name, "rule", json.dumps(readings["rule"]), flush=True)
    return readings


def main() -> int:
    device = ({"platform": "cpu"} if CPU
              else backend_guard.require_accelerator("tpu"))
    seed = 4444000001
    result = {
        "what": (
            "PR 44, my chip run (one TPU v5 lite chip): the flash kernels "
            "alone under a sliding window, bfloat16, causal; milliseconds "
            f"a call, the median of {TIMED_CALLS} timed calls after one "
            "that compiles; fwd, dkv, dq: the op's three kernels, each "
            "alone, at the named (block_q, block_k); pairs: block pairs "
            "of one head the band leaves something of, of the grid's; "
            "rule: what the op takes where no block is named, and the "
            "gradient through the public op at those blocks (forward "
            "once, dK/dV, dQ)."),
        "device": device, "seed": seed, "cases": {},
    }
    for name, (qs, ks, window, blocks) in CASES.items():
        result["cases"][name] = {
            "q": qs, "k_v": ks, "window": window,
            "readings": measure(name, qs, ks, window, blocks, seed)}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash-band-micro-PR44.json", "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
