#!/usr/bin/env python3
"""The flash kernels alone under a sliding window, on the chip: what
each pair of blocks costs where the band is short (ISSUE 44: 72 query
heads over 8 key/value heads of 128, two sequences of 8192, a 512-key
band) and where it is long (the SmallThinker cell's 28 over 4, one
sequence of 16384, a 4096-key band), forward, dK/dV and dQ each alone,
on the grid of the band alone (this tree, ISSUE 45) and, with
``--parent``, beside it on the grid over every pair of blocks (a
``git archive`` of the parent commit, whose kernels skip the pairs the
band empties and still step over them).

    git archive --prefix=.chipcheck/parent/ HEAD | tar -x
    chiprun --timeout 1500 -- python3 scripts/flash_band_micro.py \
        --parent .chipcheck/parent

Writes ``chiprun_out/flash-band-micro-PR45.json`` (kept as
``benchmarks/results/flash-band-micro-PR45.json``; PR 44's record of
the full grid alone is ``flash-band-micro-PR44.json``); ``--cpu``
rehearses at a toy size in interpret mode. Milliseconds are host-clock
medians of calls that end in ``block_until_ready``; ``rule`` is what
the op resolves to where no block is named, ``pairs`` the block pairs
a head's full grid visits of all it steps over, and ``grid`` the
band grid's steps and those that compute (``band_grid``).
"""

import importlib
import importlib.util
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
KERNELS = ("fwd", "dkv", "dq")


def load_parent(tree):
    """The parent's op as a module of its own (its package-level
    imports resolve to this tree's, which the op does not differ in)."""
    spec = importlib.util.spec_from_file_location(
        "parent_flash_attention",
        os.path.join(tree, "elephas_tpu", "ops", "flash_attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


PARENT = (load_parent(sys.argv[sys.argv.index("--parent") + 1])
          if "--parent" in sys.argv else None)
# the grids timed: the band's (this tree) and the one over every pair
GRIDS = {"band": fa, **({"full": PARENT} if PARENT else {})}
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


def three_kernels(op, scale, bq, bk, window):
    """The op's kernels at named blocks (None: the rule's), each a
    jitted call of its own: forward -> (out, lse); dK/dV; dQ."""
    fwd = jax.jit(lambda q, k, v: op._flash_forward(
        q, k, v, scale, True, bq, bk, INTERPRET, window))
    bwd = lambda pick: jax.jit(  # noqa: E731
        lambda q, k, v, out, lse, g: op._flash_backward_kernels(
            scale, True, bq, bk, INTERPRET, (q, k, v, out, lse), g,
            window)[pick])
    return {"fwd": fwd, "dkv": bwd(slice(1, 3)), "dq": bwd(slice(0, 1))}


def same(got, want) -> bool:
    return all(bool((a == b).all()) for a, b in zip(got, want))


def measure(name, qs, ks, window, blocks, seed) -> dict:
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], qs, jnp.bfloat16)
    k = jax.random.normal(keys[1], ks, jnp.bfloat16)
    v = jax.random.normal(keys[2], ks, jnp.bfloat16)
    g = jax.random.normal(keys[3], qs, jnp.bfloat16)
    scale = qs[-1] ** -0.5
    readings = {}
    out, lse = three_kernels(fa, scale, blocks[0], blocks[0], window)["fwd"](
        q, k, v)
    for bq, bk in itertools.product(blocks, blocks):
        row = {"pairs": pairs(qs[1], bq, bk, window),
               "grid": {kernel: fa.band_grid(
                   kernel, qs[1], ks[1], bq, bk, window)
                   for kernel in KERNELS}}
        results = {}
        for grid, op in GRIDS.items():
            fns = three_kernels(op, scale, bq, bk, window)
            row[grid] = {}
            for kernel in KERNELS:
                args = (q, k, v) if kernel == "fwd" else (
                    q, k, v, out, lse, g)
                row[grid][kernel], results[grid, kernel] = timed(
                    fns[kernel], *args)
        got = results["band", "fwd"]
        if got is not None:
            diff = got[0].astype(jnp.float32) - out.astype(jnp.float32)
            row["band"]["fwd"]["rel_l2_to_first"] = float(
                jnp.linalg.norm(diff)
                / jnp.linalg.norm(out.astype(jnp.float32)))
        if PARENT:
            # the same pairs in the same order: bit for bit
            row["band_equals_full"] = {
                kernel: same(results["band", kernel], results["full", kernel])
                for kernel in KERNELS
                if results["band", kernel] is not None
                and results["full", kernel] is not None}
        readings[f"({bq}, {bk})"] = row
        print(name, (bq, bk), json.dumps(row), flush=True)
    # what the op takes where no block is named
    readings["rule"] = {"blocks": {
        kernel: fa._resolve_blocks(
            None, None, qs[1], ks[1], qs[2], ks[2], 2, kernel, window)
        for kernel in ("fwd", "bwd")}}
    for grid, op in GRIDS.items():
        rule = readings["rule"][grid] = {}
        rule["fwd"], _ = timed(
            three_kernels(op, scale, None, None, window)["fwd"], q, k, v)
        fn = jax.jit(jax.grad(lambda q, k, v, op=op: jnp.sum(
            op._flash_attention_bhsd(
                q, k, v, scale, True, None, None, INTERPRET, window)
            .astype(jnp.float32) * g.astype(jnp.float32)), (0, 1, 2)))
        rule["fwd_and_bwd"], _ = timed(fn, q, k, v)
    print(name, "rule", json.dumps(readings["rule"]), flush=True)
    return readings


def main() -> int:
    device = ({"platform": "cpu"} if CPU
              else backend_guard.require_accelerator("tpu"))
    seed = 4545000001
    result = {
        "what": (
            "PR 45, my chip run (one TPU v5 lite chip): the flash kernels "
            "alone under a sliding window, bfloat16, causal; milliseconds "
            f"a call, the median of {TIMED_CALLS} timed calls after one "
            "that compiles; fwd, dkv, dq: the op's three kernels, each "
            "alone, at the named (block_q, block_k); band: on the grid "
            "of the band alone (this tree); full: the parent's kernels "
            "in the same process, on the grid over every pair of blocks; "
            "grid: [steps, computing] of a head and sequence on the band "
            "grid; pairs: block pairs of one head the band leaves "
            "something of, of the full grid's; band_equals_full: the two "
            "grids' results equal bit for bit; rule: what the op takes "
            "where no block is named, and the gradient through the "
            "public op at those blocks (forward once, dK/dV, dQ)."),
        "device": device, "seed": seed, "parent": bool(PARENT), "cases": {},
    }
    for name, (qs, ks, window, blocks) in CASES.items():
        result["cases"][name] = {
            "q": qs, "k_v": ks, "window": window,
            "readings": measure(name, qs, ks, window, blocks, seed)}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash-band-micro-PR45.json", "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
