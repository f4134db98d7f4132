#!/usr/bin/env python3
"""What a scope of the epoch program is made of, from the traces that
traced runs of a cell left in their checkouts: device milliseconds a
step of every operation under the scope, by the pass it belongs to
(forward, recomputation, backward) and by what it does.

    python3 scripts/attn_proj_split.py --cell laguna-fit-seq8k \
        --side parent=.chipcheck/parent --side change=.chipcheck/change \
        --out chiprun_out/PR46/attn-proj-split.json [--scope attn.proj]

Run it in the chip call that made the traces (``scripts/run_pairs.py``
with ``--trace 1``: a checkout's ``.bench_work/trace/<cell>/`` holds the
last traced run of that cell), or anywhere the ``.xplane.pb`` files
are: it touches no device. An operation's pass and kind are read from
its ``tf_op`` path (``benchmarks/harness/xplane_ops.py``), which ends in
the jax primitive the instruction or the root of its fusion came from;
a fusion that holds a product and the elementwise work around it counts
under its root, so the kinds are a reading of XLA's fusions, not of the
source. The rows come back whole beside the sums, so that another
reading needs no chip.
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the last primitive of a path, by what the layer does with it
KINDS = (
    ("product", ("dot_general",)),
    ("cast", ("convert_element_type",)),
    ("rotation", ("mul", "add", "sub", "neg", "concatenate", "slice",
                  "select_n", "pad", "dynamic_slice",
                  "dynamic_update_slice")),
    ("layout", ("transpose", "reshape", "copy", "broadcast_in_dim",
                "squeeze", "reduce_precision")),
    ("sum", ("reduce_sum", "add_any")),
)


def pass_of(path: str) -> str:
    # a recomputed operation stands under the backward pass's
    # ``transpose(jvp(...))`` too: ask for it first
    if "rematted_computation" in path:
        return "recomputation"
    if "transpose(" in path:
        return "backward"
    return "forward"


def kind_of(path: str) -> str:
    last = re.sub(r"[\[:].*", "", path.rstrip("/").rsplit("/", 1)[-1])
    for kind, primitives in KINDS:
        if last in primitives:
            return kind
    return "other:" + last


def result_shape(text: str) -> str:
    """``bf16[16384,9216]`` of ``%fusion.12 = bf16[16384,9216]{1,0}
    fusion(...)``."""
    found = re.search(r"=\s*\(?([a-z0-9]+\[[0-9,]*\])", text)
    return found.group(1) if found else ""


def split(tree: str, cell: str, scope: str, steps: float) -> dict:
    """The scope's operations in the window of ``tree``'s last traced
    run of ``cell``, a row for each path, opcode and result shape, the
    dearest first, and their sums by pass and kind."""
    from benchmarks.harness import program_spans, xplane, xplane_ops

    path = xplane.find_xplane(
        os.path.join(tree, ".bench_work", "trace", cell))
    window = program_spans.mirrored_spans(path)["window"]
    found: dict = {}
    for start, end, text, op_path in xplane_ops.device_ops(path):
        if xplane.opcode(text) in xplane.CONTAINERS:
            continue
        if window is not None:
            start, end = max(start, window[0]), min(end, window[1])
        if end <= start or scope not in op_path:
            continue
        key = (op_path, xplane.opcode(text), result_shape(text))
        ms, count = found.get(key, (0.0, 0))
        found[key] = (ms + (end - start) / 1e6, count + 1)
    rows, sums = [], {}
    for (op_path, opcode, result), (ms, count) in sorted(
            found.items(), key=lambda kv: -kv[1][0]):
        row = {"path": op_path, "opcode": opcode, "result": result,
               "pass": pass_of(op_path), "kind": kind_of(op_path),
               "ms_per_step": round(ms / steps, 4),
               "per_step": round(count / steps, 2)}
        rows.append(row)
        by = sums.setdefault(row["pass"], {})
        by[row["kind"]] = round(
            by.get(row["kind"], 0.0) + row["ms_per_step"], 3)
    return {"xplane": os.path.relpath(path, tree), "window_ns": window,
            "ms_per_step": round(sum(r["ms_per_step"] for r in rows), 3),
            "ms_per_step_by_pass_and_kind": sums, "rows": rows}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--scope", default="attn.proj")
    ap.add_argument("--side", action="append", required=True,
                    metavar="SIDE=DIR")
    ap.add_argument("--steps", type=float, required=True,
                    help="training steps in the traced window (the "
                         "[scopes] line's steps=)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    record = {"cell": args.cell, "scope": args.scope, "steps": args.steps,
              "sides": {}}
    for side in args.side:
        name, tree = side.split("=", 1)
        found = split(
            os.path.join(ROOT, tree), args.cell, args.scope, args.steps)
        record["sides"][name] = found
        print(json.dumps({"side": name, "ms_per_step": found["ms_per_step"],
                          "by_pass_and_kind":
                              found["ms_per_step_by_pass_and_kind"]}),
              flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
