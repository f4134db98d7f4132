#!/usr/bin/env python3
"""What differs between the signatures that the epoch function's
dispatch cache holds, and where a dispatch that met a new one spent its
time (PR 40; chip or CPU).

    chiprun --timeout 1500 -- python3 scripts/explain_signatures.py \
        --workload resnet50-fit-staged --seed 4040000001 --seconds 40 --trace 0

Runs ``benchmarks/run.py`` with these arguments in this process, with
``jax_explain_cache_misses`` on and ``MeshRunner._dispatch_epoch``
wrapped: before each call it notes every argument leaf as the dispatch
cache sees it (its type, sharding, whether it is committed, weak type,
dtype, shape) and runs the call under ``cProfile``. After the run it
prints one ``[signature]`` line a dispatch that differs from the one
before it (which leaves, and what of them), and for each dispatch whose
span says ``new_signature`` the profile's costliest functions by
cumulative time, with the seconds that Python's garbage collector ran
inside the dispatch (``gc.callbacks``; the profile charges them to
whatever function was running). The profiler slows the dispatch it
watches, so the benchmark's own lines of this run are not measurements.
"""

import cProfile
import gc
import io
import json
import os
import pstats
import runpy
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def describe(leaf):
    """An argument leaf as the dispatch cache's key holds it."""
    import jax

    if not isinstance(leaf, jax.Array):
        return (type(leaf).__name__, str(getattr(leaf, "dtype", "")),
                tuple(getattr(leaf, "shape", ())))
    return ("jax.Array", str(leaf.sharding), bool(leaf._committed),
            bool(leaf.weak_type), str(leaf.dtype), tuple(leaf.shape))


def main() -> int:
    import jax

    jax.config.update("jax_explain_cache_misses", True)
    from elephas_tpu import telemetry, worker

    seen = []  # one a dispatch: (leaf descriptions, profile, gc)
    inner = worker.MeshRunner._dispatch_epoch
    collecting = {"since": 0.0, "seconds": 0.0, "collections": []}

    def on_gc(phase, info):
        if phase == "start":
            collecting["since"] = time.monotonic()
        else:
            collecting["seconds"] += time.monotonic() - collecting["since"]
            collecting["collections"].append(info["generation"])

    gc.callbacks.append(on_gc)

    def watched(self, state, mvs, xb, yb, **where):
        named = {"tv": state[0], "ntv": state[1], "ov": state[2],
                 "mvs": mvs, "xb": xb, "yb": yb}
        leaves = {
            f"{name}{jax.tree_util.keystr(path)}": describe(leaf)
            for name, tree in named.items()
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
        }
        profile = cProfile.Profile()
        collecting.update(seconds=0.0, collections=[])
        out = profile.runcall(inner, self, state, mvs, xb, yb, **where)
        seen.append((leaves, profile, {
            "gc_s": round(collecting["seconds"], 4),
            "gc_generations": list(collecting["collections"])}))
        return out

    worker.MeshRunner._dispatch_epoch = watched
    sys.argv = ["benchmarks/run.py"] + sys.argv[1:]
    try:
        runpy.run_path(os.path.join(ROOT, "benchmarks", "run.py"),
                       run_name="__main__")
        rc = 0
    except SystemExit as stop:
        rc = int(stop.code or 0)

    spans = telemetry.default_tracer().events(name="fit.epoch_dispatch")
    for i, ((leaves, profile, collected), span) in enumerate(zip(seen, spans)):
        args = span["args"]
        if i:
            before = seen[i - 1][0]
            changed = {k: {"was": before.get(k), "is": v}
                       for k, v in leaves.items() if before.get(k) != v}
            if changed:
                kinds = sorted({
                    f"{json.dumps(c['was'][:4])} -> {json.dumps(c['is'][:4])}"
                    for c in changed.values()})
                print("[signature] " + json.dumps({
                    "dispatch": i, "trace": args.get("trace"),
                    "epoch": args.get("epoch"), "leaves": len(leaves),
                    "changed": len(changed), "kinds": kinds,
                    "examples": dict(list(changed.items())[:3]),
                    "new_signature": args.get("new_signature")}), flush=True)
        if args.get("new_signature"):
            text = io.StringIO()
            pstats.Stats(profile, stream=text).sort_stats(
                "cumulative").print_stats(45)
            print(f"[profile] dispatch={i} trace={args.get('trace')} "
                  f"epoch={args.get('epoch')} span_ms="
                  f"{round(span['dur'] * 1e3, 2)} args="
                  + json.dumps({k: v for k, v in args.items()
                                if k.startswith(("jax_", "cache_"))})
                  + " gc=" + json.dumps(collected), flush=True)
            print(text.getvalue(), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
