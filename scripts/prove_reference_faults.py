#!/usr/bin/env python3
"""``benchmarks/prove.py``'s readings for an LM cell whose faults are the
model's own: the reference under another configuration, put in the
program's place.

    chiprun --timeout 3300 -- python3 scripts/prove_reference_faults.py \
        --workload smallthinker-fit-seq16k --seeds 12 --controls 3 --faults 3 \
        --fault band_dropped sliding_window_layout '[0,0,0,0,0,0,0,0]' \
        --fault router_fed_m assumed.router_input '"expert_input"' --out <file>

``prove.py``'s own fault leaves half of each batch out, and a batch of
one sequence has no half. A ``--fault NAME KEY JSON`` here is a reading
of the model that the program does not compute (full attention where a
layer has a window; a router fed the experts' input): the float32
reference follows the first epoch's steps with ``KEY`` of the
configuration set to ``JSON`` (several ``--fault`` of one name set
several keys), and its loss, velocity and change stand against the
sound reference's as the program's do. The limits of ``correct`` must
refuse each on every seed read; the record says whether they did.

As ``scripts/prove_off_chip.py`` does, every variable of the master
model is given its host copy once the first epoch's state has been
read, so that the reference has the chip to itself. The other
arguments are ``prove.py``'s.
"""

import argparse
import copy
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

GAPS = ("loss_gap", "velocity_gap", "change_gap")


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def with_keys(config: dict, settings: list) -> dict:
    """A copy of ``config`` with each dotted ``key`` set to ``value``."""
    config = copy.deepcopy(config)
    for key, value in settings:
        *path, leaf = key.split(".")
        target = config
        for part in path:
            target = target[part]
        target[leaf] = value
    return config


def fault_gaps(driver, ctx, run, seed, settings: list) -> dict:
    """The reference under ``settings``, in the program's place, against
    the sound reference that ``compare_first_epoch`` left in ``run``."""
    import numpy as np

    sound, ctx.config = ctx.config, with_keys(ctx.config, settings)
    try:
        other = driver.follow_reference(ctx, run, seed)
    finally:
        ctx.config = sound
    got = driver.gaps_against(
        run["reference"], float(np.mean(other["losses"])),
        other["velocity_norm"], other["change_norm"])
    return {k: got[k] for k in GAPS}


def refused(gaps: dict, limits: dict) -> list:
    return [k for k in GAPS if gaps[k] > limits[k]]


def prove(args, prove_py, park) -> dict:
    from benchmarks.harness import manifest as mf
    from benchmarks.harness import runner, stats

    faults: dict = {}
    for name, key, value in args.fault or []:
        faults.setdefault(name, []).append((key, json.loads(value)))
    ctx, device = prove_py.context(args)
    limits = ctx.config["correct"]["limits"]
    driver = mf.load_module("drivers", ctx.traffic["kind"])
    job = driver.prepare(ctx)
    rows = []
    for i, seed in enumerate(prove_py.seeds_of(args)):
        if i:
            driver.reseed(ctx, job, seed)
        first = driver.first_epoch(ctx, job)
        park(job["model"])
        run = {"first": first, "data": (job["x"], job["y"])}
        sound = driver.compare_first_epoch(ctx, run, seed=seed)
        row = {"seed": seed, "loss": first["loss"],
               "sound": {k: sound[k] for k in GAPS},
               "detail": sound["detail"]}
        if i < args.controls:
            row["control"] = driver.control_gaps(ctx, run, seed)
        if i < args.faults:
            row["faults"] = {
                name: fault_gaps(driver, ctx, run, seed, settings)
                for name, settings in faults.items()}
        runner.say("prove", **{k: v for k, v in row.items() if k != "detail"})
        rows.append(row)
    others = {"control": [r["control"] for r in rows if "control" in r]}
    for name in faults:
        others[name] = [r["faults"][name] for r in rows if "faults" in r]
    summary = {
        "limits": limits,
        "sound": {k: {"max": max(r["sound"][k] for r in rows),
                      "median": stats.median([r["sound"][k] for r in rows]),
                      "seeds": len(rows)} for k in GAPS},
        "sound_refused_on": [r["seed"] for r in rows
                             if refused(r["sound"], limits)],
    }
    for name, read in others.items():
        summary[name] = {
            "seeds": len(read),
            "min": {k: min(g[k] for g in read) for k in GAPS} if read else None,
            "refused_by": [refused(g, limits) for g in read],
        }
    return {"kind": "fit", "device": device, "rows": rows,
            "faults": {n: [list(s) for s in f] for n, f in faults.items()},
            "summary": summary}


def main() -> int:
    prove_py = _load(os.path.join(ROOT, "benchmarks", "prove.py"), "prove_py")
    park = _load(os.path.join(ROOT, "scripts", "prove_off_chip.py"),
                 "prove_off_chip").park
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--faults", type=int, default=3)
    parser.add_argument("--fault", nargs=3, action="append",
                        metavar=("NAME", "KEY", "JSON"))
    parser.add_argument("--seed0", type=int, default=2147483659)
    parser.add_argument("--set", nargs=2, action="append",
                        metavar=("KEY", "JSON"),
                        help="override a traffic key (or config.<key>)")
    parser.add_argument("--out", required=True)
    parser.add_argument("--cpu", action="store_true",
                        help="toy rehearsal on the CPU; never a measurement")
    args = parser.parse_args()
    result = prove(args, prove_py, park)
    result["workload"] = args.workload
    result["overrides"] = args.set or []
    result["total_s"] = round(time.monotonic() - prove_py.T_PROCESS, 1)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["summary"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
