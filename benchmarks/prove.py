#!/usr/bin/env python3
"""The builder's own measurements behind the limits in a configuration
file: one process, one set-up, many readings.

    python benchmarks/prove.py --workload <cell> --seeds 12 --controls 3 --faults 3

It reads the numbers that decide ``correct`` on many seeds
(weights, inputs and optimizer made anew for each). For the first
``--controls`` of them it reads them again with the reference put in
the program's place one precision down, and for the first ``--faults``
with the reference trained on half of each batch: the readings a limit
is set from. What was read goes to ``--out`` (a file under
``benchmarks/results/`` by default). Needs the chip, like ``run.py``; a
benchmark run never calls this. It drives a ``fit_staged`` cell; a later
kind of traffic brings a script of its own.
"""

import time

T_PROCESS = time.monotonic()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness import runner, stats  # noqa: E402


def context(args):
    os.environ.setdefault("KERAS_BACKEND", "jax")
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, args.workload)
    from elephas_tpu.utils import backend_guard

    if not args.cpu:
        runner.place_compile_cache()
        device = backend_guard.require_accelerator("tpu")
    else:
        backend_guard.force_cpu_devices(cell["chips"])
        device = backend_guard.device_record()
    from benchmarks.harness.compile_meter import CompileMeter, ProgramSizes

    config = mf.config_of(manifest, cell)
    traffic = mf.load_json("traffic", cell["traffic"])
    for key, value in (args.set or []):
        target, leaf = (traffic, key)
        if key.startswith("config."):
            target, leaf = config, key[len("config."):]
        *path, leaf = leaf.split(".")
        for part in path:
            target = target[part]
        target[leaf] = json.loads(value)
    ctx = runner.Context(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=args.seed0, seconds=0.0, trace=0,
        t_process=T_PROCESS, meter=CompileMeter(), sizes=ProgramSizes(),
    )
    return ctx, device


def seeds_of(args) -> list:
    # large and far apart: the driver's seeds pass 2**31
    return [args.seed0 + 104729 * 7919 * i for i in range(args.seeds)]


def prove_fit(args) -> dict:
    ctx, device = context(args)
    driver = mf.load_module("drivers", ctx.traffic["kind"])
    job = driver.prepare(ctx)
    rows = []
    for i, seed in enumerate(seeds_of(args)):
        if i:
            driver.reseed(ctx, job, seed)
        first = driver.first_epoch(ctx, job)
        run = {"first": first, "data": (job["x"], job["y"])}
        row = {"seed": seed, "loss": first["loss"]}
        sound = driver.compare_first_epoch(ctx, run, seed=seed)
        row["sound"] = {k: sound[k] for k in
                        ("loss_gap", "velocity_gap", "change_gap")}
        row["detail"] = sound["detail"]
        if i < args.controls:
            # the reference one precision down, in the program's place:
            # its own state against the float32 reference's
            row["control"] = driver.control_gaps(ctx, run, seed)
        if i < args.faults:
            # the fault the loss limit is held against
            row["half_batch"] = driver.half_batch_gaps(ctx, run, seed)
        runner.say("prove", **{k: v for k, v in row.items() if k != "detail"})
        rows.append(row)
    return {"kind": "fit", "device": device, "rows": rows,
            "summary": summarise(rows)}


def summarise(rows) -> dict:
    out = {}
    names = rows[0]["sound"].keys()
    for name in names:
        sound = [r["sound"][name] for r in rows]
        control = [r["control"][name] for r in rows if "control" in r]
        halved = [r["half_batch"][name] for r in rows if "half_batch" in r]
        out[name] = {"sound_max": max(sound), "sound_median":
                     stats.median(sound), "seeds": len(sound),
                     "control_min": min(control) if control else None,
                     "control_seeds": len(control),
                     "half_batch_min": min(halved) if halved else None}
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--faults", type=int, default=0)
    parser.add_argument("--seed0", type=int, default=2147483659)
    parser.add_argument("--set", nargs=2, action="append",
                        metavar=("KEY", "JSON"),
                        help="override a traffic key (or config.<key>)")
    parser.add_argument("--out")
    parser.add_argument("--cpu", action="store_true",
                        help="toy rehearsal on the CPU; never a measurement")
    args = parser.parse_args()
    result = prove_fit(args)
    result["workload"] = args.workload
    result["overrides"] = args.set or []
    result["total_s"] = round(time.monotonic() - T_PROCESS, 1)
    out = args.out or os.path.join(
        mf.HERE, "results", f"fit-{args.workload}.json"
    )
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["summary"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
