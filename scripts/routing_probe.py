#!/usr/bin/env python3
"""What an LM cell's loss, rate and (where it is sparse) routers do inside
a window, by learning rate.

    chiprun --timeout 1800 -- python3 scripts/routing_probe.py \
        --workload nemotron3nano-fit-seq8k --seeds 2 --epochs 14 \
        --lr 0.05 --lr 0.01 --lr 0.002 --out chiprun_out/PR42/routing-probe.json

One ``fit`` call of ``--epochs`` epochs a seed and learning rate on the
cell's model, rows and compiled epoch program (the optimizer's
``learning_rate`` variable is assigned; nothing compiles again), and of
its ``fit.counters`` events by layer and epoch: the share of a layer's
token slots that go to the held experts and the fullest held expert's
tokens over the held experts' mean; beside them each epoch's seconds
and loss, and the rate over the epochs after the first. A cell whose
held share moves inside the window measures another step at its end
than at its start (``PERF.md``, PR 37 and PR 42). The other arguments
are ``benchmarks/prove.py``'s.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def probe(args, prove_py) -> dict:
    from benchmarks.harness import manifest as mf
    from benchmarks.harness import runner
    from elephas_tpu import telemetry

    ctx, device = prove_py.context(args)
    driver = mf.load_module("drivers", ctx.traffic["kind"])
    job = driver.prepare(ctx)
    batch = int(ctx.traffic["batch_size"])
    # a dense cell has neither experts nor counters: its probe is of the
    # loss and the rate alone
    held = ctx.config.get("num_experts_held", 0)
    tracer = telemetry.default_tracer()
    runs = []
    for i, seed in enumerate(prove_py.seeds_of(args)):
        for j, lr in enumerate(args.lr):
            if i or j:
                driver.reseed(ctx, job, seed)
            job["model"].optimizer.learning_rate.assign(lr)
            since = tracer.seq
            t0 = time.monotonic()
            history = job["sm"].fit(
                job["rdd"], epochs=args.epochs, batch_size=batch)
            call_s = time.monotonic() - t0
            stamps = [e["mono_ns"] / 1e9
                      for e in tracer.events(since, name="fit.epoch")]
            counted = [e["args"]["layers"]
                       for e in tracer.events(since, name="fit.counters")]
            epoch_s = [b - a for a, b in zip(stamps, stamps[1:])]
            layers = sorted(counted[0]) if counted else []
            run = {
                "seed": seed, "lr": lr, "call_s": round(call_s, 2),
                "epoch_s": [round(s, 4) for s in epoch_s],
                "rate": len(job["x"]) * len(epoch_s) / sum(epoch_s),
                "losses": [round(float(v), 4) for v in history["loss"]],
                "layers": layers,
                "held_percent_by_epoch_and_layer": [
                    [round(100.0 * c[n]["held_slots"] / c[n]["slots"], 2)
                     for n in layers] for c in counted],
                "max_over_mean_by_epoch_and_layer": [
                    [round(c[n]["max_expert_tokens"] * held
                           / max(c[n]["held_slots"], 1), 2)
                     for n in layers] for c in counted],
            }
            runner.say("probe", **run)
            runs.append(run)
    return {"workload": args.workload, "device": device,
            "epochs": args.epochs, "runs": runs}


def main() -> int:
    spec = importlib.util.spec_from_file_location(
        "prove_py", os.path.join(ROOT, "benchmarks", "prove.py"))
    prove_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(prove_py)
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--epochs", type=int, default=12)
    parser.add_argument("--lr", type=float, action="append", required=True)
    parser.add_argument("--seed0", type=int, default=2147483659)
    parser.add_argument("--set", nargs=2, action="append",
                        metavar=("KEY", "JSON"),
                        help="override a traffic key (or config.<key>)")
    parser.add_argument("--out", required=True)
    parser.add_argument("--cpu", action="store_true",
                        help="toy rehearsal on the CPU; never a measurement")
    args = parser.parse_args()
    result = probe(args, prove_py)
    result["total_s"] = round(time.monotonic() - prove_py.T_PROCESS, 1)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
