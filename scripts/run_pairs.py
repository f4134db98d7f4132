#!/usr/bin/env python3
"""Runs cells of the benchmark from several checkouts in one chip call,
one after the other, and keeps each run's log and one record a run.

    chiprun --timeout 3600 -- python3 scripts/run_pairs.py --pr 39 \
        --call K1 --tree parent=.chipcheck/parent --tree change=.chipcheck/change \
        parent:kanana2-fit-seq8k:3939100003:0 change:kanana2-fit-seq8k:3939100003:0

A run is ``<side>:<cell>:<seed>:<trace>``: ``benchmarks/run.py`` of that
side's checkout, from its root, with ``--seconds`` as ``BENCHMARK.json``
has it. The two sides of a pair share a seed and every pair has its own;
a checkout is a ``git archive`` unpacked under ``.chipcheck/`` (ignored by
git, copied to the chip). The big epoch programs of two sides push each
other out of the machine's compile cache, so consecutive runs of one side
set up in half the time of alternating ones.

Each run prints, after the harness's lines, ``[epochs]`` with the seconds
between the measured call's consecutive ``fit.epoch`` events (the
harness's ``readings`` are these, as rates, to one decimal). An untraced
run of a tree that has ``benchmarks/harness/epoch_spans.py`` (PR 40) also
reads the ring's epoch-by-epoch metrics, which the harness reads in a
traced run only: their ``[dispatch]``, ``[first_epoch]`` and ``[memory]``
lines, and ``[spans]`` with the values. The records go to
``chiprun_out/PR<n>/<call>.jsonl`` in the shape of
``benchmarks/results/sets/``: the result line whole, and beside it the
numbers of the ``[fit]``, ``[check]``, ``[scopes]``, ``[epochs]`` and
``[spans]`` lines; each run's ``fit.*`` ring events go beside its log as
``<stem>.ring.json``.
"""

import argparse
import json
import os
import re
import runpy
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_epoch_spans(stamps) -> dict:
    """The checkout's own readers of ``epoch_spans.METRICS`` (PR 40) on
    the window that ``stamps`` (monotonic seconds of the measured call's
    ``fit.epoch`` events) open and close; a reader that finds nothing
    is left out."""
    from benchmarks.harness import epoch_spans
    from benchmarks.harness import manifest as mf

    run = {"window": {"t0": stamps[0], "t1": stamps[-1]}}
    out = {}
    for name in epoch_spans.METRICS:
        value = mf.load_module("metrics", name).read(run)
        if value is not None:
            out[name] = float(value)
    return out


def child(argv) -> int:
    """``benchmarks/run.py`` of the checkout this process stands in,
    then the epochs' seconds from the program's own ring."""
    ring_out = None
    if argv[:1] == ["--ring"]:
        ring_out, argv = argv[1], argv[2:]
    sys.argv = ["benchmarks/run.py"] + argv
    try:
        runpy.run_path("benchmarks/run.py", run_name="__main__")
        rc = 0
    except SystemExit as stop:
        rc = int(stop.code or 0)
    from elephas_tpu import telemetry

    events = telemetry.default_tracer().events(name="fit.epoch")
    # the measured call is the last one: its events share a trace id
    last = events[-1]["args"].get("trace") if events else None
    call = [e["mono_ns"] / 1e9 for e in events
            if e["args"].get("trace") == last]
    print("[epochs] seconds=" + json.dumps(
        [round(b - a, 4) for a, b in zip(call, call[1:])]), flush=True)
    untraced = argv[argv.index("--trace") + 1] == "0"
    if untraced and len(call) >= 2 and os.path.isfile(
            os.path.join("benchmarks", "harness", "epoch_spans.py")):
        print("[spans] values=" + json.dumps(read_epoch_spans(call)),
              flush=True)
    if ring_out:
        with open(ring_out, "w") as f:
            json.dump([e for e in telemetry.default_tracer().events()
                       if e["name"].startswith("fit.")], f)
    return rc


def fields(log: str, tag: str) -> dict:
    """The ``key=<json>`` fields of the log's lines that start with
    ``[tag]``; a ``[check]`` line's number goes under its own name."""
    out: dict = {}
    for line in log.splitlines():
        if not line.startswith(f"[{tag}] "):
            continue
        found = dict(re.findall(r'(\w+)=(\[.*?\]|\{.*?\}|"[^"]*"|\S+)', line))
        parsed = {}
        for key, text in found.items():
            try:
                parsed[key] = json.loads(text)
            except ValueError:
                parsed[key] = text
        if tag == "check" and "number" in parsed:
            out[parsed["number"]] = parsed["value"]
        else:
            out.update(parsed)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pr", type=int, required=True)
    ap.add_argument("--call", required=True)
    ap.add_argument("--tree", action="append", default=[],
                    metavar="SIDE=DIR")
    ap.add_argument("runs", nargs="+", metavar="SIDE:CELL:SEED:TRACE")
    args = ap.parse_args()
    trees = dict(t.split("=", 1) for t in args.tree)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", f"PR{args.pr}")
    os.makedirs(out_dir, exist_ok=True)
    worst = 0
    for order, run in enumerate(args.runs, 1):
        side, cell, seed, trace = run.split(":")
        tree = os.path.join(ROOT, trees[side])
        stem = f"{args.call}-{order:02d}-{side}-{cell}-t{trace}"
        t0 = time.monotonic()
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             "--ring", os.path.join(out_dir, stem + ".ring.json"),
             "--workload", cell, "--seed", seed, "--seconds", str(seconds),
             "--trace", trace],
            cwd=tree, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        run_s = round(time.monotonic() - t0, 1)
        with open(os.path.join(out_dir, stem + ".log"), "w") as f:
            f.write(done.stdout)
        lines = [ln for ln in done.stdout.splitlines()
                 if ln.startswith('{"correct"')]
        record = {
            "pr": args.pr, "cell": cell, "side": side, "tree": trees[side],
            "call": args.call, "order": order, "seed": int(seed),
            "trace": int(trace), "rc": done.returncode, "run_s": run_s,
            "line": json.loads(lines[-1]) if lines else None,
            "fit": fields(done.stdout, "fit"),
            "check": fields(done.stdout, "check"),
            "scopes": fields(done.stdout, "scopes").get("ms_per_step"),
            "epoch_seconds": fields(done.stdout, "epochs").get("seconds"),
            "spans": fields(done.stdout, "spans").get("values"),
        }
        with open(os.path.join(out_dir, args.call + ".jsonl"), "a") as f:
            f.write(json.dumps(record) + "\n")
        line = record["line"] or {}
        metric = lambda name: line.get(  # noqa: E731
            "metrics", {}).get(name, {}).get("value")
        print(json.dumps({
            "run": run, "rc": done.returncode, "run_s": run_s,
            "correct": line.get("correct"),
            "rate": metric("fit_examples_per_s_per_chip"),
            "setup_s": metric("setup_s"),
            "hbm": line.get("device", {}).get("memory_peak_bytes"),
            "check": {k: record["check"].get(k) for k in
                      ("loss_gap", "velocity_gap", "change_gap")},
            "scopes": record["scopes"],
            "tail": None if lines else done.stdout[-1500:],
        }), flush=True)
        worst = worst or done.returncode
    return worst


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        sys.exit(child(sys.argv[2:]))
    sys.exit(main())
