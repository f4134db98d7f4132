"""One run of one cell: device, set-up, window, check, metrics, line."""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

from benchmarks.harness import manifest as mf

WORK_DIRNAME = ".bench_work"


def say(what: str, **fields) -> None:
    """A line of the run's own log (never the last line of stdout)."""
    print(
        f"[{what}] "
        + " ".join(f"{k}={json.dumps(v, default=str)}" for k, v in fields.items()),
        flush=True,
    )


class Context:
    """What a driver is handed: the cell, its files, the seed, the
    window's length, and the run's meters."""

    def __init__(self, *, manifest, cell, config, traffic, seed, seconds,
                 trace, t_process, meter=None, sizes=None, work_dir=None):
        self.manifest, self.cell = manifest, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.t_process = bool(trace), t_process
        self.meter, self.sizes = meter, sizes
        self.work_dir = work_dir or os.path.join(mf.ROOT, WORK_DIRNAME)
        self.chips = int(cell["chips"])
        self.builder = mf.load_module("builders", config["builder"])
        self.reference = mf.load_module("reference", cell["config"])
        self.say = say

    def trace_dir(self) -> str:
        path = os.path.join(self.work_dir, "trace", self.cell["name"])
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path, exist_ok=True)
        return path


def start_trace(trace_dir: str) -> None:
    """The profiler with the Python tracer off: it slows the host code
    that the window measures, and the reduction reads none of it."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def place_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at a fixed path inside the checkout, with every program
    stored. Its size limit is the machine's own
    (``JAX_COMPILATION_CACHE_MAX_SIZE``); the harness sets none.
    Returns the directory."""
    import jax

    from elephas_tpu.utils import backend_guard

    cache_dir = backend_guard.use_compile_cache(mf.ROOT)
    # store every program, not only those that took a second to
    # compile: hundreds of small ones are most of a warm set-up, and a
    # cell's entries stay well under the chip machine's 192 MiB
    # (``cache_bytes`` in each run's [done] line, PERF.md)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def cache_bytes(cache_dir: str, since_wall: float) -> dict:
    """Bytes of the cache directory, and of the entries this process
    wrote or read: an entry's own time of writing or of last access, or
    that of the ``-atime`` file JAX keeps beside it where the cache
    evicts, is no older than ``since_wall``."""
    total = used = entries = 0
    if not os.path.isdir(cache_dir):
        return {"cache_dir_bytes": 0, "run_entries": 0, "run_bytes": 0}
    for name in os.listdir(cache_dir):
        if not name.endswith("-cache"):
            continue
        path = os.path.join(cache_dir, name)
        try:
            st = os.stat(path)
            stamps = [st.st_mtime, st.st_atime]
            beside = path[: -len("-cache")] + "-atime"
            if os.path.exists(beside):
                stamps.append(os.stat(beside).st_mtime)
        except OSError:  # evicted by another process meanwhile
            continue
        total += st.st_size
        if max(stamps) >= since_wall:
            used += st.st_size
            entries += 1
    return {"cache_dir_bytes": total, "run_entries": entries,
            "run_bytes": used}


def memory_peak(sizes) -> dict:
    """Peak bytes on the fullest chip: the allocator's own peak plus the
    largest program's temporaries, which that peak leaves out."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    temps = sizes.largest() if sizes is not None else 0
    return {"allocator_peak_bytes": max(peaks), "program_temp_bytes": temps,
            "memory_peak_bytes": max(peaks) + temps}


def collect_metrics(manifest, cell, section, run) -> dict:
    """``{name: {"value", "unit"}}`` from each metric's own reader; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for entry in mf.metrics_of(manifest, cell, section):
        reader = mf.load_module("metrics", entry["name"])
        value = reader.read(run)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            say("metric", name=entry["name"], value=str(value),
                note="not finite: left out of the line")
            continue
        out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def finish(ctx: Context, run: dict, device: dict) -> dict:
    """The result line of a run whose window has closed and whose check
    has been made."""
    section = "per_layer" if ctx.trace else "end_to_end"
    metrics = collect_metrics(ctx.manifest, ctx.cell, section, run)
    device = dict(device, memory_peak_bytes=run["memory"]["memory_peak_bytes"])
    line = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if ctx.trace and run.get("trace"):
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_gaps"],
        }
    return line


def drive(ctx: Context) -> dict:
    """Set-up, window and check of one cell through its driver."""
    driver = mf.load_module("drivers", ctx.traffic["kind"])
    run = driver.run(ctx)
    run["setup_s"] = run["window"]["t0"] - ctx.t_process
    run["chips"] = ctx.chips
    run["config"], run["traffic"] = ctx.config, ctx.traffic
    run["builder"] = ctx.builder
    run.setdefault("trace", None)
    verdict = driver.check(ctx, run)
    for name, (value, limit, ok) in verdict["numbers"].items():
        say("check", number=name, value=value, limit=limit, ok=ok)
    run["correct"] = bool(verdict["correct"])
    run["check"] = verdict
    return run


def main(argv, t_process: float) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    wall_start = time.time()

    os.environ.setdefault("KERAS_BACKEND", "jax")
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, args.workload)
    config = mf.config_of(manifest, cell)
    traffic = mf.load_json("traffic", cell["traffic"])

    # the system under test: without it there is nothing to measure
    from elephas_tpu.utils import backend_guard

    cache_dir = place_compile_cache()
    device = backend_guard.require_accelerator("tpu")
    if device["count"] != cell["chips"]:
        print(
            f"cell {cell['name']} needs {cell['chips']} chip(s); JAX "
            f"reports {device}", file=sys.stderr,
        )
        return 3
    from benchmarks.harness.compile_meter import CompileMeter, ProgramSizes
    from benchmarks.harness.peaks import peaks_for

    peaks_for(device["kind"])  # an unknown kind is an error, now
    say("device", **device, compile_cache=cache_dir,
        cache_entries=backend_guard.compile_cache_entries(cache_dir))
    ctx = Context(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=args.trace,
        t_process=t_process, meter=CompileMeter(), sizes=ProgramSizes(),
    )
    run = drive(ctx)
    run["device_kind"] = device["kind"]
    line = finish(ctx, run, device)
    say("done", total_s=round(time.monotonic() - t_process, 2),
        setup_s=run["setup_s"],
        cache_entries=backend_guard.compile_cache_entries(cache_dir),
        **cache_bytes(cache_dir, wall_start))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0
