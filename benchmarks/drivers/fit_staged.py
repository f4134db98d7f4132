"""Driver of the ``fit_staged`` kind: one long ``SparkModel.fit`` over
data staged on the device, read epoch by epoch from the program's own
``fit.epoch`` telemetry events.

Set-up builds one ``SparkModel`` from the seeded weights and takes its
first epoch with it (one ``fit`` call of one epoch: this compiles or
loads the epoch program, and its loss and state are what the check
compares with the reference). The measured call is a second ``fit`` on
the same object and the same compiled program. Its window opens at its
first ``fit.epoch`` event, so that stage-in is paid before the window,
and closes at its last; the cell's rate is all the examples of the
epochs between them over all those seconds.
"""

from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

from benchmarks.harness import seeds, stats


def make_examples(cfg: dict, traffic: dict, seed: int):
    """Seeded inputs, cheaply. Images: a seeded block of ``base_block``
    normal images expanded to ``examples`` rows that all differ (each a
    base image under its own gain and offset); the label is the base
    image's, so the loss can fall. Sequences: each row steps through
    the vocabulary from its own start with its own stride, and the
    target is the next token."""
    n, block = int(traffic["examples"]), int(traffic["base_block"])
    rng = seeds.rng_for(seed, 10)
    if traffic["example"] == "sequence":
        length = int(traffic.get("sequence_length", cfg.get("n_positions")))
        starts = rng.integers(0, cfg["vocab_size"], size=(n, 1))
        strides = 1 + rng.permutation(n)[:, None] % block
        tokens = (starts + strides * np.arange(length + 1)) % cfg["vocab_size"]
        tokens = tokens.astype(np.int32)
        return tokens[:, :-1], tokens[:, 1:]
    side, ch = cfg["image_size"], cfg["channels"]
    base = rng.standard_normal((block, side, side, ch), dtype=np.float32)
    base_labels = rng.integers(0, cfg["num_classes"], size=block)
    which = rng.permutation(n) % block
    gain = (0.5 + rng.random(n, dtype=np.float32))[:, None, None, None]
    offset = (0.1 * rng.standard_normal(n, dtype=np.float32))[
        :, None, None, None
    ]
    x = base[which]
    x *= gain
    x += offset
    return x, base_labels[which].astype(np.int32)


def _epoch_events(since_seq: int) -> list:
    from elephas_tpu import telemetry

    return telemetry.default_tracer().events(since_seq, name="fit.epoch")


def _trace_some_epochs(ctx, since_seq, first, count, out, stop):
    """Watcher thread of a traced run: the profiler runs from the
    ``first``-th epoch event of the measured call for ``count`` epochs
    (a trace of the whole call would be too large to bring back)."""
    import jax

    def wait_for(n):
        while not stop.is_set():
            if len(_epoch_events(since_seq)) >= n:
                return True
            time.sleep(0.02)
        return False

    if not wait_for(first):
        return
    from benchmarks.harness.runner import start_trace

    trace_dir = ctx.trace_dir()
    start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        wait_for(first + count)
    jax.profiler.stop_trace()
    out["dir"] = trace_dir


def _read_state(model) -> dict:
    """Variable path -> array, read to the host: the model's variables
    and the optimizer's momenta after a ``fit`` call's write-back."""
    state = {v.path: np.asarray(v.value) for v in model.variables}
    momenta = {
        v.path: np.asarray(v.value) for v in model.optimizer.variables
    }
    return {"variables": state, "momenta": momenta}


def prepare(ctx) -> dict:
    """The model with the seeded weights in it, its ``SparkModel``, and
    the seeded rows as the RDD that ``fit`` takes."""
    from elephas_tpu import SparkModel

    cfg = ctx.config
    t0 = time.monotonic()
    params = ctx.reference.init_params(cfg, ctx.seed)
    jax_ready = time.monotonic()
    model = ctx.builder.build(cfg, params)
    built = time.monotonic()
    sm = SparkModel(model, **cfg["spark_model"])
    job = {"model": model, "sm": sm}
    reseed(ctx, job, ctx.seed, params)
    ctx.say("fit", weights_s=round(jax_ready - t0, 2),
            model_s=round(built - jax_ready, 2),
            rows_and_rdd_s=round(time.monotonic() - built, 2),
            workers=sm.num_workers, examples=len(job["x"]))
    return job


def reseed(ctx, job: dict, seed: int, params=None) -> None:
    """Rows, weights and a fresh optimizer for ``seed`` on the model
    that is there (prove.py reads a dozen seeds after one set-up)."""
    from elephas_tpu.data import SparkContext
    from elephas_tpu.utils import rdd_utils

    if params is None:
        params = ctx.reference.init_params(ctx.config, seed)
        ctx.builder.assign(job["model"], params)
        for var in job["model"].optimizer.variables:
            if "learning_rate" not in var.path:
                var.assign(np.zeros(var.shape, var.dtype))
    job["start"] = {k: np.asarray(v) for k, v in params.items()}
    job["x"], job["y"] = make_examples(ctx.config, ctx.traffic, seed)
    workers = job["sm"].num_workers
    job["rdd"] = rdd_utils.to_simple_rdd(
        SparkContext(f"local[{workers}]"), job["x"], job["y"],
        num_partitions=workers,
    )


def first_epoch(ctx, job: dict) -> dict:
    """Set-up's epoch: one ``fit`` call of one epoch on the object, and
    through the call and the compiled program, that the window drives.
    Its loss and the state it leaves are what the check compares."""
    t0 = time.monotonic()
    history = job["sm"].fit(
        job["rdd"], epochs=1, batch_size=int(ctx.traffic["batch_size"])
    )
    first = {"loss": float(history["loss"][0]),
             "state": _read_state(job["model"]), "start": job["start"]}
    ctx.say("fit", first_epoch_call_s=round(time.monotonic() - t0, 2),
            first_loss=first["loss"], **ctx.meter.mark())
    return first


def run(ctx) -> dict:
    import jax

    from elephas_tpu import telemetry

    traffic = ctx.traffic
    batch = int(traffic["batch_size"])
    job = prepare(ctx)
    first = first_epoch(ctx, job)
    sm, rdd, x, y = job["sm"], job["rdd"], job["x"], job["y"]

    # the epoch behind the window's first event, and as many whole
    # epochs after it as cover --seconds: the same work for every seed
    epochs = 1 + math.ceil(ctx.seconds / float(traffic["epoch_seconds"]))
    tracer = telemetry.default_tracer()
    since = tracer.seq
    traced, stop = {}, threading.Event()
    watcher = None
    if ctx.trace:
        watcher = threading.Thread(
            target=_trace_some_epochs,
            args=(ctx, since, 2, int(traffic["trace_epochs"]), traced, stop),
            daemon=True,
        )
        watcher.start()
    # the program stamps fit.epoch with time.time(); the harness's clock
    # is monotonic: one pair of readings maps one onto the other
    wall_to_mono = time.monotonic() - time.time()
    setup_mark = ctx.meter.mark()
    t_call = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.fit_call"):
        history = sm.fit(rdd, epochs=epochs, batch_size=batch)
    t_return = time.monotonic()
    stop.set()
    if watcher is not None:
        watcher.join()
    events = _epoch_events(since)
    stamps = [e["ts"] + wall_to_mono for e in events]
    # compiles between the first and the last epoch event cannot be told
    # from the call's others by the meter alone: the whole measured call
    # is held to zero, stage-in included
    in_call = ctx.meter.since(setup_mark)
    from benchmarks.harness.runner import memory_peak

    memory = memory_peak(ctx.sizes)
    losses = [float(v) for v in history["loss"]]
    rate = stats.window_rate(stamps, len(x), ctx.chips)
    readings = stats.epoch_readings(stamps, len(x), ctx.chips)
    ctx.say("fit", epochs_asked=epochs, epoch_events=len(events),
            call_s=round(t_return - t_call, 2),
            first_event_after_call_s=round(stamps[0] - t_call, 3)
            if stamps else None,
            window_s=round(stamps[-1] - stamps[0], 3) if stamps else None,
            rate=rate, readings=[round(r, 1) for r in readings],
            losses=[round(v, 4) for v in losses], **memory)

    run = {
        "kind": "fit_staged",
        "window": {"t0": stamps[0] if stamps else t_return,
                   "t1": stamps[-1] if stamps else t_return},
        "fit_call": {"t_call": t_call, "t_return": t_return},
        "epochs": {"asked": epochs, "stamps": stamps, "losses": losses,
                   "examples": len(x), "rate": rate, "readings": readings},
        "compile": {"setup": setup_mark, "window": in_call},
        "memory": memory,
        "attempted": epochs,
        "failed": (epochs - len(events))
        + sum(1 for v in losses if not math.isfinite(v)),
        "first": first,
        "data": (x, y),
        "programs": dict(ctx.sizes.temps) if ctx.sizes else {},
    }
    if traced.get("dir"):
        from benchmarks.harness import xplane

        run["trace"] = xplane.reduce_trace(xplane.find_xplane(traced["dir"]))
    # free the program's state before the reference takes the chip
    del sm, rdd, history
    job.clear()
    gc.collect()
    return run


def leaf_gaps(got: dict, want: dict) -> dict:
    """By leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    floor = float(np.median(list(want.values())))
    return {path: abs(got[path] - ref_norm) / max(ref_norm, floor)
            for path, ref_norm in want.items()}


def worst_leaf_gap(got: dict, want: dict) -> tuple:
    """The largest of ``leaf_gaps`` and its leaf; a gap that is not
    finite is the largest there is."""
    gaps = leaf_gaps(got, want)
    where = max(gaps, key=lambda p: gaps[p] if math.isfinite(gaps[p])
                else math.inf)
    return (gaps[where] if math.isfinite(gaps[where]) else math.inf), where


def worst_by_kind(got: dict, want: dict) -> dict:
    """The worst leaf of each kind of variable (``kernel``, ``gamma``,
    ``beta``, ``bias``): printed beside the numbers compared, not
    judged, for the PR that wants a limit a kind."""
    out: dict = {}
    for path, gap in leaf_gaps(got, want).items():
        kind = path.rsplit("/", 1)[-1]
        out[kind] = round(max(out.get(kind, 0.0), gap), 4)
    return out


def momentum_path(variable_path: str) -> str:
    """keras names a variable's SGD momentum ``SGD/<path with _>_momentum``."""
    return "SGD/" + variable_path.replace("/", "_") + "_momentum"


def follow_reference(ctx, run, seed, lower: bool = False,
                     half_batch: bool = False) -> dict:
    """The reference through the first epoch's steps, on the same rows
    from the same seeded weights. ``half_batch`` is a fault, not a
    control: each step sees the first half of its batch twice and the
    second half never."""
    x, y = run["data"]
    batch = int(ctx.traffic["batch_size"])
    steps = int(ctx.traffic["steps_per_epoch"])

    def rows(a, i):
        got = a[i * batch:(i + 1) * batch]
        if half_batch:
            got = np.concatenate([got[:batch // 2], got[:batch // 2]])
        return got

    batches = ((rows(x, i), rows(y, i)) for i in range(steps))
    t0 = time.monotonic()
    ref = ctx.reference.follow(ctx.config, seed, batches, lower=lower)
    ref["seconds"] = round(time.monotonic() - t0, 2)
    return ref


def gaps_against(ref: dict, loss: float, velocity: dict, change: dict) -> dict:
    """The three numbers compared, of anything put in the program's
    place: the epoch's loss, and by the worst leaf the norm of the
    optimizer's velocity and of the parameters' change after it."""
    ref_loss = float(np.mean(ref["losses"]))
    change_gap, change_at = worst_leaf_gap(change, ref["change_norm"])
    velocity_gap, velocity_at = worst_leaf_gap(velocity, ref["velocity_norm"])
    return {
        "loss_gap": abs(loss - ref_loss) / abs(ref_loss),
        "velocity_gap": velocity_gap,
        "change_gap": change_gap,
        "detail": {"reference_s": ref["seconds"], "ref_loss": ref_loss,
                   "loss": loss, "velocity_at": velocity_at,
                   "change_at": change_at,
                   "velocity_by_kind": worst_by_kind(
                       velocity, ref["velocity_norm"]),
                   "change_by_kind": worst_by_kind(
                       change, ref["change_norm"]),
                   "ref_step_losses": [round(v, 4) for v in ref["losses"]]},
    }


def compare_first_epoch(ctx, run, seed=None) -> dict:
    """The program's first epoch against the reference's."""
    seed = ctx.seed if seed is None else seed
    ref = run["reference"] = follow_reference(ctx, run, seed)
    state, start = run["first"]["state"], run["first"]["start"]
    norm = lambda a: float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))
    change = {
        p: norm(state["variables"][p] - start[p]) for p in ref["change_norm"]
    }
    velocity = {
        p: norm(state["momenta"][momentum_path(p)])
        for p in ref["velocity_norm"]
    }
    return gaps_against(ref, run["first"]["loss"], velocity, change)


def _in_the_programs_place(ctx, run, seed, **how) -> dict:
    """The reference run another way, put in the program's place,
    against the float32 reference that ``compare_first_epoch`` left in
    ``run``."""
    other = follow_reference(ctx, run, seed, **how)
    got = gaps_against(
        run["reference"], float(np.mean(other["losses"])),
        other["velocity_norm"], other["change_norm"],
    )
    return {k: got[k] for k in ("loss_gap", "velocity_gap", "change_gap")}


def control_gaps(ctx, run, seed) -> dict:
    """The control: the reference one precision down (every tensor that
    the configuration holds in bfloat16 held in fp8)."""
    return _in_the_programs_place(ctx, run, seed, lower=True)


def half_batch_gaps(ctx, run, seed) -> dict:
    """The fault that the loss limit is held against: half of each
    batch left out."""
    return _in_the_programs_place(ctx, run, seed, half_batch=True)


def check(ctx, run) -> dict:
    limits = ctx.config["correct"]["limits"]
    got = compare_first_epoch(ctx, run)
    ctx.say("check", **got["detail"])
    losses = run["epochs"]["losses"]
    numbers = {
        name: (got[name], limits[name], got[name] <= limits[name])
        for name in ("loss_gap", "velocity_gap", "change_gap")
    }
    finite = all(math.isfinite(v) for v in losses) and bool(losses)
    numbers["losses_not_finite"] = (
        sum(1 for v in losses if not math.isfinite(v)), 0, finite
    )
    falls = bool(losses) and losses[-1] < run["first"]["loss"]
    numbers["last_loss_minus_first"] = (
        (losses[-1] - run["first"]["loss"]) if losses else math.nan, 0.0,
        falls,
    )
    numbers["epochs_missing"] = (run["failed"], 0, run["failed"] == 0)
    run.pop("data", None)
    return {"correct": all(ok for _v, _l, ok in numbers.values()),
            "numbers": numbers}
