"""What the readers of the window's epochs one by one share: the
program's ``fit.epoch_dispatch`` and ``fit.loss_wait`` spans and its
``fit.memory`` events, read from the telemetry ring epoch by epoch and
not as a median, so that the window's first epoch (the measured call's
second, which every call pays for) is seen and not dropped as the
outlier it is.

The window and which spans are its own are ``program_spans``'s. Every
reader here describes one record, :func:`window_record`, and they are
reported together or not at all: against a program whose dispatch spans
carry no ``signatures`` (the parent of the PR that added the args) the
record is None, so every reader returns None and none raises.
"""

from __future__ import annotations

from benchmarks.harness import program_spans, stats

DISPATCH = "fit.epoch_dispatch"
LOSS_WAIT = "fit.loss_wait"
MEMORY = "fit.memory"
JAX_STAGES = ("jax_trace_s", "jax_lower_s", "jax_compile_s")
# the per-layer metrics whose readers stand on this module
METRICS = (
    "fit_window_stall_share", "fit_first_epoch_excess_ms",
    "fit_dispatch_max_ms", "fit_dispatch_jax_ms",
    "fit_new_signatures_in_window", "fit_loss_wait_first_excess_ms",
    "setup_first_dispatch_s", "setup_first_dispatch_jax_s",
    "fit_hbm_in_use_gb",
)


def jax_seconds(span) -> float:
    """Seconds JAX itself accounts for under a span: tracing, lowering
    and compiling (a cache load is inside the last)."""
    return sum(float(span["args"].get(k, 0.0)) for k in JAX_STAGES)


def _instrumented(spans) -> list:
    return [s for s in spans
            if s["name"] == DISPATCH and "signatures" in s["args"]]


def window_record(run, events=None):
    """The window epoch by epoch: ``{"window_s", "intervals_s",
    "dispatch", "loss_wait", "memory"}``. ``intervals_s`` are the
    seconds between consecutive ``fit.epoch`` events; ``dispatch`` and
    ``loss_wait`` the window's spans in sequence order (a streamed
    epoch has a dispatch a block); ``memory`` its ``fit.memory`` events.
    None where there is no window or no dispatch span with the args."""
    events = program_spans.ring_events() if events is None else events
    found = program_spans.window_spans(run, events)
    if found is None:
        return None
    epochs, spans = found
    dispatch = _instrumented(spans)
    if not dispatch:
        return None
    first, last = epochs[0], epochs[-1]
    return {
        "window_s": (last["mono_ns"] - first["mono_ns"]) / 1e9,
        "intervals_s": [(b["mono_ns"] - a["mono_ns"]) / 1e9
                        for a, b in zip(epochs, epochs[1:])],
        "dispatch": dispatch,
        "loss_wait": [s for s in spans if s["name"] == LOSS_WAIT],
        "memory": [e for e in events if e["name"] == MEMORY
                   and first["seq"] < e["seq"] < last["seq"]],
    }


def first_dispatch(events=None):
    """The process's first ``fit.epoch_dispatch`` (set-up's one-epoch
    call, epoch 0: the epoch program traced, lowered and compiled or
    loaded under it); None where it lacks the args."""
    events = program_spans.ring_events() if events is None else events
    found = _instrumented(events)
    return min(found, key=lambda s: s["seq_begin"]) if found else None


def first_excess_ms(values):
    """The first of ``values`` (seconds) less the median of the others,
    in ms; None where there are no others."""
    if len(values) < 2:
        return None
    return (values[0] - stats.median(values[1:])) * 1e3


def by_epoch_s(spans) -> list:
    """Seconds in ``spans`` by epoch, in the epochs' order (a streamed
    epoch's dispatches add up)."""
    out: dict = {}
    for s in spans:
        out[s["args"]["epoch"]] = out.get(s["args"]["epoch"], 0.0) + s["dur"]
    return [out[k] for k in sorted(out)]


def dispatch_fields(span) -> dict:
    """A dispatch span as a ``[dispatch]`` line carries it: what JAX
    did under it only where it did something."""
    args = span["args"]
    out = {"epoch": args.get("epoch"), "ms": round(span["dur"] * 1e3, 3),
           "new_signature": args.get("new_signature"),
           "signatures": args.get("signatures")}
    if "block" in args:
        out["block"] = args["block"]
    if args.get("jax_events"):
        for key in JAX_STAGES + ("jax_cache_load_s",):
            out[key[:-2] + "_ms"] = round(float(args.get(key, 0.0)) * 1e3, 3)
        for key in ("jax_events", "cache_hits", "cache_misses",
                    "jax_longest"):
            out[key] = args.get(key)
    return out
