"""What the ``program_span`` readers share: the program's own ``fit.*``
spans, read from the telemetry ring after the measured call and, in a
traced run, from the host plane of the run's ``.xplane.pb``, where the
program mirrors them as ``TraceAnnotation`` events carrying ``seq``.

The window is found on the clock (``mono_ns`` of the ``fit.epoch``
events against ``run["window"]``, monotonic seconds); which spans
belong to it is then decided by sequence number alone, the ring's only
ordering: a span is the window's if it began after the window's first
``fit.epoch`` event and before its last. So the write-back behind the
opening event is out and the one behind the closing event is in: as
many of each per-epoch span as the window has epochs.

Against a program without these spans (the parent of the PR that added
them: no ``fit.*`` span, no ``mono_ns``) every function here returns
None and none raises.
"""

from __future__ import annotations

import os

from benchmarks.harness import manifest as mf
from benchmarks.harness import runner, stats, xplane

ROOT_SPAN = "fit.call"
EPOCH_EVENT = "fit.epoch"
SPAN_PREFIX = "fit."
# the mapped wall stamps that opened and closed the window and the
# events' own monotonic stamps are two readings of one instant
EDGE_S = 1e-3


def ring_events() -> list:
    from elephas_tpu import telemetry

    return telemetry.default_tracer().events()


def window_epochs(run, events) -> list:
    """The ``fit.epoch`` events that opened, divided and closed the
    window, in sequence order; None where there are fewer than two."""
    lo, hi = run["window"]["t0"] - EDGE_S, run["window"]["t1"] + EDGE_S
    found = sorted(
        (e for e in events
         if e["name"] == EPOCH_EVENT and e.get("mono_ns") is not None
         and lo <= e["mono_ns"] / 1e9 <= hi),
        key=lambda e: e["seq"],
    )
    return found if len(found) >= 2 else None


def _spans(events, after_seq: int, before_seq: int) -> list:
    """Complete ``fit.*`` spans other than the root that began between
    two sequence numbers."""
    return [
        e for e in events
        if e["ph"] == "X" and e["name"].startswith(SPAN_PREFIX)
        and e["name"] != ROOT_SPAN
        and after_seq < e["seq_begin"] < before_seq
    ]


def window_spans(run, events=None):
    """``(epochs, spans)``: the window's ``fit.epoch`` events and the
    ``fit.*`` spans that began between its first and its last."""
    events = ring_events() if events is None else events
    epochs = window_epochs(run, events)
    if epochs is None:
        return None
    return epochs, _spans(events, epochs[0]["seq"], epochs[-1]["seq"])


def median_span_ms(run, name: str, events=None, **where):
    """Median duration of the window's spans called ``name`` whose args
    match ``where``."""
    found = window_spans(run, events)
    if found is None:
        return None
    durs = [
        s["dur"] for s in found[1]
        if s["name"] == name
        and all(s["args"].get(k) == v for k, v in where.items())
    ]
    return stats.median(durs) * 1e3 if durs else None


def epoch_unspanned_ms(run, events=None):
    """Median, over the intervals between consecutive ``fit.epoch``
    events of the window, of the interval less what the ``fit.*`` spans
    cover of it: the host time that the tracing still cannot name."""
    events = ring_events() if events is None else events
    epochs = window_epochs(run, events)
    if epochs is None:
        return None
    # the spans of the interval behind the opening event began before it
    spans = _spans(events, -1, epochs[-1]["seq"])
    if not spans:
        return None
    covered = [(s["mono_ns"], s["mono_ns"] + s["dur"] * 1e9) for s in spans]
    rest = []
    for a, b in zip(epochs, epochs[1:]):
        lo, hi = a["mono_ns"], b["mono_ns"]
        inside = xplane.union(xplane.clip(covered, lo, hi))
        rest.append((hi - lo) - sum(e - s for s, e in inside))
    return stats.median(rest) / 1e6


def call_spans_s(run, names, events=None):
    """Seconds in the spans called one of ``names`` inside the
    ``fit.call`` span that holds the window: stage-in by owner."""
    events = ring_events() if events is None else events
    epochs = window_epochs(run, events)
    if epochs is None:
        return None
    at = epochs[0]["seq"]
    calls = [
        e for e in events
        if e["name"] == ROOT_SPAN and e["ph"] == "X"
        and e["seq_begin"] < at < e["seq"]
    ]
    if not calls:
        return None
    inside = _spans(events, calls[0]["seq_begin"], calls[0]["seq"])
    durs = [s["dur"] for s in inside if s["name"] in names]
    return sum(durs) if durs else None


# -- the traced run's own file --------------------------------------------


def xplane_of(run):
    """The ``.xplane.pb`` that this run's driver recorded: under the
    work directory, in the directory of the cell whose configuration
    and traffic the run carries. None for an untraced run."""
    if not run.get("trace"):
        return None
    manifest = mf.load_manifest()
    for cell in manifest["workloads"]:
        if (mf.config_of(manifest, cell) != run["config"]
                or mf.load_json("traffic", cell["traffic"]) != run["traffic"]):
            continue
        try:
            return xplane.find_xplane(
                os.path.join(
                    mf.ROOT, runner.WORK_DIRNAME, "trace", cell["name"])
            )
        except FileNotFoundError:
            return None
    return None


def mirrored_spans(path: str) -> dict:
    """``{"window": (start_ns, end_ns) or None, "spans": [(start_ns,
    end_ns, name, seq)]}`` from the host plane: the benchmark's
    ``bench.window`` and the program's mirrored ``fit.*`` events."""
    from jax.profiler import ProfileData

    window, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == xplane.WINDOW_SPAN and window is None:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(SPAN_PREFIX):
                    seq = dict(e.stats).get("seq")
                    spans.append((
                        e.start_ns, e.start_ns + e.duration_ns, e.name,
                        None if seq is None else int(seq),
                    ))
    return {"window": window, "spans": sorted(spans)}


def idle_under_spans(gaps, mirrored: dict):
    """Idle seconds of the device by the ``fit.*`` span (the root
    aside) that the host was in: ``{"idle_s", "owned_s", "by_span"}``.
    ``gaps`` are ``reduce_trace``'s, seconds from the start of the
    ``bench.window`` span; None where the trace holds no mirrored span
    or no such window."""
    spans = [s for s in mirrored["spans"] if s[2] != ROOT_SPAN and s[1] > s[0]]
    if not spans or not gaps or mirrored["window"] is None:
        return None
    lo = mirrored["window"][0]
    by_name: dict[str, list] = {}
    for a, b, name, _seq in spans:
        by_name.setdefault(name, []).append(((a - lo) / 1e9, (b - lo) / 1e9))
    everything = xplane.union(iv for ivs in by_name.values() for iv in ivs)
    idle = owned = 0.0
    by_span = dict.fromkeys(by_name, 0.0)
    for a, b, _name in gaps:
        idle += b - a
        owned += sum(e - s for s, e in xplane.clip(everything, a, b))
        for name, ivs in by_name.items():
            by_span[name] += sum(e - s for s, e in xplane.clip(ivs, a, b))
    return {"idle_s": idle, "owned_s": owned, "by_span": by_span}


def clock_offset(mirrored: dict, events) -> dict:
    """The ring's record and the trace's record of one span join on
    ``(name, seq)``: the offset between ``mono_ns`` and the trace's
    clock over the joined pairs, and how far it spreads."""
    ring = {
        (e["name"], e.get("seq_begin", e["seq"])): e["mono_ns"]
        for e in events if e.get("mono_ns") is not None
    }
    offsets = sorted(
        ring[(name, seq)] - start for start, _end, name, seq in
        mirrored["spans"] if (name, seq) in ring
    )
    if not offsets:
        return {"joined": 0}
    return {"joined": len(offsets),
            "offset_ns_median": stats.median(offsets),
            "offset_ns_spread": offsets[-1] - offsets[0]}
