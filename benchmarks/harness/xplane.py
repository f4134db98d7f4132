"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the
device plane ``/device:TPU:<n>`` carries the lines ``XLA Ops`` (one event
for each operation that ran) and ``XLA Modules`` (one for each compiled
program), on the same clock as the host plane ``/host:CPU``, whose
thread lines carry the ``TraceAnnotation`` spans the benchmark wraps its
own calls in (names starting ``bench.``).

Busy is the union of the operations' intervals inside the window, idle
the complement. Each idle gap is named after the programs that ran
before and after it and the innermost ``bench.`` span that encloses it,
so a gap's owner reads off the line.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# containers whose own event spans their children's: counted in the busy
# union (which is a union), left out of the per-operation sums
CONTAINERS = ("while", "conditional", "call")
_UNSAFE = re.compile(r"[^A-Za-z0-9_.:\-]")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def stable_name(event_name: str, width: int = 64) -> str:
    """An operation's printed name: its HLO text cut and stripped of
    what a line of JSON or a shell would trip on."""
    return _UNSAFE.sub("_", event_name.lstrip("%"))[:width]


def program_name(module_event: str) -> str:
    """``jit_per_worker(123456)`` -> ``jit_per_worker``."""
    return module_event.split("(", 1)[0]


def opcode(event_name: str) -> str:
    head, _eq, rest = event_name.partition(" = ")
    found = _OPCODE.search(" " + rest) if rest else None
    return found.group(1) if found else ""


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def clip(intervals, lo, hi) -> list:
    return [
        (max(a, lo), min(b, hi)) for a, b in intervals
        if min(b, hi) > max(a, lo)
    ]


def read_planes(path: str) -> dict:
    """``{"devices": [{"name", "ops", "modules"}], "spans": [...]}``
    with every event as ``(start_ns, end_ns, name)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue

            def events(line_name):
                line = lines.get(line_name)
                return [] if line is None else [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                ]

            devices.append({
                "name": plane.name,
                "ops": events("XLA Ops"),
                "modules": events("XLA Modules"),
            })
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        )
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "spans": sorted(spans)}


def _enclosing(spans, at_ns) -> str:
    """Innermost ``bench.`` span (the window span aside) holding ``at_ns``."""
    best = None
    for start, end, name in spans:
        if name != WINDOW_SPAN and start <= at_ns <= end:
            if best is None or end - start < best[1] - best[0]:
                best = (start, end, name)
    return best[2] if best else "-"


def _neighbour(modules, at_ns, before: bool) -> str:
    """The program that the operation before (after) a gap belongs to:
    the one that started last before the gap's start (the one that
    starts first among those still to end after the gap's end)."""
    if before:
        started = [m for m in modules if m[0] < at_ns]
        return (program_name(max(started, key=lambda m: m[0])[2])
                if started else "window_edge")
    to_end = [m for m in modules if m[1] > at_ns]
    return (program_name(min(to_end, key=lambda m: m[0])[2])
            if to_end else "window_edge")


def reduce_trace(path: str, top: int = 10) -> dict:
    """Everything the per-layer readers take from a trace.

    ``window_s`` is the ``bench.window`` span (the whole trace where
    there is none); ``busy_s`` the operations' union inside it, averaged
    over the devices; ``device_ops`` and ``idle_gaps`` the ``top``
    largest sums by name; ``programs`` each program's runs inside the
    window as ``(start_s, end_s)`` from the window's start, on the first
    device; ``gaps`` the idle gaps there as ``(start_s, end_s, name)``."""
    planes = read_planes(path)
    devices, spans = planes["devices"], planes["spans"]
    if not devices:
        raise ValueError(f"{path}: no device plane with an 'XLA Ops' line")
    window = [s for s in spans if s[2] == WINDOW_SPAN]
    if window:
        lo, hi = window[0][0], window[0][1]
    else:
        lo = min(d["ops"][0][0] for d in devices if d["ops"])
        hi = max(max(e[1] for e in d["ops"]) for d in devices if d["ops"])
    busy = []
    for dev in devices:
        merged = union(clip([(a, b) for a, b, _n in dev["ops"]], lo, hi))
        busy.append(sum(b - a for a, b in merged))
    first = devices[0]
    op_sums: dict[str, float] = {}
    for a, b, name in first["ops"]:
        if opcode(name) in CONTAINERS:
            continue
        for ca, cb in clip([(a, b)], lo, hi):
            key = stable_name(name)
            op_sums[key] = op_sums.get(key, 0.0) + (cb - ca)
    merged = union(clip([(a, b) for a, b, _n in first["ops"]], lo, hi))
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    gaps, gap_sums = [], {}
    modules = clip_named(first["modules"], lo, hi)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        name = "%s - %s %s" % (
            _neighbour(modules, a, True) if a > lo else "window_edge",
            _neighbour(modules, b, False) if b < hi else "window_edge",
            _enclosing(spans, (a + b) / 2),
        )
        gaps.append(((a - lo) / 1e9, (b - lo) / 1e9, name))
        gap_sums[name] = gap_sums.get(name, 0.0) + (b - a)
    programs: dict[str, list] = {}
    for a, b, name in modules:
        programs.setdefault(program_name(name), []).append(
            ((a - lo) / 1e9, (b - lo) / 1e9)
        )

    def largest(sums):
        rows = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / 1e9] for name, ns in rows]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "devices": len(devices),
        "device_ops": largest(op_sums),
        "idle_gaps": largest(gap_sums),
        "programs": programs,
        "gaps": gaps,
        "spans": [((a - lo) / 1e9, (b - lo) / 1e9, n) for a, b, n in spans],
    }


def clip_named(events, lo, hi) -> list:
    return [
        (max(a, lo), min(b, hi), n) for a, b, n in events
        if min(b, hi) > max(a, lo)
    ]
