"""Device time by the program's named scopes, from a run's ``.xplane.pb``.

The events of a TPU plane's ``XLA Ops`` line carry the instruction's
text and its time, and nothing of where in the program it came from.
The plane's event metadata does: each operation's ``tf_op`` stat is its
``op_name``, the path of ``jax.named_scope`` names the program wrapped
it in (``.../gdn.scan/...``; ``transpose(jvp(gdn.scan))`` on the way
back). ``jax.profiler.ProfileData`` does not hand metadata stats over,
so this module reads the few fields it needs from the file itself: the
protobuf wire format of ``XSpace`` (tsl/profiler/protobuf/xplane.proto),
with no dependency beyond the standard library.

Against a program without the scopes every function here returns None
or an empty result, and none raises.
"""

from __future__ import annotations

import re

from benchmarks.harness import program_spans, xplane

OPS_LINE = "XLA Ops"
SCOPE_STAT = "tf_op"


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")


def _map_entry(buf):
    """``(key, value bytes)`` of one ``map<int64, Message>`` entry."""
    key, value = 0, b""
    for number, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def device_ops(path: str) -> list:
    """``[(start_ns, end_ns, instruction text, scope path)]`` for every
    event of the first TPU plane's ``XLA Ops`` line. ``scope path`` is
    the operation's ``tf_op`` (empty where it has none)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = [v for number, v in fields(space) if number == 1]
    found = []
    for plane in planes:
        name, lines, event_md, stat_md = "", [], {}, {}
        for number, v in fields(plane):
            if number == 2:
                name = _text(v)
            elif number == 3:
                lines.append(v)
            elif number == 4:
                key, value = _map_entry(v)
                event_md[key] = value
            elif number == 5:
                key, value = _map_entry(v)
                stat_md[key] = value
        if name.startswith("/device:") and "CUSTOM" not in name:
            found.append((name, lines, event_md, stat_md))
    if not found:
        return []
    _name, lines, event_md, stat_md = sorted(found, key=lambda p: p[0])[0]
    scope_ids = set()
    for key, value in stat_md.items():
        for number, v in fields(value):
            if number == 2 and _text(v) == SCOPE_STAT:
                scope_ids.add(key)
    described = {}  # metadata id -> (instruction text, scope path)

    def describe(metadata_id):
        if metadata_id not in described:
            text, scope = "", ""
            for number, v in fields(event_md.get(metadata_id, b"")):
                if number == 2:
                    text = _text(v)
                elif number == 5:  # an XStat of the metadata
                    stat_id, value = 0, None
                    for n2, v2 in fields(v):
                        if n2 == 1:
                            stat_id = v2
                        elif n2 == 5:
                            value = v2
                    if stat_id in scope_ids and value is not None:
                        scope = _text(value)
            described[metadata_id] = (text, scope)
        return described[metadata_id]

    events = []
    for line in lines:
        line_name, t0_ns, raw = "", 0, []
        for number, v in fields(line):
            if number == 2:
                line_name = _text(v)
            elif number == 3:
                t0_ns = v
            elif number == 4:
                raw.append(v)
        if line_name != OPS_LINE:
            continue
        for event in raw:
            metadata_id = offset_ps = duration_ps = 0
            for number, v in fields(event):
                if number == 1:
                    metadata_id = v
                elif number == 2:
                    offset_ps = v
                elif number == 3:
                    duration_ps = v
            text, scope = describe(metadata_id)
            start = t0_ns + offset_ps / 1000.0
            events.append((start, start + duration_ps / 1000.0, text, scope))
    return events


def seconds_by_path(path: str, window=None) -> dict:
    """Device seconds inside ``window`` (``(start_ns, end_ns)``; the
    whole trace where None) by scope path, containers (``while``,
    ``conditional``, ``call``) left out: their children are counted
    themselves. Operations without a path are left out too."""
    sums: dict = {}
    for start, end, text, scope in device_ops(path):
        if not scope or xplane.opcode(text) in xplane.CONTAINERS:
            continue
        if window is not None:
            start, end = max(start, window[0]), min(end, window[1])
            if end <= start:
                continue
        sums[scope] = sums.get(scope, 0.0) + (end - start) / 1e9
    return sums


def under(by_path: dict, scope: str) -> float:
    """The seconds of the paths that hold ``scope``."""
    return sum(v for k, v in by_path.items() if scope in k)


# a name the program gave with ``jax.named_scope``, as this repo writes
# them (``layer.part``), wherever it stands in a path: also inside
# ``transpose(jvp(gdn.scan))``
NAMED_SCOPE = re.compile(r"[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+")


def steps_in_window(run):
    """How many training steps the traced window holds: the epoch
    program's time inside it over one whole run's, times the steps of
    an epoch."""
    from benchmarks.harness import readers

    runs = readers.program_runs(run, "epoch_program")
    if not runs:
        return None
    durations = [b - a for a, b in runs]
    whole = max(durations)
    if whole <= 0:
        return None
    return sum(durations) / whole * int(run["traffic"]["steps_per_epoch"])


def scope_ms_per_step(run, scope: str):
    """Milliseconds a training step of the device time under ``scope``
    (forward, recomputation and backward), from the traced run's own
    file; None for an untraced run or a program without the scope. The
    file is reduced once a run, by path, and kept in the run's record;
    a ``[scopes]`` line then logs every named scope the trace holds,
    those that no metric reads too."""
    if "scope_seconds" not in run:
        path = program_spans.xplane_of(run)
        steps = steps_in_window(run) if path else None
        if not path or not steps:
            run["scope_seconds"] = None
        else:
            window = program_spans.mirrored_spans(path)["window"]
            by_path = seconds_by_path(path, window)
            run["scope_seconds"] = {"steps": steps, "by_path": by_path}
            from benchmarks.harness.runner import say

            named = sorted({n for k in by_path for n in NAMED_SCOPE.findall(k)})
            say("scopes", steps=round(steps, 3),
                ms_per_step={n: round(1e3 * under(by_path, n) / steps, 3)
                             for n in named})
    found = run["scope_seconds"]
    seconds = under(found["by_path"], scope) if found else 0.0
    if not seconds:
        return None
    return 1e3 * seconds / found["steps"]


def roofline_share(run, scope: str, cost: dict):
    """The least time the chip could take for ``cost`` (``flops`` at the
    bf16 peak or ``bytes`` at the memory's, whichever is longer) over
    the time under ``scope``, in percent."""
    from benchmarks.harness import readers

    ms = scope_ms_per_step(run, scope)
    if not ms or cost is None:
        return None
    peaks = readers.peaks(run)
    least_s = max(cost["flops"] / peaks["bf16_flops"],
                  cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)


def window_counters(run, events=None):
    """The ``fit.counters`` events of the window's epochs summed by
    counter over the layers: ``{"epochs", "held_slots", "slots",
    "max_expert_tokens"}``; None where the program emits none."""
    events = program_spans.ring_events() if events is None else events
    epochs = program_spans.window_epochs(run, events)
    if epochs is None:
        return None
    lo, hi = epochs[0]["seq"], epochs[-1]["seq"]
    mine = [e for e in events
            if e["name"] == "fit.counters" and lo < e["seq"] < hi]
    if not mine:
        return None
    total: dict = {"epochs": len(mine)}
    for event in mine:
        for counts in event["args"]["layers"].values():
            for key, value in counts.items():
                total[key] = total.get(key, 0) + int(value)
    return total
