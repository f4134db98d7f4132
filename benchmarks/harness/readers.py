"""Reductions that several metric readers share. A metric's own module
under ``metrics/`` names its layer, unit, source and the end-to-end
metric it moves, and calls one of these on the run's record; what finds
nothing to read returns None."""

from __future__ import annotations

from benchmarks.harness import stats
from benchmarks.harness.peaks import peaks_for


def compiles_in_window(run):
    return float(run["compile"]["window"]["compiles"])


def program_runs(run, key: str):
    """``(start_s, end_s)`` runs in the traced window of the program
    whose name holds the traffic file's ``key`` (``epoch_program``)."""
    trace, wanted = run.get("trace"), run["traffic"].get(key)
    if not trace or not wanted:
        return None
    runs = [
        iv for name, ivs in trace["programs"].items() if wanted in name
        for iv in ivs
    ]
    return sorted(runs) or None


def gap_between_runs_ms(run, key: str):
    """Median time from the end of one run of the program to the start
    of its next."""
    runs = program_runs(run, key)
    if not runs or len(runs) < 2:
        return None
    return stats.median(
        [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    ) * 1e3


def device_idle_share(run):
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def hbm_peak_gb(run):
    return run["memory"]["memory_peak_bytes"] / 1e9


def peaks(run) -> dict:
    return peaks_for(run["device_kind"])
