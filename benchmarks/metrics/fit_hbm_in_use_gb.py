"""The largest bytes_in_use of the window's fit.memory events (worker.py, MeshRunner._emit_memory: the allocator on the fullest chip once an epoch, after the loss is read): the state resident between epochs; a [memory] line logs the epochs at which the allocator's peak rose."""

from benchmarks.harness import epoch_spans
from benchmarks.harness.runner import say

LAYER = "device"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    rec = epoch_spans.window_record(run)
    if rec is None or not rec["memory"]:
        return None
    events = [e["args"] for e in rec["memory"]]
    say("memory",
        bytes_in_use=[a["bytes_in_use"] for a in events],
        peak_bytes_in_use=max(a["peak_bytes_in_use"] for a in events),
        peak_rose_at=[a["epoch"] for a in events if a["peak_rose"]])
    return max(a["bytes_in_use"] for a in events) / 1e9
