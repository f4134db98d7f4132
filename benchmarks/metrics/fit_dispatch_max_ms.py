"""The longest fit.epoch_dispatch span of the window (worker.py, MeshRunner._dispatch_epoch); a [dispatch] line logs every epoch's, with its new_signature and what JAX did under it."""

from benchmarks.harness import epoch_spans
from benchmarks.harness.runner import say

LAYER = "epoch runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    rec = epoch_spans.window_record(run)
    if rec is None:
        return None
    say("dispatch", where="window",
        epochs=[epoch_spans.dispatch_fields(s) for s in rec["dispatch"]])
    return max(s["dur"] for s in rec["dispatch"]) * 1e3
