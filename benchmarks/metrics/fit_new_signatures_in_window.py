"""Dispatches of the window whose call added an entry to the epoch function's dispatch cache (new_signature on fit.epoch_dispatch, from _cache_size(): worker.py, MeshRunner._dispatch_epoch); should read 0."""

from benchmarks.harness import epoch_spans

LAYER = "epoch runner"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    rec = epoch_spans.window_record(run)
    if rec is None:
        return None
    return sum(1 for s in rec["dispatch"] if s["args"].get("new_signature"))
