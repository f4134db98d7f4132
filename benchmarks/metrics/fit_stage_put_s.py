"""Stage-in to the device: the measured call's fit.shard_data span (worker.py, run_epochs), the dispatch of the rows' device_put."""

from benchmarks.harness import program_spans

LAYER = "epoch runner"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return program_spans.call_spans_s(run, ("fit.shard_data",))
