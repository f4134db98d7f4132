"""Stage-in on the host: the measured call's fit.partition_arrays, fit.to_mesh (spark_model.py) and fit.stack_batches (worker.py, run_epochs) spans."""

from benchmarks.harness import program_spans

LAYER = "epoch runner"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return program_spans.call_spans_s(
        run, ("fit.partition_arrays", "fit.to_mesh", "fit.stack_batches")
    )
