"""Median over the window's epochs of the program's fit.callbacks span (worker.py, MeshRunner._end_epoch): what SparkModel.fit hangs on each epoch."""

from benchmarks.harness import program_spans

LAYER = "entry points"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return program_spans.median_span_ms(run, "fit.callbacks")
