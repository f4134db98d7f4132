"""Median over the window's epochs of the program's fit.loss_wait span (worker.py, run_epochs): the host blocked on the epoch program."""

from benchmarks.harness import program_spans

LAYER = "epoch runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return program_spans.median_span_ms(run, "fit.loss_wait")
