"""Median over the window's epoch intervals of the interval less the fit.* spans inside it: what the tracing still cannot see."""

from benchmarks.harness import program_spans

LAYER = "epoch runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return program_spans.epoch_unspanned_ms(run)
