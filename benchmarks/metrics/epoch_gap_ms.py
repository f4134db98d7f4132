"""Median idle time between the end of one epoch program and the start of the next, from the trace."""

from benchmarks.harness import readers

LAYER = "epoch runner"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return readers.gap_between_runs_ms(run, "epoch_program")
