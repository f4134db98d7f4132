"""Compile events inside the window, from JAX's monitoring events; must read 0."""

from benchmarks.harness import readers

LAYER = "entry points"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return readers.compiles_in_window(run)
