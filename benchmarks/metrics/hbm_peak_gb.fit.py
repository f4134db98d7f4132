"""Allocator peak on the fullest chip plus the largest program's temporaries (memory_analysis), which that peak leaves out."""

from benchmarks.harness import readers

LAYER = "device"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return readers.hbm_peak_gb(run)
