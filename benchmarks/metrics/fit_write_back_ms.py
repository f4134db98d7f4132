"""Median over the window's epochs of the program's fit.write_back span (worker.py, MeshRunner._end_epoch), the call's last one (final) left out."""

from benchmarks.harness import program_spans

LAYER = "epoch runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return program_spans.median_span_ms(run, "fit.write_back", final=False)
