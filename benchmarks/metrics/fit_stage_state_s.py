"""Stage-in of the state: the measured call's fit.device_state span (worker.py, run_epochs), every variable to the host and back."""

from benchmarks.harness import program_spans

LAYER = "epoch runner"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return program_spans.call_spans_s(run, ("fit.device_state",))
