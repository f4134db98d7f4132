"""1 - union of device operations over the traced window."""

from benchmarks.harness import readers

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return readers.device_idle_share(run)
