"""Median over the window's per-epoch readings of examples in an epoch over the seconds between consecutive fit.epoch events, a chip: the steady statistic beside the window's rate."""

from benchmarks.harness import stats

LAYER = "epoch runner"
UNIT = "examples/s/chip"
SOURCE = "host_clock"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    readings = run.get("epochs", {}).get("readings")
    return stats.median(readings) if readings else None
