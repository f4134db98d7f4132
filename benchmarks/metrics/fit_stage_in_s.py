"""fit() call to the start of its first epoch: time to the first fit.epoch event less one median epoch."""

from benchmarks.harness import stats

LAYER = "epoch runner"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    epochs = run.get("epochs")
    if not epochs or len(epochs["stamps"]) < 3:
        return None
    first = epochs["stamps"][0] - run["fit_call"]["t_call"]
    return first - stats.median(stats.epoch_intervals(epochs["stamps"]))
