"""Sparse-block calls that took the blocked path (more slots routed to the held experts than the one buffer holds, where the routing plan saves nothing) over all its calls, in percent, over the window's epochs, from the program's fit.counters events."""

from benchmarks.harness import xplane_ops

LAYER = "epoch runner"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    counted = xplane_ops.window_counters(run)
    if counted is None or not counted.get("calls"):
        return None
    return 100.0 * counted.get("blocked_calls", 0) / counted["calls"]
