"""Token slots routed to held experts over all token slots, in percent, over the window's epochs, from the program's fit.counters events (6.25 under uniform routing with 32 of 512 held)."""

from benchmarks.harness import xplane_ops

LAYER = "epoch runner"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    counted = xplane_ops.window_counters(run)
    if counted is None or not counted.get("slots"):
        return None
    return 100.0 * counted["held_slots"] / counted["slots"]
