"""The fullest held expert's tokens over the mean of the held experts', summed over the window's steps and layers, from the program's fit.counters events: the imbalance the dropless path absorbed."""

from benchmarks.harness import xplane_ops

LAYER = "epoch runner"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    counted = xplane_ops.window_counters(run)
    if counted is None or not counted.get("held_slots"):
        return None
    held = int(run["config"]["num_experts_held"])
    return counted["max_expert_tokens"] / (counted["held_slots"] / held)
