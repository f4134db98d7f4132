"""The grouped products' operations and bytes a step for the token slots the layers' counters say were routed here (the builder's moe_experts_step_cost) at the chip's binding peak, over the device time under moe.experts, in percent."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    cost = getattr(run["builder"], "moe_experts_step_cost", None)
    counted = xplane_ops.window_counters(run)
    if cost is None or counted is None:
        return None
    steps = counted["epochs"] * int(run["traffic"]["steps_per_epoch"])
    return xplane_ops.roofline_share(
        run, "moe.experts",
        cost(run["config"], run["traffic"], counted["held_slots"] / steps))
