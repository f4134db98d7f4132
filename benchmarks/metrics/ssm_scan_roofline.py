"""The selective scan's operations and bytes a step (the builder's ssm_scan_step_cost: the chunked form at the published chunk from the shapes, whatever implements the rule) at the chip's binding peak, over the device time under ssm.scan, in percent."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    cost = getattr(run["builder"], "ssm_scan_step_cost", None)
    if cost is None:
        return None
    return xplane_ops.roofline_share(
        run, "ssm.scan", cost(run["config"], run["traffic"]))
