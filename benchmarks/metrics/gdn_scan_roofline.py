"""The gated delta rule's operations and bytes a step (the builder's gdn_scan_step_cost, from the shapes) at the chip's binding peak, over the device time under gdn.scan, in percent."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    cost = getattr(run["builder"], "gdn_scan_step_cost", None)
    if cost is None:
        return None
    return xplane_ops.roofline_share(
        run, "gdn.scan", cost(run["config"], run["traffic"]))
