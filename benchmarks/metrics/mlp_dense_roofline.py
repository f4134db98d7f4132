"""The dense feed-forwards' operations and bytes a step (the builder's mlp_dense_step_cost: three products a token a layer forward and twice that backward, from the shapes; recomputation not counted) at the chip's binding peak, over the device time under mlp.dense, in percent."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    cost = getattr(run["builder"], "mlp_dense_step_cost", None)
    if cost is None:
        return None
    return xplane_ops.roofline_share(
        run, "mlp.dense", cost(run["config"], run["traffic"]))
