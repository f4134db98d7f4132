"""Device time a training step under the program's attn.proj scope (the attention layers' q, k, v and output projections, the rotation and, where a layer has one, the output gate's projection: forward, recomputation and backward), from the traced run's .xplane.pb."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return xplane_ops.scope_ms_per_step(run, "attn.proj")
