"""Device time a training step under the program's ssm.conv scope (the Mamba-2 layers' causal depthwise convolution over x, B and C with its bias and silu, in float32 passes; forward, recomputation and backward), from the traced run's .xplane.pb."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return xplane_ops.scope_ms_per_step(run, "ssm.conv")
