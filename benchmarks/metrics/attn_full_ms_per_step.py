"""Device time a training step under the program's attn.full scope (the gated-attention layer's flash kernel, its backward pass and its output gate), from the traced run's .xplane.pb."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return xplane_ops.scope_ms_per_step(run, "attn.full")
