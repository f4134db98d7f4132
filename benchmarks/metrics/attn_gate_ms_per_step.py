"""Device time a training step under the program's attn.gate scope (the per-head output gate: its sigmoid and the multiplication of each head's result by it, forward, recomputation and backward; the gate's projection runs under attn.proj), from the traced run's .xplane.pb."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return xplane_ops.scope_ms_per_step(run, "attn.gate")
