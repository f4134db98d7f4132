"""Device time a training step under the program's mlp.dense scope (the dense SwiGLU feed-forwards: gate, up, silu(gate) * up and down; forward, recomputation and backward), from the traced run's .xplane.pb."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return xplane_ops.scope_ms_per_step(run, "mlp.dense")
