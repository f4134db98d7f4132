"""Device time a training step under the program's ssm.norm scope (the Mamba-2 layers' gate and grouped RMS norm, y * silu(z) then the norm, in float32 passes; forward, recomputation and backward), from the traced run's .xplane.pb."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return xplane_ops.scope_ms_per_step(run, "ssm.norm")
