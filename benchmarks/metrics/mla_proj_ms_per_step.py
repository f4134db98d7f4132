"""Device time a training step under the program's mla.proj scope (latent attention's five projections, the latent's norm, the rotation and the assembly of q and k), from the traced run's .xplane.pb."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return xplane_ops.scope_ms_per_step(run, "mla.proj")
