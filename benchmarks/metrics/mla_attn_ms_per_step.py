"""Device time a training step under the program's attn.mla scope (latent attention's flash kernel, forward, recomputed and its two backward kernels, with the layout copies around them), from the traced run's .xplane.pb."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    return xplane_ops.scope_ms_per_step(run, "attn.mla")
