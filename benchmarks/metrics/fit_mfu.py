"""Shape-derived forward and backward FLOPs an example times the window's examples/s/chip over the chip's bf16 peak."""

from benchmarks.harness import readers

LAYER = "kernels"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    rate = run.get("epochs", {}).get("rate")
    if not rate:
        return None
    flops = run["builder"].train_flops_per_example(run["config"], run["traffic"])
    return 100.0 * flops * rate / readers.peaks(run)["bf16_flops"]
