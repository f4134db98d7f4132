"""Bytes of one float32 [B, H, S / Q, Q, Q] factor of the chunked selective scan's masked product, summed over the Mamba-2 layers, in GB, from the program's ssd.chunks events, which a mixer emits as it is traced with the chunk Q it runs and the factor's bytes by its shapes: a count, not a time; it says which chunk the program ran and what a kernel that keeps a chunk's factors in fast memory would stop writing."""

from benchmarks.harness import program_spans

LAYER = "kernels"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"

EVENT = "ssd.chunks"


def read(run, events=None):
    """None against a program that emits no such event (the parent of
    the PR that added it, a model without a Mamba-2 layer)."""
    events = program_spans.ring_events() if events is None else events
    # a layer is traced in set-up's call, again in the measured one and
    # again under recomputation: each distinct layer counts once
    layers = {e["args"]["layer"]: e["args"]["bytes"]
              for e in events if e["name"] == EVENT}
    held = sum(layers.values())
    return held / 1e9 if held else None
