"""What the recomputed attention layers keep of q, k and v for the backward pass (projected, rotated, heads first: what the flash kernels read), in GB over the model's layers, from the program's remat.kept events, which a layer emits as it is traced with the names it keeps and the bytes q, k and v hold by their shapes; memory spent so that the backward pass does not project, rotate and transpose again."""

from benchmarks.harness import program_spans

LAYER = "kernels"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"

EVENT = "remat.kept"
NAMES = ("attn_q", "attn_k", "attn_v")


def read(run, events=None):
    """None against a program that emits no such event (the parent of
    the PR that added it, a model whose layers keep none of the
    three)."""
    events = program_spans.ring_events() if events is None else events
    # a layer is traced in set-up's call and again in the measured one:
    # each distinct layer counts once
    layers = {e["args"]["layer"]: e["args"]["bytes"]
              for e in events if e["name"] == EVENT}
    held = sum(kept.get(name, 0) for kept in layers.values()
               for name in NAMES)
    return held / 1e9 if held else None
