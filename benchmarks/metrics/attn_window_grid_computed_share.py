"""Grid steps that compute over all the grid steps of the three flash kernels under a sliding window (forward, dK/dV, dQ, a head and sequence at the blocks each resolved), in percent, from the program's flash.grid events, which it emits as it traces each windowed kernel call; a grid over every pair of blocks steps over the pairs the band empties, a grid of the band alone does not."""

from benchmarks.harness import program_spans

LAYER = "kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "fit_examples_per_s_per_chip"

EVENT = "flash.grid"


def read(run, events=None):
    """None against a program that emits no such event (the parent of
    the PR that added it, a model without a windowed layer)."""
    events = program_spans.ring_events() if events is None else events
    # a kernel is traced once a layer and again under recomputation:
    # each distinct grid counts once
    grids = {tuple(sorted(e["args"].items())): e["args"]
             for e in events if e["name"] == EVENT}.values()
    steps = sum(grid["steps"] for grid in grids)
    if not steps:
        return None
    return 100.0 * sum(grid["computing"] for grid in grids) / steps
