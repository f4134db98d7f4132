"""All the examples of the window's epochs over all its seconds, a chip: the window runs from the first fit.epoch event of the measured fit call to its last."""

LAYER = "end to end"
UNIT = "examples/s/chip"
SOURCE = "host_clock"
MOVES = None


def read(run):
    return run.get("epochs", {}).get("rate")
