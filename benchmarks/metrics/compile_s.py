"""Seconds in the backend compiler (cache retrieval included) during set-up, from JAX's monitoring events."""

LAYER = "entry points"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return run["compile"]["setup"]["seconds"]
