"""Process start to the start of the measured window: loading, building, warm-up, compilation and, for fit, stage-in."""

LAYER = "end to end"
UNIT = "s"
SOURCE = "host_clock"
MOVES = None


def read(run):
    return run["setup_s"]
