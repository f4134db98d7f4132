"""Share of the device's idle time in the traced window that lies under a mirrored fit.* span other than fit.call, from the run's own .xplane.pb; logs idle seconds by span in a [gaps] line."""

from benchmarks.harness import program_spans
from benchmarks.harness.runner import say

LAYER = "device"
UNIT = "%"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    path = program_spans.xplane_of(run)
    if path is None:
        return None
    mirrored = program_spans.mirrored_spans(path)
    found = program_spans.idle_under_spans(run["trace"]["gaps"], mirrored)
    if found is None or found["idle_s"] <= 0:
        return None
    say("gaps", idle_s=found["idle_s"], owned_s=found["owned_s"],
        by_span=found["by_span"],
        clock=program_spans.clock_offset(mirrored, program_spans.ring_events()))
    return 100.0 * found["owned_s"] / found["idle_s"]
