"""jax_trace_s + jax_lower_s + jax_compile_s of the process's first fit.epoch_dispatch span; a [dispatch] line gives the three apart, the cache load inside the last, and the cache's hits and misses."""

from benchmarks.harness import epoch_spans
from benchmarks.harness.runner import say

LAYER = "epoch runner"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    span = epoch_spans.first_dispatch()
    if span is None:
        return None
    say("dispatch", where="setup", **epoch_spans.dispatch_fields(span))
    return epoch_spans.jax_seconds(span)
