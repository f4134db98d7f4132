"""The window's first interval between fit.epoch events (the measured call's second epoch) less the median of its others; a [first_epoch] line splits the excess into fit.epoch_dispatch (and what JAX accounts for of it), fit.loss_wait and the tail."""

from benchmarks.harness import epoch_spans
from benchmarks.harness.runner import say

LAYER = "epoch runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    rec = epoch_spans.window_record(run)
    if rec is None:
        return None
    excess = epoch_spans.first_excess_ms(rec["intervals_s"])
    if excess is None:
        return None
    dispatch = epoch_spans.first_excess_ms(
        epoch_spans.by_epoch_s(rec["dispatch"]))
    waited = epoch_spans.first_excess_ms(
        epoch_spans.by_epoch_s(rec["loss_wait"]))
    first = rec["dispatch"][0]["args"]["epoch"]
    jax_ms = 1e3 * sum(epoch_spans.jax_seconds(s) for s in rec["dispatch"]
                       if s["args"]["epoch"] == first)
    say("first_epoch", excess_ms=excess, dispatch_excess_ms=dispatch,
        dispatch_jax_ms=jax_ms, loss_wait_excess_ms=waited,
        tail_excess_ms=None if dispatch is None or waited is None
        else excess - dispatch - waited,
        intervals_s=[round(v, 4) for v in rec["intervals_s"]])
    return excess
