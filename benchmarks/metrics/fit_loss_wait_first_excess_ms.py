"""The first window epoch's fit.loss_wait span less the median of the others: the part of the first epoch's excess that the device or the runtime holds, not the host."""

from benchmarks.harness import epoch_spans

LAYER = "epoch runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    rec = epoch_spans.window_record(run)
    if rec is None:
        return None
    return epoch_spans.first_excess_ms(
        epoch_spans.by_epoch_s(rec["loss_wait"]))
