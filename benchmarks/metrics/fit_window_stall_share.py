"""Share of the whole window in which the host was not blocked on the epoch program: 100 x (window seconds less the window's fit.loss_wait spans) / window seconds. The loop is serial (dispatch, block on the loss, tail), so this bounds the device's idle share over all the window's epochs from above, its first included, which no trace holds."""

from benchmarks.harness import epoch_spans

LAYER = "epoch runner"
UNIT = "%"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    rec = epoch_spans.window_record(run)
    if rec is None or rec["window_s"] <= 0:
        return None
    waited = sum(s["dur"] for s in rec["loss_wait"])
    return 100.0 * (rec["window_s"] - waited) / rec["window_s"]
