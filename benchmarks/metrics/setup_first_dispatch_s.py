"""The process's first fit.epoch_dispatch span: set-up's one-epoch call, epoch 0, under which the epoch program is traced, lowered and compiled or loaded."""

from benchmarks.harness import epoch_spans

LAYER = "epoch runner"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    span = epoch_spans.first_dispatch()
    return None if span is None else span["dur"]
