"""jax_trace_s + jax_lower_s + jax_compile_s summed over the window's fit.epoch_dispatch spans: how much of the dispatches JAX's own monitoring events account for (worker.py, JaxWork)."""

from benchmarks.harness import epoch_spans

LAYER = "epoch runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "fit_examples_per_s_per_chip"


def read(run):
    rec = epoch_spans.window_record(run)
    if rec is None:
        return None
    return 1e3 * sum(epoch_spans.jax_seconds(s) for s in rec["dispatch"])
