import pytest

from benchmarks.harness import manifest as mf


@pytest.fixture(scope="module")
def metric():
    return mf.load_module("metrics", "attn_window_grid_computed_share")


def _grid(kernel, blocks, steps, computing, window=512):
    return {"name": "flash.grid", "args": {
        "kernel": kernel, "window": window, "block_q": blocks[0],
        "block_k": blocks[1], "steps": steps, "computing": computing}}


def test_each_distinct_grid_counts_once(metric):
    # Laguna's three kernels on the band's grid, the forward kernel
    # traced for three layers and again under recomputation
    events = [_grid("fwd", (512, 1024), 32, 23)] * 6 + [
        _grid("dkv", (512, 512), 32, 31), _grid("dq", (512, 512), 32, 31),
        {"name": "fit.epoch", "args": {}}] * 3
    assert metric.read({}, events) == pytest.approx(100 * 85 / 96)
    # the grid over every pair of blocks at the same blocks
    full = [_grid("fwd", (512, 1024), 128, 23),
            _grid("dkv", (512, 512), 256, 31),
            _grid("dq", (512, 512), 256, 31)]
    assert metric.read({}, full) == pytest.approx(100 * 85 / 640)


def test_a_program_without_the_event_reads_nothing(metric):
    assert metric.read({}, []) is None
    assert metric.read({}, [{"name": "fit.epoch", "args": {}}]) is None


def test_the_entry_repeats_the_file(metric):
    entry = next(m for m in mf.load_manifest()["per_layer"]
                 if m["name"] == "attn_window_grid_computed_share")
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        metric.LAYER, metric.UNIT, metric.SOURCE, metric.MOVES)
    assert entry["workloads"] == ["smallthinker-fit-seq16k", "laguna-fit-seq8k"]
