import pytest

from benchmarks.harness import manifest as mf


@pytest.fixture(scope="module")
def metric():
    return mf.load_module("metrics", "attn_kept_gb")


def _kept(layer, heads, kv_heads, tokens=16384, width=128):
    q, kv = (tokens * n * width * 2 for n in (heads, kv_heads))
    return {"name": "remat.kept", "args": {
        "layer": layer,
        "kept": ["attn_q", "attn_k", "attn_v", "flash_out", "flash_lse"],
        "bytes": {"attn_q": q, "attn_k": kv, "attn_v": kv}}}


def test_each_distinct_layer_counts_once(metric):
    # Laguna's five layers (full, three sliding, full), each traced in
    # set-up's call and again in the measured one
    layers = [_kept(f"layer{i}_attn", heads, 8)
              for i, heads in enumerate((48, 72, 72, 72, 48))]
    events = (layers + [{"name": "fit.epoch", "args": {}}]) * 2
    want = 16384 * 128 * 2 * (3 * (72 + 16) + 2 * (48 + 16)) / 1e9
    assert metric.read({}, events) == pytest.approx(want)
    assert want == pytest.approx(1.644, abs=1e-3)
    # bytes under any other name (a later layer's) are not counted
    for event in layers:
        event["args"]["bytes"]["flash_out"] = 1 << 30
    assert metric.read({}, events) == pytest.approx(want)


def test_the_other_two_cells(metric):
    small = [_kept(f"layer{i}_attn", 28, 4) for i in range(8)]
    assert metric.read({}, small) == pytest.approx(1.208, abs=1e-3)
    assert metric.read({}, [_kept("layer5_attn", 32, 2)]) == pytest.approx(
        0.151, abs=1e-3)


def test_a_program_without_the_event_reads_nothing(metric):
    assert metric.read({}, []) is None
    assert metric.read({}, [{"name": "fit.epoch", "args": {}}]) is None
    # a layer that keeps what the forward kernel gave and nothing more
    assert metric.read({}, [{"name": "remat.kept", "args": {
        "layer": "a", "kept": ["flash_out"],
        "bytes": {"flash_out": 1024}}}]) is None


def test_the_entry_repeats_the_file(metric):
    entry = next(m for m in mf.load_manifest()["per_layer"]
                 if m["name"] == "attn_kept_gb")
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        metric.LAYER, metric.UNIT, metric.SOURCE, metric.MOVES)
    assert entry["better"] == "lower"
    assert entry["workloads"] == [
        "smallthinker-fit-seq16k", "nemotron3nano-fit-seq8k",
        "laguna-fit-seq8k"]
