import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import peaks, xplane_ops

TRACE = os.path.join(os.path.dirname(__file__), "data", "toy_v5e.xplane.pb")
CELL = "laguna-fit-seq8k"


@pytest.fixture(scope="module")
def window():
    return mf.load_module("metrics", "attn_window_by_layer_roofline")


@pytest.fixture(scope="module")
def full():
    return mf.load_module("metrics", "attn_full_by_layer_roofline")


def _cell(name):
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, name)
    return {"config": mf.config_of(manifest, cell),
            "traffic": mf.load_json("traffic", cell["traffic"]),
            "device_kind": "TPU v5 lite"}


@pytest.fixture(scope="module")
def cell():
    return _cell(CELL)


def test_the_band_of_512_keys_at_8192_positions(window):
    # 32 blocks of 256: a query block sees its own key block and the two
    # before it (511 keys back from its first query reach two blocks)
    assert window.band_pairs(8192, 512) == 1 + 2 + 30 * 3 == 93
    assert window.band_pairs(8192, 8192) == 32 * 33 // 2 == 528
    assert window.band_pairs(8192, 1) == 32
    # the accepted reader's count, at its cell's band
    accepted = mf.load_module("metrics", "attn_window_roofline")
    for s, w in ((16384, 4096), (8192, 512), (8192, 8192)):
        assert window.band_pairs(s, w) == accepted.band_pairs(s, w)


def test_the_costs_count_each_layers_own_heads(window, full, cell):
    cfg, traffic = cell["config"], cell["traffic"]
    # three sliding layers of 72 heads, two sequences, 7 products
    cost = window.step_cost(cfg, traffic)
    assert cost["flops"] == 2 * 256 * 256 * 128 * 93 * 7 * 2 * 72 * 3
    assert cost["flops"] == pytest.approx(4.72e12, rel=0.002)
    # a token a layer: q and o 72 x 128, k and v 8 x 128; forward reads
    # three and writes o, backward reads those and dO, writes three
    moved = 2 * (2 * 72 + 2 * 8) + 72
    assert cost["bytes"] == 2 * moved * 128 * 8192 * 2 * 3
    # two full layers of 48 heads (layers 0 and 4), the causal triangle
    cost_full = full.step_cost(cfg, traffic)
    assert cost_full["flops"] == 2 * 256 * 256 * 128 * 528 * 7 * 2 * 48 * 2
    moved = 2 * (2 * 48 + 2 * 8) + 48
    assert cost_full["bytes"] == 2 * moved * 128 * 8192 * 2 * 2
    peak = peaks.peaks_for(cell["device_kind"])
    for c in (cost, cost_full):  # the operations bind in both
        assert c["flops"] / peak["bf16_flops"] > (
            c["bytes"] / peak["hbm_bytes_per_s"])
    # the layers are read from the lists, not from one head count
    n = cfg["num_hidden_layers"]
    assert cfg["num_attention_heads_per_layer"][:n] == [48, 72, 72, 72, 48]
    assert cfg["num_attention_heads"] == 48


def test_the_recorded_trace_under_the_scopes_reads_as_shares(
        window, full, cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    for metric, scope in ((window, "attn.window"), (full, "attn.full")):
        renamed = {}
        for n, (path, seconds) in enumerate(sorted(by_path.items())):
            where = f"transpose(jvp({scope}))" if n % 2 else scope
            renamed[path.replace("jit(big)", f"jit(big)/{where}")] = seconds
        run = dict(cell, scope_seconds={"steps": 1.0, "by_path": renamed})
        kind = "sliding_attention" if metric is window else "full_attention"
        cost = window.step_cost(cell["config"], cell["traffic"], kind)
        assert metric.read(run) == pytest.approx(
            100.0 * cost["flops"] / 197e12
            / xplane_ops.under(renamed, scope), rel=1e-6)
        for name, part in (("attn_proj_ms_per_step", "attn.proj"),
                           ("attn_gate_ms_per_step", "attn.gate")):
            ms = mf.load_module("metrics", name)
            assert ms.read(run) is None  # no such scope in this trace
            moved = {k.replace(scope, part): v for k, v in renamed.items()}
            assert ms.read(dict(run, scope_seconds={
                "steps": 2.0, "by_path": moved})) == pytest.approx(
                1e3 * xplane_ops.under(moved, part) / 2.0, rel=1e-9)


@pytest.mark.parametrize("other", [
    "smallthinker-fit-seq16k", "qwen3next-fit-seq8k",
    "nemotron3nano-fit-seq8k", "kanana2-fit-seq8k"])
def test_a_configuration_with_one_head_count_reads_as_nothing(
        window, full, other):
    """Without ``num_attention_heads_per_layer`` (every accepted
    configuration, and this cell's program at the parent commit) both
    readers return nothing, scopes or no scopes, and do not raise."""
    run = _cell(other)
    by_path = xplane_ops.seconds_by_path(TRACE)
    scoped = {p.replace("jit(big)", "jit(big)/attn.window/attn.full"): s
              for p, s in by_path.items()}
    run["scope_seconds"] = {"steps": 1.0, "by_path": scoped}
    assert "num_attention_heads_per_layer" not in run["config"]
    assert window.step_cost(run["config"], run["traffic"]) is None
    assert window.read(run) is None and full.read(run) is None


def test_a_program_without_the_scopes_reads_as_nothing(window, full, cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": by_path})
    assert window.read(run) is None and full.read(run) is None
