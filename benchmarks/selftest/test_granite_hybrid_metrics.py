import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import peaks, xplane_ops

TRACE = os.path.join(os.path.dirname(__file__), "data", "toy_v5e.xplane.pb")
CELL = "granite4hmicro-fit-seq8k"
OTHER = "nemotron3nano-fit-seq8k"


@pytest.fixture(scope="module")
def cell():
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, CELL)
    config = mf.config_of(manifest, cell)
    return {"config": config,
            "traffic": mf.load_json("traffic", cell["traffic"]),
            "builder": mf.load_module("builders", config["builder"]),
            "device_kind": "TPU v5 lite"}


def test_the_scan_is_counted_at_the_published_chunk_and_one_group(cell):
    builder, cfg = cell["builder"], cell["config"]
    # a token a layer, in multiply-adds: the one group's row of C B^T
    # against the chunk's 256 keys at state 128; a head's row of the
    # masked product at 64 wide; its share of the chunk's state and what
    # the carried state adds, 64 x 128 each
    macs = 1 * 256 * 128 + 64 * (256 * 64 + 64 * 128 + 64 * 128)
    assert builder.scan_macs_per_token_layer(cfg) == macs == 2_129_920
    assert builder.layer_counts(cfg) == {"mamba": 9, "attention": 1}
    cost = builder.ssm_scan_step_cost(cfg, cell["traffic"])
    # forward once and twice that backward, 16384 tokens, nine layers
    assert cost["flops"] == 3 * 2 * macs * 16384 * 9
    assert cost["flops"] == pytest.approx(1.88e12, rel=0.01)
    inputs = 2 * (4096 + 2 * 128) + 4 * 64
    assert cost["bytes"] == (3 * inputs + 2 * 2 * 4096) * 16384 * 9
    peak = peaks.peaks_for(cell["device_kind"])
    # the operations bind here (9.6 ms against 5.4 of bytes): one group
    # moves an eighth of the other cell's B and C and the chunk of 256
    # doubles the masked product
    assert cost["flops"] / peak["bf16_flops"] > (
        cost["bytes"] / peak["hbm_bytes_per_s"])


def test_the_feed_forwards_are_three_products_a_layer(cell):
    builder, cfg = cell["builder"], cell["config"]
    cost = builder.mlp_dense_step_cost(cfg, cell["traffic"])
    assert cost["flops"] == 3 * 2 * 3 * 2048 * 8192 * 16384 * 10
    assert cost["flops"] == pytest.approx(49.5e12, rel=0.01)
    # a token a layer in bfloat16: x and y 2048 each, gate, up and hidden
    # 8192 each, once forward and once backward; the three weights read
    # twice in bfloat16 and their gradients written in float32
    rows = 2 * (2 * 2048 + 3 * 8192) * 16384 * 2
    weights = 3 * 2048 * 8192 * (2 + 2 + 4)
    assert cost["bytes"] == 10 * (rows + weights)
    peak = peaks.peaks_for(cell["device_kind"])
    assert cost["flops"] / peak["bf16_flops"] > 5 * (
        cost["bytes"] / peak["hbm_bytes_per_s"])  # the operations bind


def test_the_recorded_trace_under_the_scopes_reads_as_share_and_ms(cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    renamed = {
        path.replace("jit(big)", "jit(big)/mlp.dense").replace(
            "jit(small)", "jit(small)/transpose(jvp(ssm.conv))"): seconds
        for path, seconds in by_path.items()}
    run = dict(cell, scope_seconds={"steps": 2.0, "by_path": renamed})
    cost = cell["builder"].mlp_dense_step_cost(
        cell["config"], cell["traffic"])
    least = cost["flops"] / 197e12
    share = mf.load_module("metrics", "mlp_dense_roofline")
    assert share.read(run) == pytest.approx(
        100.0 * least / (xplane_ops.under(renamed, "mlp.dense") / 2.0),
        rel=1e-6)
    for name, scope in (("mlp_dense_ms_per_step", "mlp.dense"),
                        ("ssm_conv_ms_per_step", "ssm.conv")):
        assert mf.load_module("metrics", name).read(run) == pytest.approx(
            1e3 * xplane_ops.under(renamed, scope) / 2.0, rel=1e-9)
    assert mf.load_module("metrics", "ssm_norm_ms_per_step").read(run) is None


def test_a_program_without_the_scope_or_the_count_reads_as_nothing(cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": by_path})
    for name in ("mlp_dense_roofline", "mlp_dense_ms_per_step",
                 "ssm_conv_ms_per_step", "ssm_norm_ms_per_step"):
        assert mf.load_module("metrics", name).read(run) is None
    other = mf.load_module("builders", "keras_nemotron_h")
    share = mf.load_module("metrics", "mlp_dense_roofline")
    assert share.read(dict(run, builder=other)) is None


def _chunks(layer, chunk, heads=64, batch=2, length=8192):
    return {"name": "ssd.chunks", "args": {
        "layer": layer, "chunk": chunk, "chunks": length // chunk,
        "heads": heads, "groups": 1,
        "bytes": 4 * batch * heads * (length // chunk) * chunk * chunk}}


def test_the_factors_count_each_distinct_layer_once():
    metric = mf.load_module("metrics", "ssm_scan_factors_gb")
    # this cell: nine mixers at the published chunk of 256, each traced in
    # set-up's call, in the measured one and under recomputation
    nine = [_chunks(f"layer{i}_mamba", 256) for i in range(10) if i != 5]
    events = (nine + [{"name": "fit.epoch", "args": {}}]) * 3
    assert metric.read({}, events) == pytest.approx(9 * 1.0737, abs=1e-3)
    # the other cell: four mixers at a chunk of 128
    four = [_chunks(f"layer{i}_mamba", 128) for i in (0, 2, 4, 7)]
    assert metric.read({}, four) == pytest.approx(4 * 0.5369, abs=1e-3)
    assert metric.read({}, []) is None
    assert metric.read({}, [{"name": "remat.kept", "args": {}}]) is None


NEW = {"mlp_dense_ms_per_step": [CELL], "mlp_dense_roofline": [CELL],
       "ssm_conv_ms_per_step": [CELL, OTHER],
       "ssm_norm_ms_per_step": [CELL, OTHER],
       "ssm_scan_factors_gb": [CELL, OTHER]}


def test_the_entries_repeat_the_files():
    entries = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    for name, cells in NEW.items():
        metric, entry = mf.load_module("metrics", name), entries[name]
        assert (entry["layer"], entry["unit"], entry["source"],
                entry["moves"]) == (
            metric.LAYER, metric.UNIT, metric.SOURCE, metric.MOVES)
        assert entry["workloads"] == cells
        assert entry["better"] == (
            "higher" if name.endswith("_roofline") else "lower")


def test_the_cell_reads_the_shared_scopes_too():
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, CELL)
    names = {m["name"] for m in mf.metrics_of(manifest, cell, "per_layer")}
    assert names >= set(NEW) | {
        "ssm_scan_ms_per_step", "ssm_scan_roofline", "ssm_proj_ms_per_step",
        "attn_full_ms_per_step", "attn_full_roofline",
        "attn_proj_ms_per_step", "attn_kept_gb", "fit_hbm_in_use_gb"}
    assert not {n for n in names if n.startswith("moe_")}
    # one attention layer by the key the attn.full count reads
    step_cost = mf.load_module("metrics", "attn_full_roofline").step_cost
    cfg = mf.config_of(manifest, cell)
    traffic = mf.load_json("traffic", cell["traffic"])
    pairs = 32 * 33 // 2
    assert step_cost(cfg, traffic)["flops"] == (
        2.0 * 256 * 256 * 64 * pairs * 7 * 2 * 32 * 1)
