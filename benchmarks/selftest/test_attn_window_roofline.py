import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import peaks, xplane_ops

TRACE = os.path.join(os.path.dirname(__file__), "data", "toy_v5e.xplane.pb")
CELL = "smallthinker-fit-seq16k"


@pytest.fixture(scope="module")
def metric():
    return mf.load_module("metrics", "attn_window_roofline")


@pytest.fixture(scope="module")
def cell():
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, CELL)
    return {"config": mf.config_of(manifest, cell),
            "traffic": mf.load_json("traffic", cell["traffic"]),
            "device_kind": "TPU v5 lite"}


def test_the_cost_is_the_band_of_the_causal_triangle(metric, cell):
    # 64 blocks of 256: a query block sees its own key block and the 16
    # before it (4095 keys back from its first query reach 16 blocks)
    assert metric.band_pairs(16384, 4096) == 136 + 48 * 17 == 952
    assert metric.band_pairs(16384, 16384) == 64 * 65 // 2
    assert metric.band_pairs(16384, 1) == 64
    assert metric.band_pairs(8192, 4096) == 136 + 16 * 17
    cost = metric.step_cost(cell["config"], cell["traffic"])
    # six window layers of the eight, 28 heads, 1 sequence, 7 products
    assert cost["flops"] == 2 * 256 * 256 * 128 * 952 * 7 * 28 * 6
    assert cost["flops"] / 6 == pytest.approx(3.13e12, rel=0.002)
    # a token a layer: q and o 28 x 128, k and v 4 x 128; forward reads
    # three and writes o, backward reads those and dO, writes three
    moved = 2 * (2 * 28 + 2 * 4) + 28
    assert cost["bytes"] == 2 * moved * 128 * 16384 * 6
    peak = peaks.peaks_for(cell["device_kind"])
    assert cost["flops"] / peak["bf16_flops"] > (
        cost["bytes"] / peak["hbm_bytes_per_s"])  # the operations bind


def test_the_full_layers_reader_takes_this_files_keys(cell):
    """``attn_full_roofline`` counts the two full layers of the eight
    from ``full_attention_interval`` 4, as the hybrid LM's file gives
    it: the causal triangle of 64 blocks, 28 heads over 4."""
    full = mf.load_module("metrics", "attn_full_roofline")
    cost = full.step_cost(cell["config"], cell["traffic"])
    assert cost["flops"] == 2 * 256 * 256 * 128 * 2080 * 7 * 28 * 2
    cfg = cell["config"]
    n = cfg["num_hidden_layers"]
    assert n // cfg["full_attention_interval"] == n - sum(
        cfg["sliding_window_layout"][:n])


def test_the_recorded_trace_under_the_scope_reads_as_a_share(metric, cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    renamed = {}
    for n, (path, seconds) in enumerate(sorted(by_path.items())):
        scope = "transpose(jvp(attn.window))" if n % 2 else "attn.window"
        renamed[path.replace("jit(big)", f"jit(big)/{scope}")] = seconds
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": renamed})
    cost = metric.step_cost(cell["config"], cell["traffic"])
    least = cost["flops"] / 197e12
    assert metric.read(run) == pytest.approx(
        100.0 * least / xplane_ops.under(renamed, "attn.window"), rel=1e-6)
    ms = mf.load_module("metrics", "attn_window_ms_per_step")
    assert ms.read(run) == pytest.approx(
        1e3 * xplane_ops.under(renamed, "attn.window"), rel=1e-9)


def test_a_program_without_the_scope_or_the_layout_reads_as_nothing(
        metric, cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": by_path})
    assert metric.read(run) is None
    other = mf.config_of(
        mf.load_manifest(),
        mf.find_cell(mf.load_manifest(), "kanana2-fit-seq8k"))
    assert metric.read(dict(run, config=other)) is None
