import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import peaks, xplane_ops

TRACE = os.path.join(os.path.dirname(__file__), "data", "toy_v5e.xplane.pb")
CELL = "nemotron3nano-fit-seq8k"


@pytest.fixture(scope="module")
def cell():
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, CELL)
    config = mf.config_of(manifest, cell)
    return {"config": config,
            "traffic": mf.load_json("traffic", cell["traffic"]),
            "builder": mf.load_module("builders", config["builder"]),
            "device_kind": "TPU v5 lite"}


def test_the_cost_is_the_chunked_form_at_the_published_chunk(cell):
    builder, cfg = cell["builder"], cell["config"]
    # a token a layer, in multiply-adds: a group's row of C B^T against
    # the chunk's 128 keys at state 128; a head's row of the masked
    # product at 64 wide; its share of the chunk's state and what the
    # carried state adds, 64 x 128 each
    macs = 8 * 128 * 128 + 64 * (128 * 64 + 64 * 128 + 64 * 128)
    assert builder.scan_macs_per_token_layer(cfg) == macs == 1_703_936
    assert builder.layer_counts(cfg) == {"M": 4, "E": 4, "*": 1}
    cost = builder.ssm_scan_step_cost(cfg, cell["traffic"])
    # forward once and twice that backward, 16384 tokens, four layers
    assert cost["flops"] == 3 * 2 * macs * 16384 * 4
    assert cost["flops"] == pytest.approx(0.67e12, rel=0.01)
    # x and y 4096 and B and C 1024 each in bfloat16, dt 64 in float32:
    # read and written once forward; read again with dy and written as
    # four gradients backward
    inputs = 2 * (4096 + 2 * 1024) + 4 * 64
    assert cost["bytes"] == (3 * inputs + 2 * 2 * 4096) * 16384 * 4
    peak = peaks.peaks_for(cell["device_kind"])
    assert cost["bytes"] / peak["hbm_bytes_per_s"] > (
        cost["flops"] / peak["bf16_flops"])  # the bytes bind: 4.3 ms


def test_the_experts_are_two_products(cell):
    builder, cfg = cell["builder"], cell["config"]
    cost = builder.moe_experts_step_cost(cfg, cell["traffic"], 24576.0)
    assert cost["flops"] == 3 * 2 * 2 * 2688 * 1856 * 24576
    weights = 8 * 2 * 2688 * 1856 * 4
    rows = 24576 * (2 * 2688 + 2 * 1856) * 2
    assert cost["bytes"] == weights * 8 + 3 * rows


def test_the_recorded_trace_under_the_scopes_reads_as_share_and_ms(cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    # the trace's two programs stand for the mixer's projections and
    # for its scan's backward pass
    renamed = {
        path.replace("jit(big)", "jit(big)/ssm.proj").replace(
            "jit(small)", "jit(small)/transpose(jvp(ssm.scan))"): seconds
        for path, seconds in by_path.items()}
    assert xplane_ops.under(renamed, "ssm.scan") > 0
    assert xplane_ops.under(renamed, "ssm.proj") > 0
    run = dict(cell, scope_seconds={"steps": 2.0, "by_path": renamed})
    cost = cell["builder"].ssm_scan_step_cost(cell["config"], cell["traffic"])
    least = cost["bytes"] / 819e9
    share = mf.load_module("metrics", "ssm_scan_roofline")
    assert share.read(run) == pytest.approx(
        100.0 * least / (xplane_ops.under(renamed, "ssm.scan") / 2.0),
        rel=1e-6)
    for name, scope in (("ssm_scan_ms_per_step", "ssm.scan"),
                        ("ssm_proj_ms_per_step", "ssm.proj")):
        assert mf.load_module("metrics", name).read(run) == pytest.approx(
            1e3 * xplane_ops.under(renamed, scope) / 2.0, rel=1e-9)


def test_a_program_without_the_scope_or_the_count_reads_as_nothing(cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": by_path})
    for name in ("ssm_scan_roofline", "ssm_scan_ms_per_step",
                 "ssm_proj_ms_per_step"):
        assert mf.load_module("metrics", name).read(run) is None
    other = mf.load_module("builders", "keras_resnet")
    share = mf.load_module("metrics", "ssm_scan_roofline")
    assert share.read(dict(run, builder=other)) is None
