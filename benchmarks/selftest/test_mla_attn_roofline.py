import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import peaks, xplane_ops

TRACE = os.path.join(os.path.dirname(__file__), "data", "toy_v5e.xplane.pb")


@pytest.fixture(scope="module")
def metric():
    return mf.load_module("metrics", "mla_attn_roofline")


@pytest.fixture(scope="module")
def cell():
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, "kanana2-fit-seq8k")
    return {"config": mf.config_of(manifest, cell),
            "traffic": mf.load_json("traffic", cell["traffic"]),
            "device_kind": "TPU v5 lite"}


def test_the_cost_is_the_causal_triangle_at_two_widths(metric, cell):
    cost = metric.step_cost(cell["config"], cell["traffic"])
    layers = cell["config"]["num_hidden_layers"]
    # 528 of 32 x 32 block pairs, 32 heads, 2 sequences; four products
    # 192 wide (q k^T twice, ds k, ds^T q) and three 128 wide
    assert cost["flops"] == (
        2 * 256 * 256 * (4 * 192 + 3 * 128) * 528 * 32 * 2 * layers)
    assert cost["flops"] / layers == pytest.approx(5.10e12, rel=0.002)
    # a token a layer: q 32 x 192, k 32 x 128 + 64, v and o 32 x 128
    forward = 32 * 192 + 32 * 128 + 64 + 2 * 32 * 128
    backward = forward + 32 * 128 + 32 * 192 + 32 * 128 + 64 + 32 * 128
    assert cost["bytes"] == 2 * (forward + backward) * 8192 * 2 * layers
    peak = peaks.peaks_for(cell["device_kind"])
    assert cost["flops"] / peak["bf16_flops"] > (
        cost["bytes"] / peak["hbm_bytes_per_s"])  # the operations bind


def test_the_recorded_trace_under_the_scope_reads_as_a_share(metric, cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    renamed = {}
    for n, (path, seconds) in enumerate(sorted(by_path.items())):
        scope = "transpose(jvp(attn.mla))" if n % 2 else "attn.mla"
        renamed[path.replace("jit(big)", f"jit(big)/{scope}")] = seconds
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": renamed})
    cost = metric.step_cost(cell["config"], cell["traffic"])
    least = cost["flops"] / 197e12
    assert metric.read(run) == pytest.approx(
        100.0 * least / xplane_ops.under(renamed, "attn.mla"), rel=1e-6)


def test_a_program_without_the_scope_or_the_widths_reads_as_nothing(
        metric, cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": by_path})
    assert metric.read(run) is None
    other = mf.config_of(
        mf.load_manifest(),
        mf.find_cell(mf.load_manifest(), "qwen3next-fit-seq8k"))
    assert metric.read(dict(run, config=other)) is None
