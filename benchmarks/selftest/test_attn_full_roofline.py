import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import peaks, xplane_ops

TRACE = os.path.join(os.path.dirname(__file__), "data", "toy_v5e.xplane.pb")


@pytest.fixture(scope="module")
def metric():
    return mf.load_module("metrics", "attn_full_roofline")


@pytest.fixture(scope="module")
def cell():
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, "qwen3next-fit-seq8k")
    return {"config": mf.config_of(manifest, cell),
            "traffic": mf.load_json("traffic", cell["traffic"]),
            "device_kind": "TPU v5 lite"}


def test_the_cost_is_the_causal_triangle_of_seven_products(metric, cell):
    cost = metric.step_cost(cell["config"], cell["traffic"])
    # 528 of 32 x 32 block pairs, 32 heads, 2 x 256^3 a product a pair
    assert cost["flops"] == 7 * 528 * 32 * 2 * 256 ** 3
    assert cost["flops"] == pytest.approx(3.97e12, rel=0.002)
    # 16 query and 2 key/value heads of 256 over 2 x 8192 positions
    assert cost["bytes"] == 2 * (2 * 36 + 16) * 256 * 8192 * 2
    peak = peaks.peaks_for(cell["device_kind"])
    assert cost["flops"] / peak["bf16_flops"] > (
        cost["bytes"] / peak["hbm_bytes_per_s"])  # the operations bind


def test_the_recorded_trace_under_the_scope_reads_as_a_share(metric, cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    busy = xplane_ops.under(by_path, "dot_general")
    # the toy program's products as if the model had run them under
    # attn.full, forward and on the way back, over one step
    renamed = {}
    for n, (path, seconds) in enumerate(sorted(by_path.items())):
        scope = "transpose(jvp(attn.full))" if n % 2 else "attn.full"
        renamed[path.replace("jit(big)", f"jit(big)/{scope}")] = seconds
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": renamed})
    least = 3.968e12 / 197e12
    assert metric.read(run) == pytest.approx(
        100.0 * least / xplane_ops.under(renamed, "attn.full"), rel=1e-3)
    assert xplane_ops.under(renamed, "attn.full") >= busy


def test_a_program_without_the_scope_reads_as_nothing(metric, cell):
    by_path = xplane_ops.seconds_by_path(TRACE)
    run = dict(cell, scope_seconds={"steps": 1.0, "by_path": by_path})
    assert metric.read(run) is None
    assert metric.read(dict(cell, trace=None, window={"t0": 0.0, "t1": 1.0},
                            )) is None
