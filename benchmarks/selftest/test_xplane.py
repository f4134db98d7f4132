import os

import pytest

from benchmarks.harness import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "toy_v5e.xplane.pb")
# recorded on one TPU v5e chip (PR 23): 20 turns of a 2.9 ms program of
# four matrix products and a 0.1 ms one, inside one bench.window span


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_trace(TRACE)


def test_window_is_the_bench_window_span(reduced):
    assert reduced["window_s"] == pytest.approx(0.3047, abs=1e-3)
    assert reduced["devices"] == 1


def test_busy_is_the_union_of_device_operations(reduced):
    runs = reduced["programs"]
    assert len(runs["jit_big"]) == 20 and len(runs["jit_small"]) == 20
    by_program = sum(b - a for ivs in runs.values() for a, b in ivs)
    assert reduced["busy_s"] <= by_program
    assert reduced["busy_s"] == pytest.approx(by_program, rel=0.02)
    assert 0.05 < reduced["busy_s"] < 0.07


def test_top_operations_have_stable_printed_names(reduced):
    names = [n for n, _s in reduced["device_ops"]]
    assert len(names) <= 10
    assert names[0].startswith("convolution_tanh_fusion")
    assert all(set(n) <= set(
        "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.:-"
    ) and len(n) <= 64 for n in names)
    seconds = [s for _n, s in reduced["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)


def test_idle_gaps_name_their_neighbours_and_add_up(reduced):
    idle = sum(b - a for a, b, _n in reduced["gaps"])
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"], rel=1e-6)
    names = {n for n, _s in reduced["idle_gaps"]}
    assert "jit_big - jit_small -" in names or any(
        n.startswith("jit_big - jit_small") for n in names)
    # a gap inside a program belongs to that program on both sides
    inside = [n for _a, _b, n in reduced["gaps"] if n.startswith("jit_big - jit_big")]
    assert inside


def test_union_and_clip():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert xplane.clip([(0, 10)], 2, 4) == [(2, 4)]
    assert xplane.program_name("jit_per_worker(123)") == "jit_per_worker"
    assert xplane.opcode("%while.3 = (f32[2]{0}) while(%tuple), body=%b") == "while"
