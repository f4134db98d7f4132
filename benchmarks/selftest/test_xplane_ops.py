import os

import pytest

from benchmarks.harness import xplane, xplane_ops

TRACE = os.path.join(os.path.dirname(__file__), "data", "toy_v5e.xplane.pb")
# recorded on one TPU v5e chip (PR 23): 20 turns of a 2.9 ms program of
# four matrix products (op_name "jit(big)/dot_general") and a 0.1 ms one


@pytest.fixture(scope="module")
def ops():
    return xplane_ops.device_ops(TRACE)


def test_the_file_itself_gives_the_events_profile_data_gives(ops):
    plain = sorted(xplane.read_planes(TRACE)["devices"][0]["ops"])
    assert len(ops) == len(plain)
    for (a0, a1, text, _scope), (b0, b1, name) in zip(sorted(ops), plain):
        assert text == name
        assert abs(a0 - b0) <= 1 and abs(a1 - b1) <= 2  # ns, rounding


def test_an_operation_carries_the_scope_path_it_came_from(ops):
    scoped = [o for o in ops if o[3]]
    assert scoped and any(o[3].startswith("jit(big)/") for o in scoped)
    fusions = [o for o in ops if o[2].startswith("%convolution_tanh_fusion")]
    assert fusions and all("dot_general" in o[3] for o in fusions)


def test_seconds_by_path_sum_inside_the_window(ops):
    whole = xplane_ops.seconds_by_path(TRACE)
    assert xplane_ops.under(whole, "no.such") == 0.0
    assert 0.04 < xplane_ops.under(whole, "dot_general") < 0.07
    first = min(o[0] for o in ops)
    half = xplane_ops.seconds_by_path(TRACE, (first, first + 0.15e9))
    assert 0 < xplane_ops.under(half, "dot_general") < xplane_ops.under(
        whole, "dot_general")


def test_named_scopes_are_found_in_the_paths_not_listed_in_the_harness():
    found = xplane_ops.NAMED_SCOPE.findall
    assert found("jit(epoch)/while/body/layer0_gdn/gdn.scan/dot_general") == [
        "gdn.scan"]
    assert found("jit(epoch)/transpose(jvp(moe.route))/sort") == ["moe.route"]
    assert found("jit(big)/dot_general") == []
    by_path = {"jit(f)/a.b/mul": 1.0, "jit(f)/transpose(jvp(a.b))/mul": 2.0,
               "jit(f)/c.d/add": 4.0, "jit(f)/add": 8.0}
    assert xplane_ops.under(by_path, "a.b") == 3.0
    assert xplane_ops.under(by_path, "c.d") == 4.0


def test_a_program_without_scopes_or_counters_reads_as_nothing():
    run = {"trace": None, "traffic": {"steps_per_epoch": 8},
           "window": {"t0": 0.0, "t1": 1.0}}
    assert xplane_ops.scope_ms_per_step(run, "gdn.scan") is None
    assert xplane_ops.roofline_share(
        run, "gdn.scan", {"flops": 1.0, "bytes": 1.0}) is None
    assert xplane_ops.window_counters(run, events=[]) is None


def test_window_counters_sum_the_windows_epochs():
    def epoch(seq, mono):
        return {"name": "fit.epoch", "seq": seq, "mono_ns": mono, "ph": "i",
                "args": {}}

    def counters(seq, held):
        return {"name": "fit.counters", "seq": seq, "ph": "i", "args": {
            "layers": {"a": {"held_slots": held, "slots": 100,
                             "max_expert_tokens": 7},
                       "b": {"held_slots": held, "slots": 100,
                             "max_expert_tokens": 9}}}}

    events = [counters(1, 5), epoch(2, int(1e9)), counters(3, 6),
              epoch(4, int(2e9)), counters(5, 8), epoch(6, int(3e9)),
              counters(7, 50), epoch(8, int(9e9))]
    run = {"window": {"t0": 1.0, "t1": 3.0}}
    got = xplane_ops.window_counters(run, events)
    assert got == {"epochs": 2, "held_slots": 28, "slots": 400,
                   "max_expert_tokens": 32}
