"""The ``program_span`` readers on toy events (medians, the unspanned
remainder, membership of the window, None where there is nothing), on
the ring a toy run of the fit driver leaves behind, and the
idle-under-span reduction on recorded traces."""

import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import program_spans as ps
from benchmarks.harness import runner, xplane
from benchmarks.selftest import toy

MS = 1_000_000  # ns
DATA = os.path.join(os.path.dirname(__file__), "data")


class Ring:
    """Events as ``EventTracer`` records them, on a clock the test sets."""

    def __init__(self):
        self.events, self.seq = [], 0

    def _next(self):
        self.seq += 1
        return self.seq - 1

    def instant(self, name, at_ms, **args):
        self.events.append({
            "name": name, "ph": "i", "seq": self._next(),
            "ts": 1e9 + at_ms / 1e3, "mono_ns": at_ms * MS, "args": args,
        })

    def span(self, name, start_ms, dur_ms, **args):
        begin = self._next()
        self.events.append({
            "name": name, "ph": "X", "seq_begin": begin, "seq": self._next(),
            "ts": 1e9 + start_ms / 1e3, "mono_ns": start_ms * MS,
            "dur": dur_ms / 1e3, "args": args,
        })


def toy_call(ring, start_ms, epochs, write_back_ms, slow_epoch=None):
    """One ``fit`` call as the staged path records it: 100 ms epochs of
    5 dispatch + 60 loss wait + write-back + 1 callbacks, the rest
    unspanned; ``slow_epoch``'s write-back takes 300 ms more. The root
    is recorded by hand, since it opens first and closes last."""
    root_begin = ring._next()
    t = start_ms
    for name, dur in (("fit.partition_arrays", 40), ("fit.to_mesh", 1),
                      ("fit.stack_batches", 9), ("fit.shard_data", 20),
                      ("fit.device_state", 30)):
        ring.span(name, t, dur)
        t += dur
    for epoch in range(epochs):
        ring.span("fit.epoch_dispatch", t, 5, epoch=epoch)
        ring.span("fit.loss_wait", t + 5, 60, epoch=epoch)
        wb = write_back_ms + (300 if epoch == slow_epoch else 0)
        ring.span("fit.write_back", t + 70, wb, epoch=epoch, final=False)
        # the callbacks span opens, the event fires inside it, it closes
        begin = ring._next()
        ring.instant("fit.epoch", t + 70 + wb, epoch=epoch)
        ring.events.append({
            "name": "fit.callbacks", "ph": "X", "seq_begin": begin,
            "seq": ring._next(), "ts": 0.0, "mono_ns": (t + 70 + wb) * MS,
            "dur": 1 / 1e3, "args": {"epoch": epoch, "count": 1},
        })
        t += 100 + (300 if epoch == slow_epoch else 0)
    ring.span("fit.write_back", t, 500, epoch=epochs - 1, final=True)
    ring.events.append({
        "name": "fit.call", "ph": "X", "seq_begin": root_begin,
        "seq": ring._next(), "ts": 0.0, "mono_ns": start_ms * MS,
        "dur": (t + 500 - start_ms) / 1e3, "args": {},
    })


def window_of(ring, call_index):
    """``run["window"]`` as the driver sets it: the first and the last
    ``fit.epoch`` event of one call, in monotonic seconds."""
    calls = [e for e in ring.events if e["name"] == "fit.call"]
    call = calls[call_index]
    stamps = [e["mono_ns"] / 1e9 for e in ring.events
              if e["name"] == "fit.epoch"
              and call["seq_begin"] < e["seq"] < call["seq"]]
    return {"window": {"t0": stamps[0], "t1": stamps[-1]}}


@pytest.fixture()
def two_calls():
    """Set-up's one-epoch call, then the measured call of 6 epochs whose
    fourth write-back stalls."""
    ring = Ring()
    toy_call(ring, 0, 1, write_back_ms=25)
    toy_call(ring, 2000, 6, write_back_ms=20, slow_epoch=3)
    return ring, window_of(ring, 1)


def test_medians_are_over_the_windows_epochs_and_leave_the_final_out(
        two_calls):
    ring, run = two_calls
    epochs, spans = ps.window_spans(run, ring.events)
    assert [e["args"]["epoch"] for e in epochs] == [0, 1, 2, 3, 4, 5]
    # five intervals, five of each per-epoch span: the opening event's
    # own epoch is out, the closing event's is in, the final is out
    for name in ("fit.epoch_dispatch", "fit.loss_wait", "fit.write_back",
                 "fit.callbacks"):
        assert [s["args"]["epoch"] for s in spans if s["name"] == name] == [
            1, 2, 3, 4, 5], name
    assert not [s for s in spans if s["args"].get("final")]
    wb = ps.median_span_ms(run, "fit.write_back", ring.events, final=False)
    assert wb == pytest.approx(20.0)      # the stall is one of five
    assert ps.median_span_ms(run, "fit.loss_wait", ring.events) == (
        pytest.approx(60.0))
    assert ps.median_span_ms(run, "fit.callbacks", ring.events) == (
        pytest.approx(1.0))
    assert ps.median_span_ms(run, "fit.write_back", ring.events,
                             final=True) is None
    assert ps.median_span_ms(run, "fit.nothing", ring.events) is None


def test_unspanned_is_the_interval_less_the_spans_inside_it(two_calls):
    ring, run = two_calls
    # each 100 ms interval holds 5 + 60 + 20 + 1 ms of spans (the
    # callbacks span straddles the event: its tail and the next one's
    # head make one whole): 14 ms nobody names; the stalled interval's
    # extra 300 ms are write-back, so it reads 14 too
    assert ps.epoch_unspanned_ms(run, ring.events) == pytest.approx(14.0)
    # a span nobody recorded shows up as unspanned time
    without = [e for e in ring.events if e["name"] != "fit.loss_wait"]
    assert ps.epoch_unspanned_ms(run, without) == pytest.approx(74.0)


def test_stage_in_is_read_from_the_call_that_holds_the_window(two_calls):
    ring, run = two_calls
    rows = ("fit.partition_arrays", "fit.to_mesh", "fit.stack_batches")
    assert ps.call_spans_s(run, rows, ring.events) == pytest.approx(0.050)
    assert ps.call_spans_s(run, ("fit.shard_data",), ring.events) == (
        pytest.approx(0.020))
    assert ps.call_spans_s(run, ("fit.device_state",), ring.events) == (
        pytest.approx(0.030))
    assert ps.call_spans_s(run, ("fit.nothing",), ring.events) is None


def test_nothing_to_read_is_none_and_never_raises(two_calls):
    ring, run = two_calls
    elsewhere = {"window": {"t0": 500.0, "t1": 600.0}}
    parent = [  # the parent's ring: fit.epoch alone, and no mono_ns
        {k: v for k, v in e.items() if k != "mono_ns"}
        for e in ring.events if e["name"] == "fit.epoch"
    ]
    for events, where in (([], run), (ring.events, elsewhere), (parent, run)):
        assert ps.window_spans(where, events) is None
        assert ps.median_span_ms(where, "fit.write_back", events) is None
        assert ps.epoch_unspanned_ms(where, events) is None
        assert ps.call_spans_s(where, ("fit.shard_data",), events) is None
    assert ps.xplane_of({"trace": None}) is None
    assert ps.idle_under_spans([(0.0, 1.0, "a - b -")],
                               {"window": None, "spans": []}) is None


def test_idle_under_spans_splits_each_gap_by_the_span_over_it():
    gaps = [(0.0, 1.0, "a - b -"), (2.0, 2.5, "b - a -")]
    ns = 1_000_000_000
    mirrored = {"window": (10 * ns, 20 * ns), "spans": [
        (10 * ns, 13 * ns, "fit.call", 0),                 # the root: no owner
        (int(10.2 * ns), int(10.8 * ns), "fit.write_back", 2),
        (int(10.9 * ns), int(12.1 * ns), "fit.callbacks", 4),
        (int(12.2 * ns), int(12.3 * ns), "fit.write_back", 6),
    ]}
    got = ps.idle_under_spans(gaps, mirrored)
    assert got["idle_s"] == pytest.approx(1.5)
    assert got["by_span"]["fit.write_back"] == pytest.approx(0.6 + 0.1)
    assert got["by_span"]["fit.callbacks"] == pytest.approx(0.1 + 0.1)
    assert got["owned_s"] == pytest.approx(0.9)
    assert "fit.call" not in got["by_span"]


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    ctx = toy.context("resnet50-fit-staged", 1.0, 2**31 + 11,
                      str(tmp_path_factory.mktemp("work")))
    return ctx, runner.drive(ctx)


def test_a_toy_run_of_the_fit_driver_reports_the_span_metrics(toy_run):
    ctx, run = toy_run
    run["device_kind"] = toy.DEVICE["kind"]
    got = runner.collect_metrics(ctx.manifest, ctx.cell, "per_layer", run)
    epochs, spans = ps.window_spans(run)
    assert len(epochs) == run["epochs"]["asked"] == 5
    for name in ("fit_write_back_ms", "fit_loss_wait_ms", "fit_callbacks_ms",
                 "fit_epoch_unspanned_ms", "fit_stage_rows_s",
                 "fit_stage_put_s", "fit_stage_state_s"):
        assert got[name]["value"] >= 0.0, name
    assert "idle_under_span_share.fit" not in got     # an untraced run
    # the inside reading adds up to the outside one
    interval = 1e3 * (run["window"]["t1"] - run["window"]["t0"]) / 4
    inside = sum(s["dur"] for s in spans) * 1e3 / 4
    assert inside + got["fit_epoch_unspanned_ms"]["value"] == pytest.approx(
        interval, rel=0.25)
    stage = sum(got[n]["value"] for n in (
        "fit_stage_rows_s", "fit_stage_put_s", "fit_stage_state_s"))
    call_to_first = run["epochs"]["stamps"][0] - run["fit_call"]["t_call"]
    assert 0 < stage < call_to_first


def test_mirrored_spans_are_read_from_a_recorded_host_plane(tmp_path):
    import jax

    from elephas_tpu import telemetry

    tracer = telemetry.default_tracer()
    since = tracer.seq
    runner.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        with telemetry.trace_span("fit.call"):
            with telemetry.trace_span("fit.write_back", epoch=0):
                telemetry.emit("fit.epoch", epoch=0)
    jax.profiler.stop_trace()
    mirrored = ps.mirrored_spans(xplane.find_xplane(str(tmp_path)))
    assert mirrored["window"][1] > mirrored["window"][0]
    ring = {(e["name"], e.get("seq_begin", e["seq"]))
            for e in tracer.events(since)}
    assert {(n, s) for _a, _b, n, s in mirrored["spans"]} == ring
    clock = ps.clock_offset(mirrored, tracer.events(since))
    assert clock["joined"] == 3
    # one clock: the pairs agree to well under a millisecond
    assert clock["offset_ns_spread"] < 1_000_000


# recorded on one TPU v5e chip (PR 24) with the ring's events beside it:
# inside one bench.window and one fit.call span, 20 turns of
# fit.epoch_dispatch (a 0.35 ms program of four matrix products),
# fit.loss_wait (a host read of it), fit.write_back (2 ms of sleep, a
# small program and its read), 1 ms of sleep that no span owns, and
# fit.callbacks around one fit.epoch instant
SPANS_TRACE = os.path.join(DATA, "toy_spans_v5e.xplane.pb")
SPANS_RING = os.path.join(DATA, "toy_spans_v5e.ring.json")


def test_idle_under_spans_on_a_recorded_trace_with_mirrored_spans():
    reduced = xplane.reduce_trace(SPANS_TRACE)
    mirrored = ps.mirrored_spans(SPANS_TRACE)
    names = [n for _a, _b, n, _s in mirrored["spans"]]
    assert names.count("fit.call") == 1
    for name in ("fit.epoch_dispatch", "fit.loss_wait", "fit.write_back",
                 "fit.callbacks", "fit.epoch"):
        assert names.count(name) == 20, name
    got = ps.idle_under_spans(reduced["gaps"], mirrored)
    assert got["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert got["idle_s"] == pytest.approx(0.28876, abs=1e-4)
    assert got["owned_s"] == pytest.approx(0.26577, abs=1e-4)
    # what no span owns: the 20 sleeps of 1 ms and the loop's own steps
    assert 0.020 < got["idle_s"] - got["owned_s"] < 0.025
    by = got["by_span"]
    assert by["fit.write_back"] == pytest.approx(0.13438, abs=1e-4)
    assert by["fit.loss_wait"] == pytest.approx(0.12656, abs=1e-4)
    assert by["fit.epoch_dispatch"] < 0.005 and by["fit.callbacks"] < 0.001
    # by name the nested instant counts again under fit.callbacks; the
    # owned total is a union and counts it once
    assert sum(by.values()) - by["fit.epoch"] == pytest.approx(
        got["owned_s"], rel=1e-6)
    assert 100 * got["owned_s"] / got["idle_s"] == pytest.approx(92.04, abs=0.01)


def test_the_ring_and_the_recorded_trace_join_on_name_and_seq():
    import json

    with open(SPANS_RING) as f:
        events = json.load(f)
    mirrored = ps.mirrored_spans(SPANS_TRACE)
    clock = ps.clock_offset(mirrored, events)
    assert clock["joined"] == len(events) == len(mirrored["spans"]) == 101
    # mono_ns and the trace's clock differ by one constant: the pairs
    # of a 0.3 s trace agree to 30 us
    assert clock["offset_ns_spread"] < 50_000
    # and a span's monotonic duration is the annotation's, to the same
    spans = {(n, s): b - a for a, b, n, s in mirrored["spans"]}
    for e in events:
        if e["ph"] == "X":
            assert spans[(e["name"], e["seq_begin"])] == pytest.approx(
                e["dur"] * 1e9, abs=50_000)


def test_the_recorded_toy_trace_has_no_mirrored_span():
    mirrored = ps.mirrored_spans(os.path.join(DATA, "toy_v5e.xplane.pb"))
    assert mirrored["spans"] == [] and mirrored["window"] is not None
    gaps = xplane.reduce_trace(
        os.path.join(DATA, "toy_v5e.xplane.pb"))["gaps"]
    assert ps.idle_under_spans(gaps, mirrored) is None
