"""The readers of the window's epochs one by one (``harness/epoch_spans.py``
and the nine metrics over it) on the ring of one chip run of
``resnet50-fit-staged`` (PR 40, seed 4040000203, traced), on the ring of
the parent's run beside it (no args on its spans: None, nothing raised),
on windows cut to two epochs and to one, on a streamed call's several
dispatches an epoch, and in a toy run of the driver."""

import json
import os

import pytest

from benchmarks.harness import epoch_spans
from benchmarks.harness import manifest as mf
from benchmarks.harness import program_spans, runner
from benchmarks.selftest import toy
from benchmarks.selftest.test_program_spans import Ring

DATA = os.path.join(os.path.dirname(__file__), "data")
METRICS = epoch_spans.METRICS
# the traced run's own result line (chiprun_out/PR40/R0.jsonl, order 1)
ON_THE_CHIP = {
    "fit_window_stall_share": 2.116710402220793,
    "fit_first_epoch_excess_ms": 468.5483910000001,
    "fit_dispatch_max_ms": 474.337852,
    "fit_dispatch_jax_ms": 0.4436969757080078,
    "fit_new_signatures_in_window": 1.0,
    "fit_loss_wait_first_excess_ms": -0.20237899999986375,
    "setup_first_dispatch_s": 12.122635513,
    "setup_first_dispatch_jax_s": 11.836033821105957,
    "fit_hbm_in_use_gb": 2.72775424,
}


def recorded(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def window_of_last_call(events, intervals=None):
    """``run["window"]`` as the driver sets it, from the last call's
    ``fit.epoch`` events; cut to its first ``intervals`` where given."""
    last = [e for e in events if e["name"] == "fit.epoch"][-1]["args"]["trace"]
    stamps = [e["mono_ns"] / 1e9 for e in events
              if e["name"] == "fit.epoch" and e["args"]["trace"] == last]
    if intervals is not None:
        stamps = stamps[:intervals + 1]
    return {"window": {"t0": stamps[0], "t1": stamps[-1]}}


def read_all(monkeypatch, events, run) -> dict:
    monkeypatch.setattr(program_spans, "ring_events", lambda: events)
    return {name: mf.load_module("metrics", name).read(run)
            for name in METRICS}


def test_each_reader_on_the_recorded_ring_reads_what_the_chip_run_printed(
        monkeypatch, capsys):
    events = recorded("ring-resnet50-fit-staged-PR40.json")
    got = read_all(monkeypatch, events, window_of_last_call(events))
    for name, value in ON_THE_CHIP.items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    rec = epoch_spans.window_record(window_of_last_call(events), events)
    assert len(rec["intervals_s"]) == 16
    assert [s["args"]["epoch"] for s in rec["dispatch"]] == list(range(1, 17))
    assert [e["args"]["epoch"] for e in rec["memory"]] == list(range(1, 17))
    # the excess is the dispatch's, and JAX's own events own none of it
    assert got["fit_dispatch_max_ms"] == pytest.approx(
        got["fit_first_epoch_excess_ms"], rel=0.02)
    assert got["fit_dispatch_jax_ms"] < 1.0
    assert epoch_spans.first_dispatch(events)["args"]["trace"] == "fit-r0e0"
    lines = capsys.readouterr().out.splitlines()
    assert sum(ln.startswith("[dispatch] where=\"window\"") for ln in lines) == 1
    assert sum(ln.startswith("[dispatch] where=\"setup\"") for ln in lines) == 1
    assert sum(ln.startswith("[first_epoch] ") for ln in lines) == 1
    (memory,) = [ln for ln in lines if ln.startswith("[memory] ")]
    assert "peak_rose_at=[]" in memory


def test_the_parents_ring_reads_none_and_nothing_raises(monkeypatch):
    events = recorded("ring-resnet50-fit-staged-PR40-parent.json")
    run = window_of_last_call(events)
    # the parent has the window and the spans, without the args
    epochs, spans = program_spans.window_spans(run, events)
    assert len(epochs) == 17 and spans
    assert read_all(monkeypatch, events, run) == dict.fromkeys(METRICS)
    assert epoch_spans.window_record(run, events) is None
    assert epoch_spans.first_dispatch(events) is None
    for nothing in ([], [e for e in events if e["name"] == "fit.epoch"]):
        assert read_all(monkeypatch, nothing, run) == dict.fromkeys(METRICS)
    elsewhere = {"window": {"t0": 5.0, "t1": 6.0}}
    full = recorded("ring-resnet50-fit-staged-PR40.json")
    got = read_all(monkeypatch, full, elsewhere)
    assert all(got[name] is None for name in METRICS
               if not name.startswith("setup_"))


def test_windows_of_two_epochs_and_of_one(monkeypatch):
    events = recorded("ring-resnet50-fit-staged-PR40.json")
    two = read_all(monkeypatch, events, window_of_last_call(events, 2))
    rec = epoch_spans.window_record(window_of_last_call(events, 2), events)
    first, second = rec["intervals_s"]
    assert two["fit_first_epoch_excess_ms"] == pytest.approx(
        (first - second) * 1e3)
    waits = [s["dur"] for s in rec["loss_wait"]]
    assert len(waits) == 2
    assert two["fit_loss_wait_first_excess_ms"] == pytest.approx(
        (waits[0] - waits[1]) * 1e3)
    assert two["fit_window_stall_share"] == pytest.approx(
        100 * (first + second - sum(waits)) / (first + second))
    assert two["fit_new_signatures_in_window"] == 1
    assert two["fit_dispatch_max_ms"] == ON_THE_CHIP["fit_dispatch_max_ms"]
    # one epoch has no others to stand against: the two excesses are
    # None, the rest still read
    one = read_all(monkeypatch, events, window_of_last_call(events, 1))
    assert one["fit_first_epoch_excess_ms"] is None
    assert one["fit_loss_wait_first_excess_ms"] is None
    for name in METRICS:
        if "excess" not in name:
            assert one[name] is not None, name
    assert one["fit_window_stall_share"] > two["fit_window_stall_share"]


JAX_NOTHING = {"jax_trace_s": 0.0, "jax_lower_s": 0.0, "jax_compile_s": 0.0,
               "jax_cache_load_s": 0.0, "jax_events": 0}


def test_a_streamed_epochs_dispatches_add_up(monkeypatch):
    """Three epochs of two blocks: 100 ms epochs of 2 x 5 ms dispatch
    and 80 ms loss wait; the second epoch's first dispatch meets a new
    signature, takes 40 ms more, 1 ms of it JAX's tracing."""
    ring = Ring()
    t = 0
    for epoch in range(3):
        slow = epoch == 1
        for block in range(2):
            late = 40 if slow and block == 0 else 0
            ring.span("fit.epoch_dispatch", t, 5 + late, epoch=epoch,
                      block=block, signatures=2 if epoch else 1,
                      new_signature=bool(late or (epoch == 0 and block == 0)),
                      **dict(JAX_NOTHING, jax_trace_s=0.001 if late else 0.0,
                             jax_events=1 if late else 0))
            t += 5 + late
        ring.span("fit.loss_wait", t, 80, epoch=epoch)
        ring.instant("fit.memory", t + 81, epoch=epoch, peak_rose=slow,
                     bytes_in_use=(3 if slow else 2) * 10**9,
                     peak_bytes_in_use=4 * 10**9)
        ring.instant("fit.epoch", t + 90, epoch=epoch)
        t += 90
    stamps = [e["mono_ns"] / 1e9 for e in ring.events
              if e["name"] == "fit.epoch"]
    run = {"window": {"t0": stamps[0], "t1": stamps[-1]}}
    got = read_all(monkeypatch, ring.events, run)
    assert got["fit_first_epoch_excess_ms"] == pytest.approx(40.0)
    assert got["fit_dispatch_max_ms"] == pytest.approx(45.0)
    assert got["fit_dispatch_jax_ms"] == pytest.approx(1.0)
    assert got["fit_new_signatures_in_window"] == 1
    assert got["fit_loss_wait_first_excess_ms"] == pytest.approx(0.0)
    assert got["fit_window_stall_share"] == pytest.approx(
        100 * (240 - 160) / 240)
    assert got["fit_hbm_in_use_gb"] == pytest.approx(3.0)
    assert got["setup_first_dispatch_s"] == pytest.approx(0.005)
    rec = epoch_spans.window_record(run, ring.events)
    assert epoch_spans.by_epoch_s(rec["dispatch"]) == pytest.approx(
        [0.050, 0.010])


def test_a_toy_run_of_the_fit_driver_reports_the_new_metrics(tmp_path):
    ctx = toy.context("resnet50-fit-staged", 1.0, 2**31 + 13, str(tmp_path))
    run = runner.drive(ctx)
    run["device_kind"] = toy.DEVICE["kind"]
    got = runner.collect_metrics(ctx.manifest, ctx.cell, "per_layer", run)
    # the CPU keeps no allocator statistics: no fit.memory, so no reading
    for name in set(METRICS) - {"fit_hbm_in_use_gb"}:
        assert name in got, name
    assert "fit_hbm_in_use_gb" not in got
    assert 0.0 <= got["fit_window_stall_share"]["value"] <= 100.0
    assert got["fit_dispatch_jax_ms"]["value"] <= (
        got["fit_dispatch_max_ms"]["value"] * run["epochs"]["asked"])
    assert got["setup_first_dispatch_jax_s"]["value"] <= (
        got["setup_first_dispatch_s"]["value"])
    for entry in ctx.manifest["per_layer"]:
        if entry["name"] in METRICS:
            assert entry["workloads"] == [
                c["name"] for c in ctx.manifest["workloads"]]
