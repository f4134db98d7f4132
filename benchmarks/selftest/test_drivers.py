"""Each driver called directly at toy size on the CPU, its record
reduced to a last line of the contract's shape; and the timed path
broken underneath, which ``correct`` has to see."""

import json

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import runner
from benchmarks.selftest import toy


def line_of(ctx, run):
    run["device_kind"] = toy.DEVICE["kind"]
    return json.loads(json.dumps(runner.finish(ctx, run, toy.DEVICE)))


def shape_ok(line, wanted):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert set(wanted) <= set(line["metrics"])
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)
    assert line["device"]["memory_peak_bytes"] >= 0


@pytest.fixture(scope="module")
def fit_run(tmp_path_factory):
    ctx = toy.context("resnet50-fit-staged", 2.0, 2**31 + 5,
                      str(tmp_path_factory.mktemp("work")))
    return ctx, runner.drive(ctx)


def test_fit_staged_reads_the_whole_window_and_agrees_with_the_reference(
        fit_run):
    ctx, run = fit_run
    line = line_of(ctx, run)
    shape_ok(line, ["fit_examples_per_s_per_chip", "setup_s"])
    assert line["correct"] is True and line["failed"] == 0
    asked, stamps = run["epochs"]["asked"], run["epochs"]["stamps"]
    assert asked == 1 + 8 and len(run["epochs"]["readings"]) == asked - 1
    assert (run["window"]["t0"], run["window"]["t1"]) == (
        stamps[0], stamps[-1])
    assert line["metrics"]["fit_examples_per_s_per_chip"]["value"] == (
        pytest.approx(8 * 64 / (stamps[-1] - stamps[0])))
    assert run["compile"]["window"]["compiles"] == 0
    # float32 at toy size: the reference and the program are one function
    numbers = run["check"]["numbers"]
    assert numbers["loss_gap"][0] < 1e-5
    assert numbers["change_gap"][0] < 1e-3


def test_fit_staged_sees_a_step_that_returns_its_state_unchanged(
        tmp_path, monkeypatch):
    from elephas_tpu import worker

    real = worker.MeshRunner._build_epoch_fn

    def broken(self, metric_objects=None):
        fn = real(self, metric_objects)

        def epoch(tv, ntv, ov, mvs, xb, yb):
            import jax.numpy as jnp

            kept_tv = [jnp.copy(a) for a in tv]   # the call donates them
            kept_ov = [jnp.copy(a) for a in ov]
            _tv, ntv2, _ov, mvs2, loss = fn(tv, ntv, ov, mvs, xb, yb)
            return kept_tv, ntv2, kept_ov, mvs2, loss

        return epoch

    monkeypatch.setattr(worker.MeshRunner, "_build_epoch_fn", broken)
    ctx = toy.context("resnet50-fit-staged", 1.0, 77, str(tmp_path))
    ctx.config["correct"]["limits"] = {
        "loss_gap": 0.05, "velocity_gap": 0.5, "change_gap": 0.5}
    run = runner.drive(ctx)
    assert run["correct"] is False
    assert run["check"]["numbers"]["change_gap"][2] is False


def test_fit_staged_sees_half_of_each_batch_left_out(tmp_path, monkeypatch):
    from elephas_tpu import worker

    real = worker.MeshRunner._build_epoch_fn

    def broken(self, metric_objects=None):
        fn = real(self, metric_objects)

        def epoch(tv, ntv, ov, mvs, xb, yb):
            import jax.numpy as jnp

            # [workers, steps, batch, ...]: the first half of each
            # batch twice, the second half never
            half = xb.shape[2] // 2
            xb = jnp.concatenate([xb[:, :, :half], xb[:, :, :half]], axis=2)
            yb = jnp.concatenate([yb[:, :, :half], yb[:, :, :half]], axis=2)
            return fn(tv, ntv, ov, mvs, xb, yb)

        return epoch

    monkeypatch.setattr(worker.MeshRunner, "_build_epoch_fn", broken)
    ctx = toy.context("resnet50-fit-staged", 1.0, 79, str(tmp_path))
    ctx.config["correct"]["limits"] = {
        "loss_gap": 0.02, "velocity_gap": 0.5, "change_gap": 0.5}
    run = runner.drive(ctx)
    assert run["correct"] is False
    assert run["check"]["numbers"]["loss_gap"][2] is False


def test_the_control_comes_out_as_not_correct_and_so_does_half_a_batch(
        fit_run):
    """The reference one precision down, in the program's place, at a
    size a test run can hold: it has to fail a limit that sound runs
    pass with room. And the fault the loss limit is held against."""
    ctx, run = fit_run
    driver = mf.load_module("drivers", "fit_staged")
    x, y = driver.make_examples(ctx.config, ctx.traffic, ctx.seed)
    run = dict(run, data=(x, y))
    sound = driver.compare_first_epoch(ctx, run)
    lower = driver.control_gaps(ctx, run, ctx.seed)
    assert max(sound["velocity_gap"], sound["change_gap"]) * 3 < max(
        lower["velocity_gap"], lower["change_gap"])
    halved = driver.half_batch_gaps(ctx, run, ctx.seed)
    assert sound["loss_gap"] * 3 < halved["loss_gap"]


def test_sequences_come_from_the_seed_with_their_next_token_as_target():
    driver = mf.load_module("drivers", "fit_staged")
    cfg = {"vocab_size": 300, "n_positions": 16}
    traffic = {"examples": 12, "base_block": 4, "example": "sequence"}
    x, y = driver.make_examples(cfg, traffic, 2**31 + 3)
    again, _ = driver.make_examples(cfg, traffic, 2**31 + 3)
    assert x.shape == y.shape == (12, 16) and (x == again).all()
    assert (x[:, 1:] == y[:, :-1]).all()
    assert len({tuple(r) for r in x}) == 12     # rows that all differ
