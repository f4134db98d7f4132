"""When the master model is synced at an epoch boundary (ISSUE 26): only
ahead of a callback that reads it there; after the call, always.

Calls are counted and arrays compared; no test here reads a clock.
"""

import json

import numpy as np
import pytest

from elephas_tpu import SparkModel, telemetry
from elephas_tpu.worker import MeshRunner, reads_model
from tests.conftest import make_mlp
from tests.test_telemetry import _own_events, _toy_rows as _rows

WORKERS = 2
BATCH = 8


def _spark_model(**kwargs):
    return SparkModel(make_mlp(8, 2), num_workers=WORKERS, **kwargs)


def _partitions(x, y):
    """The rows as ``SparkModel.fit`` hands them to ``run_epochs``."""
    return list(zip(np.array_split(x, WORKERS), np.array_split(y, WORKERS)))


def _state(model):
    """Everything a ``fit`` leaves on the master model, optimizer included."""
    return [np.asarray(v.value) for v in model.variables] + [
        np.asarray(v.value) for v in model.optimizer.variables
    ]


def _assert_bit_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _differ(a, b):
    return any(not np.array_equal(p, q) for p, q in zip(a, b))


@pytest.fixture
def write_backs(monkeypatch):
    """Counts the calls of ``MeshRunner._write_back`` (per-epoch syncs
    and the ``final`` one alike)."""
    calls = []
    original = MeshRunner._write_back

    def counted(self, tv, ntv, ov=None, **how):
        calls.append(len(tv) + len(ntv) + len(ov or ()))
        return original(self, tv, ntv, ov, **how)

    monkeypatch.setattr(MeshRunner, "_write_back", counted)
    return calls


def _write_back_spans(since):
    return [
        e for e in _own_events(telemetry.default_tracer(), since)
        if e["name"] == "fit.write_back"
    ]


def _synced_epochs(since):
    """The epochs whose per-epoch ``fit.write_back`` span carried the
    state across, and a check that the others carried nothing."""
    synced = []
    for e in _write_back_spans(since):
        args = e["args"]
        if args["final"]:
            continue
        assert (args["bytes"] > 0) == (args["variables"] > 0)
        if args["bytes"]:
            synced.append(args["epoch"])
    return synced


@pytest.mark.parametrize("declared, epoch, reads", [
    (None, 0, True),  # nothing declared: taken to read the model
    (True, 3, True),
    (False, 3, False),
    (lambda epoch: epoch % 10 == 9, 8, False),
    (lambda epoch: epoch % 10 == 9, 9, True),
])
def test_declaration_decides_by_list_and_epoch_alone(declared, epoch, reads):
    from elephas_tpu.worker import _reads_model_at

    def cb(_epoch, _loss):
        pass

    if declared is not None:
        assert reads_model(declared)(cb) is cb  # still the same callable
    assert _reads_model_at(cb, epoch) is reads


def test_plain_fit_writes_back_once(write_backs):
    """(a) No callback of a plain ``fit`` reads the model: one
    write-back, the ``final`` one; the per-epoch spans stay and say
    that nothing crossed."""
    epochs = 5
    since = telemetry.default_tracer().seq
    sm = _spark_model()
    sm.fit(_rows(), epochs=epochs, batch_size=BATCH)
    assert len(write_backs) == 1
    spans = _write_back_spans(since)
    per_epoch = [e["args"] for e in spans if not e["args"]["final"]]
    assert [a["epoch"] for a in per_epoch] == list(range(epochs))
    assert all(a["bytes"] == 0 and a["variables"] == 0 for a in per_epoch)
    (final,) = [e["args"] for e in spans if e["args"]["final"]]
    assert final["epoch"] == epochs - 1
    assert final["variables"] == write_backs[0] > 4
    assert final["bytes"] >= sum(
        w.nbytes for w in sm.master_network.get_weights()
    )


def test_plain_fit_leaves_what_a_sync_every_epoch_leaves(write_backs):
    """(b) The state and the history of a plain ``fit`` are bit-equal to
    those of the same epochs with a sync forced at every boundary (an
    undeclared no-op callback straight through ``run_epochs``)."""
    epochs = 4
    x, y = _rows()
    plain = _spark_model()
    history = plain.fit((x, y), epochs=epochs, batch_size=BATCH)
    assert len(write_backs) == 1

    forced = _spark_model()
    runner = forced._get_runner()
    forced_history = runner.run_epochs(
        _partitions(x, y), epochs, BATCH, callbacks=[lambda epoch, loss: None]
    )
    assert len(write_backs) == 1 + epochs + 1

    assert history["loss"] == forced_history["loss"]
    assert history["loss"][-1] < history["loss"][0]
    _assert_bit_equal(
        _state(plain.master_network), _state(forced.master_network)
    )


@pytest.mark.parametrize("every, epochs, synced", [
    (1, 3, [0, 1, 2]),
    (2, 4, [1, 3]),
    (10, 3, []),
])
def test_checkpoints_sync_the_epochs_they_save(
    tmp_path, write_backs, every, epochs, synced
):
    """(c) ``checkpoint_dir`` syncs exactly the epochs whose checkpoint is
    due, and the archive of epoch k holds what a k-epoch fit leaves."""
    import keras

    from elephas_tpu.utils.checkpoint import checkpoint_path

    ckdir = str(tmp_path / "ck")
    since = telemetry.default_tracer().seq
    _spark_model().fit(
        _rows(), epochs=epochs, batch_size=BATCH,
        checkpoint_dir=ckdir, checkpoint_every=every,
    )
    assert _synced_epochs(since) == synced
    assert len(write_backs) == len(synced) + 1
    for epoch in synced:
        done = epoch + 1
        saved = keras.saving.load_model(checkpoint_path(ckdir, done))
        fresh = _spark_model()
        fresh.fit(_rows(), epochs=done, batch_size=BATCH)
        _assert_bit_equal(
            saved.get_weights(), fresh.master_network.get_weights()
        )


# what the parent commit (a337a37) gives for this fit, on the CPU
PARENT_VAL_LOSS = [0.4594757556915283, 0.3562657833099365, 0.271917462348938]


def test_validation_reads_live_weights_every_epoch(write_backs):
    """(d) ``validation_split`` evaluates the weights of each epoch: the
    per-epoch ``val_loss`` history is the parent's."""
    epochs = len(PARENT_VAL_LOSS)
    since = telemetry.default_tracer().seq
    history = _spark_model().fit(
        _rows(), epochs=epochs, batch_size=BATCH, validation_split=0.25
    )
    assert _synced_epochs(since) == list(range(epochs))
    assert len(write_backs) == epochs + 1
    assert history["val_loss"] == pytest.approx(PARENT_VAL_LOSS, rel=1e-5)
    assert len(set(history["val_loss"])) == epochs


def test_parameter_server_publishes_live_weights_every_epoch(write_backs):
    """(e) A parameter-server mode publishes, at every epoch boundary,
    weights that differ from the start and from the epoch before."""
    epochs = 3
    sm = _spark_model(
        mode="asynchronous", parameter_server_mode="http", port=0
    )
    published = [[w.copy() for w in sm.master_network.get_weights()]]
    publish = sm._publish_weights

    def spy(final=False):
        if not final:
            published.append(
                [w.copy() for w in sm._get_runner().host_weights()]
            )
        publish(final=final)

    sm._publish_weights = spy
    since = telemetry.default_tracer().seq
    sm.fit(_rows(), epochs=epochs, batch_size=BATCH)
    assert _synced_epochs(since) == list(range(epochs))
    assert len(write_backs) == epochs + 1
    assert len(published) == 1 + epochs
    for before, after in zip(published, published[1:]):
        assert _differ(before, after)
    _assert_bit_equal(published[-1], sm.master_network.get_weights())


def test_undeclared_callback_reads_live_weights(write_backs):
    """(f) A plain lambda handed straight to ``run_epochs`` declares
    nothing, so it sees the weights of its epoch, as it always did."""
    epochs = 3
    x, y = _rows()
    sm = _spark_model()
    runner = sm._get_runner()
    seen = [[w.copy() for w in sm.master_network.get_weights()]]
    runner.run_epochs(
        _partitions(x, y), epochs, BATCH,
        callbacks=[lambda epoch, loss: seen.append(
            [w.copy() for w in sm.master_network.get_weights()]
        )],
    )
    assert len(write_backs) == epochs + 1
    for before, after in zip(seen, seen[1:]):
        assert _differ(before, after)
    _assert_bit_equal(seen[-1], sm.master_network.get_weights())


@pytest.mark.parametrize("reading", ["nothing", "checkpoints"])
def test_streamed_loop_obeys_the_same_rule(tmp_path, write_backs, reading):
    """(g) ``run_epochs_stream`` shares ``_end_epoch``."""
    epochs = 3
    extra = {}
    if reading == "checkpoints":
        extra = {"checkpoint_dir": str(tmp_path / "ck"), "checkpoint_every": 2}
    since = telemetry.default_tracer().seq
    sm = _spark_model()
    history = sm.fit(
        _rows(), epochs=epochs, batch_size=BATCH, stream_block_steps=2,
        **extra,
    )
    names = {e["name"] for e in telemetry.default_tracer().events(since)}
    assert "fit.input_wait" in names  # the streamed loop ran
    synced = [1] if extra else []
    assert _synced_epochs(since) == synced
    assert len(write_backs) == len(synced) + 1
    assert history["loss"][-1] < history["loss"][0]

    staged = _spark_model()
    staged.fit(_rows(), epochs=epochs, batch_size=BATCH)
    for a, b in zip(
        sm.master_network.get_weights(), staged.master_network.get_weights()
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _tp_model():
    from tests.test_tensor_parallel import _mlp

    return _mlp(8, 2, hidden=32, seed=5), {"model_parallel": 2}


def _pp_model():
    from tests.test_pipeline import _pp_mlp

    return _pp_mlp(8, 2, seed=5), {"pipeline_parallel": 2}


@pytest.mark.parametrize("layout", [_tp_model, _pp_model])
def test_tagged_list_passes_through_other_runners(tmp_path, layout):
    """(h) ``SparkModel.fit`` hands the same declared callbacks to the
    tensor-parallel and the pipeline runner, which keep their own loops
    and their own way to the master model: each callback runs once an
    epoch, in order, and validation sees the weights of its epoch."""
    epochs = 3
    model, kwargs = layout()
    log = str(tmp_path / "history.jsonl")
    since = telemetry.default_tracer().seq
    history = SparkModel(model, **kwargs).fit(
        _rows(128), epochs=epochs, batch_size=16, validation_split=0.25,
        history_log=log,
    )
    emitted = [
        e["args"]["epoch"]
        for e in telemetry.default_tracer().events(since, name="fit.epoch")
    ]
    assert emitted == list(range(epochs))
    with open(log) as f:
        lines = [json.loads(line) for line in f]
    assert [l["epoch"] for l in lines[:-1]] == [1, 2, 3]
    assert lines[-1]["final"] is True
    assert len(history["val_loss"]) == epochs
    assert len(set(history["val_loss"])) == epochs


# -- where the master model is while the runner holds the state (ISSUE 27) --


@pytest.fixture
def far_host(monkeypatch):
    """Makes the host's device another than the workers': the last of
    the virtual CPU devices stands for the host beside a chip, so that
    a parked master is told from one on the default device."""
    import jax

    far = jax.devices()[-1]
    assert far not in jax.devices()[:WORKERS]
    original = jax.local_devices

    def local_devices(*args, backend=None, **kwargs):
        if backend == "cpu":
            return [far]
        return original(*args, backend=backend, **kwargs)

    monkeypatch.setattr(jax, "local_devices", local_devices)
    return far


def _where(model):
    return {
        d for v in list(model.variables) + list(model.optimizer.variables)
        for d in v.value.devices()
    }


def test_master_waits_on_the_host_during_fit_and_comes_back(far_host):
    """While the runner holds the state the master's variables are host
    arrays (the state is on the devices once, not twice); the call's
    last write-back puts them where Keras puts any variable, and what
    it leaves is bit-equal to a fit that parked nothing far away."""
    import jax

    x, y = _rows()
    sm = _spark_model()
    seen = []

    @reads_model(False)
    def look(epoch, loss):
        seen.append(_where(sm.master_network))

    history = sm._get_runner().run_epochs(
        _partitions(x, y), 3, BATCH, callbacks=[look])
    assert seen == [{far_host}] * 3
    assert _where(sm.master_network) == {jax.devices()[0]}
    assert "accuracy" in history and len(history["accuracy"]) == 3

    twin = _spark_model()
    twin_history = twin.fit((x, y), epochs=3, batch_size=BATCH)
    assert history["loss"] == twin_history["loss"]
    _assert_bit_equal(_state(sm.master_network), _state(twin.master_network))


def test_callback_that_reads_the_master_finds_it_live_off_the_host(far_host):
    """A sync ahead of a reading callback assigns the variables as it
    always did: live weights, on the default device."""
    x, y = _rows()
    sm = _spark_model()
    start = _state(sm.master_network)
    seen = []

    def read(epoch, loss):  # declares nothing: reads the model
        seen.append((_where(sm.master_network),
                     _differ(_state(sm.master_network), start)))

    sm._get_runner().run_epochs(_partitions(x, y), 2, BATCH, callbacks=[read])
    assert [live for _where_, live in seen] == [True, True]
    assert all(far_host not in where for where, _live in seen)


def test_final_write_back_frees_the_runners_copy(monkeypatch):
    """The call's last write-back frees each device leaf once it is
    read; a sync at an epoch boundary frees nothing (the next epoch
    trains on)."""
    import jax

    kept = []
    original = MeshRunner._write_back

    def spy(self, tv, ntv, ov=None, **how):
        out = original(self, tv, ntv, ov, **how)
        leaves = [l for part in (tv, ntv, ov or ()) for l in part]
        kept.append((how.get("release", False), [
            l.is_deleted() for l in leaves if isinstance(l, jax.Array)]))
        return out

    monkeypatch.setattr(MeshRunner, "_write_back", spy)
    x, y = _rows()
    sm = _spark_model()
    sm._get_runner().run_epochs(
        _partitions(x, y), 2, BATCH, callbacks=[lambda epoch, loss: None])
    assert [release for release, _ in kept] == [False, False, True]
    assert not any(kept[0][1]) and not any(kept[1][1])
    assert kept[2][1] and all(kept[2][1])
