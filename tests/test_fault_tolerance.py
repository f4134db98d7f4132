"""Fault-tolerant training runtime (ISSUE 3): journaled restartable
parameter servers, sequence-ID idempotent updates, bounded resend of
unacked pushes, worker leases/status, supervised worker retry across a
PS crash, and the driver's worker-loss failure budget.

The acceptance contract: a seeded fault plan that kills and restarts
the PS mid-training and duplicates >=10% of update frames still
completes async training, applies each sequence ID exactly once
(bit-exact against a duplicate-free run on the same data order), and
worker loss beyond the failure budget raises a clear error. These
tests ride the same per-test SIGALRM deadline as the other PS socket
suites (conftest `_PS_DEADLINE_MODULES`).
"""

import os
import tempfile

import numpy as np
import pytest

from elephas_tpu.fault import (
    FaultBudgetExceeded,
    FaultPlan,
    RestartablePS,
    SocketFaults,
    run_chaos_training,
    use_plan,
)
from elephas_tpu.parameter import journal
from elephas_tpu.parameter.client import HttpClient, SocketClient
from elephas_tpu.parameter.server import HttpServer, SocketServer

_CLIENTS = {"socket": (SocketServer, SocketClient),
            "http": (HttpServer, HttpClient)}


def _seeded_deltas(seed: int, n: int, shapes=((8, 4), (4,))):
    rng = np.random.default_rng(seed)
    return [
        [rng.normal(size=s).astype(np.float32) for s in shapes]
        for _ in range(n)
    ]


# -- journal format ------------------------------------------------------


def test_journal_roundtrip_bit_exact_with_seq_table():
    import ml_dtypes

    weights = [
        np.linspace(0, 1, 12, dtype=np.float64).reshape(3, 4),
        np.arange(5, dtype=np.int32),
        np.ones((2, 2), ml_dtypes.bfloat16),
    ]
    table = {"worker-a": 41, "worker-b": 7}
    with tempfile.TemporaryDirectory() as d:
        journal.save_journal(d, weights, table, meta={"mode": "hogwild"})
        restored, seq, meta = journal.load_journal(d)
    assert meta["mode"] == "hogwild"
    assert seq == table
    for a, b in zip(restored, weights):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a, np.float64), np.asarray(b, np.float64)
        )


def test_journal_missing_returns_none_corrupt_raises():
    with tempfile.TemporaryDirectory() as d:
        assert journal.load_journal(d) is None
        path = journal.journal_path(d)
        journal.save_journal(d, [np.ones(4)], {"w": 1})
        with open(path, "rb") as f:
            data = f.read()
        with open(path, "wb") as f:
            f.write(data[: len(data) // 2])  # torn file
        with pytest.raises(ValueError):
            journal.load_journal(d)


def test_crash_mid_snapshot_recovers_and_cleans_tmp():
    """Torn-write recovery (ISSUE 6 satellite): a kill BETWEEN the tmp
    write and the atomic replace leaves the previous journal intact
    plus an orphaned ``.tmp-ps-journal.bin-*`` file (atomic_write names
    tmps exactly so); the next load_journal recovers the OLD state and
    removes the orphan (a chaos-restart loop must not accumulate one
    tmp file per crash). Foreign tmp files are left alone."""
    import glob as _glob

    with tempfile.TemporaryDirectory() as d:
        good = [np.full(4, 2.0, np.float32)]
        journal.save_journal(d, good, {"w": 5})
        # the exact post-SIGKILL disk state: a half-written snapshot
        # under atomic_write's tmp naming, never replaced into place
        torn = os.path.join(d, ".tmp-" + journal.JOURNAL_NAME + "-x1y2")
        with open(torn, "wb") as f:
            f.write(b"EPSJ\x01torn mid-write")
        foreign = os.path.join(d, ".tmp-something-else")
        with open(foreign, "wb") as f:
            f.write(b"not ours")

        restored, seq, _ = journal.load_journal(d)
        np.testing.assert_array_equal(restored[0], good[0])  # old state
        assert seq == {"w": 5}
        assert not os.path.exists(torn)  # orphan cleaned
        assert os.path.exists(foreign)  # not ours: untouched
        assert os.path.exists(journal.journal_path(d))


# -- idempotent apply (the acceptance bit-exact clause) ------------------


@pytest.mark.parametrize("transport", ["socket", "http"])
def test_duplicate_updates_apply_exactly_once_bit_exact(transport):
    """>=10% of update frames duplicated on the wire (seeded stride)
    apply bit-exactly like a duplicate-free run on the same data
    order — each sequence ID lands exactly once."""
    server_cls, client_cls = _CLIENTS[transport]
    deltas = _seeded_deltas(seed=3, n=20)
    plan = FaultPlan(seed=1, duplicate_fraction=0.25)

    def run(duplicates: bool):
        server = server_cls(
            [np.zeros((8, 4), np.float32), np.zeros(4, np.float32)],
            mode="asynchronous", port=0,
        )
        server.start()
        try:
            client = client_cls(
                master=f"127.0.0.1:{server.port}", client_id="w0"
            )
            if duplicates:
                client.chaos_duplicate = plan.duplicate
            for d in deltas:
                client.update_parameters(d)
            final = client.get_parameters()
            stats = (client.chaos_dups_sent, server.updates_duplicate,
                     server.updates_applied)
            if hasattr(client, "close"):
                client.close()
            return final, stats
        finally:
            server.stop()

    clean, (_, _, clean_applied) = run(duplicates=False)
    chaotic, (dups_sent, dups_skipped, applied) = run(duplicates=True)
    assert dups_sent >= len(deltas) // 10, "plan must duplicate >=10%"
    assert dups_skipped == dups_sent  # every duplicate was a no-op
    assert applied == clean_applied == len(deltas)
    for a, b in zip(chaotic, clean):
        np.testing.assert_array_equal(a, b)  # bit-exact


def test_unacked_push_resent_and_lost_counter_drains():
    """PR-2 known issue fixed: a push whose connection dies before its
    pipelined ack is RESENT (sequence dedup makes that safe) instead of
    only being counted — `updates_lost` rises on the drop and drains to
    zero once the resend is acked, and the final state is exactly-once."""
    server = SocketServer([np.zeros(4, np.float32)], port=0)
    server.start()
    try:
        client = SocketClient(master=f"127.0.0.1:{server.port}",
                              client_id="w0")
        client.update_parameters([np.ones(4, np.float32)])  # ack pending
        client._sock.close()  # connection dies holding the unacked push
        client.update_parameters([np.ones(4, np.float32)])
        assert client.updates_lost == 0  # drained by the resend
        assert client.updates_resent == 1
        got = client.get_parameters()[0]
        np.testing.assert_array_equal(got, np.full(4, 2.0))  # exactly once
        client.close()
    finally:
        server.stop()


def test_flush_confirms_final_pushes():
    """flush() leaves nothing in doubt: every pipelined push is acked
    (or resent) before it returns."""
    server = SocketServer([np.zeros(2, np.float32)], port=0)
    server.start()
    try:
        client = SocketClient(master=f"127.0.0.1:{server.port}")
        for _ in range(3):
            client.update_parameters([np.ones(2, np.float32)])
        client.flush()
        assert not client._unacked and not client._resend
        np.testing.assert_array_equal(
            client.get_parameters()[0], np.full(2, 3.0)
        )
        client.close()
    finally:
        server.stop()


# -- leases / status -----------------------------------------------------


@pytest.mark.parametrize("transport", ["socket", "http"])
def test_heartbeat_membership_and_status_counters(transport):
    server_cls, client_cls = _CLIENTS[transport]
    server = server_cls([np.zeros(4)], port=0, lease_timeout=30.0)
    server.start()
    try:
        client = client_cls(master=f"127.0.0.1:{server.port}",
                            client_id="worker-7")
        client.heartbeat()
        client.update_parameters([np.ones(4)])
        status = client.status()
        assert status["mode"] == "asynchronous"
        member = status["members"]["worker-7"]
        assert member["live"] and member["age_s"] < 30.0
        assert status["updates_applied"] == 1
        assert status["seq_table"] == {"worker-7": 0}
        if hasattr(client, "close"):
            client.close()
    finally:
        server.stop()


# -- journaled restart ---------------------------------------------------


def test_kill_restart_replays_journal_and_still_dedups():
    """A crash-killed server restarts from its journal on the same
    port: weights within journal lag, sequence table intact — so a
    post-restart resend of an already-journaled seq is still skipped."""
    with tempfile.TemporaryDirectory() as d:
        ps = RestartablePS(
            SocketServer, [np.zeros(4, np.float32)], journal_dir=d,
            journal_every=1,  # journal every update: no lag window
        )
        try:
            client = SocketClient(master=f"127.0.0.1:{ps.port}",
                                  client_id="w0")
            for _ in range(3):
                client.update_parameters([np.ones(4, np.float32)])
            client.flush()
            ps.kill()
            ps.restart()
            assert ps.server.restored_from_journal
            assert ps.server.seq_table == {"w0": 2}
            np.testing.assert_array_equal(
                ps.server.weights[0], np.full(4, 3.0)
            )
            # a stale resend from before the crash is still deduped
            client2 = SocketClient(master=f"127.0.0.1:{ps.port}",
                                   client_id="w0")
            client2._resend.append((2, client2._encode_update(
                [np.ones(4, np.float32)]
            )))
            client2.flush()
            assert client2.updates_duplicate == 1
            np.testing.assert_array_equal(
                ps.server.weights[0], np.full(4, 3.0)  # unchanged
            )
            client2.close()
        finally:
            ps.stop()


def test_chaos_training_survives_ps_crash_and_converges(tmp_path):
    """The acceptance scenario end to end: async worker training with a
    seeded plan that kills+restarts the PS mid-training and duplicates
    >=10% of update frames COMPLETES (supervised retry pauses through
    the outage), applies every expected update exactly once, and lands
    in the same loss ballpark as the fault-free run."""
    from elephas_tpu.fault.harness import _chaos_data, _chaos_model

    clean = run_chaos_training("socket", rows=192, epochs=2, seed=0,
                               plan=None, batch_size=64)
    plan = FaultPlan(
        seed=0,
        kill_ps_after_updates=2,
        restart_delay_s=0.4,
        duplicate_fraction=0.25,
    )
    faulted = run_chaos_training(
        "socket", rows=192, epochs=2, seed=0, plan=plan,
        journal_dir=str(tmp_path), journal_every=1, batch_size=64,
    )
    assert faulted["kills"] == 1 and faulted["restarts"] == 1
    assert faulted["journal_restored"]
    assert faulted["recovery_s"] is not None and faulted["recovery_s"] > 0
    # every update applied exactly once despite duplicates + resends
    assert faulted["updates_applied"] == clean["updates_applied"]
    assert faulted["duplicates_sent"] >= 1
    assert faulted["duplicates_skipped"] >= faulted["duplicates_sent"]
    assert faulted["updates_lost_final"] == 0
    # converges to the same ballpark as fault-free on the same data
    x, y, d, k = _chaos_data(0, 192)
    model = _chaos_model(0, d, k)
    initial = float(model.evaluate(x, y, verbose=0))
    model.set_weights(clean["final_weights"])
    clean_loss = float(model.evaluate(x, y, verbose=0))
    model.set_weights(faulted["final_weights"])
    faulted_loss = float(model.evaluate(x, y, verbose=0))
    assert clean_loss < initial * 0.95
    assert faulted_loss < initial * 0.95
    assert faulted_loss < clean_loss * 1.5 + 0.05, (faulted_loss, clean_loss)


# -- supervised worker retry under wire faults ---------------------------


def test_worker_survives_injected_socket_drops():
    """Periodic injected connection drops (the sockets fault hook) are
    absorbed by client retries + the supervised period retry — training
    completes and the lost-push counter drains."""
    # granularity note: the hook fires per socket PRIMITIVE (one sync
    # period crosses it dozens of times, server side included), so the
    # stride is in ops, not rounds — too dense and every retry of every
    # period fails too
    plan = FaultPlan(
        seed=0, socket_faults=SocketFaults(drop_every=53),
    )
    out = run_chaos_training("socket", rows=128, epochs=2, seed=0,
                             plan=plan, batch_size=64)
    assert out["updates_applied"] >= 4  # all periods landed
    assert out["updates_lost_final"] == 0


# -- driver failure budget ----------------------------------------------


def _budget_fit(blobs, failure_budget, failed_partitions):
    import keras

    from elephas_tpu import SparkModel

    x, y, d, k = blobs
    keras.utils.set_random_seed(0)
    model = keras.Sequential([
        keras.layers.Input((d,)),
        keras.layers.Dense(8, activation="relu"),
        keras.layers.Dense(k, activation="softmax"),
    ])
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    spark_model = SparkModel(
        model, mode="asynchronous", num_workers=4,
        failure_budget=failure_budget,
    )
    plan = FaultPlan(seed=0, failed_partitions=failed_partitions)
    with use_plan(plan):
        return spark_model.fit((x[:256], y[:256]), epochs=1, batch_size=32)


def test_worker_loss_within_budget_continues(blobs):
    history = _budget_fit(blobs, failure_budget=1, failed_partitions=(2,))
    assert len(history["loss"]) == 1  # trained on the survivors


def test_worker_loss_beyond_budget_raises_clearly(blobs):
    with pytest.raises(FaultBudgetExceeded, match="failure_budget=1"):
        _budget_fit(blobs, failure_budget=1, failed_partitions=(0, 2))


# -- fit(resume=True) seeds the PS from its journal ----------------------


def test_resume_seeds_master_from_ps_journal(blobs, tmp_path):
    """A driver restart with resume=True replays the PS journal: the
    journaled (possibly sub-epoch) weights — not the older epoch
    checkpoint — become the master state and the served weights."""
    import keras

    from elephas_tpu import SparkModel

    x, y, d, k = blobs
    ckpt_dir, journal_dir = str(tmp_path / "ckpt"), str(tmp_path / "ps")
    keras.utils.set_random_seed(0)
    model = keras.Sequential([
        keras.layers.Input((d,)),
        keras.layers.Dense(8, activation="relu"),
        keras.layers.Dense(k, activation="softmax"),
    ])
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    spark_model = SparkModel(
        model, mode="asynchronous", num_workers=2,
        parameter_server_mode="socket", port=0,
        ps_journal_dir=journal_dir,
    )
    spark_model.fit((x[:128], y[:128]), epochs=1, batch_size=32,
                    checkpoint_dir=ckpt_dir)
    # simulate post-checkpoint PS-side progress (what a crash would
    # strand in the journal): bump the journaled weights directly
    weights, table, _ = journal.load_journal(journal_dir)
    marker = [np.asarray(w) + 0.125 for w in weights]
    journal.save_journal(journal_dir, marker, table)

    spark_model2 = SparkModel(
        model, mode="asynchronous", num_workers=2,
        parameter_server_mode="socket", port=0,
        ps_journal_dir=journal_dir,
    )
    # resume with MORE epochs would retrain; equal epochs exits at the
    # restore point — the master must then hold the journaled weights
    spark_model2.fit((x[:128], y[:128]), epochs=1, batch_size=32,
                     checkpoint_dir=ckpt_dir, resume=True)
    got = spark_model2.master_network.get_weights()
    for a, b in zip(got, marker):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_non_resume_fit_does_not_replay_stale_journal(blobs, tmp_path):
    """A FRESH fit (resume=False) over a directory holding a previous
    run's journal must start from the model's own weights — silently
    continuing from stale journal state is the one unacceptable
    default. (resume=True replays it; tested above.)"""
    import keras

    from elephas_tpu import SparkModel

    x, y, d, k = blobs
    journal_dir = str(tmp_path)
    keras.utils.set_random_seed(0)
    model = keras.Sequential([
        keras.layers.Input((d,)),
        keras.layers.Dense(8, activation="relu"),
        keras.layers.Dense(k, activation="softmax"),
    ])
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    stale = [np.asarray(w) + 9.0 for w in model.get_weights()]
    journal.save_journal(journal_dir, stale, {"old-worker": 99})
    spark_model = SparkModel(
        model, mode="asynchronous", num_workers=2,
        parameter_server_mode="socket", port=0,
        ps_journal_dir=journal_dir,
    )
    spark_model.start_server(restore_journal=False)  # the fit() default
    try:
        server = spark_model._parameter_server
        assert not server.restored_from_journal
        assert server.seq_table == {}
        for a, b in zip(server.get_parameters(), model.get_weights()):
            np.testing.assert_array_equal(a, b)  # fresh, not stale
    finally:
        spark_model.stop_server()
    # the clean stop overwrote the stale journal with this run's state
    restored, seq, _ = journal.load_journal(journal_dir)
    assert seq == {}
    np.testing.assert_array_equal(restored[0], model.get_weights()[0])
