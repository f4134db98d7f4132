"""Continuous weight deployment (ISSUE 20): the version ledger's
monotonic generation mint (rollback included), the serving-side
subscriber's consistent-cut pull with apply-iff-newer idempotence,
the canary controller's promote/rollback state machine over the fleet
Router, chaos convergence through a shard kill mid-deployment, and
the weight-generation stamp on every debug surface plus the migration
wire's mixed-generation refusal.

Socket-opening tests here ride the same per-test SIGALRM deadline as
the other PS suites (conftest ``_PS_DEADLINE_MODULES``).
"""

import tempfile
import time

import numpy as np
import pytest

from elephas_tpu.deploy import (
    CanaryController,
    VersionLedger,
    WeightSubscriber,
)
from elephas_tpu.parameter.client import ShardedClient
from elephas_tpu.parameter.server import SocketServer

VOCAB, MAXLEN = 16, 32


def _weights(seed: int = 0, n: int = 4):
    rng = np.random.default_rng(seed)
    shapes = [(8, 4), (4,), (3, 3), (6,)][:n]
    return [rng.normal(size=s).astype(np.float32) for s in shapes]


def _store(weights, **kw):
    """In-process PS store: an UNstarted SocketServer is a plain
    host-side object with the full store surface (set_weights /
    get_parameters / status / write_journal) — no sockets needed
    until a test actually wants the wire."""
    return SocketServer(
        [np.asarray(w) for w in weights], mode="asynchronous",
        port=0, **kw,
    )


def _lm(seed: int = 1):
    """Private model instance — deployment tests MUTATE model weights
    (that is the point), so nothing here shares the module fixture.
    Same seed ⇒ identical init, the fleet-replica invariant."""
    from elephas_tpu.models import transformer_lm

    return transformer_lm(
        vocab_size=VOCAB, maxlen=MAXLEN, d_model=32, num_heads=2,
        num_layers=2, dropout=0.0, seed=seed,
    )


@pytest.fixture(scope="module")
def lm():
    """Shared read-only model for tests that never rewrite weights."""
    return _lm(seed=0)


def make_engine(model, **overrides):
    from elephas_tpu.serving import InferenceEngine

    kw = dict(
        num_slots=2, paged=True, block_size=4, num_blocks=16,
        preemption=True, prefix_cache=True,
    )
    kw.update(overrides)
    return InferenceEngine(model, **kw)


class _FakeModel:
    def __init__(self):
        self.weights = None

    def set_weights(self, weights):
        self.weights = [np.asarray(w) for w in weights]


class _FakeEngine:
    """The three things a subscriber touches on an engine — enough to
    unit-test the poll protocol without a compile."""

    telemetry_label = "fake-engine"

    def __init__(self):
        self.model = _FakeModel()
        self.weight_version = 0
        self.refreshes = 0

    def refresh_weights(self, version=None):
        if version is not None:
            self.weight_version = int(version)
        self.refreshes += 1


class _FakeClient:
    """Scriptable PS-client surface for cut/tear/outage scenarios."""

    def __init__(self, weights, version=0, shards=1):
        self.weights = [np.asarray(w) for w in weights]
        self.versions = [int(version)] * shards
        self.status_error = None
        self.pull_error = None

    def status(self):
        if self.status_error is not None:
            raise self.status_error
        return [{"weight_version": v} for v in self.versions]

    def get_parameters(self):
        if self.pull_error is not None:
            raise self.pull_error
        return [w.copy() for w in self.weights]


# -- the ledger ----------------------------------------------------------


class TestVersionLedger:
    def test_publish_mints_monotonic_and_stamps_every_surface(self):
        w = _weights()
        store = _store(w)
        ledger = VersionLedger(store)
        assert ledger.version == 0
        w1 = [x + 1.0 for x in w]
        assert ledger.publish(w1) == 1
        assert store.status()["weight_version"] == 1
        for a, b in zip(store.get_parameters(), w1):
            np.testing.assert_array_equal(a, b)
        assert ledger.publish([x + 2.0 for x in w]) == 2
        st = ledger.status()
        assert st["version"] == 2 and st["converged"]
        assert st["shard_versions"] == [2]
        assert ledger.known_versions() == [0, 1, 2]

    def test_rollback_mints_new_generation_with_old_content(self):
        w = _weights()
        store = _store(w)
        ledger = VersionLedger(store)
        w1 = [x + 1.0 for x in w]
        ledger.publish(w1)
        ledger.publish([x + 2.0 for x in w])
        # rollback is a FORWARD publication of generation 1's content
        assert ledger.rollback(1) == 3
        assert ledger.version == 3
        assert store.status()["weight_version"] == 3
        for a, b in zip(store.get_parameters(), w1):
            np.testing.assert_array_equal(a, b)  # bit-exact restore
        with pytest.raises(KeyError, match="99"):
            ledger.rollback(99)

    def test_history_bound_evicts_oldest(self):
        w = _weights()
        ledger = VersionLedger(_store(w), keep_generations=2)
        for k in range(3):
            ledger.publish([x + float(k + 1) for x in w])
        assert ledger.known_versions() == [2, 3]
        with pytest.raises(KeyError, match="generation 0"):
            ledger.weights_of(0)
        with pytest.raises(KeyError):
            ledger.rollback(1)  # evicted — loud, not a silent re-seed
        with pytest.raises(ValueError, match="keep_generations"):
            VersionLedger(_store(w), keep_generations=0)

    def test_resumes_above_store_generation(self):
        w = _weights()
        store = _store(w)
        store.set_weights([x.copy() for x in w], weight_version=5)
        ledger = VersionLedger(store)
        assert ledger.version == 5
        assert ledger.publish([x + 1.0 for x in w]) == 6  # never reuse

    def test_journal_restores_generation_and_content(self):
        w = _weights(seed=3)
        with tempfile.TemporaryDirectory() as jd:
            store = _store(w, journal_dir=jd, journal_every=1)
            ledger = VersionLedger(store)
            ledger.publish([x + 1.0 for x in w])
            w2 = [x + 2.0 for x in w]
            ledger.publish(w2)
            # crash-restart: a fresh server over the same journal dir
            # comes back INTO generation 2, weights bit-exact
            revived = _store(
                [np.zeros_like(x) for x in w], journal_dir=jd,
            )
            assert revived.restored_from_journal
            assert revived.status()["weight_version"] == 2
            for a, b in zip(revived.get_parameters(), w2):
                np.testing.assert_array_equal(a, b)
            # a supervisor restarted over it keeps minting above 2
            assert VersionLedger(revived).version == 2


# -- the subscriber ------------------------------------------------------


class TestWeightSubscriber:
    def test_applies_iff_newer_never_twice(self):
        w = _weights()
        eng = _FakeEngine()
        client = _FakeClient(w, version=1)
        sub = WeightSubscriber(eng, client)
        assert sub.poll_once() == 1
        assert eng.weight_version == 1 and eng.refreshes == 1
        for a, b in zip(eng.model.weights, w):
            np.testing.assert_array_equal(a, b)
        # same generation again: the version compare makes the retry
        # a no-op — THE double-apply guard
        assert sub.poll_once() is None
        assert sub.applies == 1 and eng.refreshes == 1
        # an older store (rolled-back shard view) never applies
        client.versions = [0]
        assert sub.poll_once() is None
        assert sub.applies == 1
        st = sub.status()
        assert st["applied_version"] == 1 and st["pulls"] == 1

    def test_pin_holds_generation_until_unpinned(self):
        eng = _FakeEngine()
        client = _FakeClient(_weights(), version=1)
        sub = WeightSubscriber(eng, client, staleness_bound=2)
        sub.poll_once()
        sub.pin(1)
        client.versions = [2]
        assert sub.poll_once() is None  # seen but refused
        assert sub.skips["pinned"] == 1
        assert sub.status()["seen_version"] == 2
        assert sub.violations == 0  # a pinned lag is intentional
        sub.unpin()
        assert sub.poll_once() == 2
        assert eng.weight_version == 2

    def test_mixed_cut_skips_serving_never_tears(self):
        eng = _FakeEngine()
        client = _FakeClient(_weights(), version=1, shards=2)
        sub = WeightSubscriber(eng, client)
        client.versions = [2, 1]  # deployment in flight
        assert sub.poll_once() is None
        assert sub.skips["mixed_cut"] == 1 and sub.pulls == 0
        client.versions = [2, 2]
        assert sub.poll_once() == 2

    def test_torn_pull_discards_the_gather(self):
        eng = _FakeEngine()
        client = _FakeClient(_weights(), version=1)
        orig = client.get_parameters

        def moving_pull():
            out = orig()
            client.versions = [2]  # store moves mid-pull
            return out

        client.get_parameters = moving_pull
        sub = WeightSubscriber(eng, client)
        assert sub.poll_once() is None
        assert sub.skips["torn_pull"] == 1
        assert sub.applies == 0 and eng.weight_version == 0
        client.get_parameters = orig
        assert sub.poll_once() == 2  # clean cut next round

    def test_wire_errors_skip_and_staleness_counts(self):
        eng = _FakeEngine()
        client = _FakeClient(_weights(), version=1)
        sub = WeightSubscriber(eng, client, staleness_bound=0)
        client.status_error = ConnectionRefusedError("ps down")
        assert sub.poll_once() is None
        assert sub.skips["wire_error"] == 1
        assert sub.violations == 0  # nothing newer SEEN yet
        client.status_error = None
        client.pull_error = TimeoutError("pull hung")
        assert sub.poll_once() is None
        assert sub.skips["wire_error"] == 2
        # the cut was seen before the pull died: lag 1 > bound 0
        assert sub.violations == 1
        assert sub.status()["staleness"] == 1
        client.pull_error = None
        assert sub.poll_once() == 1
        assert sub.status()["staleness"] == 0
        with pytest.raises(ValueError, match="staleness_bound"):
            WeightSubscriber(eng, client, staleness_bound=-1)

    def test_background_thread_converges_and_stops(self):
        w = _weights()
        eng = _FakeEngine()
        store = _store(w)
        ledger = VersionLedger(store)
        sub = WeightSubscriber(eng, store)
        with sub.start(interval_s=0.01):
            ledger.publish([x + 1.0 for x in w])
            deadline = time.monotonic() + 30
            while sub.applied_version != 1:
                assert time.monotonic() < deadline, sub.status()
                time.sleep(0.01)
        assert sub._thread is None  # stopped
        assert eng.weight_version == 1
        with sub.start(interval_s=60):
            with pytest.raises(RuntimeError, match="already started"):
                sub.start()

    def test_live_engine_applies_generation_end_to_end(self):
        """The real path: ledger → in-process store → subscriber →
        ``refresh_weights(version=)`` on a compiled engine, weights
        bit-exact and the engine still serving afterwards."""
        from elephas_tpu.serving import InferenceEngine

        model = _lm(seed=1)
        engine = InferenceEngine(model, num_slots=2)
        store = _store(model.get_weights())
        ledger = VersionLedger(store)
        sub = WeightSubscriber(engine, store)
        w2 = [w * 1.05 for w in model.get_weights()]
        version = ledger.publish(w2)
        assert sub.poll_once() == version
        assert engine.weight_version == version
        assert engine.stats()["weight_version"] == version
        for a, b in zip(model.get_weights(), w2):
            np.testing.assert_array_equal(a, b)
        out = engine.run([([2, 3, 4], 3)])
        assert out and all(len(t) >= 1 for t in out.values())
        assert sub.status()["skips"] == {
            "wire_error": 0, "mixed_cut": 0, "pinned": 0,
            "torn_pull": 0,
        }


    def test_generations_pushed_between_requests_keep_the_streams(self):
        """A live deployment tears nothing: the same closed-loop drive
        runs twice over one engine, the second time with the ledger
        publishing a new generation (same content, new number) at
        three points between requests. Every generation applies
        exactly once with no skip, the engine ends on the ledger's
        number, and every stream equals its steady-state twin."""
        from elephas_tpu.serving import InferenceEngine

        model = _lm(seed=1)
        engine = InferenceEngine(model, num_slots=2)
        store = _store(model.get_weights())
        ledger = VersionLedger(store)
        sub = WeightSubscriber(engine, store)
        content = [np.asarray(w).copy() for w in model.get_weights()]
        rng = np.random.default_rng(51)
        prompts = [
            rng.integers(1, VOCAB, size=6).tolist() for _ in range(8)
        ]
        push_at = {2, 4, 6}

        def drive(pushing):
            streams = []
            for i, p in enumerate(prompts):
                if pushing and i in push_at:
                    version = ledger.publish([w.copy() for w in content])
                    assert sub.poll_once() == version
                out = engine.run([(p, 8)])
                streams.append(list(out.values())[0].tolist())
            return streams

        steady = drive(pushing=False)
        assert drive(pushing=True) == steady
        assert sub.applies == len(push_at)
        assert not any(sub.skips.values())
        assert engine.weight_version == ledger.version == len(push_at)
        engine.release_telemetry()


# -- canary rollout ------------------------------------------------------


class _ScriptedWatchdog:
    """Watchdog stand-in the controller can read deterministically:
    here the state machine is the subject. The real ``slo_burn``
    under traffic is ``test_deadline_misses_burn_and_roll_back``
    below, and the rule itself is pinned by ``test_telemetry_fleet``."""

    def __init__(self):
        self.burning = False
        self.evaluations = 0

    def evaluate(self):
        self.evaluations += 1
        return []

    def report(self):
        active = [{"rule": "slo_burn"}] if self.burning else []
        return {"active": active}


def _fleet(tmp_models=None, poll_every=50, **engine_kw):
    from elephas_tpu.fleet import Router

    models = tmp_models or [_lm(seed=1), _lm(seed=1)]
    engines = {
        "stable": make_engine(models[0], **engine_kw),
        "canary": make_engine(models[1], **engine_kw),
    }
    store = _store(models[0].get_weights())
    ledger = VersionLedger(store)
    router = Router(engines, poll_every=poll_every)
    subs = {
        name: WeightSubscriber(eng, store)
        for name, eng in engines.items()
    }
    return engines, store, ledger, router, subs


class TestCanaryController:
    def test_promote_on_clean_window(self):
        engines, store, ledger, router, subs = _fleet()
        base = [w.copy() for w in store.get_parameters()]
        with router:
            ctrl = CanaryController(
                router, ledger, subs, canary=["canary"], share=0.5,
                window=2, watchdog=_ScriptedWatchdog(),
            )
            gen = ctrl.begin([w * 1.01 for w in base])
            assert gen == 1 and ctrl.state == "canary"
            # canary applied, stable pinned at the baseline
            assert subs["canary"].applied_version == 1
            assert subs["stable"].applied_version == 0
            assert subs["stable"].pinned == 0
            assert router.canary_status() == {
                "replicas": ["canary"], "share": 0.5,
                "placements_seen": 0,
            }
            assert ctrl.evaluate() == "canary"  # clean 1 of 2
            assert ctrl.evaluate() == "idle"    # clean 2 → promote
            assert ctrl.last_outcome == "promoted"
            assert ctrl.promotions == 1 and ctrl.rollbacks == 0
            # stable unpinned and converged on the candidate
            assert subs["stable"].pinned is None
            assert subs["stable"].applied_version == 1
            assert engines["stable"].weight_version == 1
            assert router.canary_status()["share"] == 0.0
            # begin() is single-flight only while one is live
            ctrl.begin([w * 1.02 for w in base])
            with pytest.raises(RuntimeError, match="already in flight"):
                ctrl.begin(base)

    def test_rollback_restores_baseline_content_fleet_wide(self):
        engines, store, ledger, router, subs = _fleet()
        base = [w.copy() for w in store.get_parameters()]
        wd = _ScriptedWatchdog()
        with router:
            ctrl = CanaryController(
                router, ledger, subs, canary=["canary"], share=0.25,
                window=4, watchdog=wd,
            )
            ctrl.begin([w * 1.5 for w in base])  # a "bad" candidate
            wd.burning = True
            assert ctrl.evaluate() == "idle"
            assert ctrl.last_outcome == "rolled_back"
            assert ctrl.rollbacks == 1
            # monotonic: the rollback is generation 2 serving
            # generation 0's content, bit-exact, on EVERY replica
            assert ledger.version == 2
            for sub in subs.values():
                assert sub.applied_version == 2
                assert sub.pinned is None
            for name in ("stable", "canary"):
                assert engines[name].weight_version == 2
                for a, b in zip(
                    engines[name].model.get_weights(), base
                ):
                    np.testing.assert_array_equal(a, b)
            assert router.canary_status()["share"] == 0.0
            with pytest.raises(RuntimeError, match="roll back"):
                ctrl.rollback()

    def test_deadline_misses_burn_and_roll_back(self):
        """The whole loop on real counters: the 0.5 split places
        traffic on the canary, met deadlines leave the cycle open,
        then first-token deadlines no engine can meet (a microsecond)
        burn the SLO on the fleet scraper's view and the controller's
        own watchdog rolls the cycle back. Exactly one anomaly fires
        and one clears, every replica lands on the rollback
        generation, and the split is gone."""
        from elephas_tpu.serving.policy import FairSharePolicy

        engines, store, ledger, router, subs = _fleet(
            poll_every=4, policy=FairSharePolicy(),
        )
        content = [w.copy() for w in store.get_parameters()]
        rng = np.random.default_rng(53)

        def burst(deadline_ms):
            reqs = [
                router.submit(
                    rng.integers(1, VOCAB, size=6).tolist(), 4,
                    ttft_deadline_ms=deadline_ms,
                )
                for _ in range(6)
            ]
            assert all(r.wait(120) for r in reqs)
            router.scraper.poll()

        with router:
            ctrl = CanaryController(
                router, ledger, subs, canary=["canary"], share=0.5,
                window=4,
            )
            router.scraper.poll()
            ctrl.watchdog.evaluate()  # the rule's delta baseline
            candidate = ctrl.begin([w.copy() for w in content])
            burst(60_000.0)
            assert router.canary_status()["placements_seen"] >= 1
            assert ctrl.evaluate() == "canary"  # met: no hair trigger
            router.set_canary(["canary"], 1.0)
            burst(0.001)
            assert ctrl.evaluate() == "idle"
            assert ctrl.last_outcome == "rolled_back"
            router.scraper.poll()
            ctrl.watchdog.evaluate()  # a quiet window clears it
            report = ctrl.watchdog.report()
            assert report["fired_total"] == 1
            assert report["cleared_total"] == 1
            assert ledger.version > candidate  # a forward generation
            for sub in subs.values():
                assert sub.applied_version == ledger.version
            assert router.canary_status()["share"] == 0.0
        ctrl.release_telemetry()
        ctrl.watchdog.release_telemetry()
        router.release_telemetry()
        for part in (*subs.values(), ledger, *engines.values()):
            part.release_telemetry()

    def test_constructor_validates_loudly(self):
        engines, store, ledger, router, subs = _fleet()
        try:
            kw = dict(watchdog=_ScriptedWatchdog())
            with pytest.raises(ValueError, match="PROPER subset"):
                CanaryController(
                    router, ledger, subs,
                    canary=["stable", "canary"], **kw,
                )
            with pytest.raises(ValueError, match="not replicas"):
                CanaryController(
                    router, ledger, subs, canary=["ghost"], **kw,
                )
            with pytest.raises(ValueError, match="no subscriber"):
                CanaryController(
                    router, ledger, {"canary": subs["canary"]},
                    canary=["canary"], **kw,
                )
            with pytest.raises(ValueError, match="window"):
                CanaryController(
                    router, ledger, subs, canary=["canary"],
                    window=0, **kw,
                )
            ctrl = CanaryController(
                router, ledger, subs, canary=["canary"], **kw,
            )
            with pytest.raises(RuntimeError, match="promote"):
                ctrl.promote()
            assert ctrl.evaluate() == "idle"  # no-op while idle
        finally:
            for eng in engines.values():
                eng.release_telemetry()


# -- chaos: shard kill mid-deployment ------------------------------------


def test_shard_kill_mid_deployment_converges_exactly_once():
    """Kill one PS shard between two publications: pulls fail loudly
    (counted, serving keeps the old generation), the parked push
    fires the ``ps_unreachable`` watchdog rule, the restarted shard
    rejoins from its journal on the OLD generation (mixed cut — still
    no apply), and the next publication converges every replica with
    exactly one apply per generation — zero double-applies."""
    from elephas_tpu.fault import (
        DeployChaosStore,
        ShardedRestartablePS,
    )
    from elephas_tpu.telemetry.watch import (
        PsUnreachableRule,
        Watchdog,
    )

    w = _weights(seed=11)
    with tempfile.TemporaryDirectory() as jd:
        harness = ShardedRestartablePS(
            SocketServer, w, 2, journal_dir=jd, journal_every=1,
        )
        clients = {}
        try:
            store = DeployChaosStore(harness)
            ledger = VersionLedger(store)
            engines = {name: _FakeEngine() for name in ("a", "b")}
            for name in engines:
                clients[name] = ShardedClient(
                    harness.endpoints, harness.shard_map,
                    transport="socket", client_id=name, retries=1,
                )
            subs = {
                name: WeightSubscriber(
                    engines[name], clients[name], staleness_bound=1,
                )
                for name in engines
            }
            wd = Watchdog(rules=[PsUnreachableRule(clear_after=2)])
            wd.evaluate()  # prime the delta baseline

            g1 = ledger.publish([x + 1.0 for x in w])
            assert all(
                sub.poll_once() == g1 for sub in subs.values()
            )
            harness.kill(0)
            g2 = ledger.publish([x + 2.0 for x in w])  # past the corpse
            for sub in subs.values():
                assert sub.poll_once() is None  # outage = stale, not torn
                assert sub.skips["wire_error"] >= 1
                assert sub.applied_version == g1
            # training pushes against the dead slice park → the
            # watchdog names the outage (pulls alone never park).
            # First park mints the labeled series; the delta-based
            # rule needs one evaluation as its baseline before the
            # second park shows as a rising count.
            zeros = [np.zeros_like(x) for x in w]
            clients["a"].update_parameters(zeros)
            wd.evaluate()
            clients["a"].update_parameters(zeros)
            assert any(
                a.rule == "ps_unreachable" for a in wd.evaluate()
            )
            harness.restart(0)
            assert harness.servers[0].restored_from_journal
            # the revived shard journaled at g1: a MIXED cut — seen,
            # counted, never applied
            assert not ledger.status()["converged"]
            for sub in subs.values():
                assert sub.poll_once() is None
                assert sub.skips["mixed_cut"] >= 1
            clients["a"].flush()  # replay the parked push exactly-once
            wd.evaluate()
            assert wd.evaluate() == []  # quiet window clears
            rep = wd.report()
            assert rep["fired_total"] == 1
            assert rep["cleared_total"] == 1
            # the NEXT publication re-converges the store and fleet
            g3 = ledger.publish([x + 2.0 for x in w])
            assert g3 == g2 + 1
            assert all(
                sub.poll_once() == g3 for sub in subs.values()
            )
            assert ledger.status()["converged"]
            for sub in subs.values():
                # g1 and g3 applied once each; g2 never landed; a
                # re-poll after convergence applies NOTHING again
                assert sub.applies == 2
                assert sub.poll_once() is None
                assert sub.applies == 2
            counters = harness.counters()
            assert counters["updates_duplicate"] == 0
        finally:
            for cl in clients.values():
                cl.close()
            harness.stop()


# -- the stamp on every surface ------------------------------------------


class TestWeightVersionSurfaces:
    def test_stats_snapshot_and_explain_carry_the_generation(self, lm):
        from elephas_tpu.serving import InferenceEngine

        engine = InferenceEngine(lm, num_slots=2, flight_recorder=8)
        engine.refresh_weights(version=3)
        assert engine.stats()["weight_version"] == 3
        assert engine.debug_snapshot()["weight_version"] == 3
        r1 = engine.submit([2, 3, 4], 2)
        engine.run()
        engine.refresh_weights(version=4)
        r2 = engine.submit([2, 3, 4], 2)
        engine.run()
        # each record keeps the generation it was SUBMITTED under —
        # how a trace diagnoses a request that straddled a deployment
        assert engine.explain(r1.rid)["weight_version"] == 3
        assert engine.explain(r2.rid)["weight_version"] == 4
        engine.release_telemetry()
        # the draft-model cascade (refresh_weights re-stamps the
        # drafter) is pinned token-exact in test_serving_prefix.py::
        # test_versioned_refresh_cascades_to_draft_model


# -- migration wire ------------------------------------------------------


class TestMigrationWeightVersion:
    def _warm_record(self, engine, prompt=(2, 3, 4, 5, 2, 3, 4, 5)):
        from elephas_tpu.fleet import decode_record, encode_record

        req = engine.submit(list(prompt), 8)
        for _ in range(4):
            engine.step()
        payload = engine.export_request(req.rid)
        assert payload["n_blocks"] > 0  # warm — K/V travels
        return decode_record(encode_record(payload))

    def _drain(self, engine):
        while engine.scheduler.has_work:
            engine.step()

    def test_generation_refusal_and_unversioned_interop(self, lm):
        """Warm resume across replicas: mismatched NON-zero
        generations refuse loudly; convergence unblocks the same
        record; and the shard-identity idiom (0 = "cannot verify")
        keeps legacy v2 records and unversioned engines
        interoperating."""
        a = make_engine(lm)
        b = make_engine(lm)
        c = make_engine(lm)  # stays unversioned (weight_version 0)
        a.refresh_weights(version=5)
        b.refresh_weights(version=7)
        record = self._warm_record(a)
        assert record["weight_ver"] == 5
        with pytest.raises(ValueError, match="weight_ver"):
            b.import_request(record)
        # convergence unblocks the SAME record
        b.refresh_weights(version=5)
        resumed = b.import_request(record)
        self._drain(b)
        assert resumed.done
        # legacy v2 record (no weight_ver) into a versioned engine:
        # the record cannot verify, so it passes
        b.refresh_weights(version=7)
        legacy = dict(
            self._warm_record(a, prompt=(3, 4, 5, 6, 3, 4, 5))
        )
        legacy["version"] = 2
        legacy.pop("weight_ver")
        resumed2 = b.import_request(legacy)
        self._drain(b)
        assert resumed2.done
        # versioned record into an unversioned engine: also accepted
        assert c.weight_version == 0
        record3 = self._warm_record(a, prompt=(4, 5, 6, 2, 4, 5, 6))
        resumed3 = c.import_request(record3)
        self._drain(c)
        assert resumed3.done
        for eng in (a, b, c):
            eng.release_telemetry()
