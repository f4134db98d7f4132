"""Serving fleet (ISSUE 14): replicated engines behind the prefix- and
load-aware router, cross-replica live migration, cancellation, and the
chaos story.

Acceptance contracts pinned here:

- **bit-exact migration** — a request preempted on replica A and
  resumed on replica B (through the v1 wire bytes) emits the identical
  token stream as an unmigrated run at temperature 0, token for token;
- **drain** empties a replica with zero dropped and zero doubled
  tokens;
- **placement determinism** — same fleet snapshot + same prompt ⇒ same
  replica on every call AND across processes (no wall clock, no
  dict-order dependence), with the stale-view → round-robin
  degradation counted;
- **rid uniqueness** — engines mint rids from disjoint strides, the
  root-cause fix for the pre-existing ``test_serving_trace``
  reconstruction flake (rids used to collide across engines);
- **cancel** reclaims slots/blocks deterministically and is wired to
  gateway SSE client disconnects;
- chaos: kill a replica mid-stream → survivors re-drive with zero
  double tokens → the ``replica_down`` watchdog rule fires, then
  clears on restore.
"""

import json
import socket
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

from elephas_tpu import telemetry
from elephas_tpu.fleet import (
    PlacementDecision,
    Router,
    decode_record,
    encode_record,
    place,
)

VOCAB, MAXLEN = 16, 32


@pytest.fixture(scope="module")
def lm():
    """Tiny UNtrained LM: migration/placement contracts are about
    determinism, not model quality — greedy argmax of a fixed init is
    all the parity asserts need (and skipping the fit keeps the suite
    inside tier-1's wall clock)."""
    from elephas_tpu.models import transformer_lm

    return transformer_lm(
        vocab_size=VOCAB, maxlen=MAXLEN, d_model=32, num_heads=2,
        num_layers=2, dropout=0.0, seed=0,
    )


def make_engine(lm, **overrides):
    from elephas_tpu.serving import InferenceEngine

    kw = dict(
        num_slots=2, paged=True, block_size=4, num_blocks=16,
        preemption=True, prefix_cache=True,
    )
    kw.update(overrides)
    return InferenceEngine(lm, **kw)


def throttle(engines, step_s=0.01):
    """Slow every engine's ``step`` so that a drain or a kill lands
    provably mid-stream (a fast box must not finish the streams
    first)."""
    for eng in engines.values():
        real = eng.step
        eng.step = (lambda real=real: (time.sleep(step_s), real())[1])


_REF_ENGINE = {}


def reference_run(lm, prompt, max_new):
    """Unmigrated single-engine greedy run — the parity oracle. ONE
    shared engine serves every reference (temperature-0 output is a
    pure function of weights + prompt; prefix reuse between reference
    runs is exact by the PR-4/7 contracts), so the suite does not pay
    a fresh compile set per oracle call."""
    eng = _REF_ENGINE.get(id(lm))
    if eng is None:
        eng = _REF_ENGINE[id(lm)] = make_engine(lm)
    out = eng.run([(list(prompt), max_new)])
    return list(out.values())[0].tolist()


# -- rid minting (the test_serving_trace flake, fixed at the root) ----


class TestRidMinting:
    def test_engines_mint_disjoint_rids(self, lm):
        from elephas_tpu.serving.scheduler import RID_STRIDE

        a = make_engine(lm)
        b = make_engine(lm)
        ra = [a.submit([2, 3], 1) for _ in range(3)]
        rb = [b.submit([2, 3], 1) for _ in range(3)]
        rids_a = {r.rid for r in ra}
        rids_b = {r.rid for r in rb}
        assert not rids_a & rids_b
        # same stride-block per engine, consecutive within it
        assert {r.rid - a.scheduler.rid_base for r in ra} == {0, 1, 2}
        assert {r.rid - b.scheduler.rid_base for r in rb} == {0, 1, 2}
        assert abs(a.scheduler.rid_base - b.scheduler.rid_base) \
            >= RID_STRIDE
        a.release_telemetry()
        b.release_telemetry()


# -- cancellation (ISSUE 14 satellite) --------------------------------


class TestCancel:
    def test_waiting_active_and_finished(self, lm):
        from elephas_tpu.serving import RequestCancelled

        eng = make_engine(lm, num_slots=1, num_blocks=8)
        a = eng.submit([2, 3, 4], 20)
        b = eng.submit([3, 4, 5], 20)  # queued behind the one slot
        eng.step()
        assert a.tokens and not b.tokens
        # waiting cancel: leaves the queue, debt drops
        assert eng.cancel(b.rid) is True
        assert b.done and isinstance(b.error, RequestCancelled)
        assert eng.scheduler.queued_tokens == 0
        # active cancel: slot + full block reservation reclaim
        assert eng.cancel(a.rid) is True
        assert a.done and isinstance(a.error, RequestCancelled)
        assert not eng.scheduler.active
        # prompt had 3 tokens -> 1 full block may stay referenced by
        # the prefix index; everything else frees
        assert eng.scheduler.allocator.free_count >= 8 - 1
        # finished/unknown: False, not an error
        assert eng.cancel(a.rid) is False
        assert eng.cancel(10**15 + 12345) is False
        assert eng.stats()["cancelled"] == 2
        # engine keeps serving after cancels
        c = eng.submit([2, 3, 4, 5], 4)
        while eng.scheduler.has_work:
            eng.step()
        assert c.done and c.error is None and len(c.tokens) == 4
        eng.release_telemetry()

    def test_cancel_preempted_request_drops_offload(self, lm):
        eng = make_engine(lm, num_slots=2, num_blocks=10)
        low = eng.submit([2, 3, 4, 5, 2, 3], 16, priority=0)
        eng.step()
        assert low.tokens
        eng.submit([3, 4, 5, 2], 16, priority=5)
        eng.step()  # pool pressure preempts the low-priority request
        assert eng.stats()["preemptions"] >= 1
        assert low.rid in eng._offloaded
        assert eng.cancel(low.rid) is True
        assert low.rid not in eng._offloaded
        while eng.scheduler.has_work:
            eng.step()
        eng.release_telemetry()

    def test_gateway_sse_disconnect_cancels(self, lm):
        """A client that resets mid-stream reclaims its slot (the
        ROADMAP-2 hole: before this, the request decoded to
        completion into a queue nobody reads)."""
        from elephas_tpu.serving import Gateway, RequestCancelled

        eng = make_engine(lm, num_slots=1, num_blocks=8)
        real_step = eng.step

        def slow_step():
            time.sleep(0.05)  # keep the stream alive past the reset
            return real_step()

        eng.step = slow_step
        gw = Gateway(eng, port=0).start()
        try:
            body = json.dumps({
                "prompt": [2, 3, 4, 5], "max_new_tokens": 28,
                "stream": True,
            }).encode()
            s = socket.create_connection(
                ("127.0.0.1", gw.port), timeout=30
            )
            s.sendall(
                b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: " + str(len(body)).encode()
                + b"\r\n\r\n" + body
            )
            data = b""
            while b'data: {"token"' not in data:
                data += s.recv(4096)
            rid = int(
                data.split(b"X-Request-Id: ")[1].split(b"\r\n")[0]
            )
            # SO_LINGER 0 close = RST — the abrupt-death client shape
            s.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER,
                struct.pack("ii", 1, 0),
            )
            s.close()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if eng.stats()["cancelled"] >= 1:
                    break
                time.sleep(0.05)
            assert eng.stats()["cancelled"] == 1
            req = eng.finished[rid]
            assert req.done and isinstance(req.error, RequestCancelled)
            assert len(req.tokens) < 28  # cancelled mid-flight
        finally:
            del eng.step
            gw.stop()
            gw.release_telemetry()
            eng.release_telemetry()

    def test_cancel_unblocks_live_stream(self, lm):
        """Cancelling a request that a live ``/v1/generate`` handler
        is streaming must END that stream (the engine sends the
        ``(None, True)`` end sentinel), not leave the handler hanging
        on a token queue nobody will ever feed again."""
        from elephas_tpu.serving import Gateway

        eng = make_engine(lm, num_slots=1, num_blocks=8)
        real_step = eng.step

        def slow_step():
            time.sleep(0.05)
            return real_step()

        eng.step = slow_step
        gw = Gateway(eng, port=0).start()
        try:
            body = json.dumps({
                "prompt": [2, 3, 4, 5], "max_new_tokens": 28,
                "stream": True,
            }).encode()
            s = socket.create_connection(
                ("127.0.0.1", gw.port), timeout=60
            )
            s.sendall(
                b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: " + str(len(body)).encode()
                + b"\r\n\r\n" + body
            )
            data = b""
            while b'data: {"token"' not in data:
                data += s.recv(4096)
            rid = int(
                data.split(b"X-Request-Id: ")[1].split(b"\r\n")[0]
            )
            import http.client

            conn = http.client.HTTPConnection(
                "127.0.0.1", gw.port, timeout=30
            )
            conn.request("POST", f"/v1/requests/{rid}/cancel")
            assert conn.getresponse().status == 200
            conn.close()
            # the live stream ENDS: server sends the done summary
            # (with the cancel error) and closes the connection
            while b"event: done" not in data:
                chunk = s.recv(4096)
                assert chunk, "server closed without a done event"
                data += chunk
            final = json.loads(
                data.split(b"event: done\ndata: ")[1]
                .split(b"\n")[0]
            )
            assert "cancelled" in (final["error"] or "")
            assert final["n_tokens"] < 28
            s.close()
        finally:
            del eng.step
            gw.stop()
            gw.release_telemetry()
            eng.release_telemetry()

    def test_gateway_cancel_route(self, lm):
        import http.client

        from elephas_tpu.serving import Gateway

        eng = make_engine(lm, num_slots=1, num_blocks=8)
        gw = Gateway(eng, port=0).start()
        try:
            # a queued request (slot occupied) is cancellable by rid
            a = eng.submit([2, 3, 4], 20)
            b = eng.submit([3, 4, 5], 20)
            conn = http.client.HTTPConnection(
                "127.0.0.1", gw.port, timeout=30
            )
            conn.request("POST", f"/v1/requests/{b.rid}/cancel")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["cancelled"] is True
            conn.close()
            assert b.done
            conn = http.client.HTTPConnection(
                "127.0.0.1", gw.port, timeout=30
            )
            conn.request("POST", f"/v1/requests/{b.rid}/cancel")
            assert conn.getresponse().status == 404  # already done
            conn.close()
            assert not a.done  # untouched neighbor
        finally:
            gw.stop()
            gw.release_telemetry()
            eng.release_telemetry()


# -- migration wire format --------------------------------------------


class TestMigrationCodec:
    def _record(self):
        rng = np.random.default_rng(7)
        return {
            "version": 1, "rid": 42, "prompt": [2, 3, 4],
            "tokens": [5, 6], "max_new_tokens": 8,
            "temperature": 0.0, "eos_id": None, "priority": 1,
            "tenant": None, "ttft_deadline_ms": None, "trace": "t-1",
            "block_size": 4, "cur_len": 4, "n_blocks": 1,
            "rows": {
                "l0": (
                    rng.standard_normal((1, 4, 2, 3)).astype("f4"),
                    rng.standard_normal((1, 4, 2, 3)).astype("f4"),
                ),
                "l1": (
                    rng.standard_normal((1, 4, 2, 3)).astype("f4"),
                    rng.standard_normal((1, 4, 2, 3)).astype("f4"),
                ),
            },
        }

    def test_round_trip_bitwise(self):
        rec = self._record()
        back = decode_record(encode_record(rec))
        for key in ("rid", "prompt", "tokens", "max_new_tokens",
                    "cur_len", "n_blocks", "block_size", "trace",
                    "priority"):
            assert back[key] == rec[key], key
        for name, (k, v) in rec["rows"].items():
            bk, bv = back["rows"][name]
            assert bk.dtype == k.dtype and bk.shape == k.shape
            assert np.array_equal(bk, k) and np.array_equal(bv, v)

    def test_cold_record_round_trip(self):
        rec = self._record()
        rec.update(rows={}, n_blocks=0, cur_len=0)
        back = decode_record(encode_record(rec))
        assert back["rows"] == {} and back["n_blocks"] == 0

    def test_corruption_is_loud(self):
        data = encode_record(self._record())
        with pytest.raises(ValueError, match="magic"):
            decode_record(b"XXXX" + data[4:])
        with pytest.raises(ValueError, match="truncated"):
            decode_record(data[:-8])
        with pytest.raises(ValueError, match="trailing"):
            decode_record(data + b"\x00" * 4)


# -- cross-replica live migration -------------------------------------


class TestLiveMigration:
    def test_warm_migration_is_bit_exact(self, lm):
        """THE acceptance criterion: preempt on A, resume on B through
        the wire bytes, token stream identical to an unmigrated run."""
        prompt, max_new = [2, 3, 4, 5, 2, 3], 12
        ref = reference_run(lm, prompt, max_new)
        A = make_engine(lm)
        B = make_engine(lm)
        ra = A.submit(prompt, max_new)
        for _ in range(5):
            A.step()
        assert 1 <= len(ra.tokens) < max_new
        payload = A.export_request(ra.rid)
        assert payload["n_blocks"] > 0  # warm — K/V travelled
        record = decode_record(encode_record(payload))
        got = []
        rb = B.import_request(
            record, on_token=lambda t, d: got.append(int(t))
        )
        assert rb.rid == ra.rid  # identity survives migration
        while B.scheduler.has_work:
            B.step()
        assert list(prompt) + ra.tokens + got == ref
        assert rb.tokens == ref[len(prompt):]
        # A is clean: no slot, no offload record, nothing waiting
        assert not A.scheduler.active and not A.scheduler.waiting
        assert ra.rid not in A._offloaded
        assert A.stats()["migrated_out"] == 1
        assert B.stats()["migrated_in"] == 1
        assert B.stats()["resumes"] == 1  # re-entered via resume path
        A.release_telemetry()
        B.release_telemetry()

    def test_cold_export_of_waiting_request(self, lm):
        A = make_engine(lm, num_slots=1, num_blocks=8)
        B = make_engine(lm)
        a = A.submit([2, 3, 4], 4)
        b = A.submit([3, 4, 5, 2], 6)  # waits behind the single slot
        A.step()
        payload = A.export_request(b.rid)
        assert payload["n_blocks"] == 0 and payload["tokens"] == []
        rb = B.import_request(decode_record(encode_record(payload)))
        while B.scheduler.has_work:
            B.step()
        assert rb.tokens == reference_run(lm, [3, 4, 5, 2], 6)[4:]
        while A.scheduler.has_work:
            A.step()
        assert a.done and a.error is None
        A.release_telemetry()
        B.release_telemetry()

    def test_cold_record_with_tokens_refused(self, lm):
        """A cold import re-prefills the prompt only — a record that
        claims n_blocks=0 yet carries generated tokens would silently
        interleave them with tokens decoded from a context that never
        saw them. No legitimate export produces this shape; refuse."""
        B = make_engine(lm)
        rec = {
            "version": 1, "rid": 999_000_001, "prompt": [2, 3, 4],
            "tokens": [5, 6], "max_new_tokens": 8,
            "temperature": 0.0, "eos_id": None, "priority": 0,
            "tenant": None, "ttft_deadline_ms": None, "trace": None,
            "block_size": 4, "cur_len": 0, "n_blocks": 0, "rows": {},
        }
        with pytest.raises(ValueError, match="cold record"):
            B.import_request(rec)
        B.release_telemetry()

    def test_import_validation_is_loud(self, lm):
        A = make_engine(lm)
        B = make_engine(lm)
        fixed = make_engine(
            lm, paged=False, block_size=None, num_blocks=None,
            preemption=False,
        )
        ra = A.submit([2, 3, 4, 5], 8)
        for _ in range(3):
            A.step()
        payload = A.export_request(ra.rid)
        # fixed-arena target refuses a warm record
        with pytest.raises(ValueError, match="paged"):
            fixed.import_request(payload)
        # block-size mismatch refuses
        other = make_engine(lm, block_size=8, num_blocks=8)
        with pytest.raises(ValueError, match="block_size"):
            other.import_request(payload)
        # corrupt cursor refuses
        bad = dict(payload)
        bad["cur_len"] = payload["cur_len"] + 1
        with pytest.raises(ValueError, match="cur_len"):
            B.import_request(bad)
        # double-import refuses (record is single-use) — while the
        # request is live AND after it served (the bounded finished
        # registry is the best-effort replay guard)
        B.import_request(payload)
        with pytest.raises(ValueError, match="already live"):
            B.import_request(payload)
        while B.scheduler.has_work:
            B.step()
        with pytest.raises(ValueError, match="already served"):
            B.import_request(payload)
        # fixed-arena ACTIVE request refuses warm export
        rf = fixed.submit([2, 3, 4], 8)
        fixed.step()
        assert rf.tokens
        with pytest.raises(ValueError, match="fixed-arena"):
            fixed.export_request(rf.rid)
        with pytest.raises(KeyError):
            A.export_request(10**15 + 99)
        for e in (A, B, fixed, other):
            e.release_telemetry()


# -- placement determinism --------------------------------------------


SNAPSHOT_PROBES = {"r0": 0, "r1": 12, "r2": 12, "r3": 3}
SNAPSHOT_VIEW = {
    "r0": {"up": True, "blocks_free": 64, "queue_depth": 0},
    "r1": {"up": True, "blocks_free": 8, "queue_depth": 2},
    "r2": {"up": True, "blocks_free": 40, "queue_depth": 1},
    "r3": {"up": False, "blocks_free": 99, "queue_depth": 0},
}


class TestPlacementDeterminism:
    def test_same_snapshot_same_replica_every_call(self):
        first = place(SNAPSHOT_PROBES, SNAPSHOT_VIEW, 8, 0)
        assert first == PlacementDecision("r2", "affinity")
        for _ in range(50):
            assert place(SNAPSHOT_PROBES, SNAPSHOT_VIEW, 8, 0) == first
        # dict order must not matter
        shuffled_probes = dict(reversed(list(SNAPSHOT_PROBES.items())))
        shuffled_view = dict(reversed(list(SNAPSHOT_VIEW.items())))
        assert place(shuffled_probes, shuffled_view, 8, 0) == first

    def test_across_processes(self):
        """The gang contract, literally: a fresh interpreter derives
        the identical decision from the identical snapshot."""
        code = (
            "from elephas_tpu.fleet.placement import place\n"
            f"probes = {SNAPSHOT_PROBES!r}\n"
            f"view = {SNAPSHOT_VIEW!r}\n"
            "d = place(probes, view, 8, 0)\n"
            "print(d.replica, d.kind)\n"
            "d2 = place({'a': 0, 'b': 0}, {}, 8, 5)\n"
            "print(d2.replica, d2.kind)\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-1500:]
        lines = proc.stdout.strip().splitlines()
        assert lines[0] == "r2 affinity"
        assert lines[1] == "b round_robin"
        assert place({"a": 0, "b": 0}, {}, 8, 5) == PlacementDecision(
            "b", "round_robin"
        )

    def test_stages_and_floor(self):
        # below the affinity floor -> load stage
        d = place({"a": 0, "b": 7}, SNAPSHOT_VIEW_AB, 8, 0)
        assert d == PlacementDecision("a", "load")
        # at the floor -> affinity wins
        d = place({"a": 0, "b": 8}, SNAPSHOT_VIEW_AB, 8, 0)
        assert d == PlacementDecision("b", "affinity")
        # equally warm -> lighter replica (more blocks free)
        d = place({"a": 9, "b": 9}, SNAPSHOT_VIEW_AB, 8, 0)
        assert d == PlacementDecision("a", "affinity")
        # all stale -> round-robin walks the sorted names
        assert place({"a": 0, "b": 0}, {}, 8, 0).replica == "a"
        assert place({"a": 0, "b": 0}, {}, 8, 1).replica == "b"
        assert place(
            {"a": 0, "b": 0},
            {"a": {"up": False}, "b": {"up": False}}, 8, 2,
        ) == PlacementDecision("a", "round_robin")

    def test_stale_scrape_degrades_to_round_robin_counted(self, lm):
        """End-to-end degradation: every scrape target failing flips
        the fleet view stale, placement falls back to round-robin,
        and the router COUNTS it (the rising-rate signal)."""
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        router = Router(engines, placement="load", poll_every=100)
        with router:
            dead = {
                name: (lambda: (_ for _ in ()).throw(
                    ConnectionError("scrape down")
                ))
                for name in engines
            }
            for name in engines:
                router.scraper.remove_target(name)
                router.scraper.add_target(name, dead[name])
            router.refresh_view()
            assert all(
                not row["up"]
                for row in router.scraper.fleet_stats().values()
            )
            before = router.stats()["stale_placements"]
            reqs = [router.submit([2, 3, 4], 2) for _ in range(4)]
            assert all(r.wait(60) for r in reqs)
            st = router.stats()
            assert st["stale_placements"] == before + 4
            # round-robin floor still BALANCES: both replicas served
            assert all(
                v["placements"] >= 1 for v in st["replicas"].values()
            )
        router.release_telemetry()
        for e in engines.values():
            e.release_telemetry()


SNAPSHOT_VIEW_AB = {
    "a": {"up": True, "blocks_free": 12, "queue_depth": 0},
    "b": {"up": True, "blocks_free": 4, "queue_depth": 1},
}


# -- the router ------------------------------------------------------


class TestRouter:
    def test_affinity_routes_shared_prefix_to_warm_replica(self, lm):
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        router = Router(engines, min_affinity_tokens=4, poll_every=2)
        shared = [2, 3, 4, 5, 2, 3, 4, 5]
        with router:
            first = router.submit(shared + [2], 4)
            assert first.wait(60)
            home = first.replica
            followers = []
            for t in (3, 4, 5):
                r = router.submit(shared + [int(t)], 4)
                assert r.wait(60)
                followers.append(r)
            # every shared-prefix request landed on the warm replica
            assert all(r.replica == home for r in followers)
            st = router.stats()
            assert st["placements"]["affinity"] >= 3
            # and the replica actually served them from its cache
            hits = engines[home].stats()["prefix_cache"]["hits"]
            assert hits >= 3
        router.release_telemetry()
        for e in engines.values():
            e.release_telemetry()

    def test_drain_zero_dropped_zero_doubled(self, lm):
        """THE drain acceptance: requests mid-flight on the drained
        replica finish on survivors with the exact reference stream."""
        prompts = [
            [2, 3, 4, 5, 2, 3], [3, 4, 5, 2], [4, 5, 2, 3, 4],
            [5, 2, 3, 4, 5, 2, 3],
        ]
        max_new = 16
        refs = [reference_run(lm, p, max_new) for p in prompts]
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        throttle(engines)  # or this is an empty-drain test
        router = Router(engines, poll_every=2)
        with router:
            reqs = [router.submit(p, max_new) for p in prompts]
            time.sleep(0.1)  # let streams get into flight
            # drain whichever replica holds the most work
            counts: dict = {}
            for r in reqs:
                counts[r.replica] = counts.get(r.replica, 0) + 1
            victim = max(sorted(counts), key=lambda n: counts[n])
            moved = router.drain(victim)
            assert moved >= 1
            assert all(r.wait(120) for r in reqs)
            for r, ref, p in zip(reqs, refs, prompts):
                assert r.error is None
                assert list(p) + r.tokens == ref  # zero drop/double
            # the drained replica is empty and out of placement
            sched = router.replicas[victim].engine.scheduler
            assert not sched.active and not sched.waiting
            nxt = router.submit([2, 3], 2)
            assert nxt.wait(60) and nxt.replica != victim
            router.undrain(victim)
            st = router.stats()
            assert st["migrated"] == moved
            assert st["drains"] == 1
            # delivered-token truth: plain host counter == registry
            assert st["tokens_delivered"] == int(
                router._m_tokens.value
            )
        router.release_telemetry()
        for e in engines.values():
            e.release_telemetry()

    def test_http_front_door(self, lm):
        import http.client

        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        router = Router(engines, port=0)
        with router:
            # non-streamed generate
            conn = http.client.HTTPConnection(
                "127.0.0.1", router.port, timeout=60
            )
            conn.request(
                "POST", "/v1/generate",
                body=json.dumps({
                    "prompt": [2, 3, 4, 5], "max_new_tokens": 4,
                    "stream": False,
                }),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            assert resp.status == 200
            doc = json.loads(resp.read())
            assert len(doc["tokens"]) == 4
            assert doc["replica"] in engines
            assert resp.getheader("X-Request-Id") == str(doc["rid"])
            conn.close()
            # SSE generate
            conn = http.client.HTTPConnection(
                "127.0.0.1", router.port, timeout=60
            )
            conn.request(
                "POST", "/v1/generate",
                body=json.dumps({
                    "prompt": [3, 4, 5], "max_new_tokens": 3,
                }),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            assert resp.status == 200
            raw = resp.read().decode()
            tokens = [
                json.loads(line[6:])["token"]
                for line in raw.splitlines()
                if line.startswith("data: {\"token\"")
            ]
            assert len(tokens) == 3
            assert "event: done" in raw
            conn.close()
            # fleet view + healthz + metrics
            for path, want in (
                ("/fleet", b"placements"),
                ("/healthz", b"ok"),
                ("/metrics", b"elephas_router_placements_total"),
            ):
                conn = http.client.HTTPConnection(
                    "127.0.0.1", router.port, timeout=60
                )
                conn.request("GET", path)
                resp = conn.getresponse()
                assert resp.status == 200, path
                assert want in resp.read(), path
                conn.close()
            # per-replica instance labels in the merged exposition
            conn = http.client.HTTPConnection(
                "127.0.0.1", router.port, timeout=60
            )
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
            assert 'instance="a"' in text and 'instance="b"' in text
            conn.close()
            # drain over the wire
            conn = http.client.HTTPConnection(
                "127.0.0.1", router.port, timeout=60
            )
            conn.request("POST", "/v1/replicas/a/drain")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["replica"] == "a"
            conn.close()
        router.release_telemetry()
        for e in engines.values():
            e.release_telemetry()

    def test_crashed_driver_redrives_on_survivor(self, lm):
        """A driver that DIES on an engine error (not a chaos kill)
        must not strand its in-flight requests: the crash hook marks
        the replica down and re-drives on the survivors, and the rid
        maps retire fully once the streams finish."""
        prompts = [[2, 3, 4, 5], [3, 4, 5, 2]]
        max_new = 12
        refs = [reference_run(lm, p, max_new) for p in prompts]
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        calls = {"n": 0}
        real = engines["a"].step

        def dying_step():
            calls["n"] += 1
            if calls["n"] > 3:
                raise RuntimeError("induced driver crash")
            time.sleep(0.005)
            return real()

        engines["a"].step = dying_step
        router = Router(engines, poll_every=4)
        with router:
            reqs = [router.submit(p, max_new) for p in prompts]
            assert all(r.wait(120) for r in reqs)
            for r, ref, p in zip(reqs, refs, prompts):
                assert r.error is None
                assert list(p) + r.tokens == ref
            assert not router.replicas["a"].alive
            # the liveness gauge flipped — the replica_down rule's
            # series — without any operator/kill_replica involvement
            up = router._mf_up.labels(
                router=router.telemetry_label, replica="a"
            ).value
            assert up == 0
            st = router.stats()
            assert st["redriven"] >= 1
            assert all(r.replica == "b" for r in reqs)
            # bookkeeping fully retired: the rid maps must not grow
            # across request lifetimes
            assert router._inflight == {}
            assert router._by_engine_rid == {}
        router.release_telemetry()
        for e in engines.values():
            e.release_telemetry()

    def test_killed_replica_redrives_each_token_exactly_once(self, lm):
        """A replica abandoned mid-stream (``kill_replica``, the chaos
        harness's entry: driver stopped, engine state lost) costs its
        clients nothing: the survivor continues every stream from its
        last delivered token, each equals the no-kill reference token
        for token, and one token minted anywhere is one token
        delivered. Counts only; the trigger is a delivered-token
        count, not a timer."""
        from elephas_tpu.telemetry.watch import ReplicaDownRule, Watchdog

        prompts = [[2, 3, 4, 5, 2, 3], [3, 4, 5, 2], [4, 5, 2, 3]]
        max_new = 16
        refs = [reference_run(lm, p, max_new) for p in prompts]
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        throttle(engines)
        router = Router(engines, poll_every=4)
        watchdog = Watchdog(rules=[ReplicaDownRule()])
        with router:
            reqs = [router.submit(p, max_new) for p in prompts]
            deadline = time.monotonic() + 60
            while router.tokens_delivered < 6:
                assert time.monotonic() < deadline, "no token in 60 s"
                time.sleep(0.002)
            on_a = sum(r.replica == "a" and not r.done for r in reqs)
            assert on_a >= 1, "the kill would have found nothing in flight"
            assert router.kill_replica("a") == on_a
            assert [x.rule for x in watchdog.evaluate()] == ["replica_down"]
            assert all(r.wait(120) for r in reqs)
            for r, ref, p in zip(reqs, refs, prompts):
                assert r.error is None
                assert list(p) + r.tokens == ref
            delivered = router.tokens_delivered
            assert delivered == sum(len(r) - len(p)
                                    for r, p in zip(refs, prompts))
            assert delivered == sum(
                e.total_generated for e in engines.values()
            )
            st = router.stats()
            assert st["redriven"] == on_a
            assert st["stale_tokens_dropped"] == 0
        watchdog.release_telemetry()
        router.release_telemetry()
        for e in engines.values():
            e.release_telemetry()

    def test_failed_drain_restores_placement(self, lm):
        """An incomplete drain (here: timeout) must re-admit the
        replica to placement instead of silently shrinking fleet
        capacity forever; only a COMPLETED drain keeps it excluded
        until undrain()."""
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        for eng in engines.values():
            real = eng.step
            eng.step = (lambda real=real: (
                time.sleep(0.01), real()
            )[1])
        router = Router(engines, poll_every=2)
        with router:
            reqs = [
                router.submit([2, 3, 4, 5, 2, 3], 12)
                for _ in range(3)
            ]
            time.sleep(0.05)  # streams into flight
            busy = next(r.replica for r in reqs)
            with pytest.raises(TimeoutError):
                router.drain(busy, timeout=-1.0)
            assert router.stats()["replicas"][busy]["draining"] \
                is False
            assert all(r.wait(120) for r in reqs)
        router.release_telemetry()
        for e in engines.values():
            e.release_telemetry()

    def test_redrive_with_no_survivor_fails_loudly(self, lm):
        """A kill while every other replica is draining leaves the
        sweep nowhere to place: the victims must FAIL (done + error +
        unblocked wait), never hang forever with no signal."""
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        real = engines["a"].step
        engines["a"].step = (lambda: (time.sleep(0.01), real())[1])
        router = Router(engines, poll_every=2)
        with router:
            assert router.drain("b") == 0  # b leaves placement, idle
            r = router.submit([2, 3, 4, 5], 24)
            assert r.replica == "a"
            time.sleep(0.03)  # into flight, well short of the budget
            assert not r.done
            router.kill_replica("a")
            assert r.wait(30), "victim handle must unblock"
            assert r.error is not None  # the placement failure
            assert router._inflight == {}
            assert router._by_engine_rid == {}
        router.release_telemetry()
        for e in engines.values():
            e.release_telemetry()

    def test_restore_clears_draining(self, lm):
        """A replica that died while drained must come back SERVING:
        restore_replica clears the draining exclusion (before this, a
        drained-then-dead replica returned permanently invisible to
        placement, despite replica_up reading 1)."""
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        router = Router(engines, poll_every=2)
        with router:
            assert router.drain("a") == 0  # idle drain, stays excluded
            router.kill_replica("a")
            fresh = make_engine(lm)
            router.restore_replica("a", fresh)
            assert router.stats()["replicas"]["a"]["draining"] is False
            seen = set()
            for i in range(6):
                r = router.submit([2, 3, int(2 + i % 4)], 2)
                assert r.wait(60)
                seen.add(r.replica)
            assert "a" in seen
        router.release_telemetry()
        fresh.release_telemetry()
        for e in engines.values():
            e.release_telemetry()

    def test_replica_scrape_is_self_only(self, lm):
        a = make_engine(lm)
        b = make_engine(lm)
        text = a.scrape(full=False)
        assert f'engine="{a.telemetry_label}"' in text
        assert f'engine="{b.telemetry_label}"' not in text
        assert f'scheduler="{a.scheduler.telemetry_label}"' in text
        a.release_telemetry()
        b.release_telemetry()


# -- chaos: replica kill -> re-drive -> replica_down fires/clears -----


@pytest.mark.slow  # multi-second streamed chaos run
class TestReplicaChaos:
    def test_kill_redrive_watchdog_cycle(self, lm):
        from elephas_tpu.fault.harness import ReplicaKiller
        from elephas_tpu.telemetry.watch import (
            ReplicaDownRule,
            Watchdog,
        )

        prompts = [
            [2, 3, 4, 5, 2, 3], [3, 4, 5, 2], [4, 5, 2, 3],
            [5, 2, 3, 4],
        ]
        max_new = 20
        refs = [reference_run(lm, p, max_new) for p in prompts]
        engines = {"a": make_engine(lm), "b": make_engine(lm)}
        throttle(engines)
        router = Router(engines, poll_every=4)
        watchdog = Watchdog(rules=[ReplicaDownRule()])
        with router:
            reqs = [router.submit(p, max_new) for p in prompts]
            killer = ReplicaKiller(
                router, "a", after_tokens=6
            )
            killer.start()
            assert killer.killed.wait(60)
            anomalies = watchdog.evaluate()
            assert [a.rule for a in anomalies] == ["replica_down"]
            assert anomalies[0].labels["replica"] == "a"
            assert all(r.wait(120) for r in reqs)
            # zero dropped, zero doubled: every stream matches the
            # unmigrated reference token for token
            for r, ref, p in zip(reqs, refs, prompts):
                assert r.error is None
                assert list(p) + r.tokens == ref
            # delivered exactly the reference token count, no extras
            total_ref = sum(len(ref) - len(p)
                            for ref, p in zip(refs, prompts))
            assert router.tokens_delivered == total_ref
            # restore with a fresh engine -> the anomaly CLEARS
            fresh = make_engine(lm)
            router.restore_replica("a", fresh)
            assert watchdog.evaluate() == []
            report = watchdog.report()
            assert report["fired_total"] == 1
            assert report["cleared_total"] == 1
            # and placement uses the reborn replica again
            seen = set()
            for i in range(6):
                r = router.submit([2, 3, 4, int(2 + i % 4)], 2)
                assert r.wait(60)
                seen.add(r.replica)
            assert "a" in seen
            killer.cancel()
        watchdog.release_telemetry()
        router.release_telemetry()
        fresh.release_telemetry()
        for e in engines.values():
            e.release_telemetry()
