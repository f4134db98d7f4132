"""The async HTTP/SSE front door (ISSUE 10).

Acceptance contract: gateway SSE output is token-identical to the
in-process engine for the same prompts (driven by a REAL HTTP client,
with concurrent streams); the policy's admission verdict surfaces as
429 + Retry-After; validation errors are loud 400s; and the lifecycle
fix — stopping the gateway severs live SSE connections and actually
releases the port (the zombie keep-alive bug class PR 3 found in the
parameter servers)."""

import http.client
import json
import socket
import threading

import numpy as np
import pytest

from elephas_tpu.serving.policy import FairSharePolicy


@pytest.fixture(scope="module")
def lm(serving_lm):
    return serving_lm


@pytest.fixture(scope="module")
def gw(lm):
    """One shared engine+gateway for the read-mostly tests (engine
    construction compiles programs — building one per test would blow
    the tier-1 wall-clock budget)."""
    from elephas_tpu.serving import Gateway, InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2,
        policy=FairSharePolicy({"a": 1.0, "b": 1.0}),
    )
    gateway = Gateway(engine, port=0).start()
    engine.gateway = gateway
    yield gateway
    engine.close()
    gateway.release_telemetry()
    engine.release_telemetry()


def _request(port, method, path, body=None, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    headers = {"Content-Type": "application/json"} if body else {}
    conn.request(
        method, path,
        body=None if body is None else json.dumps(body),
        headers=headers,
    )
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp, data


def _sse_events(raw: bytes):
    """Parse an SSE body into its JSON data events."""
    events = []
    for line in raw.decode("utf-8").splitlines():
        if line.startswith("data: "):
            events.append(json.loads(line[len("data: "):]))
    return events


def _collect_stream(port, payload, out, key):
    resp, raw = _request(port, "POST", "/v1/generate", payload)
    events = _sse_events(raw)
    tokens = [e["token"] for e in events if "token" in e]
    out[key] = (resp.status, tokens, events)


def _one_shot(lm, prompt, steps):
    from elephas_tpu.models import generate

    return generate(
        lm, np.asarray(prompt, np.int32)[None], steps=steps,
        kv_cache=True,
    )[0]


PROMPTS = [[2, 3, 4, 5], [4, 5], [3, 4, 5, 2, 3]]


def test_concurrent_sse_streams_token_exact_vs_inprocess(lm, gw):
    """Three concurrent SSE streams through a real HTTP client: every
    stream's tokens equal the in-process one-shot continuation — the
    wire adds transport, never tokens (acceptance criterion)."""
    refs = [
        list(map(int, _one_shot(lm, p, 6)[len(p):])) for p in PROMPTS
    ]
    out = {}
    threads = [
        threading.Thread(
            target=_collect_stream,
            args=(gw.port, {
                "prompt": p, "max_new_tokens": 6,
                "tenant": ("a" if i % 2 else "b"),
                "ttft_deadline_ms": 60000,
            }, out, i),
        )
        for i, p in enumerate(PROMPTS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i, ref in enumerate(refs):
        status, tokens, events = out[i]
        assert status == 200
        assert tokens == ref, (i, tokens, ref)
        # stream envelope: opening rid event, a final done summary
        assert "rid" in events[0]
        assert events[-1]["n_tokens"] == len(ref)
        assert events[-1]["error"] is None
        # the done flag marks exactly the final token
        flags = [e["done"] for e in events if "token" in e]
        assert flags == [False] * (len(ref) - 1) + [True]


def test_nonstream_returns_one_json_document(lm, gw):
    resp, raw = _request(gw.port, "POST", "/v1/generate", {
        "prompt": PROMPTS[0], "max_new_tokens": 5, "stream": False,
    })
    assert resp.status == 200
    doc = json.loads(raw)
    np.testing.assert_array_equal(
        doc["full_sequence"], _one_shot(lm, PROMPTS[0], 5)
    )
    assert doc["error"] is None and len(doc["tokens"]) == 5


def test_validation_and_routing_errors_are_loud(gw):
    port = gw.port
    resp, raw = _request(port, "POST", "/v1/generate", {"prompt": [2]})
    assert resp.status == 400 and b"max_new_tokens" in raw
    resp, raw = _request(port, "POST", "/v1/generate", {
        "prompt": [2], "max_new_tokens": 2, "frobnicate": 1,
    })
    assert resp.status == 400 and b"frobnicate" in raw
    resp, raw = _request(port, "POST", "/v1/generate", {
        "prompt": [2], "max_new_tokens": 2, "tenant": "ghost",
    })
    assert resp.status == 400 and b"unknown tenant" in raw
    resp, _ = _request(port, "GET", "/no/such/route")
    assert resp.status == 404
    resp, _ = _request(port, "GET", "/v1/generate")
    assert resp.status == 405
    # malformed JSON body
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/v1/generate", body="{not json",
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 400 and b"bad JSON" in resp.read()
    conn.close()


def test_metrics_and_stats_routes(gw):
    # one finished request of this test's own: the shared gateway may
    # be a fresh one (another worker ran the tests above)
    resp, _ = _request(gw.port, "POST", "/v1/generate", {
        "prompt": [2, 3], "max_new_tokens": 2, "tenant": "a",
        "stream": False,
    })
    assert resp.status == 200
    resp, raw = _request(gw.port, "GET", "/metrics")
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/plain")
    text = raw.decode()
    assert "elephas_serving_tokens_generated_total" in text
    assert "elephas_gateway_requests_total" in text
    resp, raw = _request(gw.port, "GET", "/stats")
    assert resp.status == 200
    stats = json.loads(raw)
    assert "tenants" in stats and "a" in stats["tenants"]
    assert stats["finished"] >= 1


def test_backpressure_429_with_retry_after(lm):
    """Overload admission control on the wire: past the queue's token
    debt bound the gateway answers 429 with the policy's deterministic
    Retry-After hint — backpressure, not a silent queue."""
    from elephas_tpu.serving import Gateway, InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=1,
        policy=FairSharePolicy({"a": 1.0}, max_queue_tokens=16,
                               retry_after_s=1.0),
    )
    with Gateway(engine, port=0) as gateway:
        # park a long request so the queue carries debt, then overflow
        out = {}
        t = threading.Thread(target=_collect_stream, args=(
            gateway.port,
            {"prompt": [2, 3, 4, 5], "max_new_tokens": 12,
             "tenant": "a"},
            out, "long",
        ))
        t.start()
        # race-free by construction: the first request's debt (4+12 =
        # 16) fits the bound alone, the second's (8+12 = 20) exceeds
        # it ALONE — the verdict is the same whether the first is
        # still queued or already admitted when this submit lands
        resp, raw = _request(gateway.port, "POST", "/v1/generate", {
            "prompt": [2, 3, 4, 5, 2, 3, 4, 5], "max_new_tokens": 12,
            "tenant": "a",
        })
        assert resp.status == 429, raw
        assert int(resp.getheader("Retry-After")) >= 1
        assert b"admission bound" in raw
        t.join(timeout=120)
        assert out["long"][0] == 200
    engine.release_telemetry()


def test_stop_severs_live_sse_and_releases_port(lm):
    """The lifecycle fix (ISSUE 10 satellite): engine.close() (the
    serve() context manager's exit) severs a LIVE SSE stream and the
    port is actually released — no zombie keep-alive handler holds it
    (PR-3 bug class, asserted by rebinding)."""
    from elephas_tpu.serving import Gateway, InferenceEngine

    engine = InferenceEngine(lm, num_slots=1)
    gateway = Gateway(engine, port=0).start()
    engine.gateway = gateway
    port = gateway.port

    # while listening, even a SO_REUSEADDR rebind must fail
    probe = socket.socket()
    probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    with pytest.raises(OSError):
        probe.bind(("127.0.0.1", port))
    probe.close()

    # open a stream long enough to still be live when we stop
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("POST", "/v1/generate", body=json.dumps(
        {"prompt": [2, 3, 4], "max_new_tokens": 25}
    ), headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.read(10)  # the stream is live
    engine.close()  # the context-manager exit path
    leftover = resp.read()  # severed: EOF, not a hang
    assert b"event: done" not in leftover  # cut mid-stream, not drained
    conn.close()

    probe = socket.socket()
    probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    probe.bind(("127.0.0.1", port))  # released — rebind succeeds
    probe.close()
    engine.close()  # idempotent
    gateway.release_telemetry()
    engine.release_telemetry()


def test_driver_crash_tears_gateway_down(lm):
    """An engine error in the driver thread must run the FULL
    teardown (port released, live handlers severed), not just flag
    the driver loop dead — and a later engine.close() stays a clean
    no-op. (Review finding: the stop() idempotence latch used to
    alias the crash flag, turning post-crash stop() into a leak.)"""
    import time

    from elephas_tpu.serving import Gateway, InferenceEngine

    engine = InferenceEngine(lm, num_slots=1)
    gateway = Gateway(engine, port=0).start()
    engine.gateway = gateway
    port = gateway.port

    def boom():
        raise RuntimeError("engine died mid-step")

    engine.step = boom
    # submitting wakes the driver, whose next step crashes
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("POST", "/v1/generate", body=json.dumps(
            {"prompt": [2, 3], "max_new_tokens": 4}
        ), headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()  # severed mid-stream or error response — either way EOF
    except (ConnectionError, http.client.HTTPException, OSError):
        pass  # the sever may race the response entirely
    finally:
        conn.close()
    # the crash path releases the port (bounded wait: teardown runs
    # on the driver thread)
    deadline = time.monotonic() + 15
    while True:
        probe = socket.socket()
        probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            probe.bind(("127.0.0.1", port))
            probe.close()
            break
        except OSError:
            probe.close()
            assert time.monotonic() < deadline, (
                "port still held 15s after the driver crashed"
            )
            time.sleep(0.1)
    engine.close()  # idempotent after the crash teardown
    gateway.release_telemetry()
    engine.release_telemetry()


def test_serve_gateway_context_manager(lm):
    """SparkModel.serve(gateway_port=0, policy=, tenants=): the
    returned engine is a context manager whose exit stops the gateway
    and frees the port."""
    from elephas_tpu import SparkModel

    with SparkModel(lm, num_workers=4).serve(
        num_slots=2, gateway_port=0, policy="fair",
        tenants={"a": 1.0},
    ) as engine:
        assert engine.gateway is not None
        port = engine.gateway.port
        resp, raw = _request(port, "POST", "/v1/generate", {
            "prompt": [2, 3, 4], "max_new_tokens": 4,
            "tenant": "a", "ttft_deadline_ms": 60000,
            "stream": False,
        })
        assert resp.status == 200
        np.testing.assert_array_equal(
            json.loads(raw)["full_sequence"],
            _one_shot(lm, [2, 3, 4], 4),
        )
    assert engine.gateway is None
    probe = socket.socket()
    probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    probe.bind(("127.0.0.1", port))
    probe.close()


# -- HTTP keep-alive (ISSUE 15 satellite) ------------------------------


def test_keepalive_serves_multiple_requests_per_connection(lm, gw):
    """Two requests over ONE http.client connection: the first
    response says keep-alive, the second is served off the same
    socket and counted in the reuse counter."""
    from elephas_tpu import telemetry

    reg = telemetry.registry()
    reused = reg.counter(
        "elephas_gateway_connections_reused_total",
        "Requests served off an already-open keep-alive "
        "connection (the handshake they did not pay)",
        labels=("gateway",),
    ).labels(gateway=gw.telemetry_label)
    before = int(reused.value)
    conn = http.client.HTTPConnection(
        "127.0.0.1", gw.port, timeout=60
    )
    try:
        for i in range(3):
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status in (200, 503)
            assert body["status"]
            assert resp.getheader("Connection") == "keep-alive"
    finally:
        conn.close()
    assert int(reused.value) == before + 2  # 3 requests, 2 reuses


def test_keepalive_client_close_honored(gw):
    """A client sending Connection: close gets exactly the legacy
    one-request connection."""
    conn = http.client.HTTPConnection(
        "127.0.0.1", gw.port, timeout=60
    )
    try:
        conn.request("GET", "/healthz", headers={"Connection": "close"})
        resp = conn.getresponse()
        resp.read()
        assert resp.getheader("Connection") == "close"
        # the server closed; a second request on the same object
        # forces http.client to reconnect (NotConnected/closed read)
    finally:
        conn.close()


def test_keepalive_generate_json_then_stats_same_socket(lm, gw):
    """A non-streaming generate followed by /stats over one socket —
    the generate response persists the connection (only SSE owns its
    socket to the end) and both answers are correct."""
    prompt, steps = [2, 3, 4], 4
    ref = _one_shot(lm, prompt, steps)
    conn = http.client.HTTPConnection(
        "127.0.0.1", gw.port, timeout=120
    )
    try:
        payload = {"prompt": prompt, "max_new_tokens": steps,
                   "stream": False}
        conn.request(
            "POST", "/v1/generate", body=json.dumps(payload),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert resp.getheader("Connection") == "keep-alive"
        np.testing.assert_array_equal(body["full_sequence"], ref)
        conn.request("GET", "/stats")
        resp2 = conn.getresponse()
        stats = json.loads(resp2.read())
        assert resp2.status == 200 and "total_generated" in stats
    finally:
        conn.close()


# -- /v1/generate batch form (ISSUE 15 satellite) ----------------------


def test_batch_generate_json_array(lm, gw):
    """One POST, three prompts, one JSON results array — every entry
    token-exact vs one-shot, index-aligned."""
    specs = [([2, 3, 4], 5), ([5, 4], 5), ([3, 4, 5, 2], 5)]
    payload = {
        "prompts": [list(p) for p, _ in specs],
        "max_new_tokens": 5, "stream": False,
    }
    resp, raw = _request(gw.port, "POST", "/v1/generate", payload)
    assert resp.status == 200
    results = json.loads(raw)["results"]
    assert [r["index"] for r in results] == [0, 1, 2]
    for (prompt, steps), entry in zip(specs, results):
        assert entry["error"] is None
        assert entry["rid"] is not None
        np.testing.assert_array_equal(
            entry["full_sequence"], _one_shot(lm, prompt, steps)
        )
    # rids are distinct: each prompt was a NORMAL submit
    assert len({r["rid"] for r in results}) == 3


def test_batch_generate_sse_multiplexed(lm, gw):
    """stream=true multiplexes the batch onto one SSE stream keyed by
    rid; per-rid token order reassembles each stream exactly."""
    specs = [([2, 3, 4], 4), ([5, 4], 6)]
    payload = {
        "prompts": [list(p) for p, _ in specs],
        "max_new_tokens": None, "stream": True,
    }
    payload["max_new_tokens"] = 4
    resp, raw = _request(gw.port, "POST", "/v1/generate", payload)
    assert resp.status == 200
    events = _sse_events(raw)
    rids = events[0]["rids"]
    assert len(rids) == 2 and all(r is not None for r in rids)
    per_rid = {r: [] for r in rids}
    for e in events[1:]:
        if "token" in e:
            per_rid[e["rid"]].append(e["token"])
    for (prompt, _), rid in zip(specs, rids):
        ref = _one_shot(lm, prompt, 4)
        np.testing.assert_array_equal(
            per_rid[rid], ref[len(prompt):]
        )


def test_batch_generate_partial_failure_isolated(lm, gw):
    """A prompt that cannot validate fails ITS entry only; the rest
    of the batch serves normally."""
    good = [2, 3, 4]
    payload = {
        "prompts": [list(good), []],  # empty prompt: ValueError
        "max_new_tokens": 4, "stream": False,
    }
    resp, raw = _request(gw.port, "POST", "/v1/generate", payload)
    assert resp.status == 200
    results = json.loads(raw)["results"]
    assert results[0]["error"] is None
    np.testing.assert_array_equal(
        results[0]["full_sequence"], _one_shot(lm, good, 4)
    )
    assert results[1]["rid"] is None
    assert "empty prompt" in results[1]["error"]


def test_batch_generate_validation(gw):
    resp, raw = _request(
        gw.port, "POST", "/v1/generate",
        {"prompt": [2, 3], "prompts": [[2]], "max_new_tokens": 2},
    )
    assert resp.status == 400
    assert "exactly one" in json.loads(raw)["error"]
    resp, raw = _request(
        gw.port, "POST", "/v1/generate",
        {"prompts": "nope", "max_new_tokens": 2},
    )
    assert resp.status == 400
    resp, raw = _request(
        gw.port, "POST", "/v1/generate",
        {"prompts": [[2]] * 999, "max_new_tokens": 2},
    )
    assert resp.status == 413


def test_oversized_body_still_answers_413(gw):
    """The keep-alive refactor must not eat read-side refusals: an
    oversized Content-Length gets its 413 response (written as soon
    as the headers land — the server never reads the refused body)
    and the connection closes: framing past a failed read is
    untrusted."""
    head = (
        f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
        f"Content-Type: application/json\r\n"
        f"Content-Length: {gw.max_body + 10}\r\n\r\n"
    ).encode("ascii")
    with socket.create_connection(
        ("127.0.0.1", gw.port), timeout=60
    ) as s:
        s.sendall(head)  # refuse fires on headers; body never sent
        resp = b""
        while True:
            chunk = s.recv(4096)
            if not chunk:
                break
            resp += chunk
    assert b"413" in resp.split(b"\r\n", 1)[0]
    assert b"exceeds" in resp
    assert b"Connection: close" in resp


def test_transfer_encoding_refused_loudly(gw):
    """Chunked bodies are refused with 501 and the connection closes:
    an unread chunked payload left buffered under keep-alive would be
    parsed as the NEXT request (request smuggling)."""
    raw = (
        b"POST /v1/generate HTTP/1.1\r\n"
        b"Host: x\r\nTransfer-Encoding: chunked\r\n\r\n"
        b"4\r\nevil\r\n0\r\n\r\n"
    )
    with socket.create_connection(
        ("127.0.0.1", gw.port), timeout=60
    ) as s:
        s.sendall(raw)
        resp = b""
        while b"\r\n\r\n" not in resp:
            chunk = s.recv(4096)
            if not chunk:
                break
            resp += chunk
    assert b"501" in resp.split(b"\r\n", 1)[0]
    assert b"Connection: close" in resp


def test_handler_crash_counts_a_500(lm, gw, monkeypatch):
    """An unexpected handler exception still lands in
    elephas_gateway_requests_total as code=500 — a fleet watching the
    5xx rate must see crashing handlers."""
    from elephas_tpu import telemetry

    fam = telemetry.registry().counter(
        "elephas_gateway_requests_total",
        "HTTP requests served by the gateway, by route and status",
        labels=("gateway", "route", "code"),
    )
    child = fam.labels(
        gateway=gw.telemetry_label, route="GET /stats", code="500"
    )
    before = int(child.value)
    monkeypatch.setattr(
        gw.engine, "stats",
        lambda: (_ for _ in ()).throw(RuntimeError("boom")),
    )
    try:
        _request(gw.port, "GET", "/stats", timeout=30)
    except Exception:
        pass  # connection severed without a response — expected
    assert int(child.value) == before + 1


def test_get_with_body_keeps_framing(gw):
    """A GET carrying a Content-Length body must have that body
    CONSUMED before the connection persists — unread bytes would
    parse as the next request line (the smuggling class the
    Transfer-Encoding refusal names)."""
    raw = (
        b"GET /healthz HTTP/1.1\r\nHost: x\r\n"
        b"Content-Length: 5\r\n\r\nhello"
        b"GET /healthz HTTP/1.1\r\nHost: x\r\n"
        b"Connection: close\r\n\r\n"
    )
    with socket.create_connection(
        ("127.0.0.1", gw.port), timeout=60
    ) as s:
        s.sendall(raw)
        resp = b""
        while True:
            try:
                chunk = s.recv(4096)
            except socket.timeout:
                break
            if not chunk:
                break
            resp += chunk
    # BOTH requests answered 200/503 healthz JSON — the body bytes
    # never leaked into the request parser as a malformed line
    assert resp.count(b'"status"') == 2, resp[:400]
    assert b"malformed" not in resp


def test_batch_generate_bad_shared_field_is_400(gw):
    """A bad batch-WIDE field (non-numeric temperature) fails the
    whole POST as a clean 400 — parity with the single-prompt form —
    instead of severing the connection responseless."""
    resp, raw = _request(
        gw.port, "POST", "/v1/generate",
        {"prompts": [[2, 3]], "max_new_tokens": 2,
         "temperature": "hot"},
    )
    assert resp.status == 400
    assert "could not convert" in json.loads(raw)["error"]


def test_batch_generate_one_token_requests_deliver(lm, gw):
    """1-token batch requests: the pending set is classified UNDER
    the engine lock at submit, so a request the driver finishes
    between submit and the handler's resume still drains its queued
    token (pre-fix, it was mistaken for a submit-time reject and its
    entry came back token-less)."""
    for _ in range(4):
        payload = {"prompts": [[2, 3, 4], [5, 4]],
                   "max_new_tokens": 1, "stream": False}
        resp, raw = _request(gw.port, "POST", "/v1/generate", payload)
        assert resp.status == 200
        results = json.loads(raw)["results"]
        for prompt, entry in zip(([2, 3, 4], [5, 4]), results):
            assert entry["error"] is None
            ref = _one_shot(lm, prompt, 1)
            assert entry["tokens"] == [int(ref[len(prompt)])], entry
            np.testing.assert_array_equal(entry["full_sequence"], ref)


def test_keepalive_ignores_blank_line_between_requests(gw):
    """RFC 7230 §3.5: a bare CRLF between keep-alive requests is
    ignored (bounded), not parsed as a malformed request line."""
    raw = (
        b"GET /healthz HTTP/1.1\r\nHost: x\r\n\r\n"
        b"\r\n"
        b"GET /healthz HTTP/1.1\r\nHost: x\r\n"
        b"Connection: close\r\n\r\n"
    )
    with socket.create_connection(
        ("127.0.0.1", gw.port), timeout=60
    ) as s:
        s.sendall(raw)
        resp = b""
        while True:
            try:
                chunk = s.recv(4096)
            except socket.timeout:
                break
            if not chunk:
                break
            resp += chunk
    assert resp.count(b'"status"') == 2, resp[:400]
    assert b"malformed" not in resp
