"""ISSUE 5: the unified telemetry subsystem.

Registry semantics (threaded exactness, bucket edges, null no-ops),
ring-buffer wraparound, the Prometheus golden render, the ``/metrics``
round-trip on a live HTTP parameter server, the no-drift contract
between attribute views and the registry, and the chaos harness's
trace-stream recovery span. What fit's spans cost on the chip is in
PERF.md (PR 24).
"""

import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

from elephas_tpu import telemetry


@pytest.fixture()
def not_null():
    """Tests that flip null mode restore it; everything else asserts
    the suite-wide default (on) so a leaked flip fails loudly."""
    assert not telemetry.null_mode()
    yield
    assert not telemetry.null_mode()


# -- registry ------------------------------------------------------------


class TestRegistry:
    def test_threaded_increments_sum_exactly(self):
        reg = telemetry.Registry()
        c = reg.counter("t_threads_total", "x")
        h = reg.histogram("t_threads_seconds", "x", buckets=(0.5,))

        def work():
            for _ in range(10_000):
                c.inc()
                h.observe(0.1)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 80_000
        counts, total, hsum = h.snapshot()
        assert total == 80_000 and counts[0] == 80_000
        assert hsum == pytest.approx(8_000.0)

    def test_get_or_create_and_mismatch(self):
        reg = telemetry.Registry()
        a = reg.counter("t_same_total", "x", labels=("k",))
        assert reg.counter("t_same_total", "x", labels=("k",)) is a
        # same name as a different kind or label schema must refuse
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("t_same_total", "x", labels=("k",))
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("t_same_total", "x", labels=("other",))
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("bad name", "x")
        h = reg.histogram("t_same_seconds", "x", buckets=(0.1, 1.0))
        assert reg.histogram(
            "t_same_seconds", "x", buckets=(1.0, 0.1)  # order-insensitive
        ) is h
        # a different ladder must refuse — observations would silently
        # land in the first caller's buckets
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("t_same_seconds", "x", buckets=(5.0,))
        with pytest.raises(ValueError):
            a.labels(wrong="v")
        with pytest.raises(ValueError):
            a.labels(k="v").inc(-1)  # counters are monotonic
        with pytest.raises(ValueError, match="call .labels"):
            a.inc()  # labeled family needs a series

    def test_label_children_are_distinct_and_cached(self):
        reg = telemetry.Registry()
        fam = reg.counter("t_labels_total", "x", labels=("who",))
        fam.labels(who="a").inc(3)
        fam.labels(who="b").inc(5)
        assert fam.labels(who="a") is fam.labels(who="a")
        assert fam.labels(who="a").value == 3
        assert fam.labels(who="b").value == 5

    def test_histogram_bucket_edges(self):
        """``le`` is INCLUSIVE: an observation exactly on a bound lands
        in that bound's bucket, epsilon above falls through."""
        reg = telemetry.Registry()
        h = reg.histogram("t_edges_seconds", "x", buckets=(0.1, 1.0))
        for v in (0.05, 0.1, 0.100001, 1.0, 2.0):
            h.observe(v)
        counts, total, _ = h.snapshot()
        assert counts == [2, 2, 1]  # (-inf,0.1], (0.1,1], (1,+inf)
        assert total == 5
        text = telemetry.render(reg)
        assert 't_edges_seconds_bucket{le="0.1"} 2' in text
        assert 't_edges_seconds_bucket{le="1"} 4' in text  # cumulative
        assert 't_edges_seconds_bucket{le="+Inf"} 5' in text
        assert "t_edges_seconds_count 5" in text

    def test_gauge_set_inc_and_callback(self):
        reg = telemetry.Registry()
        g = reg.gauge("t_gauge", "x")
        g.set(3)
        g.inc(2)
        g.dec()
        assert g.value == 4
        cb = reg.gauge("t_gauge_cb", "x")
        cb.set_function(lambda: 7.5)
        assert cb.value == 7.5
        assert "t_gauge_cb 7.5" in telemetry.render(reg)

    def test_render_golden(self):
        """The full exposition format, byte-for-byte."""
        reg = telemetry.Registry()
        c = reg.counter("g_requests_total", "Requests served",
                        labels=("engine",))
        c.labels(engine="0").inc(4)
        reg.gauge("g_slots", "Slots").set(8)
        h = reg.histogram("g_ttft_seconds", "TTFT", buckets=(0.5, 1.0))
        h.observe(0.25)
        h.observe(2.0)
        assert telemetry.render(reg) == (
            "# HELP g_requests_total Requests served\n"
            "# TYPE g_requests_total counter\n"
            'g_requests_total{engine="0"} 4\n'
            "# HELP g_slots Slots\n"
            "# TYPE g_slots gauge\n"
            "g_slots 8\n"
            "# HELP g_ttft_seconds TTFT\n"
            "# TYPE g_ttft_seconds histogram\n"
            'g_ttft_seconds_bucket{le="0.5"} 1\n'
            'g_ttft_seconds_bucket{le="1"} 1\n'
            'g_ttft_seconds_bucket{le="+Inf"} 2\n'
            "g_ttft_seconds_sum 2.25\n"
            "g_ttft_seconds_count 2\n"
        )

    def test_label_value_escaping(self):
        reg = telemetry.Registry()
        reg.counter("t_esc_total", "x", labels=("p",)).labels(
            p='a"b\\c\nd'
        ).inc()
        assert 'p="a\\"b\\\\c\\nd"' in telemetry.render(reg)


# -- null mode -----------------------------------------------------------


class TestNullMode:
    def test_null_metrics_and_tracer_are_noops(self, not_null):
        was = telemetry.set_null(True)
        try:
            assert was is False
            reg = telemetry.registry()
            c = reg.counter("nullmode_probe_total", "x")
            c.inc(100)
            assert c.value == 0
            reg.histogram("nullmode_probe_seconds", "x").observe(1.0)
            reg.gauge("nullmode_probe_gauge", "x").set(5)
            assert reg.render() == ""
            tr = telemetry.tracer()
            assert tr.emit("never") == -1
            with tr.span("never") as sp:
                sp.set(ok=True)  # the span API still works, records nothing
            assert tr.events() == []
        finally:
            telemetry.set_null(False)
        # the REAL registry never saw the null-mode names (which no
        # real series' name can contain: "n_total" was a substring of
        # elephas_serving_migrated_in_total)
        assert "nullmode_probe" not in telemetry.scrape_text()

    def test_null_engine_pays_no_registry_series(self, not_null, serving_lm):
        """An engine built under null mode records nothing and scrapes
        empty, beside engines built with telemetry on."""
        from elephas_tpu.serving import InferenceEngine

        was = telemetry.set_null(True)
        try:
            engine = InferenceEngine(serving_lm, num_slots=4)
        finally:
            telemetry.set_null(was)
        out = engine.run([([2, 3, 4], 4), ([3, 4, 5], 4)])
        assert len(out) == 2
        assert engine.scrape() == ""
        assert engine.total_generated == 0  # view of a null metric
        # behavior is untouched: the real token streams came back
        assert all(len(seq) > 3 for seq in out.values())

    def test_null_engine_eviction_warning_stays_rate_limited(
        self, not_null, serving_lm, caplog
    ):
        """The eviction-warning cadence runs on a plain count, so null
        mode (where the registry counter reads 0 forever — and
        ``0 % 1024 == 0``) cannot flip the rate limit into a
        per-eviction log flood."""
        import logging

        from elephas_tpu.serving import InferenceEngine

        was = telemetry.set_null(True)
        try:
            engine = InferenceEngine(serving_lm, num_slots=2)
        finally:
            telemetry.set_null(was)
        engine._finished_bound = 2
        engine.finished = {rid: object() for rid in range(6)}
        with caplog.at_level(
            logging.WARNING, logger="elephas_tpu.serving.engine"
        ):
            engine._evict_finished()
        assert len(engine.finished) == 2  # 4 evicted
        warnings = [
            r for r in caplog.records
            if "finished-request registry" in r.message
        ]
        assert len(warnings) == 1  # first eviction only, not all 4


# -- event tracer --------------------------------------------------------


class TestEventTracer:
    def test_ring_wraparound_keeps_newest(self):
        tr = telemetry.EventTracer(capacity=8)
        for i in range(20):
            tr.emit("e", i=i)
        evs = tr.events()
        assert len(evs) == 8
        assert [e["seq"] for e in evs] == list(range(12, 20))
        assert [e["args"]["i"] for e in evs] == list(range(12, 20))

    def test_logical_seqs_are_strictly_monotonic(self):
        tr = telemetry.EventTracer(capacity=64)
        seqs = []
        threads = [
            threading.Thread(
                target=lambda: seqs.append(tr.emit("t"))
            )
            for _ in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(set(seqs)) == 16  # no duplicate sequence numbers

    def test_span_records_duration_and_args(self):
        tr = telemetry.EventTracer(capacity=16)
        with tr.span("work", what="x") as sp:
            time.sleep(0.01)
            sp.set(outcome="done")
        (e,) = tr.events(name="work")
        assert e["ph"] == "X"
        assert e["dur"] >= 0.01
        assert e["args"] == {"what": "x", "outcome": "done"}
        assert e["seq_begin"] < e["seq"]

    def test_chrome_trace_export(self, tmp_path):
        tr = telemetry.EventTracer(capacity=16)
        tr.emit("instant", k=1)
        with tr.span("window"):
            pass
        path = str(tmp_path / "trace.json")
        assert tr.export_chrome_trace(path) == 2
        with open(path) as f:
            doc = json.load(f)
        by_name = {e["name"]: e for e in doc["traceEvents"]}
        assert by_name["instant"]["ph"] == "i"
        assert by_name["window"]["ph"] == "X"
        assert by_name["window"]["dur"] >= 0
        assert {"pid", "tid", "ts"} <= set(by_name["window"])
        assert by_name["instant"]["args"]["k"] == 1

    def test_since_seq_filter(self):
        tr = telemetry.EventTracer(capacity=32)
        tr.emit("old")
        cut = tr.seq
        tr.emit("new")
        assert [e["name"] for e in tr.events(since_seq=cut)] == ["new"]


# -- subsystem integration ----------------------------------------------


class TestHttpPsMetricsEndpoint:
    def test_metrics_roundtrip_and_no_drift(self, not_null):
        """GET /metrics on a live HTTP PS renders the process registry;
        the server/client attribute views and the scraped text agree —
        they are the same store (ISSUE 5 satellite)."""
        from elephas_tpu.parameter.client import HttpClient
        from elephas_tpu.parameter.server import HttpServer

        weights = [np.zeros((8, 8), np.float32)]
        server = HttpServer(weights, mode="asynchronous", port=0)
        server.start()
        try:
            client = HttpClient(master=f"127.0.0.1:{server.port}")
            client.update_parameters([np.ones((8, 8), np.float32)])
            client.get_parameters()
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.port, timeout=10
            )
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            body = resp.read().decode("utf-8")
            assert resp.status == 200
            assert resp.getheader("Content-Type") == telemetry.CONTENT_TYPE
            conn.close()
            client.close()

            sid = server.telemetry_label
            assert (
                f'elephas_ps_updates_applied_total{{server="{sid}"}} 1'
                in body
            )
            cid = client.telemetry_label
            sent_line = (
                f'elephas_ps_client_bytes_sent_total{{client="{cid}"}} '
                f"{client.bytes_sent}"
            )
            assert sent_line in body  # view == rendered registry value
            assert client.bytes_sent > 0 and client.bytes_received > 0
            # reset re-baselines the VIEW; the rendered counter stays
            # monotonic (Prometheus contract)
            client.reset_counters()
            assert client.bytes_sent == 0
            assert sent_line in telemetry.scrape_text()
            # pull-time gauges render for this server
            assert (
                f'elephas_ps_journal_lag_updates{{server="{sid}"}}' in body
            )
        finally:
            server.stop()

    def test_status_and_metrics_agree(self, not_null):
        from elephas_tpu.parameter.server import SocketServer

        server = SocketServer([np.zeros((4,), np.float32)], port=0)
        server.apply_update([np.ones((4,), np.float32)], "w0", 0)
        server.apply_update([np.ones((4,), np.float32)], "w0", 0)  # dup
        status = server.status()
        assert status["updates_applied"] == server.updates_applied == 1
        assert status["updates_duplicate"] == server.updates_duplicate == 1
        sid = server.telemetry_label
        text = telemetry.scrape_text()
        assert (
            f'elephas_ps_updates_duplicate_total{{server="{sid}"}} 1'
            in text
        )


class TestEngineScrape:
    def test_scrape_covers_serving_counters_no_drift(
        self, not_null, serving_lm
    ):
        from elephas_tpu.serving import InferenceEngine

        engine = InferenceEngine(serving_lm, num_slots=4, prefix_cache=True)
        out = engine.run(
            [([2, 3, 4, 5], 6), ([2, 3, 4, 5], 6), ([3, 4, 5], 4)]
        )
        assert len(out) == 3
        text = engine.scrape()
        eid = engine.telemetry_label
        assert (
            f'elephas_serving_tokens_generated_total{{engine="{eid}"}} '
            f"{engine.total_generated}" in text
        )
        prompt_tokens = 4 + 4 + 3
        assert engine.total_generated == sum(
            len(seq) for seq in out.values()
        ) - prompt_tokens
        assert (
            f'elephas_serving_requests_finished_total{{engine="{eid}"}} 3'
            in text
        )
        # latency histograms observed once per token
        assert f'elephas_serving_ttft_seconds_count{{engine="{eid}"}} 3' \
            in text
        stats = engine.stats()
        assert stats["total_generated"] == engine.total_generated
        assert stats["finished"] == 3
        # prefix-cache counters ride the same registry
        cache = engine.scheduler.prefix_cache
        assert cache.stats()["hits"] == cache.hits
        assert (
            f'elephas_prefix_cache_hits_total{{cache='
            f'"{cache.telemetry_label}"}} {cache.hits}' in text
        )
        # scheduler admissions: 3 total, split across kinds
        sid = engine.scheduler.telemetry_label
        admissions = sum(
            int(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("elephas_serving_admissions_total")
            and f'scheduler="{sid}"' in line
        )
        assert admissions == 3

    def test_spark_model_scrape(self, not_null):
        """SparkModel.scrape() renders the same process registry the
        PS /metrics endpoint serves."""
        from elephas_tpu import SparkModel
        from tests.conftest import make_mlp

        model = make_mlp(4, 2)
        sm = SparkModel(model, num_workers=2)
        marker = telemetry.registry().counter(
            "elephas_test_spark_scrape_total", "marker"
        )
        marker.inc()
        assert "elephas_test_spark_scrape_total 1" in sm.scrape()


class TestChaosTrace:
    def test_recovery_span_lands_on_trace_and_exports(
        self, not_null, tmp_path
    ):
        """A kill→restart cycle driven by PSKiller records ONE
        chaos.recovery span (recovered=True) whose duration is the
        recovery window — and the Chrome export shows the kill/restart
        instants inside it (the acceptance-criteria timeline)."""
        from elephas_tpu.fault.harness import (
            PSKiller,
            RestartablePS,
            recovery_windows_from_trace,
        )
        from elephas_tpu.parameter.client import SocketClient
        from elephas_tpu.parameter.server import SocketServer

        seq0 = telemetry.tracer().seq
        ps = RestartablePS(
            SocketServer, [np.zeros((4, 4), np.float32)],
            journal_dir=str(tmp_path / "journal"), journal_every=1,
        )
        killer = PSKiller(ps, after_updates=2, restart_delay_s=0.1)
        killer.start()
        client = SocketClient(master=f"127.0.0.1:{ps.port}", retries=5)
        delta = [np.full((4, 4), 0.01, np.float32)]
        try:
            deadline = time.monotonic() + 60
            while ps.t_recovered is None:
                assert time.monotonic() < deadline, "recovery not observed"
                try:
                    client.update_parameters(delta)
                    client.flush()
                except (ConnectionError, TimeoutError, OSError):
                    pass  # fault-lint: allow chaos window, retried above
                time.sleep(0.02)
        finally:
            killer.cancel()
            killer.join(timeout=30)
            try:
                client.close()
            except (ConnectionError, OSError):
                pass  # fault-lint: allow best-effort close under chaos
            ps.stop()

        windows = recovery_windows_from_trace(since_seq=seq0)
        assert len(windows) == 1
        assert windows[0] >= 0.1  # at least the restart delay
        assert windows[0] == pytest.approx(ps.recovery_s, abs=0.25)
        names = [
            e["name"] for e in telemetry.tracer().events(since_seq=seq0)
        ]
        assert "chaos.ps_kill" in names and "chaos.ps_restart" in names

        path = str(tmp_path / "chaos_trace.json")
        telemetry.tracer().export_chrome_trace(path, since_seq=seq0)
        with open(path) as f:
            doc = json.load(f)
        spans = [
            e for e in doc["traceEvents"]
            if e["name"] == "chaos.recovery" and e["ph"] == "X"
        ]
        assert len(spans) == 1 and spans[0]["args"]["recovered"] is True
        kill = next(
            e for e in doc["traceEvents"] if e["name"] == "chaos.ps_kill"
        )
        # the kill instant sits inside the recovery span on the timeline
        assert (
            spans[0]["ts"] <= kill["ts"] <= spans[0]["ts"] + spans[0]["dur"]
        )

    def test_harness_refuses_null_mode(self, not_null):
        from elephas_tpu.fault.harness import RestartablePS
        from elephas_tpu.parameter.server import SocketServer

        was = telemetry.set_null(True)
        try:
            with pytest.raises(RuntimeError, match="requires telemetry"):
                RestartablePS(SocketServer, [np.zeros((2,), np.float32)])
        finally:
            telemetry.set_null(was)


class TestWorkerRetryTelemetry:
    def test_supervised_retry_counts_and_emits(self, not_null):
        """A PS outage that the supervised retry rides out shows up as
        retry counter increments and worker.retry trace events."""
        from elephas_tpu.worker import AsynchronousSparkWorker

        worker = AsynchronousSparkWorker(
            json_model="{}", parameter_server_mode="socket",
            ps_retries=3, ps_retry_max_delay=0.05,
        )
        seq0 = telemetry.tracer().seq
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise ConnectionError("chaos")
            return "ok"

        assert worker._supervised(flaky) == "ok"
        assert worker._m_retries.value == 2
        events = telemetry.tracer().events(
            since_seq=seq0, name="worker.retry"
        )
        assert len(events) == 2
        assert events[0]["args"]["worker"] == worker.telemetry_label


class TestSeriesLifecycle:
    def test_remove_series_retires_rendering_views_survive(self):
        """remove_series drops matching children from every family that
        carries the label; children handed out earlier keep working
        (retired components' read-back views must not break)."""
        from elephas_tpu.telemetry.registry import Registry

        reg = Registry()
        a = reg.counter(
            "elephas_t_lifecycle_total", "x", labels=("engine",)
        ).labels(engine="a")
        b = reg.counter(
            "elephas_t_lifecycle_total", "x", labels=("engine",)
        ).labels(engine="b")
        g = reg.gauge(
            "elephas_t_lifecycle_gauge", "x", labels=("engine",)
        ).labels(engine="a")
        a.inc(3)
        b.inc(5)
        g.set(7)
        text = reg.render()
        assert 'elephas_t_lifecycle_total{engine="a"} 3' in text
        assert 'elephas_t_lifecycle_gauge{engine="a"} 7' in text
        assert reg.remove_series(engine="a") == 2  # counter + gauge
        text = reg.render()
        assert 'engine="a"' not in text
        assert 'elephas_t_lifecycle_total{engine="b"} 5' in text
        # the retired child object itself stays live for its holder
        a.inc()
        assert a.value == 4
        # re-registering the same label mints a FRESH series
        a2 = reg.counter(
            "elephas_t_lifecycle_total", "x", labels=("engine",)
        ).labels(engine="a")
        assert a2.value == 0 and a2 is not a

    def test_remove_series_validation(self):
        from elephas_tpu.telemetry.registry import NullRegistry, Registry

        reg = Registry()
        fam = reg.counter(
            "elephas_t_val_total", "x", labels=("server",)
        )
        fam.labels(server="0")
        with pytest.raises(ValueError, match="at least one label"):
            reg.remove_series()
        with pytest.raises(ValueError, match="cannot remove by"):
            fam.remove(nope="0")
        # a label no family carries is a harmless no-op
        assert reg.remove_series(zebra="0") == 0
        assert NullRegistry().remove_series(server="0") == 0

    def test_component_release_telemetry_bounds_scrape(self, not_null):
        """Churned components (the unbounded-growth shape: clients per
        partition, chaos-restarted servers) retire their series via
        release_telemetry(); scrape output stops growing and the
        counter-backed properties keep reading."""
        from elephas_tpu.parameter.server import SocketServer

        server = SocketServer([np.zeros((4,), np.float32)], port=0)
        server.apply_update([np.ones((4,), np.float32)], "w0", 0)
        sid = server.telemetry_label
        assert f'server="{sid}"' in telemetry.scrape_text()
        server.release_telemetry()
        text = telemetry.scrape_text()
        assert f'server="{sid}"' not in text  # counters AND pull gauges
        assert server.updates_applied == 1  # object-held view survives

    def test_engine_release_cascades(self, not_null, serving_lm):
        from elephas_tpu.serving import InferenceEngine

        engine = InferenceEngine(serving_lm, num_slots=2, prefix_cache=True)
        engine.run([([2, 3, 4, 5], 4)])
        labels = (
            f'engine="{engine.telemetry_label}"',
            f'scheduler="{engine.scheduler.telemetry_label}"',
            f'cache="{engine.scheduler.prefix_cache.telemetry_label}"',
        )
        text = telemetry.scrape_text()
        assert all(lbl in text for lbl in labels)
        engine.release_telemetry()
        text = telemetry.scrape_text()
        assert not any(lbl in text for lbl in labels)
        assert engine.total_generated > 0  # views still read


class TestPrefillStallSemantics:
    def test_lone_long_prompt_never_counts_as_stalled(
        self, not_null, serving_lm
    ):
        """A single long prompt consuming the whole per-step chunk
        budget ADVANCES every step — it is not deferred, so the stall
        counter must stay 0 (it counts slots that got NO chunk this
        step, not slots that merely remain mid-prefill)."""
        from elephas_tpu.serving import InferenceEngine

        long_prompt = [2, 3, 4, 5] * 4  # 16 tokens = 4 chunks
        engine = InferenceEngine(
            serving_lm, num_slots=4, prefill_chunk=4, prefill_budget=4,
        )
        out = engine.run([(long_prompt, 4)])
        assert len(out) == 1
        assert engine._m_prefill_stalls.value == 0
        engine.release_telemetry()

    def test_concurrent_long_prompts_count_deferred_slots(
        self, not_null, serving_lm
    ):
        """Two long prompts behind a one-chunk budget: each step serves
        one slot and defers the other, so the stall counter rises."""
        from elephas_tpu.serving import InferenceEngine

        long_prompt = [2, 3, 4, 5] * 4
        engine = InferenceEngine(
            serving_lm, num_slots=4, prefill_chunk=4, prefill_budget=4,
        )
        out = engine.run([(long_prompt, 4), (list(long_prompt), 4)])
        assert len(out) == 2
        assert engine._m_prefill_stalls.value > 0
        engine.release_telemetry()


# -- one clock, fit's spans, the mirror into the device trace (ISSUE 24) --


class TestOneClock:
    def test_every_event_carries_mono_ns_in_seq_order(self):
        tr = telemetry.EventTracer(capacity=64)
        before = time.monotonic_ns()
        tr.emit("a")
        with tr.span("b"):
            tr.emit("inside")
        tr.complete("c", 0.0)
        tr.emit("d")
        evs = sorted(
            tr.events(), key=lambda e: e.get("seq_begin", e["seq"])
        )
        assert [e["name"] for e in evs] == ["a", "b", "inside", "c", "d"]
        stamps = [e["mono_ns"] for e in evs]
        assert all(isinstance(m, int) for m in stamps)
        assert stamps == sorted(stamps)
        assert before <= stamps[0] and stamps[-1] <= time.monotonic_ns()

    def test_span_dur_is_monotonic_and_ts_stays_wall(self, monkeypatch):
        from elephas_tpu.telemetry import events as events_mod

        class Clock:
            """A wall clock that steps back an hour inside the span."""

            def __init__(self):
                self.wall = iter([1_000_000.0, 1_000_000.0 - 3600.0])
                self.mono = iter([5_000_000_000, 5_250_000_000])

            def time(self):
                return next(self.wall)

            def monotonic_ns(self):
                return next(self.mono)

        monkeypatch.setattr(events_mod, "time", Clock())
        tr = telemetry.EventTracer(capacity=8)
        with tr.span("stepped"):
            pass
        monkeypatch.undo()
        (e,) = tr.events()
        assert e["ts"] == 1_000_000.0  # the wall reading at the start
        assert e["mono_ns"] == 5_000_000_000
        assert e["dur"] == 0.25  # the monotonic difference, not -3600

    def test_complete_backdates_both_clocks(self):
        tr = telemetry.EventTracer(capacity=8)
        tr.complete("measured", 0.5)
        (e,) = tr.events()
        now_wall, now_mono = time.time(), time.monotonic_ns()
        assert e["dur"] == 0.5
        assert 0.5 <= now_wall - e["ts"] < 5.0
        assert 0.5e9 <= now_mono - e["mono_ns"] < 5e9


FIT_EPOCHS = 17
# what JAX did under a span (ISSUE 40): worker.JaxWork sets them all
JAX_WORK_ARGS = {
    "jax_trace_s", "jax_lower_s", "jax_compile_s", "jax_cache_load_s",
    "jax_events", "cache_hits", "cache_misses", "jax_longest",
}
# a span's name -> the args it must carry (ISSUE 24's table)
PER_CALL_SPANS = {
    "fit.call": {"epochs", "batch_size", "workers"} | JAX_WORK_ARGS,
    "fit.partition_arrays": {"rows", "bytes"},
    "fit.to_mesh": set(),
    "fit.stack_batches": {"bytes"},
    "fit.shard_data": {"bytes"},
    "fit.device_state": {"variables", "bytes"} | JAX_WORK_ARGS,
}
PER_EPOCH_SPANS = {
    "fit.epoch_dispatch": (
        {"epoch", "signatures", "new_signature"} | JAX_WORK_ARGS),
    "fit.loss_wait": {"epoch"},
    "fit.write_back": {"epoch", "variables", "bytes", "final"},
    "fit.callbacks": {"epoch", "count"},
}


def _toy_spark_model(num_workers=2):
    from tests.conftest import make_mlp
    from elephas_tpu import SparkModel

    return SparkModel(make_mlp(8, 2), num_workers=num_workers)


def _own_events(tracer, since):
    """This thread's events (another test's leftover serving thread
    may be recording into the same ring)."""
    me = threading.get_ident()
    return [e for e in tracer.events(since) if e["tid"] == me]


def _toy_rows(n=64):
    rng = np.random.default_rng(3)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    return x, (x.sum(axis=1) > 0).astype(np.int32)


@pytest.fixture(scope="module")
def staged_fit_events(spark_context):
    """The default ring's events of one staged 17-epoch ``fit`` call
    over an RDD (the path the benchmark's fit cell drives)."""
    from elephas_tpu.utils import rdd_utils

    assert not telemetry.null_mode()
    x, y = _toy_rows()
    rdd = rdd_utils.to_simple_rdd(spark_context, x, y, num_partitions=2)
    tracer = telemetry.default_tracer()
    since = tracer.seq
    _toy_spark_model().fit(rdd, epochs=FIT_EPOCHS, batch_size=8)
    return _own_events(tracer, since)


class TestFitSpans:
    def test_ring_keeps_every_epoch_event_inside_the_budget(
        self, staged_fit_events
    ):
        """At most 12 ring events an epoch and 12 a call outside the
        epoch loop, so that a 17-epoch call of a model with hundreds of
        variables cannot push its first ``fit.epoch`` out of the default
        ring (the benchmark's window opens on it)."""
        evs = staged_fit_events
        assert telemetry.events.DEFAULT_CAPACITY == 8192
        epochs = [e for e in evs if e["name"] == "fit.epoch"]
        assert [e["args"]["epoch"] for e in epochs] == list(range(FIT_EPOCHS))
        per_epoch = [
            e for e in evs
            if "epoch" in e["args"] and not e["args"].get("final")
        ]
        assert len(per_epoch) <= 12 * FIT_EPOCHS
        assert len(evs) - len(per_epoch) <= 12

    def test_span_tree_of_a_staged_call(self, staged_fit_events):
        evs = staged_fit_events
        spans = [e for e in evs if e["ph"] == "X"]
        (root,) = [e for e in spans if e["name"] == "fit.call"]
        assert root["args"]["epochs"] == FIT_EPOCHS
        assert root["args"]["batch_size"] == 8
        assert root["args"]["workers"] == 2
        trace = root["args"]["trace"]
        assert trace.startswith("fit-r") and trace.endswith("e0")
        for e in evs:  # everything is inside the root, under its scope
            assert e["name"].startswith("fit.")
            assert e["args"]["trace"] == trace
            if e is not root:
                assert root["seq_begin"] < e.get("seq_begin", e["seq"])
                assert e["seq"] < root["seq"]
        for name, keys in PER_CALL_SPANS.items():
            (e,) = [s for s in spans if s["name"] == name]
            assert keys <= set(e["args"]), name
        for name, keys in PER_EPOCH_SPANS.items():
            found = [
                s for s in spans
                if s["name"] == name and not s["args"].get("final")
            ]
            assert [s["args"]["epoch"] for s in found] == list(
                range(FIT_EPOCHS)
            ), name
            assert all(keys <= set(s["args"]) for s in found), name
        (last,) = [s for s in spans if s["args"].get("final")]
        assert last["name"] == "fit.write_back"
        assert last["args"]["epoch"] == FIT_EPOCHS - 1
        # stage-in comes before the first epoch, in the table's order
        order = [s["name"] for s in sorted(spans, key=lambda s: s["seq_begin"])]
        assert order[:7] == [
            "fit.call", "fit.partition_arrays", "fit.to_mesh",
            "fit.stack_batches", "fit.shard_data", "fit.device_state",
            "fit.epoch_dispatch",
        ]
        sizes = {s["name"]: s["args"] for s in spans}
        assert sizes["fit.partition_arrays"]["rows"] == 64
        assert sizes["fit.partition_arrays"]["bytes"] == 64 * 8 * 4 + 64 * 4
        assert sizes["fit.device_state"]["variables"] > 4
        assert (
            sizes["fit.write_back"]["bytes"]
            == sizes["fit.device_state"]["bytes"] > 0
        )

    def test_streamed_fit_records_input_wait(self):
        x, y = _toy_rows()
        tracer = telemetry.default_tracer()
        since = tracer.seq
        _toy_spark_model().fit(
            (x, y), epochs=2, batch_size=8, stream_block_steps=2
        )
        evs = _own_events(tracer, since)
        names = {e["name"] for e in evs}
        assert names >= {
            "fit.call", "fit.device_state", "fit.input_wait",
            "fit.epoch_dispatch", "fit.loss_wait", "fit.write_back",
            "fit.callbacks", "fit.epoch",
        }
        assert "fit.stack_batches" not in names  # nothing staged whole
        for epoch in range(2):
            waits = [
                e["args"]["block"] for e in evs
                if e["name"] == "fit.input_wait"
                and e["args"]["epoch"] == epoch
            ]
            runs = [
                e["args"]["block"] for e in evs
                if e["name"] == "fit.epoch_dispatch"
                and e["args"]["epoch"] == epoch
            ]
            # one wait for each block and one that finds the end
            assert runs and waits == list(range(len(runs) + 1))


def _dispatches(events):
    return [e for e in events if e["name"] == "fit.epoch_dispatch"]


class TestJaxWorkOnSpans:
    """ISSUE 40: what JAX did under ``fit.epoch_dispatch``,
    ``fit.device_state`` and ``fit.call``, whether a dispatch met a new
    signature, and the allocator once an epoch. Counts only: the CPU
    mesh says nothing about time."""

    def test_first_dispatch_holds_the_epoch_programs_trace_lower_compile(
        self, staged_fit_events
    ):
        first, *steady = _dispatches(staged_fit_events)
        args = first["args"]
        assert args["epoch"] == 0 and args["new_signature"] is True
        assert args["signatures"] == 1
        assert args["jax_events"] >= 3  # a trace, a lowering, a compile
        assert "per_worker" in args["jax_longest"]
        for key in ("jax_trace_s", "jax_lower_s", "jax_compile_s"):
            assert args[key] > 0.0, key
        # the stages are exclusive of what nests in them, so they add
        # up to time that passed once, under the span
        staged = sum(args[k] for k in (
            "jax_trace_s", "jax_lower_s", "jax_compile_s"))
        assert staged <= first["dur"]
        assert args["jax_cache_load_s"] <= args["jax_compile_s"]
        assert len(steady) == FIT_EPOCHS - 1

    def test_steady_dispatches_record_no_jax_work_and_no_new_signature(
        self, staged_fit_events
    ):
        for e in _dispatches(staged_fit_events)[1:]:
            args = e["args"]
            assert args["jax_events"] == 0, args
            assert args["jax_longest"] == ""
            assert args["jax_trace_s"] == args["jax_compile_s"] == 0.0
            assert args["new_signature"] is False
            assert args["signatures"] == 1

    def test_root_span_holds_the_calls_totals(self, staged_fit_events):
        (root,) = [e for e in staged_fit_events if e["name"] == "fit.call"]
        inner = [
            e for e in staged_fit_events
            if e["name"] in ("fit.epoch_dispatch", "fit.device_state")
        ]
        for key in ("jax_events", "cache_hits", "cache_misses"):
            assert root["args"][key] >= sum(e["args"][key] for e in inner)
        assert root["args"]["jax_events"] >= 3
        assert root["args"]["jax_compile_s"] >= sum(
            e["args"]["jax_compile_s"] for e in inner)

    def test_signatures_never_fall_and_rise_where_new_signature_says(self):
        """Two calls on one object, one worker (where, before ISSUE 41
        named the epoch function's output shardings, set-up's call and
        a longer one showed it different arguments): the two args agree
        dispatch by dispatch, and the count ends where it began."""
        x, y = _toy_rows()
        sm = _toy_spark_model(num_workers=1)
        tracer = telemetry.default_tracer()
        since = tracer.seq
        sm.fit((x, y), epochs=1, batch_size=8)
        sm.fit((x, y), epochs=4, batch_size=8)
        found = _dispatches(_own_events(tracer, since))
        assert [e["args"]["epoch"] for e in found] == [0, 0, 1, 2, 3]
        seen = 0
        for e in found:
            args = e["args"]
            assert args["signatures"] >= seen
            assert args["new_signature"] is (args["signatures"] > seen)
            # a call that added no entry traced, lowered, compiled nothing
            if not args["new_signature"]:
                assert args["jax_events"] == 0
            seen = args["signatures"]
        assert found[0]["args"]["new_signature"] is True
        assert seen == 1 == sm._get_runner()._epoch_fn._cache_size()

    @pytest.mark.parametrize(
        "stream", [{}, {"stream_block_steps": 2}], ids=["staged", "streamed"])
    @pytest.mark.parametrize("num_workers", [1, 2])
    def test_the_state_comes_back_under_the_sharding_it_went_in_with(
        self, monkeypatch, num_workers, stream
    ):
        """ISSUE 41: the epoch program names its outputs' shardings, so
        a dispatch fed the last one's outputs shows the dispatch cache
        the arguments that ``_device_state`` showed it: one signature
        for the life of the runner, whatever the mesh's size. A
        ``new_signature`` after a runner's first dispatch is a
        regression (on the chip it cost 0.4-1.4 s of host time)."""
        import jax

        from elephas_tpu import worker

        shardings = []  # one a dispatch: the state's leaves, in and out
        inner = worker.MeshRunner._dispatch_epoch

        def watched(self, state, *rest, **where):
            # read before the call: it donates the state's buffers
            went_in = [leaf.sharding for leaf in jax.tree.leaves(state)]
            out = inner(self, state, *rest, **where)
            shardings.append((
                went_in, [leaf.sharding for leaf in jax.tree.leaves(out[:3])]))
            return out

        monkeypatch.setattr(worker.MeshRunner, "_dispatch_epoch", watched)
        x, y = _toy_rows()
        sm = _toy_spark_model(num_workers=num_workers)
        tracer = telemetry.default_tracer()
        since = tracer.seq
        sm.fit((x, y), epochs=1, batch_size=8, **stream)
        sm.fit((x, y), epochs=4, batch_size=8, **stream)
        found = _dispatches(_own_events(tracer, since))
        assert len(found) == len(shardings) >= 5
        assert [e["args"]["signatures"] for e in found] == [1] * len(found)
        assert [e["args"]["new_signature"] for e in found] == (
            [True] + [False] * (len(found) - 1))
        runner = sm._get_runner()
        given = {leaf.sharding
                 for part in runner._device_state() for leaf in part}
        assert len(given) == 1  # what _device_state gives every leaf
        for went_in, came_back in shardings:
            assert len(went_in) == len(came_back) > 4
            assert set(went_in) == set(came_back) == given
        assert runner._epoch_fn._cache_size() == 1

    def test_streamed_dispatches_carry_the_args(self):
        x, y = _toy_rows()
        tracer = telemetry.default_tracer()
        since = tracer.seq
        _toy_spark_model().fit(
            (x, y), epochs=2, batch_size=8, stream_block_steps=2
        )
        evs = _own_events(tracer, since)
        found = _dispatches(evs)
        assert len(found) > 2  # a dispatch a block
        for e in found:
            assert PER_EPOCH_SPANS["fit.epoch_dispatch"] | {"block"} <= set(
                e["args"])
        assert found[0]["args"]["jax_events"] >= 3
        assert all(e["args"]["jax_events"] == 0 for e in found[1:])
        for name in ("fit.device_state", "fit.call"):
            (e,) = [s for s in evs if s["name"] == name]
            assert PER_CALL_SPANS[name] <= set(e["args"]), name

    def test_another_threads_compile_leaves_the_spans_args_alone(self):
        import jax
        import jax.numpy as jnp

        from elephas_tpu.worker import JaxWork

        def compile_something(scale):
            jax.jit(lambda a: a * scale + 1)(jnp.ones(3)).block_until_ready()

        tr = telemetry.EventTracer(capacity=8)
        with tr.span("fit.epoch_dispatch") as sp, JaxWork(sp):
            other = threading.Thread(target=compile_something, args=(3.5,))
            other.start()
            other.join(timeout=60)
            assert not other.is_alive()
        with tr.span("fit.epoch_dispatch") as sp, JaxWork(sp):
            compile_something(4.5)  # this thread's own: counted
        theirs, own = (e["args"] for e in tr.events())
        assert theirs["jax_events"] == 0 and theirs["jax_longest"] == ""
        assert theirs["jax_compile_s"] == 0.0
        assert own["jax_events"] >= 3 and own["jax_compile_s"] > 0.0

    def test_listeners_are_registered_once_a_process(self):
        from jax._src import monitoring

        from elephas_tpu import worker

        x, y = _toy_rows()
        for _ in range(3):  # runners and calls
            sm = _toy_spark_model()
            sm.fit((x, y), epochs=1, batch_size=8)
            sm.fit((x, y), epochs=1, batch_size=8)
        durations = monitoring.get_event_duration_listeners()
        events = monitoring.get_event_listeners()
        assert durations.count(worker._on_jax_duration) == 1
        assert events.count(worker._on_jax_event) == 1

    def test_null_mode_records_nothing_and_opens_no_frame(self, not_null):
        from elephas_tpu import worker

        x, y = _toy_rows()
        tracer = telemetry.default_tracer()
        telemetry.set_null(True)
        try:
            since = tracer.seq
            opened = []
            sp = telemetry.trace_span("fit.epoch_dispatch")
            with sp, worker.JaxWork(sp):
                opened.append(list(getattr(worker._jax_frames, "open", [])))
            history = _toy_spark_model().fit((x, y), epochs=2, batch_size=8)
        finally:
            telemetry.set_null(False)
        assert opened == [[]]
        assert len(history["loss"]) == 2
        assert _own_events(tracer, since) == []
        # and the listeners, with no frame open, return at their first line
        worker._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 1.0, fun_name="f")
        worker._on_jax_event("/jax/compilation_cache/cache_hits")

    def test_one_memory_event_an_epoch_where_the_backend_keeps_statistics(
        self, monkeypatch
    ):
        from elephas_tpu import worker

        x, y = _toy_rows()
        tracer = telemetry.default_tracer()
        since = tracer.seq
        _toy_spark_model().fit((x, y), epochs=2, batch_size=8)
        # the CPU backend keeps none: no event, and nothing raised
        assert worker._fullest_device_stats() is None
        assert not [e for e in _own_events(tracer, since)
                    if e["name"] == "fit.memory"]
        peaks = iter([700, 900, 900, 950])
        monkeypatch.setattr(
            worker, "_fullest_device_stats",
            lambda: {"bytes_in_use": 512, "peak_bytes_in_use": next(peaks)},
        )
        since = tracer.seq
        _toy_spark_model().fit((x, y), epochs=4, batch_size=8)
        evs = _own_events(tracer, since)
        found = [e["args"] for e in evs if e["name"] == "fit.memory"]
        assert [a["epoch"] for a in found] == [0, 1, 2, 3]
        assert [a["peak_rose"] for a in found] == [True, True, False, True]
        assert [a["peak_bytes_in_use"] for a in found] == [700, 900, 900, 950]
        assert all(a["bytes_in_use"] == 512 for a in found)
        # each lies between its epoch's loss read and its fit.epoch event
        for a, e in zip(found, [e for e in evs if e["name"] == "fit.memory"]):
            (wait,) = [s for s in evs if s["name"] == "fit.loss_wait"
                       and s["args"]["epoch"] == a["epoch"]]
            (mark,) = [s for s in evs if s["name"] == "fit.epoch"
                       and s["args"]["epoch"] == a["epoch"]]
            assert wait["seq"] < e["seq"] < mark["seq"]
        # the ring's budget holds with the event in it
        per_epoch = [e for e in evs if "epoch" in e["args"]
                     and not e["args"].get("final")]
        assert len(per_epoch) <= 12 * 4


def _host_annotations(trace_dir, prefixes):
    """``[(name, seq)]`` of the recorded host plane's events whose name
    starts with one of ``prefixes``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    found.append((e.name, dict(e.stats).get("seq")))
    return found


class TestProfilerMirror:
    def test_spans_reach_the_profiler_trace_and_join_on_seq(
        self, not_null, serving_lm, spark_context, tmp_path
    ):
        """One ``jax.profiler`` session over a ``fit`` and a serving
        run: the host plane holds the ring's ``fit.*`` and ``serve.*``
        spans as annotations carrying their begin sequence number."""
        import jax

        from elephas_tpu.serving import InferenceEngine
        from elephas_tpu.utils import rdd_utils

        x, y = _toy_rows()
        rdd = rdd_utils.to_simple_rdd(spark_context, x, y, num_partitions=2)
        sm = _toy_spark_model()
        engine = InferenceEngine(serving_lm, num_slots=2)
        tracer = telemetry.default_tracer()
        since = tracer.seq
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            sm.fit(rdd, epochs=2, batch_size=8)
            engine.run([([2, 3, 4], 4)])
        finally:
            jax.profiler.stop_trace()
        ring = {
            (e["name"], e.get("seq_begin", e["seq"]))
            for e in tracer.events(since)
        }
        mirrored = _host_annotations(str(tmp_path), ("fit.", "serve."))
        names = {name for name, _seq in mirrored}
        assert {"fit.write_back", "fit.epoch", "fit.call",
                "serve.decode_window"} <= names
        for name, seq in mirrored:
            assert (name, seq) in ring, (name, seq)
        # the other way: every span this thread recorded in the session
        # was mirrored (another test's leftover thread may hold a span
        # that opened before the session did)
        spans = {
            (e["name"], e["seq_begin"]) for e in tracer.events(since)
            if e["ph"] == "X" and e["name"] != "jit.compile"
            and e["tid"] == threading.get_ident()
        }
        assert spans <= set(mirrored)

    def test_no_session_and_null_mode_open_no_annotation(
        self, not_null, monkeypatch
    ):
        import jax

        opened = []

        class Recording(jax.profiler.TraceAnnotation):
            def __init__(self, name, **kw):
                opened.append(name)
                super().__init__(name, **kw)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
        tr = telemetry.EventTracer(capacity=8)
        with tr.span("quiet"):  # no session: a flag check, nothing opened
            tr.emit("quiet.instant")
        assert opened == []
        monkeypatch.setattr(
            Recording, "is_enabled", staticmethod(lambda: True)
        )
        with tr.span("loud"):
            tr.emit("loud.instant")
        assert opened == ["loud", "loud.instant"]
        del opened[:]
        telemetry.set_null(True)
        try:
            with telemetry.trace_span("never"):
                telemetry.emit("never.instant")
        finally:
            telemetry.set_null(False)
        assert opened == []


def test_importing_telemetry_leaves_jax_out():
    """A process that only records or merges telemetry (a load
    generator, ``python -m elephas_tpu.telemetry.merge``) never pays
    for JAX: neither the package nor ``telemetry`` imports it."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import elephas_tpu.telemetry as t\n"
        "with t.trace_span('s'):\n"
        "    t.emit('i')\n"
        "assert len(t.default_tracer().events()) == 2\n"
        "print(sorted(m for m in ('jax', 'jaxlib', 'keras', 'numpy') "
        "if m in sys.modules))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
