"""The benchmark's credibility gate.

A driver capture of 2026-07 recorded 613,997 img/s/chip — "MFU: 7464.7%"
— from a 0.0s timed window, because ``block_until_ready`` returned
before the work had run and nothing in ``bench.py`` sanity-checked the
number.
These tests pin the contract: a poisoned timing path provably aborts and
an impossible number can never reach the JSON record.
"""

import glob
import json
import os
import re
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


class TestRequireCredible:
    def test_sane_measurement_passes(self):
        # round-3 re-measured reality: ~2,193 img/s, 4.1 GFLOP/img, v5e peak
        bench.require_credible(
            dt=1.4, ips_chip=2193.0, flops_per_img=24e9, peak=197e12
        )

    def test_zero_width_window_rejected(self):
        # the exact failure shape of that capture: dt == 0.0
        with pytest.raises(bench.ImplausibleTiming, match="credibility floor"):
            bench.require_credible(
                dt=0.0, ips_chip=613997.0, flops_per_img=24e9, peak=197e12
            )

    def test_subfloor_window_rejected(self):
        with pytest.raises(bench.ImplausibleTiming, match="credibility floor"):
            bench.require_credible(
                dt=bench.MIN_CREDIBLE_DT / 2, ips_chip=100.0,
                flops_per_img=1e9, peak=197e12,
            )

    def test_impossible_mfu_rejected(self):
        # 613,997 img/s x 24 GFLOP/img = 7,464% of v5e peak
        with pytest.raises(bench.ImplausibleTiming, match="MFU"):
            bench.require_credible(
                dt=1.4, ips_chip=613997.0, flops_per_img=24e9, peak=197e12
            )

    def test_mfu_gate_needs_flops_and_peak(self):
        # NaN flops (e.g. --no-baseline) disables only the MFU gate;
        # the absolute dt floor still applies
        bench.require_credible(
            dt=1.0, ips_chip=1e9, flops_per_img=float("nan"), peak=197e12
        )
        bench.require_credible(
            dt=1.0, ips_chip=1e9, flops_per_img=24e9, peak=float("nan")
        )
        with pytest.raises(bench.ImplausibleTiming):
            bench.require_credible(
                dt=0.0, ips_chip=1.0, flops_per_img=float("nan"),
                peak=float("nan"),
            )

    def test_exact_peak_passes_above_fails(self):
        # boundary: implied MFU 1.0 is allowed, epsilon above is not
        peak, flops = 197e12, 1e9
        bench.require_credible(
            dt=1.0, ips_chip=peak / flops, flops_per_img=flops, peak=peak
        )
        with pytest.raises(bench.ImplausibleTiming):
            bench.require_credible(
                dt=1.0, ips_chip=peak / flops * 1.01, flops_per_img=flops,
                peak=peak,
            )


_POISONED_RUN = """
import sys, types, itertools
sys.path.insert(0, {repo!r})
import bench

# Poison the clock exactly as the round-3 anomaly did: perf_counter
# freezes, so every timed window measures ~0.0s while the work "runs".
import time
frozen = time.perf_counter()
time.perf_counter = lambda: frozen

sys.argv = ["bench.py", "--preset", "tiny", "--epochs", "1"]
bench.main()
"""


@pytest.mark.slow  # full bench subprocess (compiles a model)
class TestPoisonedTimingAborts:
    def test_frozen_clock_never_emits_json(self, tmp_path):
        """End-to-end: freeze perf_counter (the r3 anomaly made every
        timed window 0-width) and assert bench exits non-zero with no
        JSON line on stdout."""
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax")
        proc = subprocess.run(
            [sys.executable, "-c",
             _POISONED_RUN.format(repo=os.path.dirname(
                 os.path.dirname(os.path.abspath(__file__))))],
            capture_output=True, text=True, timeout=900, env=env,
        )
        assert proc.returncode != 0, (
            f"poisoned bench run must fail loudly; stdout={proc.stdout!r}"
        )
        for line in proc.stdout.splitlines():
            assert not line.startswith("{"), (
                f"poisoned run emitted a JSON record: {line}"
            )
        assert "implausible" in proc.stderr.lower() or \
            "credible" in proc.stderr.lower()


@pytest.mark.slow  # full bench subprocess (compiles a model)
class TestBenchJsonContract:
    def test_tiny_preset_emits_sane_record(self):
        """`python bench.py` on CPU still produces the one-line JSON
        contract, with the guard live (mfu<=1, dt above floor)."""
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "bench.py"),
             "--preset", "tiny", "--epochs", "1"],
            capture_output=True, text=True, timeout=900, env=env, cwd=repo,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert {"metric", "value", "unit", "vs_baseline"} <= set(rec)
        assert rec["value"] > 0
        if "mfu" in rec:
            assert 0 < rec["mfu"] <= 1.0


@pytest.mark.slow  # spins servers + trains a small keras model
class TestBenchPsContract:
    def test_ps_preset_emits_sane_record(self):
        """`bench.py --preset ps` (ISSUE 2): one JSON line whose byte
        accounting comes from real wire counters — the int8 reduction
        is deterministic (≥4x is the acceptance bar; int8 packs f32 to
        1 byte + scale headers), and the throughput section must be
        present with positive rates. Timing-dependent speedups are NOT
        asserted here (shared noisy box) — the JSON record is the
        evidence trail."""
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "bench.py"),
             "--preset", "ps", "--ps-rounds", "3", "--ps-rows", "128",
             "--ps-epochs", "1"],
            capture_output=True, text=True, timeout=900, env=env, cwd=repo,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert {"metric", "value", "unit", "vs_baseline", "wire",
                "epoch_throughput"} <= set(rec)
        assert rec["bytes_reduction_int8"] >= 3.5
        assert rec["bytes_reduction_int8_topk"] >= 4.0
        for cfg in rec["wire"].values():
            assert cfg["bytes_per_sync"] > 0
            assert cfg["p50_ms"] <= cfg["p99_ms"]
        for mode in ("asynchronous", "hogwild"):
            row = rec["epoch_throughput"][mode]
            assert row["pickle_sps"] > 0 and row["fast_sps"] > 0


@pytest.mark.slow  # two keras training runs in a bench subprocess
class TestShardedFaultsBenchContract:
    def test_faults_shards_preset_emits_sane_record(self):
        """`bench.py --preset faults --faults-shards 2` (ISSUE 6): one
        JSON line proving the acceptance criteria — the surviving
        shard progressed during the outage, per-shard applied counts
        match the fault-free run (zero double-applies), and the
        per-shard recovery window comes from the shard-stamped trace
        span, agreeing with the counters cross-check."""
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "bench.py"),
             "--preset", "faults", "--faults-shards", "2",
             "--ps-rows", "256", "--ps-epochs", "2"],
            capture_output=True, text=True, timeout=900, env=env, cwd=repo,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["num_shards"] == 2
        killed = str(rec["killed_shard"])
        assert rec["value"] > 0
        assert rec["recovery_s_by_shard"][killed] == rec["value"]
        assert abs(
            rec["recovery_s_by_shard"][killed]
            - rec["recovery_s_counters_by_shard"][killed]
        ) < 0.5
        assert all(
            v >= 1
            for v in rec["other_shards_progress_during_outage"].values()
        )
        assert (
            rec["updates_applied_by_shard"]
            == rec["updates_expected_by_shard"]
        )
        assert rec["updates_lost_final"] == 0
        assert not any(rec["pending_final"])


@pytest.mark.slow  # engines + loopback shard sockets in a subprocess
class TestDeployBenchContract:
    def test_deploy_preset_emits_sane_record(self):
        """`bench.py --preset deploy` (ISSUE 20): one JSON line proving
        the train-while-serving acceptance criteria — p99 during live
        weight pushes within the bounded factor of steady state (and
        token-exact), the canary cycle auto-rolled-back off a real
        slo_burn with exactly one fired and one cleared anomaly, the
        mid-deployment shard kill converged every replica on one
        generation with zero double-applies, and the cross-generation
        warm migration refused loudly."""
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "bench.py"),
             "--preset", "deploy", "--deploy-requests", "8"],
            capture_output=True, text=True, timeout=900, env=env, cwd=repo,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert {"metric", "value", "unit", "vs_baseline", "livepush",
                "canary", "chaos", "migration"} <= set(rec)
        assert 0 < rec["livepush"]["p99_ratio"] <= 5.0
        assert rec["livepush"]["token_exact"] is True
        assert rec["livepush"]["generations_applied"] == \
            rec["livepush"]["pushes"]
        assert rec["canary"]["watchdog_fired"] == 1
        assert rec["canary"]["watchdog_cleared"] == 1
        assert rec["canary"]["outcome"] == "rolled_back"
        assert rec["canary"]["rollback_generation"] > \
            rec["canary"]["candidate_generation"]  # monotonic ledger
        assert rec["chaos"]["double_applies"] == 0
        assert rec["chaos"]["converged_versions"] == \
            [rec["chaos"]["final_generation"]]
        assert rec["chaos"]["wire_error_skips"] >= 1
        assert rec["chaos"]["mixed_cut_skips"] >= 1
        assert rec["migration"]["mismatch_refused"] is True


class TestFaultPathLint:
    """ISSUE 3 satellite (extended to the serving vertical in ISSUE 4):
    the fault/recovery paths — and the serving engine, whose slot/
    prefix-cache bookkeeping corrupts silently if an error is eaten
    mid-step — must never swallow failures. A bare ``except:``
    anywhere, or an ``except [Base]Exception:`` whose body is only
    ``pass``, in the PS wire modules, the chaos harness, or
    ``elephas_tpu/serving/`` fails this grep-lint — unless the line
    carries an explicit ``fault-lint: allow`` tag with a reason
    (narrow handlers like ``except OSError`` around close() paths stay
    allowed; it is the catch-everything-and-ignore shape that hides
    real faults)."""

    _BARE_EXCEPT = re.compile(r"^\s*except\s*:\s*(#.*)?$")
    _BROAD_EXCEPT = re.compile(
        r"^\s*except\s+(BaseException|Exception)\b.*:\s*(#.*)?$"
    )

    @staticmethod
    def _fault_path_files():
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = [os.path.join(root, "elephas_tpu", "utils", "sockets.py")]
        for pkg in ("parameter", "fault", "serving", "telemetry",
                    "fleet", "deploy"):
            files.extend(
                sorted(glob.glob(
                    os.path.join(root, "elephas_tpu", pkg, "*.py")
                ))
            )
        # ISSUE 11: the attention kernels the serving hot path now
        # runs on (Pallas flash + the tiled serving kernels) — an
        # eaten error inside a kernel wrapper silently serves wrong
        # attention; pinned by name so a rename cannot drop them
        files.append(os.path.join(
            root, "elephas_tpu", "ops", "flash_attention.py"
        ))
        files.append(os.path.join(
            root, "elephas_tpu", "ops", "flash_serving.py"
        ))
        assert len(files) > 12  # the glob must actually find the modules
        assert all(os.path.exists(f) for f in files), [
            f for f in files if not os.path.exists(f)
        ]
        # ISSUE 6: the sharded-topology module (scatter/gather, shard
        # maps, per-shard journals) is a fault path and must be under
        # this lint — pin it explicitly so a future rename cannot
        # silently drop it from the glob
        assert any(f.endswith("sharding.py") for f in files)
        # ISSUE 7: the paged-arena modules (block allocator refcounts,
        # block-table programs) corrupt KV silently if an error is
        # eaten mid-admission — pin them the same way
        assert any(f.endswith("paged_kv.py") for f in files)
        assert any(f.endswith(os.path.join("serving", "blocks.py"))
                   for f in files)
        # ISSUE 8: the speculative drafter/throttle path rolls decode
        # cursors back over rejected K/V — an eaten error there leaves
        # a slot's resident-length bookkeeping silently wrong
        assert any(
            f.endswith(os.path.join("serving", "speculative.py"))
            for f in files
        )
        # ISSUE 11: the SP prefill path lands K/V computed on another
        # mesh into the pool — a swallowed error there is a silently
        # garbage-prefilled request
        assert any(
            f.endswith(os.path.join("serving", "sp_prefill.py"))
            for f in files
        )
        # ISSUE 10: the gateway is a NETWORK fault path (half-open
        # sockets, client aborts mid-SSE) — a swallowed error there is
        # a silent dropped stream or a leaked handler; and the policy
        # orders a gang-replicated schedule, so an eaten error forks it
        assert any(
            f.endswith(os.path.join("serving", "gateway.py"))
            for f in files
        )
        assert any(
            f.endswith(os.path.join("serving", "policy.py"))
            for f in files
        )
        # ISSUE 12: the flight recorder files lifecycle records on the
        # serving hot path — an eaten error there silently drops the
        # very evidence trail explain()/the trace route promise
        assert any(
            f.endswith(os.path.join("telemetry", "flight.py"))
            for f in files
        )
        # ISSUE 14: the fleet router IS a fault path (replica death,
        # re-drive, live migration over a wire) — a swallowed error
        # there silently drops or doubles client tokens; pinned by
        # name so a rename cannot drop the modules from the glob
        for mod in ("router.py", "migration.py", "placement.py"):
            assert any(
                f.endswith(os.path.join("fleet", mod)) for f in files
            ), mod
        # ISSUE 15: the PP serving engine offloads/restores per-stage
        # K/V across a ring — an eaten error mid-offload is a silently
        # corrupted resume; pinned by name, and the serving-shaped
        # stage planner rides along (a mis-planned split serves wrong
        # depth silently)
        assert any(
            f.endswith(os.path.join("serving", "pp_engine.py"))
            for f in files
        )
        files.append(os.path.join(
            root, "elephas_tpu", "parallel", "pipeline_runner.py"
        ))
        assert os.path.exists(files[-1])
        # ISSUE 16: bubble-fill threads chunked prefill through the
        # decode ring — an eaten error mid-fill is a silently
        # half-prefilled request decoding from garbage K/V; the
        # scheduler's fill flagging and the prefix index's refcounts
        # ride the same path (a swallowed error there double-frees a
        # shared block). Pinned by name: scheduler/prefix_cache are in
        # the serving glob, but the backend guard lives in utils/ and
        # no glob covers it — it IS the fault path for a chip that
        # fails to initialise, so a rename cannot drop it either.
        assert any(
            f.endswith(os.path.join("serving", "scheduler.py"))
            for f in files
        )
        assert any(
            f.endswith(os.path.join("serving", "prefix_cache.py"))
            for f in files
        )
        files.append(os.path.join(
            root, "elephas_tpu", "utils", "backend_guard.py"
        ))
        assert os.path.exists(files[-1])
        # ISSUE 19: the quantized-KV codec quantizes on the serving
        # write path and dequantizes inside the attention tiles — a
        # swallowed error there serves silently garbage attention or
        # lands corrupt blocks in the pool; pinned by name so a rename
        # cannot drop it out of the serving glob
        assert any(
            f.endswith(os.path.join("serving", "kv_quant.py"))
            for f in files
        )
        # ISSUE 20: the continuous-deployment path IS a fault path —
        # the subscriber's poll absorbs wire failures as counted skips
        # by design, so an extra swallowed except there silently turns
        # a torn pull into an applied one; the ledger journals every
        # publication (an eaten journal error loses the generation a
        # restarted shard restores into); the rollout controller's
        # rollback IS the recovery action. Pinned by name so a rename
        # cannot drop them out of the deploy glob.
        for mod in ("versions.py", "subscriber.py", "rollout.py"):
            assert any(
                f.endswith(os.path.join("deploy", mod)) for f in files
            ), mod
        return root, files

    def test_no_bare_or_swallowed_excepts_on_fault_paths(self):
        root, files = self._fault_path_files()
        offences = []
        for path in files:
            with open(path) as f:
                lines = f.read().splitlines()
            for i, line in enumerate(lines):
                bare = self._BARE_EXCEPT.match(line)
                broad = self._BROAD_EXCEPT.match(line)
                if not bare and not broad:
                    continue
                nxt = lines[i + 1].strip() if i + 1 < len(lines) else ""
                swallows = bare or nxt == "pass" or nxt.startswith("pass ")
                if not swallows:
                    continue
                window = lines[i : min(len(lines), i + 2)]
                if any("fault-lint: allow" in w for w in window):
                    continue
                rel = os.path.relpath(path, root)
                offences.append(f"{rel}:{i + 1}: {line.strip()}")
        assert not offences, (
            "swallowed exception on a fault/recovery path (tag with "
            "'fault-lint: allow <reason>' if truly intended):\n"
            + "\n".join(offences)
        )


class TestMetricDocDrift:
    """ISSUE 13 satellite: every ``elephas_*`` metric family name
    registered anywhere in ``elephas_tpu/`` must appear in the
    docs/API.md metric catalog — scrape-surface drift (a renamed gauge
    whose docs row still shows the old name, a new counter nobody
    documented) is fixed at the SOURCE by failing this lint. The docs
    may use brace shorthand (``elephas_serving_slo_{met,missed}_total``
    expands to both names); a deliberately-undocumented name carries a
    ``metric-doc: allow`` tag with its reason on/near the literal.
    This lint caught two real drifts on landing: the undocumented
    ``elephas_ps_client_shard_pauses_total`` and a catalog row still
    naming ``elephas_serving_blocks_total`` (renamed
    ``elephas_serving_kv_blocks`` in PR 12)."""

    # a metric name: elephas_<subsystem>_<rest> — the second
    # underscore-separated segment requirement excludes the package
    # name "elephas_tpu" appearing as a plain string
    _METRIC_LITERAL = re.compile(r'"(elephas_[a-z0-9]+_[a-z0-9_]+)"')
    # docs tokens, brace shorthand included
    _DOC_TOKEN = re.compile(r"elephas_[a-z0-9_{},]*[a-z0-9_}]")

    @staticmethod
    def _expand_braces(token: str) -> set:
        """Every name a docs token can denote. A brace group is
        either NAME shorthand (``a_{b,c}_total`` -> a_b_total,
        a_c_total) or a LABEL selector (``a_total{worker}``), and a
        token may carry both — so each group yields its alternative
        substitutions AND the truncation at the brace. Bogus
        concatenations from substituting a label selector never
        collide with a real registered name."""
        out: set = set()

        def rec(t: str) -> None:
            m = re.search(r"\{([^{}]*)\}", t)
            if m is None:
                out.add(t)
                return
            out.add(t[: m.start()])  # label-selector reading
            for alt in m.group(1).split(","):
                rec(t[: m.start()] + alt + t[m.end():])

        rec(token)
        return out

    def _documented_names(self, root) -> set:
        with open(os.path.join(root, "docs", "API.md")) as f:
            text = f.read()
        names = set()
        for token in self._DOC_TOKEN.findall(text):
            names.update(self._expand_braces(token))
            # a label selector with `=` inside (`{engine=,kernel=}`)
            # truncates the token match itself — the bare name before
            # the brace is still the documented name
            names.add(token.split("{", 1)[0])
        return names

    def _registered_names(self, root):
        """``(name, file:line)`` for every metric-name string literal
        in the package, minus ``metric-doc: allow``-tagged lines."""
        out = []
        for path in sorted(glob.glob(
            os.path.join(root, "elephas_tpu", "**", "*.py"),
            recursive=True,
        )):
            with open(path) as f:
                lines = f.read().splitlines()
            for i, line in enumerate(lines):
                for m in self._METRIC_LITERAL.finditer(line):
                    window = lines[max(0, i - 1): min(len(lines), i + 2)]
                    if any("metric-doc: allow" in w for w in window):
                        continue
                    rel = os.path.relpath(path, root)
                    out.append((m.group(1), f"{rel}:{i + 1}"))
        return out

    def test_every_registered_metric_is_documented(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        documented = self._documented_names(root)
        registered = self._registered_names(root)
        # the scan must actually see the catalog and the registrations
        assert len(documented) > 30 and len(registered) > 30
        missing = sorted({
            f"{name} ({where})"
            for name, where in registered if name not in documented
        })
        assert not missing, (
            "metric family name(s) registered in elephas_tpu/ but "
            "absent from the docs/API.md catalog — document them (or "
            "tag the registration with 'metric-doc: allow <reason>'):"
            "\n" + "\n".join(missing)
        )

    def test_brace_expansion(self):
        assert {
            "elephas_serving_slo_met_total",
            "elephas_serving_slo_missed_total",
        } <= self._expand_braces("elephas_serving_slo_{met,missed}_total")
        assert self._expand_braces("elephas_fleet_up") == {
            "elephas_fleet_up"
        }
        # shorthand + label selector on one token: both names resolve
        assert {
            "elephas_prefix_cache_hits_total",
            "elephas_prefix_cache_misses_total",
        } <= self._expand_braces(
            "elephas_prefix_cache_{hits,misses}_total{cache}"
        )


class TestTelemetryWallClockLint:
    """ISSUE 5 satellite: the telemetry determinism contract says wall
    time is EXPORT-ONLY — control paths order themselves by logical
    clocks. An ad-hoc ``time.time()`` creeping into the serving or PS
    modules is exactly how a wall-clock comparison ends up steering a
    gang-replicated schedule (processes disagree, schedules fork, the
    SPMD contract breaks silently). ``elephas_tpu/telemetry/`` is the
    one place wall capture belongs (it only exports it); everywhere
    else on the serving/PS/fault paths an intentional use must carry a
    ``telemetry-lint: allow`` tag with its reason. (``time.monotonic``
    / ``perf_counter`` for local durations stay allowed — they never
    cross processes.)"""

    _WALL_CLOCK = re.compile(r"(?<![\w.])time\.time\(")

    def test_no_adhoc_wall_clock_on_control_paths(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = [os.path.join(root, "elephas_tpu", "utils", "sockets.py")]
        # ISSUE 14: the fleet router's placement/re-drive decisions
        # are deterministic by contract — wall clock there would fork
        # what identical processes derive from identical snapshots
        for pkg in ("parameter", "fault", "serving", "fleet",
                    "deploy"):
            files.extend(
                sorted(glob.glob(
                    os.path.join(root, "elephas_tpu", pkg, "*.py")
                ))
            )
        # ISSUE 20: deployment decisions (apply-or-skip, canary
        # promote/rollback windows) run on version compares and
        # evaluation counts by contract — wall clock in them would
        # make replicas disagree about which generation to serve;
        # pinned by name so a rename cannot drop them from the glob
        for mod in ("versions.py", "subscriber.py", "rollout.py"):
            assert any(
                f.endswith(os.path.join("deploy", mod)) for f in files
            ), mod
        assert any(
            f.endswith(os.path.join("fleet", "router.py"))
            for f in files
        )
        # ISSUE 11: the attention kernels run INSIDE gang-replicated
        # programs — wall clock there would fork compiled behavior
        # across processes; pinned by name like the serving modules
        files.append(os.path.join(
            root, "elephas_tpu", "ops", "flash_attention.py"
        ))
        files.append(os.path.join(
            root, "elephas_tpu", "ops", "flash_serving.py"
        ))
        # ISSUE 15: the PP wave schedule and the serving stage planner
        # are pure functions of the submission sequence — wall clock
        # in either would fork the waves gang processes must derive
        # identically; pinned by name like the other serving modules
        files.append(os.path.join(
            root, "elephas_tpu", "parallel", "pipeline_runner.py"
        ))
        assert any(
            f.endswith(os.path.join("serving", "pp_engine.py"))
            for f in files
        )
        # ISSUE 16: bubble-fill admission (the fill flag) and the
        # prefix index's match/commit decisions order a gang-
        # replicated schedule — wall clock in either forks which
        # requests fill vs prefill across processes; pinned by name
        assert any(
            f.endswith(os.path.join("serving", "scheduler.py"))
            for f in files
        )
        assert any(
            f.endswith(os.path.join("serving", "prefix_cache.py"))
            for f in files
        )
        assert len(files) > 9
        assert all(os.path.exists(f) for f in files), [
            f for f in files if not os.path.exists(f)
        ]
        # ISSUE 7: the paged scheduler/allocator order a gang-
        # replicated schedule — wall clock there forks SPMD processes
        assert any(f.endswith("paged_kv.py") for f in files)
        assert any(f.endswith(os.path.join("serving", "blocks.py"))
                   for f in files)
        # ISSUE 8: drafting/throttling decisions replicate across the
        # gang — wall clock in them would fork the schedule the same way
        assert any(
            f.endswith(os.path.join("serving", "speculative.py"))
            for f in files
        )
        # ISSUE 10: the policy's fair-share/EDF/aging order IS the
        # schedule — it runs on logical clocks (waves, token counts,
        # declared deadline classes) by contract, and the gateway must
        # not smuggle wall time into submit ordering either
        assert any(
            f.endswith(os.path.join("serving", "policy.py"))
            for f in files
        )
        # ISSUE 11: the SP prefill module feeds a gang-replicated
        # landing path the same way
        assert any(
            f.endswith(os.path.join("serving", "sp_prefill.py"))
            for f in files
        )
        # ISSUE 19: quantize-on-write runs INSIDE gang-replicated
        # serving programs — wall clock in the codec would fork
        # compiled behavior across processes; pinned by name
        assert any(
            f.endswith(os.path.join("serving", "kv_quant.py"))
            for f in files
        )
        assert any(
            f.endswith(os.path.join("serving", "gateway.py"))
            for f in files
        )
        # ISSUE 12: the flight recorder and the registry's exemplar
        # slots store PER-REQUEST evidence — a wall-clock capture
        # there would smuggle non-deterministic values into records
        # gang processes are supposed to reconstruct identically
        # (wall time belongs to the event tracer's export path only);
        # pinned by name, like the serving modules
        files.append(os.path.join(
            root, "elephas_tpu", "telemetry", "flight.py"
        ))
        files.append(os.path.join(
            root, "elephas_tpu", "telemetry", "registry.py"
        ))
        # ISSUE 13: the watchdog/aggregator/merge layer evaluates and
        # re-renders observability state — its cadence is the
        # caller's; an ad-hoc wall-clock comparison inside it would be
        # exactly the telemetry-drives-behavior leak the contract
        # bans. Pinned by name like the serving modules.
        for mod in ("watch.py", "aggregate.py", "merge.py"):
            files.append(os.path.join(
                root, "elephas_tpu", "telemetry", mod
            ))
        assert all(os.path.exists(f) for f in files[-5:])
        offences = []
        for path in files:
            with open(path) as f:
                lines = f.read().splitlines()
            for i, line in enumerate(lines):
                if not self._WALL_CLOCK.search(line):
                    continue
                window = lines[max(0, i - 1): min(len(lines), i + 2)]
                if any("telemetry-lint: allow" in w for w in window):
                    continue
                rel = os.path.relpath(path, root)
                offences.append(f"{rel}:{i + 1}: {line.strip()}")
        assert not offences, (
            "ad-hoc wall clock on a serving/PS control path — route it "
            "through elephas_tpu.telemetry (events capture wall time "
            "export-only) or tag the line with "
            "'telemetry-lint: allow <reason>':\n" + "\n".join(offences)
        )

    _GLOBAL_TELEMETRY = re.compile(
        r"telemetry\.(tracer|registry|emit|trace_span)\("
    )

    def test_emission_sites_capture_telemetry_at_construction(self):
        """ISSUE 12 satellite: every per-request emission site must be
        null-mode-safe BY CONSTRUCTION — components capture the
        tracer/registry once, in ``__init__`` (where the captured
        object is itself the null singleton under null mode), and
        record through the captured attribute forever after. A
        module-level ``telemetry.emit(...)`` / ``telemetry.tracer()``
        creeping into a serving method re-resolves null mode per call:
        flipping the global flag mid-serve would then fork what an
        engine records from what it was built to record (the
        on-vs-null bench comparison silently stops measuring the
        configured engine). Grep-lint: those calls may appear in
        ``serving/`` only inside ``__init__`` (tag genuinely intended
        exceptions with ``telemetry-lint: allow``)."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = sorted(glob.glob(
            os.path.join(root, "elephas_tpu", "serving", "*.py")
        ))
        assert len(files) > 8
        # ISSUE 13: the new fleet-observability modules carry the same
        # capture-at-construction contract — a Watchdog/FleetScraper
        # that re-resolved null mode per evaluate()/poll() would fork
        # what it was built to record; pinned by name so a rename
        # cannot drop them
        for mod in ("watch.py", "aggregate.py", "merge.py"):
            files.append(os.path.join(
                root, "elephas_tpu", "telemetry", mod
            ))
        assert all(os.path.exists(f) for f in files[-3:])
        # ISSUE 14: the fleet modules carry the same capture-at-
        # construction contract (the router's emission sites record
        # through attributes captured in __init__)
        files.extend(sorted(glob.glob(
            os.path.join(root, "elephas_tpu", "fleet", "*.py")
        )))
        assert any(
            f.endswith(os.path.join("fleet", "router.py"))
            for f in files
        )
        # ISSUE 15: the PP engine's per-window telemetry (bubble
        # gauge, serve.wave spans, jit.compile watching) records
        # through attributes captured in __init__ like every other
        # serving module; pinned by name so a rename cannot drop it
        assert any(
            f.endswith(os.path.join("serving", "pp_engine.py"))
            for f in files
        )
        # ISSUE 16: bubble-fill telemetry (fill counters, fill_admit/
        # fill_complete/fill_demote spans) and the prefix index's
        # hit/miss counters record through captured attributes like
        # every other serving emission site; pinned by name
        assert any(
            f.endswith(os.path.join("serving", "scheduler.py"))
            for f in files
        )
        assert any(
            f.endswith(os.path.join("serving", "prefix_cache.py"))
            for f in files
        )
        # ISSUE 20: the deploy subsystem's emission sites (pull/apply
        # counters, staleness gauge, canary outcome counters, ledger
        # version gauge) record through attributes captured in
        # __init__ like every serving module — a subscriber that
        # re-resolved null mode per poll would fork what it was built
        # to record; pinned by name
        files.extend(sorted(glob.glob(
            os.path.join(root, "elephas_tpu", "deploy", "*.py")
        )))
        for mod in ("versions.py", "subscriber.py", "rollout.py"):
            assert any(
                f.endswith(os.path.join("deploy", mod)) for f in files
            ), mod
        offences = []
        for path in files:
            with open(path) as f:
                lines = f.read().splitlines()
            # indentation-aware __init__ tracking: a nested helper def
            # inside __init__ (deeper indent) does not end it; the
            # next def at or above __init__'s own indent does
            init_indent = None
            for i, line in enumerate(lines):
                stripped = line.strip()
                if stripped.startswith(("def ", "async def ")):
                    indent = len(line) - len(line.lstrip())
                    if stripped.startswith("def __init__"):
                        init_indent = indent
                    elif init_indent is not None \
                            and indent <= init_indent:
                        init_indent = None
                if not self._GLOBAL_TELEMETRY.search(line):
                    continue
                if init_indent is not None:
                    continue
                window = lines[max(0, i - 1): min(len(lines), i + 2)]
                if any("telemetry-lint: allow" in w for w in window):
                    continue
                rel = os.path.relpath(path, root)
                offences.append(f"{rel}:{i + 1}: {stripped}")
        assert not offences, (
            "per-request emission through the GLOBAL telemetry "
            "resolvers outside __init__ — capture registry()/tracer() "
            "at construction and record through the captured "
            "attribute (or tag with 'telemetry-lint: allow <reason>'):"
            "\n" + "\n".join(offences)
        )


class TestFlashAttentionLint:
    """ISSUE 11 satellite: the serving hot path runs tiled
    online-softmax attention (``ops/flash_serving.py``) — a
    full-materialized score matrix creeping back into ``serving/`` is
    exactly how the O(T²) memory term the flash graft removed returns
    silently (it would still be CORRECT, so no test would catch it;
    only the TTFT/memory regression would, months later). This
    grep-lint fails any attention-score einsum in ``elephas_tpu/
    serving/`` — an ``jnp.einsum`` whose output is a ``[.., query,
    key]`` score matrix (``->bhs`` / ``->bhcs`` / ``->bhij`` and their
    att@V consumers) — unless the line carries an explicit
    ``flash-lint: allow`` tag with a reason. The naive-fallback path
    (the parity oracle ``attention="naive"`` keeps selectable) is
    tagged; new untagged materializations fail."""

    # score-matrix producers and their att@V consumers: the shapes the
    # naive kernels materialize ([B,H,(C,)S] / [B,H,S,S] scores).
    # \s* spans newlines — the einsum spec often sits on its own line.
    _SCORE_EINSUM = re.compile(
        r'jnp\.einsum\(\s*"[^"]*->(?:bhs|bhcs|bhij)"'
        r'|jnp\.einsum\(\s*"(?:bhs|bhcs|bhij)[^"]*->'
    )

    def test_no_untagged_materialized_attention_in_serving(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = sorted(glob.glob(
            os.path.join(root, "elephas_tpu", "serving", "*.py")
        ))
        # ISSUE 14: fleet modules sit on the serving hot path too —
        # nothing there should ever materialize a score matrix
        files.extend(sorted(glob.glob(
            os.path.join(root, "elephas_tpu", "fleet", "*.py")
        )))
        assert len(files) > 12
        offences = []
        for path in files:
            with open(path) as f:
                text = f.read()
            lines = text.splitlines()
            for match in self._SCORE_EINSUM.finditer(text):
                i = text.count("\n", 0, match.start())  # 0-based line
                window = lines[max(0, i - 2): min(len(lines), i + 3)]
                if any("flash-lint: allow" in w for w in window):
                    continue
                rel = os.path.relpath(path, root)
                offences.append(f"{rel}:{i + 1}: {lines[i].strip()}")
        assert not offences, (
            "full-materialized attention einsum in serving/ outside "
            "the tagged naive-fallback path — route it through "
            "ops/flash_serving (or tag the line with 'flash-lint: "
            "allow <reason>'):\n" + "\n".join(offences)
        )


class TestBackendGuard:
    """No path may let a run without a working chip look like a pass.
    JAX itself picks the CPU silently when no accelerator answers, so
    the guard's job is the opposite of a fallback: ask directly, and
    raise naming what was found."""

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def test_cpu_env_alone_selects_cpu_in_subprocess(self):
        """``JAX_PLATFORMS=cpu`` and nothing else yields the CPU in a
        child — and the child never maps the accelerator's runtime, so
        it neither needs nor disturbs a chip its parent holds (the
        only kind of child bench.py starts)."""
        env = {
            k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_NUM_CPU_DEVICES")
        }
        env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax")
        proc = subprocess.run(
            [sys.executable, "-c",
             "from elephas_tpu.utils.backend_guard import device_record;"
             "import jax.numpy as jnp; jnp.ones(4).sum().block_until_ready();"
             "print('DEVICE=%r' % device_record());"
             "print('LIBTPU_MAPPED=%s' % "
             "('libtpu' in open('/proc/self/maps').read()))"],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=self.REPO,
        )
        assert proc.returncode == 0, proc.stderr[-1500:]
        assert (
            "DEVICE={'platform': 'cpu', 'kind': 'cpu', 'count': 1}"
            in proc.stdout
        )
        assert "LIBTPU_MAPPED=False" in proc.stdout

    def test_failing_probe_propagates(self, monkeypatch):
        """A backend that dies at initialisation is the caller's error
        to see: no thread, no timeout, no switch to the CPU."""
        import jax

        from elephas_tpu.utils import backend_guard

        def dying():
            raise RuntimeError(
                "Unable to initialize backend 'tpu': "
                "make_c_api_client failed: INTERNAL"
            )

        monkeypatch.setattr(jax, "devices", dying)
        with pytest.raises(RuntimeError, match="make_c_api_client"):
            backend_guard.require_accelerator()
        monkeypatch.undo()
        assert jax.config.jax_platforms == "cpu"  # conftest's, untouched
        assert backend_guard.device_record()["count"] == 8

    def test_asking_for_the_chip_on_cpu_raises_naming_the_platform(self):
        from elephas_tpu.utils import backend_guard

        for want in (None, "tpu"):
            with pytest.raises(RuntimeError) as ei:
                backend_guard.require_accelerator(want)
            assert "found platform 'cpu'" in str(ei.value)
            assert "8 x cpu" in str(ei.value)
        assert backend_guard.require_accelerator("cpu")["platform"] == "cpu"

    def test_bench_without_a_chip_prints_no_record(self):
        """bench.py, not told ``JAX_PLATFORMS=cpu``, on a machine where
        JAX found only the CPU: non-zero exit, no JSON line — never the
        tiny preset's CPU numbers under a device metric's name."""
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env.update(KERAS_BACKEND="jax")
        proc = subprocess.run(
            [sys.executable, "-c",
             "import sys; sys.argv = ['bench.py', '--no-baseline'];"
             "from elephas_tpu.utils import backend_guard;"
             "backend_guard.device_record = lambda: "
             "{'platform': 'cpu', 'kind': 'cpu', 'count': 1};"
             "import bench; bench.main()"],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=self.REPO,
        )
        assert proc.returncode != 0, proc.stdout[-500:]
        assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]
        assert "found platform 'cpu'" in proc.stderr

    def test_unknown_accelerator_kind_is_an_error(self, monkeypatch):
        """A NaN peak would disarm the implied-MFU gate: an accelerator
        missing from the peaks table raises; the CPU stays NaN."""
        import jax

        class Dev:
            platform, device_kind = "tpu", "TPU v99"

        peak, kind = bench.chip_peak_flops()
        assert peak != peak and kind == "cpu"
        monkeypatch.setattr(jax, "devices", lambda: [Dev()])
        with pytest.raises(RuntimeError, match="tpu v99"):
            bench.chip_peak_flops()
        Dev.device_kind = "TPU v5 lite"
        assert bench.chip_peak_flops() == (197e12, "tpu v5 lite")
