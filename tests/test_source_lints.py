"""Source lints: greps over the package's own files that fail the
suite when a rule of the code base is broken at its source: no
swallowed exception on a fault path, every registered metric family
in the docs' catalog, no ad-hoc wall clock on a control path and
telemetry captured at construction, no materialized attention score
matrix in the serving modules, no sparse-LM model file that reaches into
another.
"""

import ast
import glob
import os
import re


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


class TestLmModelFilesLint:
    """ISSUE 47: a sparse LM's model file lists its layers and imports no
    other model's, and every layer class is defined at module scope of
    ``lm_blocks`` or ``lm_mixers``. Before, the shared layers were classes
    made inside a function of the first model's file, the later models
    fetched them from it by string, and each ``model_config`` PR edited
    an earlier model's file: an import of one model module by another,
    or a class statement inside a function, in any of these files is
    that shape coming back. (``models/transformer.py`` and
    ``models/switch.py`` still build three served layers inside
    functions; they are outside this lint, a debt ``ROADMAP.md`` names
    under D1.)"""

    MODEL_MODULES = ("qwen3_next", "deepseek_v3", "smallthinker",
                     "nemotron_h", "laguna")
    BLOCK_MODULES = ("lm_blocks", "lm_mixers")

    def test_no_model_file_imports_another_or_nests_a_class(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        offences, classes = [], 0
        for module in self.MODEL_MODULES + self.BLOCK_MODULES:
            rel = os.path.join("elephas_tpu", "models", module + ".py")
            with open(os.path.join(root, rel)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                imported = []
                if isinstance(node, ast.Import):
                    imported = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    imported = [f"{node.module}.{a.name}" for a in node.names]
                for name in imported:
                    if set(name.split(".")) & set(self.MODEL_MODULES):
                        offences.append(f"{rel}:{node.lineno}: imports {name}")
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    offences.extend(
                        f"{rel}:{inner.lineno}: class {inner.name} inside "
                        f"{node.name}()" for inner in ast.walk(node)
                        if isinstance(inner, ast.ClassDef))
            if module in self.MODEL_MODULES:
                offences.extend(
                    f"{rel}:{node.lineno}: class {node.name} in a model file"
                    for node in tree.body if isinstance(node, ast.ClassDef))
            classes += sum(isinstance(n, ast.ClassDef) for n in tree.body)
        assert classes >= 15  # the parse found the block modules' layers
        assert not offences, (
            "a sparse LM's model file imports another model's, or a layer "
            "class is not at module scope of lm_blocks / lm_mixers:\n"
            + "\n".join(offences))
