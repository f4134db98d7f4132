"""Prefix-reuse KV cache + chunked prefill (ISSUE 4 tentpole).

The acceptance contract: a prefix-hit request's tokens are EXACT
against the same request served cold (including on a TP mesh — the
slot-to-slot copy crosses the sharded slot axis); chunked prefill is
token-exact against the unchunked wave while in-flight requests keep
emitting between chunks; eviction under slot pressure is
refcount-correct (a donor pinned by the current admission wave is never
evicted out from under its copy); and the compiled shape set stays
CLOSED — one decode program, one copy program, bounded chunk widths —
across mixed multi-wave workloads. What a hit or a chunk saves in
time to first token is a time on a device and has no benchmark cell
yet.
"""

import logging

import numpy as np
import pytest


@pytest.fixture(scope="module")
def lm(serving_lm):
    """The session-trained serving LM (see conftest.serving_lm)."""
    return serving_lm


SHARED = [2, 3, 4, 5, 2, 3, 4, 5]  # the "system prompt"


def _one_shot(lm, prompt, steps):
    from elephas_tpu.models import generate

    return generate(
        lm, np.asarray(prompt, np.int32)[None], steps=steps,
        kv_cache=True,
    )[0]


# -- prefix cache: host-side radix index (pure unit tests) -------------


class TestPrefixCacheIndex:
    def _cache(self):
        from elephas_tpu.serving import PrefixCache

        return PrefixCache()

    def test_longest_prefix_match_caps_below_prompt(self):
        c = self._cache()
        c.insert(0, [1, 2, 3, 4])
        # full-coverage prompt: at least one suffix token must remain
        assert c.match([1, 2, 3, 4]) == (0, 3)
        assert c.match([1, 2, 9, 9, 9]) == (0, 2)  # diverges after [1, 2]
        assert c.match([7, 8, 9]) == (None, 0)

    def test_match_is_pure_counters_commit_only_on_admission(self):
        """The admit() loop probes the queue head EVERY step while
        blocked: match() must not move counters or LRU rank — only
        commit_hit()/record_miss() (called when an admission lands)
        do."""
        c = self._cache()
        c.insert(0, [1, 2, 3])
        for _ in range(5):  # five blocked probes
            assert c.match([1, 2, 9]) == (0, 2)
        assert c.stats()["hits"] == 0 and c.stats()["misses"] == 0
        c.commit_hit(0, 2)
        c.record_miss()
        st = c.stats()
        assert st["hits"] == 1 and st["misses"] == 1
        assert st["reused_tokens"] == 2

    def test_match_prefers_most_recent_then_slot_id(self):
        c = self._cache()
        c.insert(0, [1, 2, 3])
        c.insert(1, [1, 2, 3])
        assert c.match([1, 2, 9])[0] == 1  # slot 1 inserted later (MRU)
        c.commit_hit(0, 2)  # an admission reused slot 0 -> now MRU
        assert c.match([1, 2, 9])[0] == 0

    def test_eviction_skips_leased_and_pinned(self):
        c = self._cache()
        c.insert(0, [1, 2])
        c.insert(1, [3, 4])
        assert c.evict_lru() is None  # both leased (still occupied)
        c.release(0)
        c.release(1)
        c.pin(0)  # the wave holds slot 0 as a donor
        assert c.evict_lru() == 1  # LRU is 0, but it's pinned
        c.unpin(0)
        assert c.evict_lru() == 0
        assert c.evict_lru() is None
        assert c.stats()["entries"] == 0

    def test_remove_prunes_trie(self):
        c = self._cache()
        c.insert(0, [1, 2, 3])
        c.remove(0)
        assert not c._root.children  # no leaked nodes
        assert c.match([1, 2, 3, 4]) == (None, 0)

    def test_deterministic_logical_clock(self):
        """No wall-clock anywhere: two caches driven by the same
        operation sequence make identical decisions (the gang/SPMD
        contract)."""

        def drive(c):
            out = []
            c.insert(0, [1, 2, 3]); c.release(0)
            c.insert(1, [1, 2, 4]); c.release(1)
            s, m = c.match([1, 2, 4, 7])
            c.commit_hit(s, m)
            out.append((s, m))
            out.append(c.evict_lru())
            out.append(c.evict_lru())
            return out

        assert drive(self._cache()) == drive(self._cache())


# -- engine: prefix-hit exactness --------------------------------------


def test_prefix_hit_tokens_exact_vs_cold(lm):
    """The tentpole claim: a request admitted via donor-copy + suffix
    prefill produces EXACTLY the tokens of the same request served
    cold (temperature 0) — and matches one-shot generate()."""
    from elephas_tpu.serving import InferenceEngine

    prompt_b = SHARED + [4, 5, 3]
    cold = InferenceEngine(lm, num_slots=4)
    out_cold = cold.run([(prompt_b, 7)])

    warm = InferenceEngine(lm, num_slots=4, prefix_cache=True)
    warm.run([(SHARED + [2, 3], 7)])  # seeds the donor
    rb = warm.submit(prompt_b, 7)
    out_warm = warm.run()
    assert rb.reused_tokens == len(SHARED), rb.reused_tokens
    cache = warm.scheduler.prefix_cache.stats()
    assert cache["hits"] >= 1 and cache["reused_tokens"] >= len(SHARED)
    np.testing.assert_array_equal(
        out_warm[rb.rid], list(out_cold.values())[0]
    )
    np.testing.assert_array_equal(
        out_warm[rb.rid], _one_shot(lm, prompt_b, 7)
    )
    # resubmitting the identical prompt reuses p-1 tokens (one suffix
    # token must remain — its logits seed the first sample)
    rc = warm.submit(prompt_b, 7)
    out3 = warm.run()
    assert rc.reused_tokens == len(prompt_b) - 1
    np.testing.assert_array_equal(out3[rc.rid], out_warm[rb.rid])


def test_prefix_hit_exact_on_tp_mesh(lm):
    """The copy program's donor gather crosses the mesh-sharded slot
    axis; heads ride the model axis — tokens must still be exact."""
    from elephas_tpu import SparkModel

    sm = SparkModel(lm, model_parallel=2)
    engine = sm.serve(num_slots=4, prefix_cache=True)
    engine.run([(SHARED + [2, 3], 6)])
    rb = engine.submit(SHARED + [5, 2], 6)
    out = engine.run()
    assert rb.reused_tokens == len(SHARED)
    np.testing.assert_array_equal(
        out[rb.rid], _one_shot(lm, SHARED + [5, 2], 6)
    )


# -- engine: eviction under slot pressure ------------------------------


def test_lru_donor_eviction_under_slot_pressure(lm):
    """Donors are evicted LRU when admissions outnumber free slots; the
    surviving donor is the most recently used one."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2, prefix_cache=True)
    ra = engine.submit([2, 3, 4], 3)
    rb = engine.submit([5, 4, 3], 3)
    engine.run()
    cache = engine.scheduler.prefix_cache
    assert len(cache.donor_slots) == 2  # both slots resident donors
    # touch A's prefix (a hit bumps its recency), then force pressure:
    # TWO fresh unrelated admissions need both slots — the LRU donor
    # (B's) must go first
    rc = engine.submit([2, 3, 4, 4], 3)  # hits A's entry
    rd = engine.submit([6, 6, 6], 3)
    re_ = engine.submit([7, 7, 7], 3)
    engine.run()
    assert rc.reused_tokens == 3
    assert cache.stats()["evictions"] >= 2
    # every request still token-exact while donors churned
    for r, p in ((rc, [2, 3, 4, 4]), (rd, [6, 6, 6]), (re_, [7, 7, 7])):
        np.testing.assert_array_equal(
            np.asarray(r.full_sequence), _one_shot(lm, p, 3)
        )


def test_single_slot_pinned_donor_falls_back_cold(lm):
    """Refcount correctness, the nasty corner: with ONE slot, the only
    donor is also the only evictable slot. The wave pins it for reuse,
    discovers no slot remains, and must fall back to a COLD admission
    (evicting the pinned-then-released donor) instead of livelocking —
    tokens still exact."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=1, prefix_cache=True)
    engine.run([(SHARED, 4)])
    cache = engine.scheduler.prefix_cache
    assert cache.donor_slots == [0]
    r2 = engine.submit(SHARED + [2, 3], 5)
    out = engine.run()
    assert r2.reused_tokens == 0  # cold fallback, not a hang
    assert cache.stats()["evictions"] == 1
    # the dropped-donor fallback is accounted as a MISS, not a hit
    assert cache.stats()["hits"] == 0 and cache.stats()["misses"] == 2
    np.testing.assert_array_equal(
        out[r2.rid], _one_shot(lm, SHARED + [2, 3], 5)
    )
    # no refcount leak: the new entry is evictable again
    assert cache.donor_slots == [0]
    assert cache.entry(0).pins == 0


def test_slots_all_return_to_free_list_when_cache_off(lm):
    """prefix_cache defaults OFF: reclaim still frees every slot (the
    PR-1 invariant other tests pin)."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2)
    engine.run([(SHARED, 3), ([2, 3], 3), ([4, 5, 2], 3)])
    assert sorted(engine.scheduler._free) == [0, 1]
    assert engine.scheduler.prefix_cache is None


# -- engine: chunked prefill -------------------------------------------


def test_chunked_prefill_tokens_exact_vs_unchunked(lm):
    """A long-prompt + mixed workload decoded with prefill_chunk=4 is
    token-identical to the unchunked engine at temperature 0."""
    from elephas_tpu.serving import InferenceEngine

    workload = [
        (SHARED + SHARED + [2, 3, 4], 6),  # 19-token prompt, 5 chunks
        ([4, 5], 6),
        (SHARED, 6),
    ]
    plain = InferenceEngine(lm, num_slots=4)
    chunked = InferenceEngine(lm, num_slots=4, prefill_chunk=4)
    out_p = plain.run(list(workload))
    out_c = chunked.run(list(workload))
    for rid_p, rid_c in zip(sorted(out_p), sorted(out_c)):
        np.testing.assert_array_equal(out_p[rid_p], out_c[rid_c])


def test_chunked_prefill_interleaves_with_decode(lm):
    """The structural latency property (no timing): while a long
    prompt's prefill is mid-flight, ALREADY-DECODING requests receive
    tokens in the same step()s — the blocking engine instead finishes
    the whole prefill before any of them advance."""
    from elephas_tpu.serving import InferenceEngine

    long_prompt = SHARED + SHARED + [2, 3, 4]  # 19 tokens, chunk=4
    engine = InferenceEngine(lm, num_slots=2, prefill_chunk=4)
    short = engine.submit([2, 3], 12)
    engine.step()  # short admitted + first decode window
    tokens_before = len(short.tokens)
    late = engine.submit(long_prompt, 4)
    interleaved_steps = 0
    while not late.tokens:  # long prompt still prefilling
        n0 = len(short.tokens)
        engine.step()
        if len(short.tokens) > n0 and not late.done:
            interleaved_steps += 1
        assert interleaved_steps < 100, "long prefill never finished"
    # the short request decoded DURING the long prefill (>= 2 budgeted
    # chunk steps of 4 tokens each for a 19-token prompt)
    assert interleaved_steps >= 2, interleaved_steps
    assert len(short.tokens) > tokens_before
    engine.run()
    np.testing.assert_array_equal(
        np.asarray(short.full_sequence), _one_shot(lm, [2, 3], 12)
    )
    np.testing.assert_array_equal(
        np.asarray(late.full_sequence), _one_shot(lm, long_prompt, 4)
    )


def test_prefill_budget_caps_concurrent_long_arrivals(lm):
    """The budget bounds TOTAL prefill tokens per step: two long
    prompts arriving together advance one budget's worth per step
    (lowest slot first), not one chunk EACH — otherwise in-flight
    latency would scale with the number of concurrent arrivals."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=4, prefill_chunk=4)
    long_a = SHARED + SHARED + [2, 3, 4]  # 19 tokens
    long_b = SHARED + SHARED + [5, 4]  # 18 tokens
    ra = engine.submit(long_a, 3)
    rb = engine.submit(long_b, 3)
    engine.step()  # one budget (4 tokens) spent on slot 0 only
    progress = {s: p for s, (_a, p) in engine._prefilling.items()}
    assert progress[ra.slot] == 4 and progress[rb.slot] == 0, progress
    out = engine.run()
    np.testing.assert_array_equal(out[ra.rid], _one_shot(lm, long_a, 3))
    np.testing.assert_array_equal(out[rb.rid], _one_shot(lm, long_b, 3))
    # raising the budget admits both slots into one step's work
    engine2 = InferenceEngine(
        lm, num_slots=4, prefill_chunk=4, prefill_budget=8,
    )
    r2a = engine2.submit(long_a, 3)
    r2b = engine2.submit(long_b, 3)
    engine2.step()
    progress2 = {s: p for s, (_a, p) in engine2._prefilling.items()}
    assert progress2[r2a.slot] == 4 and progress2[r2b.slot] == 4
    out2 = engine2.run()
    np.testing.assert_array_equal(out2[r2a.rid], _one_shot(lm, long_a, 3))


def test_chunked_plus_prefix_cache_compose(lm):
    """Both knobs together: donor copy + budgeted suffix chunks, still
    token-exact, still reusing the prefix."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, prefix_cache=True, prefill_chunk=4,
    )
    engine.run([(SHARED + [2, 3], 6)])
    rb = engine.submit(SHARED + [4, 5, 2], 6)
    out = engine.run()
    assert rb.reused_tokens == len(SHARED)
    np.testing.assert_array_equal(
        out[rb.rid], _one_shot(lm, SHARED + [4, 5, 2], 6)
    )


def test_refresh_weights_flushes_stale_donors(lm):
    """Donor K/V computed under old weights must NOT survive a weight
    refresh — a donor copy would silently splice stale rows into a
    new-weights request. After refresh: cache empty, donor slots back
    on the free list, and a prefix-sharing request is served COLD yet
    token-exact under the CURRENT weights."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2, prefix_cache=True)
    engine.run([(SHARED + [2, 3], 4)])
    cache = engine.scheduler.prefix_cache
    assert cache.stats()["entries"] == 1
    head = next(v for v in lm.variables if "lm_head" in v.path
                and "kernel" in v.path)
    orig = np.array(head.value)
    try:
        head.assign(-orig)  # "further training": logits flip
        engine.refresh_weights()
        assert cache.stats()["entries"] == 0
        assert sorted(engine.scheduler._free) == [0, 1]  # donors freed
        r2 = engine.submit(SHARED + [4, 5], 4)
        out = engine.run()
        assert r2.reused_tokens == 0  # no stale reuse
        # exact against one-shot generate under the NEW weights
        np.testing.assert_array_equal(
            out[r2.rid], _one_shot(lm, SHARED + [4, 5], 4)
        )
    finally:
        head.assign(orig)


def test_refresh_midway_through_chunked_prefill_never_donates(lm):
    """A prefill straddling refresh_weights() holds rows from BOTH
    weight generations: it must finish decoding but never register as
    a donor — otherwise the stale-splice the flush prevents returns
    through the side door when it finalizes into the flushed cache."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, prefix_cache=True, prefill_chunk=4,
    )
    long_prompt = SHARED + SHARED + [2, 3, 4]  # 19 tokens, 5 chunks
    r1 = engine.submit(long_prompt, 3)
    engine.step()  # mid-prefill (4/19 tokens resident)
    assert engine._prefilling
    engine.refresh_weights()  # same values; the FLUSH is the point
    engine.run()
    assert r1.done
    cache = engine.scheduler.prefix_cache
    assert cache.stats()["entries"] == 0  # straddler never inserted
    # a fresh request after the refresh donates normally again
    r2 = engine.submit(SHARED, 3)
    engine.run()
    assert cache.stats()["entries"] == 1
    r3 = engine.submit(SHARED + [4, 5], 3)
    out = engine.run()
    assert r3.reused_tokens == len(SHARED)
    np.testing.assert_array_equal(
        out[r3.rid], _one_shot(lm, SHARED + [4, 5], 3)
    )


def test_versioned_refresh_midprefill_never_mixes_generations(lm):
    """ISSUE 20 regression on the PR-4 quarantine: a versioned
    ``refresh_weights(version=)`` mid-chunked-prefill must keep the
    quarantine intact (the straddler finishes but never donates), and
    the lifecycle records must pin which generation each request ran
    under — the straddler keeps its SUBMIT-time stamp while the
    engine (and any later request) reports the new one, so a mixed
    record/engine pair is diagnosable instead of silent."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, prefix_cache=True, prefill_chunk=4,
        flight_recorder=8,
    )
    engine.refresh_weights(version=1)
    long_prompt = SHARED + SHARED + [2, 3, 4]  # 19 tokens, 5 chunks
    r1 = engine.submit(long_prompt, 3)
    engine.step()  # mid-prefill (4/19 tokens resident)
    assert engine._prefilling
    # same weight VALUES, new generation: the straddler now holds
    # rows from "both" generations — the quarantine must hold exactly
    # as it does for the unversioned refresh
    engine.refresh_weights(version=2)
    engine.run()
    assert r1.done
    cache = engine.scheduler.prefix_cache
    assert cache.stats()["entries"] == 0  # straddler never inserted
    assert engine.weight_version == 2
    assert engine.explain(r1.rid)["weight_version"] == 1  # submit-time
    r2 = engine.submit(SHARED, 3)
    engine.run()
    assert engine.explain(r2.rid)["weight_version"] == 2
    assert cache.stats()["entries"] == 1  # post-refresh donor again
    engine.release_telemetry()


def test_versioned_refresh_cascades_to_draft_model(lm):
    """ISSUE 20 satellite: ``refresh_weights(version=)`` on a
    spec-decode engine re-stamps the DRAFT model too — without the
    cascade a mixed-version fleet view would show the drafter forever
    at generation 0 — and output stays token-exact afterwards."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, speculative=True, spec_k=3, spec_drafter=lm,
    )
    assert engine._drafter.weight_version == 0
    engine.refresh_weights(version=3)
    assert engine._drafter.weight_version == 3
    out = engine.run([(SHARED + [4], 4)])
    (tokens,) = out.values()
    np.testing.assert_array_equal(
        tokens, _one_shot(lm, SHARED + [4], 4)
    )
    engine.release_telemetry()


def test_prefix_min_reuse_floor_admits_shallow_matches_cold(lm):
    """prefix_min_reuse: a 1-2 token coincidental prefix is not worth
    a copy dispatch — below the floor the request admits cold (and is
    counted as a miss); at/above the floor it reuses."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, prefix_cache=True, prefix_min_reuse=4,
    )
    engine.run([([2, 3, 4, 5, 2, 3], 3)])
    shallow = engine.submit([2, 3, 5, 5, 5], 3)  # shares only [2, 3]
    deep = engine.submit([2, 3, 4, 5, 4], 3)  # shares 4 tokens
    out = engine.run()
    assert shallow.reused_tokens == 0
    assert deep.reused_tokens == 4
    st = engine.scheduler.prefix_cache.stats()
    assert st["hits"] == 1 and st["misses"] == 2
    for r, p in ((shallow, [2, 3, 5, 5, 5]), (deep, [2, 3, 4, 5, 4])):
        np.testing.assert_array_equal(
            out[r.rid], _one_shot(lm, p, 3)
        )


def test_prefill_budget_requires_chunking(lm):
    """prefill_budget without prefill_chunk would be silently ignored
    (prefill stays a blocking wave) — reject it loudly."""
    from elephas_tpu.serving import InferenceEngine

    with pytest.raises(ValueError, match="prefill_budget requires"):
        InferenceEngine(lm, num_slots=2, prefill_budget=8)
    with pytest.raises(ValueError, match="prefill_budget=0"):
        InferenceEngine(lm, num_slots=2, prefill_chunk=4,
                        prefill_budget=0)


# -- compiled shape set stays closed -----------------------------------


def test_compile_set_closed_under_chunked_and_prefix(lm):
    """Across a mixed multi-wave workload with prefix hits, evictions,
    and chunked long prompts: ONE decode program, at most ONE copy
    program, ONE chunk width — for the engine's whole life."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, prefix_cache=True, prefill_chunk=4,
    )
    waves = [
        [(SHARED + [2, 3], 4), ([4, 5], 6)],
        [(SHARED + [4, 5], 3), (SHARED + SHARED + [3], 5)],
        [([5, 4, 3, 2], 7), (SHARED + [3, 3], 2)],
    ]
    for wave in waves:
        engine.run(wave)
    stats = engine.compile_stats()
    assert stats["decode_compiles"] == 1, stats
    assert stats["copy_compiles"] <= 1, stats
    assert stats["chunk_prefill_compiles"] == 1, stats  # one width
    assert stats["prefill_compiles"] == 0, stats  # all prefill chunked
    assert engine.scheduler.prefix_cache.stats()["hits"] >= 1


def test_compile_set_closed_prefix_without_chunking(lm):
    """prefix_cache alone: cold requests ride the bucketed full-wave
    prefill, hits ride suffix chunks whose widths come from the SAME
    closed bucket ladder."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=4, prefix_cache=True)
    engine.run([(SHARED + [2, 3], 4), ([3, 4, 5], 4)])
    engine.run([(SHARED + [4, 4], 4), (SHARED + [5, 3, 2], 4)])
    stats = engine.compile_stats()
    assert stats["decode_compiles"] == 1, stats
    # non-chunked hits FUSE the copy into the suffix chunk call — the
    # standalone copy program never compiles on this path
    assert stats["copy_compiles"] == 0, stats
    assert stats["prefill_compiles"] <= len(stats["buckets"]), stats
    assert stats["chunk_prefill_compiles"] <= len(stats["buckets"]), stats


# -- stats: TTFT / inter-token counters (ISSUE 4 satellite) ------------


def test_stats_reports_ttft_and_inter_token_percentiles(lm):
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2)
    reqs = [engine.submit(p, 5) for p in ([2, 3, 4], [4, 5])]
    engine.run()
    st = engine.stats()
    assert st["ttft_s"]["n"] == 2
    assert st["inter_token_s"]["n"] == 2 * 4  # 5 tokens -> 4 gaps each
    assert 0 < st["ttft_s"]["p50"] <= st["ttft_s"]["p99"]
    assert 0 <= st["inter_token_s"]["p50"] <= st["inter_token_s"]["p99"]
    for r in reqs:
        assert len(r.token_times) == 5
        assert r.ttft is not None and r.ttft <= (
            r.finish_time - r.submit_time
        )
        assert all(d >= 0 for d in r.inter_token_times)
        # TTFT + inter-token gaps telescope to the full latency
        total = r.ttft + sum(r.inter_token_times)
        np.testing.assert_allclose(
            total, r.finish_time - r.submit_time, rtol=1e-6
        )


# -- finished-registry eviction is loud and run()-safe -----------------


def test_finished_eviction_is_loud_and_exempts_running_batch(lm, caplog):
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2)
    engine._finished_bound = 2
    first = [([2, 3], 2), ([4, 5], 2), ([3, 4, 5], 2)]
    with caplog.at_level(logging.WARNING, "elephas_tpu.serving.engine"):
        out1 = engine.run(first)
        # all 3 results returned; registry held all 3 DURING the run
        # (the exemption), trimmed loudly to the bound afterwards
        assert len(out1) == 3
        assert len(engine.finished) == 2
        assert engine.finished_evicted == 1
        out2 = engine.run([([5, 2], 2), ([2, 4], 2)])
    assert len(out2) == 2
    # the second batch evicted the first batch's survivors — loudly
    assert engine.finished_evicted == 3
    assert any(
        "finished-request registry" in r.message for r in caplog.records
    )
    st = engine.stats()
    assert st["finished_evicted"] == 3
    assert st["finished"] == 5
