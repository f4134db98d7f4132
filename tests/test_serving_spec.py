"""Speculative decoding: draft-and-verify (ISSUE 8 tentpole).

The acceptance contract: a speculative engine's temperature-0 tokens
match plain (non-speculative) decode — and therefore one-shot
``generate()`` — bit-exactly on BOTH arenas, including TP meshes,
mid-flight arrivals, and ``steps_per_sync`` windows; the compiled
shape set stays CLOSED (a second identical workload pass compiles
nothing new); a collapsed acceptance rate throttles drafting back to
plain decode and re-probes; and the new telemetry series back stats()
and the scrape from ONE store. What speculation buys per token is a
time on a device and has no benchmark cell yet.
"""

import logging

import numpy as np
import pytest


@pytest.fixture(scope="module")
def lm(serving_lm):
    """The session-trained serving LM (see conftest.serving_lm)."""
    return serving_lm


MIXED_PROMPTS = [
    [2, 3, 4, 5],
    [4, 5],
    [3, 4, 5, 2, 3, 4, 5, 2],
    [5, 2, 3],
    [2, 3, 4, 5, 2, 3],
]


def _one_shot(lm, prompt, steps, **kw):
    from elephas_tpu.models import generate

    return generate(
        lm, np.asarray(prompt, np.int32)[None], steps=steps, **kw
    )[0]


def _check_parity(lm, engine, prompts, steps):
    # one reference per prompt: the cached one-shot path (its own
    # parity vs full recompute is test_serving's claim, not re-paid
    # here — tier-1 wall-clock)
    reqs = [engine.submit(p, max_new_tokens=steps) for p in prompts]
    out = engine.run()
    for req, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            out[req.rid], _one_shot(lm, p, steps, kv_cache=True)
        )
    return reqs


def _req(prompt, tokens=(), max_new=16):
    """A bare Request for drafter unit tests."""
    from elephas_tpu.serving.scheduler import Request

    r = Request(rid=0, prompt=tuple(prompt), max_new_tokens=max_new)
    r.tokens = [int(t) for t in tokens]
    return r


# -- n-gram / prompt-lookup drafter units -----------------------------


def test_ngram_no_match_proposes_nothing():
    from elephas_tpu.serving import NgramDrafter

    d = NgramDrafter(max_ngram=3)
    assert d.propose(_req([2, 3, 4, 5]), 4) == []  # no repeated suffix
    assert d.propose(_req([7]), 4) == []  # too short for any n-gram


def test_ngram_full_k_match():
    from elephas_tpu.serving import NgramDrafter

    d = NgramDrafter(max_ngram=3)
    # suffix [2,3,4] recurs at the start; its continuation is 5,6,7,2
    r = _req([2, 3, 4, 5, 6, 7, 2, 3, 4])
    assert d.propose(r, 4) == [5, 6, 7, 2]
    assert d.propose(r, 2) == [5, 6]  # k truncates the continuation


def test_ngram_match_spans_prompt_generated_boundary():
    from elephas_tpu.serving import NgramDrafter

    d = NgramDrafter(max_ngram=3)
    # the matched suffix [5, 6] ends in generated tokens while its
    # earlier occurrence sits in the prompt — full_sequence matching
    r = _req([2, 5, 6, 9, 4], tokens=[5, 6])
    assert d.propose(r, 2) == [9, 4]
    # and a suffix STRADDLING the boundary (prompt tail + generated)
    r2 = _req([8, 3, 4, 9, 3], tokens=[4, 9])
    assert d.propose(r2, 1) == [3]


def test_ngram_prefers_longest_then_most_recent():
    from elephas_tpu.serving import NgramDrafter

    d = NgramDrafter(max_ngram=3)
    # 1-gram [4] occurs twice earlier; the MOST RECENT one (followed
    # by 9) wins over the older one (followed by 5)
    assert d.propose(_req([4, 5, 7, 4, 9, 6, 4]), 1) == [9]
    # but a longer suffix match beats recency of a shorter one:
    # suffix [7, 4] matches at index 1 (-> 9) even though the last
    # 1-gram [4] occurrence is later
    assert d.propose(_req([3, 7, 4, 9, 5, 7, 4]), 1) == [9]


def test_ngram_validation():
    from elephas_tpu.serving import NgramDrafter

    with pytest.raises(ValueError, match="min_ngram"):
        NgramDrafter(max_ngram=2, min_ngram=3)
    with pytest.raises(ValueError, match="min_ngram"):
        NgramDrafter(max_ngram=0)


# -- temperature-0 bit-exactness vs plain decode ----------------------


def test_spec_matches_one_shot_fixed_arena(lm):
    """Speculative decode on the fixed slot arena: token-exact vs
    one-shot generate() on mixed-length prompts, with REAL acceptance
    (the periodic LM's continuations are lookup-predictable) — the
    accepted-draft path is exercised, not just the bonus token."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=4, speculative=True, spec_k=3)
    _check_parity(lm, engine, MIXED_PROMPTS, steps=8)
    s = engine.stats()
    assert s["spec_draft_tokens"] > 0
    assert s["spec_accepted_tokens"] > 0  # speculation actually landed
    assert s["spec_verify_rounds"] > 0


def test_spec_matches_one_shot_paged_arena(lm):
    """Same contract over the paged block pool: the verify window's
    rejected tail stays inside already-reserved blocks (no allocator
    interaction mid-step) and tokens stay exact."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, speculative=True, spec_k=3,
        paged=True, block_size=4,
    )
    _check_parity(lm, engine, MIXED_PROMPTS, steps=8)
    s = engine.stats()
    assert s["spec_accepted_tokens"] > 0
    # blocks fully reclaimed: no leak through the verify path
    assert engine.scheduler.allocator.free_count == engine.num_blocks


def test_spec_on_tp_mesh(lm):
    """model_parallel=2: the verify forward runs over the TP-sharded
    arena (heads on the model axis) and tokens still match one-shot."""
    from elephas_tpu import SparkModel

    engine = SparkModel(lm, model_parallel=2).serve(
        num_slots=4, speculative=True, spec_k=3
    )
    _check_parity(lm, engine, MIXED_PROMPTS[:2], steps=6)
    assert engine.stats()["spec_accepted_tokens"] > 0


def test_spec_steps_per_sync_and_midflight_arrivals(lm):
    """steps_per_sync composes (it paces the fallback decode windows;
    a verify round is already a multi-token window) and a request
    submitted mid-stream joins the next wave — all token-exact."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, speculative=True, spec_k=3, steps_per_sync=4
    )
    prompts = MIXED_PROMPTS[:3]
    reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
    late = None
    for i, _ in enumerate(engine.stream()):
        if i == 3:
            late = engine.submit([3, 4, 5], max_new_tokens=5)
    assert late is not None and late.done
    for req, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            np.asarray(req.full_sequence),
            _one_shot(lm, p, 6, kv_cache=True),
        )
    np.testing.assert_array_equal(
        np.asarray(late.full_sequence),
        _one_shot(lm, [3, 4, 5], 5, kv_cache=True),
    )
    assert sorted(engine.scheduler._free) == list(range(engine.num_slots))


def test_spec_composes_with_chunked_prefill(lm):
    """prefill_chunk + speculative: budgeted prompt chunks stream in
    between speculative rounds; mid-prefill slots never draft."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, speculative=True, spec_k=3, prefill_chunk=4
    )
    _check_parity(lm, engine, MIXED_PROMPTS, steps=8)
    assert engine.stats()["spec_accepted_tokens"] > 0


def test_spec_composes_with_prefix_cache(lm):
    """prefix_cache + speculative on the fixed arena: resident donor
    slots are outside the verify active set, so their rows survive
    verify rounds and later hits still splice correct prefixes."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=6, speculative=True, spec_k=3,
        prefix_cache=True, prefix_min_reuse=3,
    )
    shared = [2, 3, 4, 5, 2, 3]
    load = [(shared + [s], 6) for s in (2, 3)]
    _check_parity(lm, engine, [p for p, _m in load], steps=6)
    # second pass hits the donors and must STILL be exact
    reqs = [engine.submit(p, mn) for p, mn in load]
    out = engine.run()
    assert any(r.reused_tokens > 0 for r in reqs)
    for req, (p, _mn) in zip(reqs, load):
        np.testing.assert_array_equal(
            out[req.rid], _one_shot(lm, p, 6, kv_cache=True)
        )


def test_spec_eos_inside_accepted_window(lm):
    """An EOS token accepted mid-verify-window finishes the request
    exactly there — trailing accepted/bonus tokens are discarded and
    the slot frees for the waiting request."""
    from elephas_tpu.serving import InferenceEngine

    ref = _one_shot(lm, [2, 3, 4], 10, kv_cache=True)
    continuation = ref[3:]
    eos = int(continuation[4])
    stop_at = int(np.argmax(continuation == eos)) + 1

    engine = InferenceEngine(lm, num_slots=1, speculative=True, spec_k=4)
    r1 = engine.submit([2, 3, 4], max_new_tokens=10, eos_id=eos)
    r2 = engine.submit([4, 5], max_new_tokens=4)
    out = engine.run()
    np.testing.assert_array_equal(out[r1.rid], ref[: 3 + stop_at])
    np.testing.assert_array_equal(
        out[r2.rid], _one_shot(lm, [4, 5], 4, kv_cache=True)
    )
    # accepted-draft accounting counts only EMITTED drafts: matched
    # tail tokens discarded by the EOS saved no decode step and must
    # not inflate the acceptance figures
    assert r1.spec_accepted <= len(r1.tokens)


# -- closed compile set -----------------------------------------------


def test_spec_compile_set_closed_fixed(lm):
    """Second identical workload pass compiles NOTHING new: one verify
    program (window width is static, per-slot drafts ride the n_fed
    mask) plus the usual decode/prefill set."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=4, speculative=True, spec_k=3)
    workload = [(p, 8) for p in MIXED_PROMPTS]
    engine.run(workload)
    first = engine.compile_stats()
    engine.run(workload)
    assert engine.compile_stats() == first
    assert first["verify_compiles"] == 1
    assert first["decode_compiles"] <= 1  # fallback window at most


def test_spec_compile_set_closed_paged(lm):
    """Paged: one verify program per (window, table bucket) touched —
    and a second pass adds none."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, speculative=True, spec_k=3,
        paged=True, block_size=4,
    )
    workload = [(p, 8) for p in MIXED_PROMPTS]
    engine.run(workload)
    first = engine.compile_stats()
    engine.run(workload)
    assert engine.compile_stats() == first
    assert 1 <= first["verify_compiles"] <= len(first["table_buckets"])


# -- acceptance collapse: throttle + re-probe -------------------------


def test_acceptance_collapse_falls_back_and_reprobes(lm):
    """A drafter whose guesses never land trips the throttle (plain
    decode takes over), the engine RE-PROBES after the cooldown (the
    drafter is consulted again), and output stays token-exact
    throughout — speculation can degrade to baseline, never below."""
    from elephas_tpu.serving import Drafter, InferenceEngine
    from elephas_tpu.serving.speculative import AcceptanceThrottle

    class Wrong(Drafter):
        calls = 0

        def propose(self, req, k):
            Wrong.calls += 1
            return [7] * int(k)

    engine = InferenceEngine(
        lm, num_slots=1, speculative=True, spec_k=3, spec_drafter=Wrong()
    )
    # tight governor so one request exercises several cycles: probe 2
    # rounds (6 proposed), throttle 3 rounds, re-probe, ...
    engine._spec_throttle = AcceptanceThrottle(
        probe_window=6, min_rate=0.5, reprobe_rounds=3
    )
    r = engine.submit([2, 3, 4, 5], max_new_tokens=24)
    out = engine.run()
    np.testing.assert_array_equal(
        out[r.rid], _one_shot(lm, [2, 3, 4, 5], 24, kv_cache=True)
    )
    s = engine.stats()
    assert s["spec_throttled"] >= 2  # collapsed more than once
    # re-probe happened: the drafter was consulted again after the
    # first throttle window (2 probe rounds per cycle)
    assert Wrong.calls >= 4
    # fallback actually dispatched the plain decode program
    assert engine.compile_stats()["decode_compiles"] == 1
    # throttle state is bounded: finished requests are forgotten
    assert not engine._spec_throttle._state


def test_throttle_unit_semantics():
    from elephas_tpu.serving.speculative import AcceptanceThrottle

    t = AcceptanceThrottle(probe_window=4, min_rate=0.5, reprobe_rounds=2)
    assert t.should_draft(1)
    assert not t.note(1, proposed=2, accepted=2)  # healthy so far
    assert not t.note(1, proposed=1, accepted=1)  # window not full
    # 5 proposed, 3 accepted -> 0.6 >= 0.5: window slides, no trip
    assert not t.note(1, proposed=2, accepted=0)
    assert not t.throttled(1)
    assert t.note(1, proposed=4, accepted=0)  # 0/4 < 0.5 -> trip
    assert t.throttled(1)
    assert not t.should_draft(1)  # cooldown 2 -> 1
    assert not t.should_draft(1)  # cooldown 1 -> 0, window re-armed
    assert t.should_draft(1)  # re-probe
    t.forget(1)
    assert not t._state


# -- draft-model drafter ----------------------------------------------


def test_draft_model_drafter_matches_generate(lm):
    """Unit: the draft model's proposals ARE its own greedy
    continuation — catch-up + draft over the drafter's private arena
    reproduce one-shot generate() of the draft model."""
    from elephas_tpu.serving import DraftModelDrafter

    d = DraftModelDrafter(lm, num_slots=2)
    prompt = [2, 3, 4, 5, 2]
    ref = _one_shot(lm, prompt, 4, kv_cache=True)[len(prompt):]
    req = _req(prompt[:-1], tokens=[prompt[-1]])
    # slot 1, mid-stream request: catch-up ingests prompt[:-1], drafts
    # continue from the last true token
    got = d.propose_batch([(1, req, 4)])
    np.testing.assert_array_equal(got[1], ref)
    # incremental call: pretend the engine accepted 2 tokens
    req.tokens.extend(int(t) for t in ref[:2])
    got2 = d.propose_batch([(1, req, 2)])
    np.testing.assert_array_equal(
        got2[1],
        _one_shot(lm, prompt, 6, kv_cache=True)[
            len(prompt) + 2: len(prompt) + 4
        ],
    )


def test_draft_model_drafter_resets_on_occupant_change(lm):
    """Slot reuse self-heals: a new rid in the same slot triggers a
    full re-ingest, so proposals reflect the NEW request's stream."""
    from elephas_tpu.serving import DraftModelDrafter
    from elephas_tpu.serving.scheduler import Request

    d = DraftModelDrafter(lm, num_slots=1)
    r1 = Request(rid=1, prompt=(2, 3, 4), max_new_tokens=8)
    r1.tokens = [5]
    d.propose_batch([(0, r1, 3)])
    r2 = Request(rid=2, prompt=(4, 5, 2), max_new_tokens=8)
    r2.tokens = [3]
    got = d.propose_batch([(0, r2, 3)])
    ref = _one_shot(lm, [4, 5, 2], 4, kv_cache=True)[3 + 1:]
    np.testing.assert_array_equal(got[0], ref)


def test_spec_with_draft_model_self_speculation(lm):
    """Self-speculation (draft model == target): every draft matches
    the target's greedy token, so acceptance is ~total and output is
    exact — the strongest end-to-end check of the two-model plumbing
    (engine resolves a raw keras model into a DraftModelDrafter)."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, speculative=True, spec_k=3, spec_drafter=lm
    )
    _check_parity(lm, engine, MIXED_PROMPTS[:3], steps=8)
    s = engine.stats()
    assert s["spec_draft_tokens"] > 0
    assert s["spec_acceptance_rate"] > 0.9, s


def test_draft_model_validation(lm):
    from elephas_tpu.serving import DraftModelDrafter
    from elephas_tpu.models import transformer_lm

    with pytest.raises(ValueError, match="maxlen"):
        DraftModelDrafter(lm, num_slots=2, target_maxlen=64)
    with pytest.raises(ValueError, match="vocab"):
        DraftModelDrafter(lm, num_slots=2, target_vocab=16)
    clf_like = transformer_lm(
        vocab_size=8, maxlen=16, d_model=16, num_heads=2, num_layers=1
    )
    # shorter draft maxlen than the target engine's is rejected at
    # resolve time through the engine too
    from elephas_tpu.serving import InferenceEngine

    with pytest.raises(ValueError, match="maxlen"):
        InferenceEngine(
            lm, num_slots=2, speculative=True, spec_drafter=clf_like
        )
    # a PRE-BUILT instance sized for a smaller engine fails at
    # construction too, not with an IndexError mid-serve
    small = DraftModelDrafter(lm, num_slots=1)
    with pytest.raises(ValueError, match="slots"):
        InferenceEngine(
            lm, num_slots=2, speculative=True, spec_drafter=small
        )


def test_overproposing_drafter_is_clipped_not_crashed(lm):
    """A custom drafter returning MORE than its k (or drafts for
    slots it was never asked about) is clipped/dropped — the packed
    verify window and accept loop are sized by k, and uninvited
    drafts would bypass the throttle and budget caps."""
    from elephas_tpu.serving import Drafter, InferenceEngine

    class Greedy(Drafter):
        def propose(self, req, k):
            return [7] * (int(k) * 2 + 3)  # way over budget

        def propose_batch(self, items):
            out = {slot: self.propose(r, k) for slot, r, k in items}
            out[99] = [7, 7]  # a slot nobody asked about
            return out

    engine = InferenceEngine(
        lm, num_slots=2, speculative=True, spec_k=3,
        spec_drafter=Greedy(),
    )
    r = engine.submit([2, 3, 4], max_new_tokens=8)
    out = engine.run()
    np.testing.assert_array_equal(
        out[r.rid], _one_shot(lm, [2, 3, 4], 8, kv_cache=True)
    )
    # per-round clip held: never more than spec_k drafts per verify
    # round despite the drafter proposing 2k+3 every time
    assert 0 < r.spec_drafted <= engine.stats()["spec_verify_rounds"] * 3


def test_refresh_weights_propagates_to_draft_model(lm):
    """engine.refresh_weights() refreshes the drafter too: a draft
    model retrained alongside the target would otherwise keep
    drafting under stale weights, silently collapsing acceptance
    through the throttle. Self-speculation makes it visible: perturb
    the shared model, refresh, and acceptance must return to ~1."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, speculative=True, spec_k=3, spec_drafter=lm
    )
    engine.run([([2, 3, 4, 5], 8)])
    # perturb the (shared) weights: the drafter's captured copy is now
    # stale until refresh_weights() re-uploads both sides
    var = lm.variables[0]
    orig = np.asarray(var.value)
    var.assign(orig * 1.25)
    try:
        engine.refresh_weights()
        r = engine.submit([2, 3, 4, 5], max_new_tokens=8)
        out = engine.run()
        np.testing.assert_array_equal(
            out[r.rid],
            _one_shot(lm, [2, 3, 4, 5], 8, kv_cache=True),
        )
        # drafter drafts with the NEW weights: self-drafts all accept
        assert r.spec_accepted == r.spec_drafted > 0
    finally:
        var.assign(orig)


# -- knob validation + priority warning satellite ---------------------


def test_spec_knobs_require_speculative(lm):
    from elephas_tpu.serving import InferenceEngine

    with pytest.raises(ValueError, match="require speculative=True"):
        InferenceEngine(lm, num_slots=2, spec_k=4)
    with pytest.raises(ValueError, match="require speculative=True"):
        InferenceEngine(lm, num_slots=2, spec_drafter="ngram")
    with pytest.raises(ValueError, match="spec_k"):
        InferenceEngine(lm, num_slots=2, speculative=True, spec_k=0)
    with pytest.raises(ValueError, match="spec_k"):
        InferenceEngine(lm, num_slots=2, speculative=True, spec_k=99)
    with pytest.raises(ValueError, match="not a drafter"):
        InferenceEngine(
            lm, num_slots=2, speculative=True, spec_drafter=object()
        )


def test_priority_on_non_preemption_engine_warns(lm, caplog):
    """ISSUE 8 satellite (knob-validation parity with the paged
    knobs): submit(priority=) on an engine that cannot honor it warns
    LOUDLY instead of silently ignoring the knob."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2)
    with caplog.at_level(logging.WARNING, "elephas_tpu.serving.engine"):
        r = engine.submit([2, 3], max_new_tokens=2, priority=5)
    assert any("IGNORED" in rec.message for rec in caplog.records)
    out = engine.run()  # the request itself is still valid
    assert r.rid in out
    # a preemption engine consumes priority: no warning there (no run
    # needed — the warning fires at submit time or never)
    caplog.clear()
    pe = InferenceEngine(
        lm, num_slots=2, paged=True, block_size=4, preemption=True
    )
    with caplog.at_level(logging.WARNING, "elephas_tpu.serving.engine"):
        pe.submit([2, 3], max_new_tokens=2, priority=5)
    assert not any(
        "IGNORED" in rec.message for rec in caplog.records
    )


# -- stats / scrape no-drift + decode-only tok/s ----------------------


def test_spec_stats_match_metrics_scrape(lm):
    """The new speculative series are registry-backed: stats() and the
    Prometheus scrape read the SAME store, pinned by engine label."""
    import re

    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2, speculative=True, spec_k=3)
    engine.run([(p, 6) for p in MIXED_PROMPTS[:3]])
    s = engine.stats()
    scrape = engine.scrape()

    def series(name):
        pat = (
            rf'^{name}{{engine="{engine.telemetry_label}"}} '
            rf'([0-9.e+-]+)$'
        )
        vals = re.findall(pat, scrape, re.M)
        assert vals, f"{name} missing from scrape"
        return float(vals[0])

    assert series(
        "elephas_serving_spec_draft_tokens_total"
    ) == s["spec_draft_tokens"]
    assert series(
        "elephas_serving_spec_accepted_tokens_total"
    ) == s["spec_accepted_tokens"]
    assert series(
        "elephas_serving_spec_verify_rounds_total"
    ) == s["spec_verify_rounds"]
    assert series(
        "elephas_serving_spec_throttled_total"
    ) == s["spec_throttled"]
    assert s["spec_draft_tokens"] > 0
    # serve.verify spans landed in the tracer ring
    import elephas_tpu.telemetry as telemetry

    names = [e["name"] for e in telemetry.tracer().events()]
    assert "serve.verify" in names
    engine.release_telemetry()
    assert f'engine="{engine.telemetry_label}"' not in engine.scrape()


def test_decode_only_tok_s_in_stats(lm):
    """ISSUE 8 satellite: stats() reports decode-only tok/s (TTFT
    excluded) from the existing token_times — on a non-speculative
    engine too, so per-token speed is measurable everywhere."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2)
    assert engine.stats()["decode_tok_s"] is None  # nothing finished
    engine.run([(p, 6) for p in MIXED_PROMPTS[:3]])
    s = engine.stats()
    assert s["decode_tok_s"] is not None and s["decode_tok_s"] > 0
    # spec keys exist (zeroed) on a plain engine: stable stats schema
    assert s["spec_draft_tokens"] == 0
    assert s["spec_acceptance_rate"] is None
