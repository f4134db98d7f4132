"""Paged KV arena (ISSUE 7 tentpole).

The acceptance contract: the paged engine is token-exact at
temperature 0 against BOTH the fixed-arena engine and one-shot
``generate()`` on the same workload (including on a TP mesh); the
compiled-shape set stays closed (one decode program per block-table
bucket, never per request); prefix hits are copy-free block-table
splices guarded by refcounts (shared blocks survive index eviction
while a live table references them); preempt → host-offload → resume
round-trips bit-exact; and a request that can NEVER fit the block pool
is rejected loudly at submit instead of wedging the queue head. The
capacity claim (at equal KV rows the pool runs at least 1.5 times the
fixed arena's requests at once) is a count on the schedulers'
admission, below.
"""

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
    reqs = [engine.submit(p, max_new_tokens=steps) for p in prompts]
    out = engine.run()
    for req, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            out[req.rid], _one_shot(lm, p, steps, kv_cache=True)
        )
    return reqs


# -- host-side bookkeeping (no device work) ---------------------------


def test_block_allocator_refcounts():
    """Deterministic lowest-first allocation, refcounted frees, loud
    misuse."""
    from elephas_tpu.serving.blocks import BlockAllocator

    a = BlockAllocator(4, 8)
    assert a.alloc(2) == [0, 1] and a.free_count == 2
    assert a.alloc(3) is None  # short -> None, never partial
    b = a.alloc(2)
    assert b == [2, 3] and a.free_count == 0
    a.ref([0])  # shared
    assert a.deref([0, 1]) == [1]  # 0 still referenced
    assert a.deref([0]) == [0]
    assert a.free_count == 2 and a.alloc(2) == [0, 1]  # ids recycle sorted
    with pytest.raises(ValueError, match="unleased"):
        a.ref([3 + 94])
    with pytest.raises(ValueError, match="unleased"):
        a.deref([1 + 94])
    with pytest.raises(ValueError):
        BlockAllocator(0, 8)


def test_paged_prefix_index_full_block_matching():
    """The index splices FULL blocks only: a 10-token prompt at
    block_size 4 indexes 8 tokens / 2 blocks; match() is pure and
    returns block-multiple reuse, commit_hit refs the spliced blocks."""
    from elephas_tpu.serving.blocks import BlockAllocator
    from elephas_tpu.serving.prefix_cache import PagedPrefixIndex

    a = BlockAllocator(8, 4)
    idx = PagedPrefixIndex(a)
    blocks = a.alloc(3)  # a request's table for a 10-token prompt
    idx.insert(tuple(range(2, 12)), blocks)  # indexes blocks[:2]
    assert a.ref_count(blocks[0]) == 2 and a.ref_count(blocks[2]) == 1

    eid, reuse = idx.match(tuple(range(2, 12)) + (7,))
    assert eid is not None and reuse == 8  # floor(10 cap .. ) full blocks
    # pure: no counters moved yet
    assert idx.hits == 0 and idx.misses == 0
    shared = idx.commit_hit(eid, reuse)
    assert shared == blocks[:2] and idx.shared_blocks == 2
    assert a.ref_count(blocks[0]) == 3
    # a prompt equal to the indexed prefix must NOT fully match (one
    # suffix token must remain to prefill): cap at len-1 -> 4 tokens
    eid2, reuse2 = idx.match(tuple(range(2, 10)))
    assert reuse2 == 4
    # sub-block prefix: nothing spliceable
    assert idx.match((2, 3, 4)) == (None, 0)


def test_paged_prefix_index_eviction_frees_only_unreferenced():
    """evict_for() drops LRU entries but skips entries whose blocks
    are all still referenced by live tables — releasing them would
    reclaim nothing and only forget reusable prefixes."""
    from elephas_tpu.serving.blocks import BlockAllocator
    from elephas_tpu.serving.prefix_cache import PagedPrefixIndex

    a = BlockAllocator(8, 4)
    idx = PagedPrefixIndex(a)
    t1 = a.alloc(2)
    idx.insert(tuple(range(10, 18)), t1)  # entry E1 over t1
    a.deref(t1)  # owning request finished; E1 keeps the blocks alive
    t2 = a.alloc(2)
    idx.insert(tuple(range(30, 38)), t2)  # entry E2; table t2 STILL live
    assert a.free_count == 4
    freed = idx.evict_for(2)
    # E1 (LRU, unreferenced) freed its 2 blocks; E2's blocks are pinned
    # by the live table, so even asking for more frees nothing else
    assert freed == 2 and a.free_count == 6
    assert idx.evict_for(1) == 0
    # E2 RETAINED: evicting it would free nothing (live table refs),
    # so the index keeps the reusable prefix instead
    assert idx.stats()["entries"] == 1
    assert a.ref_count(t2[0]) == 2  # entry + live table


def test_paged_pool_admits_more_at_equal_kv_bytes():
    """The capacity claim the block pool exists for, counted on the
    schedulers' own admission: a fixed arena prices every slot at
    ``maxlen`` rows, so 4 slots of 128 rows hold 4 requests whatever
    their length; the same 512 rows as 32 blocks of 16 lease each
    request only its prompt + budget, and a mixed short/long set (32
    prompts of 25 and 2 of 76, 6 new tokens each) runs at least 1.5
    times as many requests at once."""
    from elephas_tpu.serving.blocks import BlockAllocator
    from elephas_tpu.serving.scheduler import Scheduler, default_buckets
    from tests.conftest import peak_admitted

    maxlen, fixed_slots, block_size = 128, 4, 16
    num_blocks = fixed_slots * maxlen // block_size
    assert num_blocks * block_size == fixed_slots * maxlen  # equal rows
    lanes = fixed_slots * 4
    rng = np.random.default_rng(17)
    mixed = [
        (rng.integers(1, 512, size=n).tolist(), 6)
        for n in [25] * (lanes * 2) + [76] * 2
    ]
    buckets = default_buckets(maxlen)
    fixed = peak_admitted(Scheduler(fixed_slots, buckets), mixed)
    paged = peak_admitted(
        Scheduler(
            lanes, buckets,
            allocator=BlockAllocator(num_blocks, block_size),
        ),
        mixed,
    )
    assert fixed == fixed_slots
    assert paged >= 1.5 * fixed, (paged, fixed)


# -- token-exactness ---------------------------------------------------


def test_paged_matches_one_shot_and_fixed_arena(lm):
    """The tentpole contract: the paged engine's greedy tokens equal
    one-shot generate() AND the fixed-arena engine's on the same
    mixed-length workload — storage paging must be invisible to the
    sampled stream."""
    from elephas_tpu.serving import InferenceEngine

    fixed = InferenceEngine(lm, num_slots=4)
    paged = InferenceEngine(lm, num_slots=4, paged=True, block_size=8)
    rf = [fixed.submit(p, max_new_tokens=8) for p in MIXED_PROMPTS]
    rp = [paged.submit(p, max_new_tokens=8) for p in MIXED_PROMPTS]
    of, op = fixed.run(), paged.run()
    for f, g, p in zip(rf, rp, MIXED_PROMPTS):
        np.testing.assert_array_equal(of[f.rid], op[g.rid])
        np.testing.assert_array_equal(
            op[g.rid], _one_shot(lm, p, 8, kv_cache=True)
        )


def test_paged_decode_window_and_chunked_prefill_keep_tokens(lm):
    """steps_per_sync > 1 and chunked prefill compose with paging —
    greedy tokens unchanged."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, paged=True, block_size=8, steps_per_sync=4,
        prefill_chunk=4,
    )
    _check_parity(lm, engine, MIXED_PROMPTS, steps=7)


def test_paged_slot_and_block_reclamation_midflight(lm):
    """More requests than slots and a tight pool: blocks and slots
    recycle mid-flight, every output token-exact, nothing leaks."""
    from elephas_tpu.serving import InferenceEngine

    # pool of 8 blocks x 4 = 32 rows for 2 slots; each request needs
    # ceil((p + 6) / 4) blocks -> admission churns through the pool
    engine = InferenceEngine(
        lm, num_slots=2, paged=True, block_size=4, num_blocks=8,
    )
    reqs = [engine.submit(p, max_new_tokens=6) for p in MIXED_PROMPTS]
    out = engine.run()
    for req, p in zip(reqs, MIXED_PROMPTS):
        np.testing.assert_array_equal(
            out[req.rid], _one_shot(lm, p, 6, kv_cache=True)
        )
    assert engine.scheduler.allocator.free_count == 8  # all blocks back
    assert sorted(engine.scheduler._free) == [0, 1]
    assert not engine.scheduler.tables


def test_paged_serve_on_tp_mesh(lm):
    """SparkModel.serve(paged=True) on the TP mesh: heads shard over
    the model axis, the block axis stays replicated, tokens match
    one-shot exactly — the gang determinism contract."""
    from elephas_tpu import SparkModel

    engine = SparkModel(lm, model_parallel=2).serve(
        num_slots=4, paged=True, block_size=8
    )
    _check_parity(lm, engine, MIXED_PROMPTS[:3], steps=6)
    k_buf, _v_buf = next(iter(engine._caches.values()))
    spec = k_buf.sharding.spec
    assert spec[0] is None, spec  # block axis replicated
    assert spec[2] == "model", spec  # heads ride the model axis


def test_paged_closed_compile_set_across_waves(lm):
    """The paged compiled-shape contract: across repeated mixed-length
    workloads, decode compiles at most once per table bucket and chunk
    programs stay within (width x table bucket); a second identical
    pass adds NOTHING."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=4, paged=True, block_size=8)
    waves = [
        [([2, 3], 4), ([4, 5, 2, 3, 4], 6)],
        [([3, 4, 5], 9), ([2, 3, 4, 5, 2, 3, 4], 3), ([5, 5], 5)],
        [([4, 3, 2], 7)],
    ]
    for wave in waves:
        engine.run(wave)
    stats = engine.compile_stats()
    n_tb = len(stats["table_buckets"])
    assert 1 <= stats["decode_compiles"] <= n_tb, stats
    assert stats["chunk_prefill_compiles"] <= (
        len(stats["buckets"]) * n_tb
    ), stats
    for wave in waves:  # warm steady state: no new shapes, ever
        engine.run(wave)
    stats2 = engine.compile_stats()
    assert stats2["decode_compiles"] == stats["decode_compiles"]
    assert (
        stats2["chunk_prefill_compiles"]
        == stats["chunk_prefill_compiles"]
    )


# -- copy-free prefix sharing -----------------------------------------


def test_prefix_hit_is_copy_free_block_splice(lm):
    """A prefix hit splices the donor's full blocks into the new
    table by refcount — no copy program exists in paged mode — and the
    hit's tokens equal the cold request's."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, paged=True, block_size=4, prefix_cache=True,
    )
    shared = [2, 3, 4, 5, 2, 3, 4, 5]  # two full blocks
    cold = engine.submit(shared + [2], max_new_tokens=6)
    engine.run()
    warm = engine.submit(shared + [3], max_new_tokens=6)
    out = engine.run()
    assert warm.reused_tokens == 8  # full-block splice
    np.testing.assert_array_equal(
        out[warm.rid], _one_shot(lm, shared + [3], 6, kv_cache=True)
    )
    s = engine.stats()
    assert s["prefix_blocks_shared"] == 2
    assert s["prefix_cache"]["hits"] == 1
    assert engine.compile_stats()["copy_compiles"] == 0
    assert cold.reused_tokens == 0


def test_shared_blocks_survive_index_eviction_under_pressure(lm):
    """Refcount safety: while a sharer's table references spliced
    blocks, pool pressure may evict the index ENTRY but the blocks
    must not free (the sharer is still attending over them) — outputs
    stay exact; after everything drains the pool is whole."""
    from elephas_tpu.serving import InferenceEngine

    # 10 blocks x 4 rows; the donor prompt takes 2 full blocks
    engine = InferenceEngine(
        lm, num_slots=2, paged=True, block_size=4, num_blocks=10,
        prefix_cache=True,
    )
    shared = [2, 3, 4, 5, 2, 3, 4, 5]
    engine.run([(shared + [2], 4)])  # seeds the index
    alloc = engine.scheduler.allocator
    idx = engine.scheduler.prefix_index
    assert idx.stats()["entries"] == 1 and alloc.free_count == 10 - 2

    # the warm request splices 2 blocks, then pressure from cold
    # traffic forces index eviction while the sharer still decodes
    warm = engine.submit(shared + [3], max_new_tokens=8)
    churn = [
        engine.submit([4, 5, 2, 3, 4, 5, 2, int(t)], max_new_tokens=8)
        for t in (3, 4, 5)
    ]
    out = engine.run()
    np.testing.assert_array_equal(
        out[warm.rid], _one_shot(lm, shared + [3], 8, kv_cache=True)
    )
    for req in churn:
        np.testing.assert_array_equal(
            out[req.rid],
            _one_shot(lm, list(req.prompt), 8, kv_cache=True),
        )
    assert warm.reused_tokens == 8
    # drained: only index entries still hold references (entries may
    # share physical blocks via earlier splices — count unique ids)
    held = {b for e in idx._entries.values() for b in e.blocks}
    assert alloc.free_count == 10 - len(held)


# -- preemption / offload / resume ------------------------------------


def test_preempt_offload_resume_token_exact(lm):
    """A higher-priority arrival preempts the active low-priority
    request (blocks offloaded to host), runs to completion, and the
    victim resumes bit-exact — BOTH final sequences equal their
    unpreempted one-shot references."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, paged=True, block_size=4, num_blocks=8,
        preemption=True,
    )
    victim = engine.submit([2, 3, 4, 5], max_new_tokens=12)
    for _ in range(3):
        engine.step()  # victim mid-decode
    assert len(victim.tokens) >= 3
    hi = engine.submit(
        [3, 4, 5, 2, 3, 4, 5, 2], max_new_tokens=12, priority=1
    )
    while engine.scheduler.has_work:
        engine.step()
    s = engine.stats()
    assert s["preemptions"] == 1 and s["resumes"] == 1
    assert s["offloaded_blocks"] >= 1
    assert not engine._offloaded  # host store drained on resume
    np.testing.assert_array_equal(
        np.asarray(victim.full_sequence),
        _one_shot(lm, [2, 3, 4, 5], 12, kv_cache=True),
    )
    np.testing.assert_array_equal(
        np.asarray(hi.full_sequence),
        _one_shot(lm, [3, 4, 5, 2, 3, 4, 5, 2], 12, kv_cache=True),
    )
    assert engine.scheduler.allocator.free_count == 8  # nothing leaked


def test_equal_priority_never_preempts(lm):
    """Preemption is strictly priority-ordered: an equal-priority
    arrival WAITS (FIFO) instead of swapping anyone out."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, paged=True, block_size=4, num_blocks=4,
        preemption=True,
    )
    first = engine.submit([2, 3, 4, 5], max_new_tokens=8)  # 3 blocks
    engine.step()
    second = engine.submit([3, 4, 5, 2], max_new_tokens=8)  # needs 3
    engine.step()
    assert engine.stats()["preemptions"] == 0
    assert second.slot is None and first.slot is not None
    out = engine.run()
    for req in (first, second):
        np.testing.assert_array_equal(
            out[req.rid],
            _one_shot(lm, list(req.prompt), 8, kv_cache=True),
        )
    assert engine.stats()["preemptions"] == 0


def test_window_overrun_past_table_bucket_never_clobbers_block_zero(lm):
    """Review regression (ISSUE 7): a finished slot stays device-
    active for the rest of its steps_per_sync window and keeps
    advancing its cursor past its reservation — and past the WHOLE
    table bucket when its neighbor's longer prompt set its cursor
    ahead. The out-of-bucket block index used to resolve to 0 (a real
    id) instead of the sentinel, scribbling the overrunner's garbage
    K/V over block 0 — the first request's resident prompt rows.
    Token-level asserts can miss it (the trained toy's argmax shrugs
    off one corrupted row), so the proof is bitwise POOL state: the
    owner's blocks must be identical with and without the
    overrunning neighbor."""
    from elephas_tpu.serving import InferenceEngine

    def owner_blocks(with_runner):
        # bs=4: owner spans blocks 0,1 (table bucket T=2); the
        # runner's longer prompt starts its cursor 4 ahead, so its
        # post-finish overrun crosses blk_idx >= T while the owner is
        # still decoding real tokens
        engine = InferenceEngine(
            lm, num_slots=2, paged=True, block_size=4,
            steps_per_sync=8,
        )
        owner = engine.submit([2, 3], max_new_tokens=6)
        if with_runner:
            engine.submit([4, 5, 2, 3, 4, 5], max_new_tokens=2)
        out = engine.run()
        np.testing.assert_array_equal(
            out[owner.rid], _one_shot(lm, [2, 3], 6, kv_cache=True)
        )
        _name, (k, _v) = next(iter(engine._caches.items()))
        return np.asarray(k)[:2].copy()  # owner's blocks 0 and 1

    np.testing.assert_array_equal(
        owner_blocks(False), owner_blocks(True)
    )


def test_same_wave_admission_never_preempted(lm):
    """Review regression (ISSUE 7): with a low- and a high-priority
    request BOTH waiting when the wave runs, the head admission (low)
    must not be chosen as the high's preemption victim inside the
    same wave — its Admission is already in the plan, so preempting
    it would double-lease its blocks and prefill into a revoked slot.
    The low request only becomes preemptible once it holds a token."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=4, paged=True, block_size=4, num_blocks=6,
        preemption=True,
    )
    low = engine.submit([2, 3, 4, 5], max_new_tokens=12)  # 4 blocks
    hi = engine.submit(
        [3, 4, 5, 2, 3, 4], max_new_tokens=10, priority=1  # 4 blocks
    )
    engine.step()  # one wave sees both: low admits, hi must WAIT
    assert low.slot is not None and len(low.tokens) >= 1
    assert engine.stats()["preemptions"] == 0
    out = engine.run()  # later steps may legally preempt low
    np.testing.assert_array_equal(
        np.asarray(low.full_sequence),
        _one_shot(lm, [2, 3, 4, 5], 12, kv_cache=True),
    )
    np.testing.assert_array_equal(
        np.asarray(hi.full_sequence),
        _one_shot(lm, [3, 4, 5, 2, 3, 4], 10, kv_cache=True),
    )
    assert engine.scheduler.allocator.free_count == 6


def test_preemption_requires_paged(lm):
    from elephas_tpu.serving import InferenceEngine

    with pytest.raises(ValueError, match="preemption requires"):
        InferenceEngine(lm, num_slots=2, preemption=True)


# -- pool-exhaustion rejection (ISSUE 7 satellite) --------------------


def test_unfittable_request_rejected_loudly_not_wedged(lm):
    """A request whose prompt + budget can never fit the pool gets
    ``req.error`` + ``done`` at submit (never queued) and the engine
    keeps serving everyone else — before this guard it would sit at
    the queue head forever, starving the whole engine."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, paged=True, block_size=4, num_blocks=4,
    )
    bad = engine.submit(list(range(2, 2 + 20)), max_new_tokens=10)
    assert isinstance(bad.error, RuntimeError) and bad.done
    assert "can never be admitted" in str(bad.error)
    assert not engine.scheduler.waiting  # never queued
    assert engine.stats()["rejected"] == 1
    # the engine still serves fitting traffic afterwards
    ok = engine.submit([2, 3], max_new_tokens=3)
    out = engine.run()
    np.testing.assert_array_equal(
        out[ok.rid], _one_shot(lm, [2, 3], 3, kv_cache=True)
    )
    # the same registry series backs the scrape — no drift
    assert (
        "elephas_serving_rejected_total" in engine.scrape()
        or engine.scrape() == ""  # telemetry null mode
    )


def test_paged_knobs_require_paged(lm):
    from elephas_tpu.serving import InferenceEngine

    with pytest.raises(ValueError, match="require paged=True"):
        InferenceEngine(lm, num_slots=2, block_size=8)
    with pytest.raises(ValueError, match="require paged=True"):
        InferenceEngine(lm, num_slots=2, num_blocks=4)
    with pytest.raises(ValueError, match="block_size"):
        InferenceEngine(lm, num_slots=2, paged=True, block_size=0)
    with pytest.raises(ValueError, match="block_size"):
        InferenceEngine(lm, num_slots=2, paged=True, block_size=999)


# -- stats / metrics no-drift (ISSUE 7 satellite) ---------------------


def test_paged_stats_match_metrics_scrape(lm):
    """queue_depth / preemptions / blocks gauges / prefix sharing are
    registry-backed: stats() and the Prometheus scrape read the SAME
    series, so they cannot drift."""
    import re

    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2, paged=True, block_size=4, num_blocks=8,
        prefix_cache=True,
    )
    shared = [2, 3, 4, 5, 2, 3, 4, 5]
    engine.run([(shared + [2], 4), (shared + [3], 4)])
    s = engine.stats()
    scrape = engine.scrape()

    def series(name, key, label):
        # the registry is process-global: pin THIS engine's series by
        # its own instance label, exactly what stats() reads back
        pat = rf'^{name}{{{key}="{label}"}} ([0-9.e+-]+)$'
        vals = re.findall(pat, scrape, re.M)
        assert vals, f"{name}{{{key}={label}}} missing from scrape"
        return float(vals[0])

    eng_l = engine.telemetry_label
    assert series(
        "elephas_serving_kv_blocks", "engine", eng_l
    ) == s["blocks_total"]
    assert series(
        "elephas_serving_blocks_free", "engine", eng_l
    ) == s["blocks_free"]
    assert series(
        "elephas_serving_preemptions_total", "engine", eng_l
    ) == s["preemptions"]
    assert series(
        "elephas_serving_rejected_total", "engine", eng_l
    ) == s["rejected"]
    assert series(
        "elephas_prefix_blocks_shared_total", "cache",
        engine.scheduler.prefix_index.telemetry_label,
    ) == s["prefix_blocks_shared"]
    assert series(
        "elephas_serving_waiting_requests", "scheduler",
        engine.scheduler.telemetry_label,
    ) == s["queue_depth"]
    engine.release_telemetry()
    assert f'engine="{eng_l}"' not in engine.scrape()
