"""SLO-aware admission policies (ISSUE 10 tentpole).

The contract under test: the policy reorders the waiting queue and
rejects at submit — it NEVER touches decoding, so temperature-0 token
streams stay bit-exact per request under any policy (including on the
TP mesh). Fair share bounds cross-tenant service gaps where FIFO does
not, deadline-EDF orders within the fair-share turn, aging promotes
any waiter past its bound (no starvation), and overload admission
control rejects loudly with a deterministic Retry-After. Goodput
under overload is a time on a device and has no benchmark cell yet.
"""

import re

import numpy as np
import pytest

from elephas_tpu.serving.policy import (
    DEFAULT_TENANT,
    AdmissionRejected,
    FairSharePolicy,
    FifoPolicy,
    Policy,
    normalize_tenants,
    resolve_policy,
)
from elephas_tpu.serving.scheduler import Scheduler, default_buckets


@pytest.fixture(scope="module")
def lm(serving_lm):
    return serving_lm


def _drain_one(s, slot, budget):
    """Simulate serving the slot's occupant to completion (host-side
    only): feed `budget` tokens, then reclaim."""
    req = s.active[slot]
    for t in range(budget):
        done = s.on_token(slot, 7)
    assert done and req.done
    s.reclaim(slot)
    return req


# -- pure host-side ordering ------------------------------------------


def test_fair_share_alternates_between_backlogged_tenants():
    """Two equal-weight tenants, each with a backlog, one slot: the
    admitted service alternates a,b,a,b — the virtual counters bound
    the gap at one request's cost. FIFO (no policy) would serve all of
    a before any b."""
    p = FairSharePolicy({"a": 1.0, "b": 1.0})
    s = Scheduler(1, default_buckets(64), policy=p)
    for i in range(3):
        s.submit(s.make_request([1] * 4, 4, tenant="a"))
    for i in range(3):
        s.submit(s.make_request([1] * 4, 4, tenant="b"))
    order = []
    for _ in range(6):
        adm = s.admit()
        assert len(adm) == 1
        order.append(adm[0].req.tenant)
        # policy.on_token is the engine's job; charge it here so the
        # decode service lands in the counters like the real loop
        for _t in range(4):
            p.on_token(s.active[adm[0].slot])
        _drain_one(s, adm[0].slot, 4)
    assert order == ["a", "b", "a", "b", "a", "b"], order


def test_fair_share_respects_weights():
    """Weight 2 vs 1: the heavy tenant receives ~2x the service — its
    counter advances half as fast per token."""
    p = FairSharePolicy({"heavy": 2.0, "light": 1.0})
    s = Scheduler(1, default_buckets(64), policy=p)
    for i in range(8):
        s.submit(s.make_request([1] * 4, 4, tenant="heavy"))
        s.submit(s.make_request([1] * 4, 4, tenant="light"))
    served = {"heavy": 0, "light": 0}
    for _ in range(9):
        adm = s.admit()
        served[adm[0].req.tenant] += 1
        for _t in range(4):
            p.on_token(s.active[adm[0].slot])
        _drain_one(s, adm[0].slot, 4)
    assert served["heavy"] == 6 and served["light"] == 3, served
    # the VTC bound: weighted virtual counters stay within one
    # request's weighted cost of each other while both are backlogged
    v = p.stats()["virtual_counters"]
    per_req_cost = (1.0 * 4 + 2.0 * 4)  # prefill + decode, weight 1
    assert abs(v["heavy"] - v["light"]) <= per_req_cost, v


def test_edf_orders_within_a_tenant():
    """Within one tenant's turn, the tighter declared TTFT deadline
    admits first regardless of submission order; no deadline sorts
    last (inf)."""
    p = FairSharePolicy({"a": 1.0})
    s = Scheduler(1, default_buckets(64), policy=p)
    loose = s.submit(s.make_request([1] * 4, 2, tenant="a",
                                    ttft_deadline_ms=5000))
    none = s.submit(s.make_request([1] * 4, 2, tenant="a"))
    tight = s.submit(s.make_request([1] * 4, 2, tenant="a",
                                    ttft_deadline_ms=50))
    order = []
    for _ in range(3):
        adm = s.admit()
        order.append(adm[0].req.rid)
        _drain_one(s, adm[0].slot, 2)
    assert order == [tight.rid, loose.rid, none.rid], order


def test_aging_promotes_starved_request():
    """A request whose tenant's counter is hopelessly behind still
    admits within aging_waves — the no-starvation bound. Without
    aging, fresh zero-counter arrivals would jump it forever."""
    p = FairSharePolicy({"rich": 1.0, "poor": 1.0}, aging_waves=3)
    s = Scheduler(1, default_buckets(64), policy=p)
    # the poor tenant has consumed an enormous weighted service
    p._vtc["poor"] = 1e9
    starved = s.submit(s.make_request([1] * 4, 2, tenant="poor"))
    waves_until_admitted = None
    for wave in range(1, 8):
        # a fresh zero-counter rival arrives every wave
        s.submit(s.make_request([1] * 4, 2, tenant="rich"))
        adm = s.admit()
        if adm[0].req.rid == starved.rid:
            waves_until_admitted = wave
            break
        _drain_one(s, adm[0].slot, 2)
    assert waves_until_admitted is not None, "starved forever"
    assert waves_until_admitted <= p.aging_waves + 1


def test_admission_control_rejects_past_token_debt_bound():
    p = FairSharePolicy({"a": 1.0}, max_queue_tokens=20,
                        retry_after_s=2.0)
    s = Scheduler(1, default_buckets(64), policy=p)
    r1 = s.make_request([1] * 4, 8, tenant="a")  # debt 12
    assert p.admission_verdict(
        r1, s.queued_tokens, s.queued_tokens_for("a")
    ).admitted
    s.submit(r1)
    r2 = s.make_request([1] * 4, 8, tenant="a")  # 12 + 12 > 20
    v = p.admission_verdict(r2, s.queued_tokens,
                            s.queued_tokens_for("a"))
    assert not v.admitted and "admission bound" in v.reason
    # deterministic Retry-After: ceil(24 / 20) = 2 shares deep -> 2x base
    assert v.retry_after_s == pytest.approx(4.0)


def test_admission_control_shares_bound_by_tenant_weight():
    """The queue budget splits by weight share: the hog shedding at
    ITS share never touches the light tenant's admission — load
    shedding falls on the tenant causing the debt."""
    p = FairSharePolicy({"hog": 1.0, "light": 1.0},
                        max_queue_tokens=40)  # 20 per tenant
    s = Scheduler(1, default_buckets(64), policy=p)
    # fill the hog's share
    s.submit(s.make_request([1] * 8, 8, tenant="hog"))  # debt 16
    over = s.make_request([1] * 8, 8, tenant="hog")     # 32 > 20
    v = p.admission_verdict(over, s.queued_tokens,
                            s.queued_tokens_for("hog"))
    assert not v.admitted and "'hog'" in v.reason
    # the light tenant's share is untouched by the hog's debt
    light = s.make_request([1] * 4, 8, tenant="light")  # 12 <= 20
    assert p.admission_verdict(
        light, s.queued_tokens, s.queued_tokens_for("light")
    ).admitted


def test_preemption_priority_derived_from_policy():
    """Paged preemption compares the POLICY's priorities (ISSUE 10):
    a deadline-carrying arrival outranks tokened best-effort work via
    the deadline boost, without the caller touching submit(priority=)."""
    from elephas_tpu.serving.blocks import BlockAllocator

    p = FairSharePolicy({"a": 1.0}, deadline_boost=1)
    alloc = BlockAllocator(4, block_size=8)
    s = Scheduler(2, default_buckets(32), allocator=alloc,
                  preemption=True, policy=p)
    best_effort = s.submit(s.make_request([1] * 8, 8, tenant="a"))
    adm, pre = s.admit_paged()
    assert [a.req.rid for a in adm] == [best_effort.rid] and not pre
    s.on_token(best_effort.slot, 7)  # has resident state to offload
    urgent = s.submit(s.make_request([1] * 8, 24, tenant="a",
                                     ttft_deadline_ms=50))
    adm, pre = s.admit_paged()
    assert [v.req.rid for v in pre] == [best_effort.rid]
    assert [a.req.rid for a in adm] == [urgent.rid]
    # once the urgent request has its first token the boost drops —
    # it can no longer preempt equal-priority work
    s.on_token(urgent.slot, 7)
    assert p.priority_of(urgent) == 0


def test_fifo_policy_keeps_submission_order():
    p = FifoPolicy({"a": 1.0, "b": 1.0})
    s = Scheduler(1, default_buckets(64), policy=p)
    rids = [
        s.submit(s.make_request([1] * 4, 2, tenant=t)).rid
        for t in ("a", "a", "b", "a")
    ]
    order = []
    for _ in range(4):
        adm = s.admit()
        order.append(adm[0].req.rid)
        _drain_one(s, adm[0].slot, 2)
    assert order == rids


def test_policy_knob_validation():
    with pytest.raises(ValueError, match="non-positive weight"):
        normalize_tenants({"a": 0.0})
    with pytest.raises(ValueError, match="max_queue_tokens"):
        FairSharePolicy(max_queue_tokens=0)
    with pytest.raises(ValueError, match="aging_waves"):
        FairSharePolicy(aging_waves=0)
    with pytest.raises(ValueError, match="retry_after_s"):
        FairSharePolicy(retry_after_s=0)
    with pytest.raises(ValueError, match="unknown policy"):
        resolve_policy("lifo")
    with pytest.raises(TypeError, match="policy must be"):
        resolve_policy(42)
    with pytest.raises(ValueError, match="tenants= only with"):
        resolve_policy(FairSharePolicy({"a": 1}), tenants={"b": 1})
    assert resolve_policy(None) is None
    assert isinstance(resolve_policy(None, {"a": 1}), FairSharePolicy)
    assert isinstance(resolve_policy("fifo"), FifoPolicy)
    assert isinstance(resolve_policy("fair"), FairSharePolicy)
    base = Policy()
    assert base.knows(None) and base.knows(DEFAULT_TENANT)
    assert not FairSharePolicy({"a": 1}).knows("ghost")


# -- engine integration ------------------------------------------------


def _one_shot(lm, prompt, steps):
    from elephas_tpu.models import generate

    return generate(
        lm, np.asarray(prompt, np.int32)[None], steps=steps,
        kv_cache=True,
    )[0]


MIXED = [[2, 3, 4, 5], [4, 5], [3, 4, 5, 2, 3, 4, 5, 2], [5, 2, 3]]


def test_submit_slo_knob_validation(lm):
    """ISSUE 10 satellite: loud validation — unknown tenant,
    non-positive deadline, deadline without a deadline-reading policy,
    tenant without any policy."""
    from elephas_tpu.serving import InferenceEngine

    bare = InferenceEngine(lm, num_slots=2)
    with pytest.raises(ValueError, match="without a policy"):
        bare.submit([2, 3], 2, tenant="a")
    with pytest.raises(ValueError, match="deadline-aware policy"):
        bare.submit([2, 3], 2, ttft_deadline_ms=100)
    assert not bare.scheduler.waiting  # nothing half-queued

    fair = InferenceEngine(
        lm, num_slots=2, policy=FairSharePolicy({"a": 1.0})
    )
    with pytest.raises(ValueError, match="unknown tenant"):
        fair.submit([2, 3], 2, tenant="ghost")
    with pytest.raises(ValueError, match="must be positive"):
        fair.submit([2, 3], 2, tenant="a", ttft_deadline_ms=0)
    fifo = InferenceEngine(
        lm, num_slots=2, policy=FifoPolicy({"a": 1.0})
    )
    with pytest.raises(ValueError, match="never reads deadlines"):
        fifo.submit([2, 3], 2, tenant="a", ttft_deadline_ms=100)
    with pytest.raises(TypeError, match="policy must be"):
        InferenceEngine(lm, num_slots=2, policy="fair")  # resolve first


def test_engine_admission_reject_is_graceful_and_counted(lm):
    """Overload admission control at the engine: the rejected request
    comes back done with AdmissionRejected (never queued), the
    admitted one is unaffected, and the reject lands in stats() and
    the per-tenant counters."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=1,
        policy=FairSharePolicy({"a": 1.0}, max_queue_tokens=14),
    )
    ok = engine.submit([2, 3, 4, 5], 8, tenant="a")   # debt 12 <= 14
    shed = engine.submit([2, 3, 4, 5], 8, tenant="a")  # 24 > 14
    assert shed.done and isinstance(shed.error, AdmissionRejected)
    assert shed.error.retry_after_s > 0
    assert len(engine.scheduler.waiting) == 1
    out = engine.run()
    assert ok.rid in out and shed.rid not in out
    np.testing.assert_array_equal(
        out[ok.rid], _one_shot(lm, [2, 3, 4, 5], 8)
    )
    s = engine.stats()
    assert s["admission_rejected"] == 1
    assert s["tenants"]["a"]["rejected"] == 1
    assert s["tenants"]["a"]["admitted"] == 1


def test_temp0_streams_bit_exact_under_any_policy(lm):
    """The decoding-neutrality contract (acceptance criterion): the
    policy reorders and rejects, never alters decoding — greedy token
    streams per request are identical under no policy, FIFO, and fair
    share (with deadlines), and all match one-shot generate()."""
    from elephas_tpu.serving import InferenceEngine

    refs = [_one_shot(lm, p, 6) for p in MIXED]

    def run(policy, with_slo):
        engine = InferenceEngine(lm, num_slots=2, policy=policy)
        kw = [
            dict(tenant=("a" if i % 2 else "b"),
                 ttft_deadline_ms=1000.0 * (i + 1))
            if with_slo else {}
            for i in range(len(MIXED))
        ]
        reqs = [
            engine.submit(p, 6, **k) for p, k in zip(MIXED, kw)
        ]
        out = engine.run()
        return [out[r.rid] for r in reqs]

    for policy, with_slo in (
        (None, False),
        (FifoPolicy({"a": 1, "b": 1}), False),
        (FairSharePolicy({"a": 1, "b": 2}), True),
    ):
        for got, ref in zip(run(policy, with_slo), refs):
            np.testing.assert_array_equal(got, ref)


def test_temp0_policy_streams_bit_exact_on_tp_mesh(lm):
    """Same neutrality on the TP mesh (acceptance criterion): the
    policy-ordered schedule is host-side and gang-replicated, so the
    sharded decode stays token-exact."""
    from elephas_tpu import SparkModel

    engine = SparkModel(lm, model_parallel=2).serve(
        num_slots=4, policy="fair", tenants={"a": 1.0, "b": 2.0},
    )
    reqs = [
        engine.submit(p, 6, tenant=("a" if i % 2 else "b"),
                      ttft_deadline_ms=500.0)
        for i, p in enumerate(MIXED[:3])
    ]
    out = engine.run()
    for req, p in zip(reqs, MIXED[:3]):
        np.testing.assert_array_equal(out[req.rid], _one_shot(lm, p, 6))


def test_tenant_stats_match_metrics_scrape(lm):
    """ISSUE 10 satellite: per-tenant queue depth, admitted/rejected,
    token and SLO counters are registry-backed — stats() and the
    Prometheus scrape read the SAME store, pinned by label (the PR 7/8
    contract)."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        lm, num_slots=2,
        policy=FairSharePolicy({"a": 1.0, "b": 1.0},
                               max_queue_tokens=40),
    )
    engine.submit(MIXED[0], 4, tenant="a", ttft_deadline_ms=60000)
    engine.submit(MIXED[1], 4, tenant="b")
    # over the debt bound (14 queued + 28 > 40) -> one reject for b
    engine.submit(MIXED[2], 20, tenant="b")
    engine.run()
    s = engine.stats()
    scrape = engine.scrape()
    eng_l = engine.telemetry_label

    def series(name, tenant):
        pat = (
            rf'^{name}{{engine="{eng_l}",tenant="{tenant}"}} '
            rf'([0-9.e+-]+)$'
        )
        vals = re.findall(pat, scrape, re.M)
        assert vals, f"{name}{{tenant={tenant}}} missing from scrape"
        return float(vals[0])

    for t in ("a", "b"):
        row = s["tenants"][t]
        assert series(
            "elephas_serving_tenant_admitted_total", t
        ) == row["admitted"]
        assert series(
            "elephas_serving_tenant_rejected_total", t
        ) == row["rejected"]
        assert series(
            "elephas_serving_tenant_tokens_total", t
        ) == row["tokens"]
        assert series(
            "elephas_serving_slo_met_total", t
        ) == row["slo_met"]
        assert series(
            "elephas_serving_tenant_queue_depth", t
        ) == row["queue_depth"] == 0
    assert s["tenants"]["a"]["slo_met"] == 1  # 60s budget: always met
    assert s["tenants"]["b"]["rejected"] == 1
    # the default tenant exists even when unused
    assert DEFAULT_TENANT in s["tenants"]
    engine.release_telemetry()


def test_policy_engine_has_zero_effect_when_unused(lm):
    """A policy-less engine's schedule is byte-for-byte the legacy
    FIFO path (no reorder hook, no debt checks) — guarded by the
    stats() surface staying config-independent."""
    from elephas_tpu.serving import InferenceEngine

    engine = InferenceEngine(lm, num_slots=2)
    engine.run([(p, 4) for p in MIXED[:2]])
    s = engine.stats()
    assert s["admission_rejected"] == 0
    assert s["tenants"] == {}
    assert "policy" not in s
