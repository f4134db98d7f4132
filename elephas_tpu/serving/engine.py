"""InferenceEngine — the continuous-batching serving loop.

One engine wraps one causal LM (a ``transformer_lm``-style FlashMHA
model) and serves any number of generation requests through two
program FAMILIES, compiled once each and reused for the life of the
server:

- one **prefill** program per prompt-length bucket (a closed, fixed
  ladder — :func:`~elephas_tpu.serving.scheduler.default_buckets`),
  writing a whole prompt's K/V into a leased slot in a single
  full-sequence forward;
- ONE **decode step** over the whole slot arena, advancing every
  in-flight sequence by one token at its own position (the vector
  write-cursor in :mod:`~elephas_tpu.serving.kv_cache`).

Each :meth:`InferenceEngine.step`: admit waiting requests into free
slots (prefill each), run the decode step, read the sampled tokens,
reclaim slots that hit EOS / their token budget. Requests can be
submitted at ANY time — they join the next step's admission wave
(iteration-level scheduling) — and finished slots free mid-flight, so
short sequences never hold long ones hostage the way one-shot batch
``generate()`` does.

Mesh-aware like the one-shot path: under a DP mesh the slot axis
shards over the batch axes; under TP the weights stay sharded through
``stateless_call`` with the planner's layouts and the arena shards
heads over the model axis. Every gang process must drive the engine
with the identical submission sequence (the SPMD contract ``generate``
already imposes); all read identical tokens.

Weights ride as jit ARGUMENTS, uploaded once at construction —
:meth:`refresh_weights` re-uploads after further training.
"""

from __future__ import annotations

import logging
import time

import numpy as np

from elephas_tpu import telemetry
from elephas_tpu.ops.flash_serving import span_bucket_for, span_buckets
from elephas_tpu.serving.blocks import BlockAllocator
from elephas_tpu.serving.kv_quant import (
    check_kv_dtype,
    quantize_rows_np,
)
from elephas_tpu.serving.kv_cache import (
    SlotKVCache,
    chunked_prefill_forward,
    prefill_forward,
    prefix_copy,
    token_decode_step,
    verify_forward,
)
from elephas_tpu.serving.paged_kv import (
    PagedKVPool,
    blocks_for,
    gather_blocks,
    paged_chunk_forward,
    paged_token_decode_step,
    paged_verify_forward,
    scatter_blocks,
    table_bucket_for,
    table_buckets,
)
from elephas_tpu.serving.policy import (
    DEFAULT_TENANT,
    AdmissionRejected,
    Policy,
)
from elephas_tpu.serving.speculative import (
    AcceptanceThrottle,
    resolve_drafter,
)
from elephas_tpu.serving.scheduler import (
    Admission,
    Request,
    Scheduler,
    default_buckets,
)

logger = logging.getLogger(__name__)


class RequestCancelled(RuntimeError):
    """Set as ``req.error`` when :meth:`InferenceEngine.cancel`
    reclaims an in-flight request (ISSUE 14): the request is ``done``
    without completing, its tokens-so-far kept for the caller."""


class _OffloadRecord:
    """Host-side K/V of a preempted request: dense block rows per
    layer plus the cursor state needed for a bit-exact resume. Rows
    are tuples of numpy arrays at the arena's STORED dtype — fp
    ``(k, v)`` pairs, or quantized ``(kq, vq, k_scale, v_scale)``
    4-tuples (ISSUE 19: offloaded blocks stay quantized on host, so
    the record is ~4x/~7x smaller and the resume round-trip is
    bitwise within the dtype)."""

    __slots__ = ("rows", "n_blocks", "cur_len")

    def __init__(self, rows, n_blocks, cur_len):
        self.rows = rows
        self.n_blocks = int(n_blocks)
        self.cur_len = int(cur_len)

    def nbytes(self) -> int:
        return sum(
            a.nbytes for leaves in self.rows.values() for a in leaves
        )


def _sample_dynamic(logits, key, temps, top_k, top_p):
    """Per-row sampling with a DYNAMIC temperature vector: rows with
    ``temps <= 0`` take greedy argmax (bit-identical to the one-shot
    path's temperature-0 branch), the rest temperature-scaled
    categorical under the engine's static top_k/top_p filters (same
    filter math as ``_sample_logits``)."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.transformer import _filter_logits

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = _filter_logits(
        logits / jnp.maximum(temps, 1e-6)[:, None], top_k, top_p
    )
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


class InferenceEngine:
    """Continuous-batching server over a slot-based KV cache.

    ``num_slots`` bounds concurrent in-flight sequences (rounded up to
    the mesh's batch-axis product so the arena shards evenly);
    ``buckets`` overrides the prompt-padding ladder; ``top_k`` /
    ``top_p`` are engine-static sampling filters; per-request
    ``temperature`` rides as data (0 = greedy).

    ``prefix_cache=True`` (ISSUE 4) keeps finished requests' prompt
    K/V resident as donor slots under a deterministic radix index:
    a new request sharing a prompt prefix pays one slot-to-slot copy
    plus suffix-only prefill instead of recomputing the prefix.
    ``prefill_chunk=c`` splits prefill into ``c``-token chunks run
    under a per-step token budget (``prefill_budget``, default one
    chunk) BETWEEN decode windows, so a long prompt arrival no longer
    stalls every in-flight request's next token. Both compose; both
    keep the compiled shape set closed (``compile_stats()``). Chunk
    boundaries consume PRNG key splits, so temp>0 sampling streams
    differ from the unchunked engine (still deterministic per
    configuration); temperature-0 tokens are exact either way.

    ``paged=True`` (ISSUE 7) swaps the fixed arena for the paged
    block pool (``block_size=``, ``num_blocks=``): per-request block
    reservations instead of per-slot maxlen rows, copy-free prefix
    sharing by refcount when ``prefix_cache=True``, and — with
    ``preemption=True`` — priority-ordered preempt → host-offload →
    resume under pool pressure (bit-exact on resume). A request that
    can never fit the pool is rejected gracefully at ``submit()``
    (``req.error``) instead of wedging the queue. Compiled shapes
    stay a closed set: one decode program per block-table bucket,
    one chunk program per (width, table bucket).

    ``speculative=True`` (ISSUE 8) decodes draft-and-verify: a cheap
    drafter (``spec_drafter``: ``"ngram"`` prompt-lookup by default, or
    a small draft model / custom :class:`~elephas_tpu.serving.\
speculative.Drafter`) proposes up to ``spec_k`` tokens per slot and ONE
    batched verify forward scores them all, accepting the longest
    greedy-matching prefix plus a bonus token — several tokens per
    target forward, bit-exact at temperature 0 (temp>0 streams diverge
    from plain decode like chunked prefill: deterministic per config,
    differently keyed). A per-request acceptance throttle falls back to
    plain decode when drafts stop landing and re-probes periodically.
    Works on both arenas; one verify program per window width (fixed)
    or (width, table bucket) pair (paged) keeps the shape set closed.

    ``policy=`` (ISSUE 10) installs an SLO admission policy
    (:mod:`~elephas_tpu.serving.policy`): per-tenant token-weighted
    fair share, deadline-EDF ordering with aging, overload admission
    control (loud :class:`~elephas_tpu.serving.policy.\
AdmissionRejected` at submit), policy-derived preemption priority, and
    tenant-labeled telemetry + SLO-attainment counters. The policy
    reorders and rejects — it NEVER touches decoding, so temperature-0
    token streams stay bit-exact per request under any policy.

    ``attention="flash"`` (ISSUE 11, the default) runs every serving
    program's attention core through the tiled online-softmax kernel
    (:mod:`elephas_tpu.ops.flash_serving`): full-bucket prefill skips
    strictly-future tiles statically, chunk/verify stream the arena
    row in tiles, and the fixed arena's decode/chunk attend over a
    SPAN BUCKET covering the live residents instead of ``maxlen``
    (compiled per touched bucket — a closed ladder). ``"naive"``
    selects the seed full-materialized path, kept as the bitwise
    parity oracle. Flash logits match naive to float tolerance;
    temperature-0 token streams are exact (see docs/API.md).

    ``flight_recorder=`` (ISSUE 12) bounds the per-request **flight
    recorder**: the engine assembles a structured lifecycle record for
    every request (admission verdict + queue wait, admission kind and
    reuse length, prefill chunks, preempt/resume, spec rounds, per-
    token step indices, finish reason) and keeps the last N finished
    ones queryable via :meth:`explain` (and the gateway's
    ``GET /v1/requests/{rid}/trace``). ``0``/``None`` — or
    construction under telemetry null mode — turns recording off
    entirely (:meth:`explain` then raises, loudly). Records are
    ordered by scheduler steps and tracer sequence numbers; wall time
    appears only in export-only fields, so recording never perturbs
    the gang-deterministic schedule.

    ``sp_prefill=`` (ISSUE 11, paged + unmeshed engines) arms
    sequence-parallel long-prompt prefill: a cold prompt of at least
    ``sp_threshold`` tokens (default ``maxlen // 2``) runs ONE
    ring/Ulysses-sharded forward over the given mesh's ``sp_axis``,
    lands its K/V straight into the slot's reserved pool blocks, and
    decodes unmeshed — removing the single-device ceiling on prompt
    ingestion (``sp_mechanism="ring"`` has no head-count constraint;
    ``"ulysses"`` needs ``num_heads % axis_size == 0``).

    Pipeline parallelism lives in its own engine (ISSUE 15):
    :class:`~elephas_tpu.serving.pp_engine.PPEngine` runs continuous
    batching over a PP×TP mesh with per-stage paged KV pools and
    microbatched decode waves — construct THIS engine via
    ``SparkModel.serve()`` on a DP/TP mesh (or directly on no mesh),
    and the PP engine when model depth no longer fits one chip group.
    """

    def __init__(self, model, num_slots: int = 8, mesh=None,
                 batch_axes=("data",), model_axis=None, rules=None,
                 top_k: int | None = None, top_p: float | None = None,
                 seed: int = 0, buckets=None, steps_per_sync: int = 1,
                 prefix_cache: bool = False,
                 prefix_min_reuse: int = 1,
                 prefill_chunk: int | None = None,
                 prefill_budget: int | None = None,
                 paged: bool = False,
                 block_size: int | None = None,
                 num_blocks: int | None = None,
                 preemption: bool = False,
                 kv_dtype: str = "fp",
                 speculative: bool = False,
                 spec_k: int | None = None,
                 spec_drafter=None,
                 policy=None,
                 attention: str = "flash",
                 sp_prefill=None,
                 sp_axis: str = "seq",
                 sp_threshold: int | None = None,
                 sp_mechanism: str = "ring",
                 flight_recorder: int | None = 256):
        import jax
        import jax.numpy as jnp

        from elephas_tpu.models.transformer import (
            validate_token_decode_model,
        )

        flash_layers, _stock, _gqa = validate_token_decode_model(
            model,
            what="the serving engine",
            hint="use one-shot generate()",
            allow_stock=False,
        )
        self.model = model
        self.maxlen = int(model.inputs[0].shape[1])
        self.vocab = int(model.outputs[0].shape[-1])
        self.top_k = top_k
        self.top_p = top_p
        if top_k is not None and not 0 < int(top_k) <= self.vocab:
            raise ValueError(
                f"top_k={top_k} outside (0, vocab={self.vocab}]"
            )
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p={top_p} outside (0, 1]")

        self.mesh = mesh
        if isinstance(batch_axes, str):
            batch_axes = (batch_axes,)
        self.batch_axes = tuple(batch_axes)
        self.model_axis = model_axis
        if mesh is not None:
            missing = [a for a in self.batch_axes if a not in mesh.shape]
            if missing:
                raise ValueError(
                    f"batch_axes {missing} not in mesh axes "
                    f"{tuple(mesh.shape)}"
                )
            dp = int(
                np.prod([mesh.shape[a] for a in self.batch_axes])
            )
            if num_slots % dp:
                rounded = num_slots + (-num_slots) % dp
                logger.info(
                    "rounding num_slots %d -> %d (multiple of the "
                    "batch-axis product %d)", num_slots, rounded, dp,
                )
                num_slots = rounded
        if num_slots < 1:
            raise ValueError(f"num_slots={num_slots} < 1")
        self.num_slots = int(num_slots)

        if buckets is not None:
            buckets = tuple(int(b) for b in buckets)
            bad = [b for b in buckets if not 0 < b <= self.maxlen]
            if bad:
                raise ValueError(
                    f"buckets {bad} outside (0, maxlen={self.maxlen}] — "
                    f"a bucket beyond maxlen would overflow the KV arena"
                )

        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if not 0 < prefill_chunk <= self.maxlen:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} outside "
                    f"(0, maxlen={self.maxlen}]"
                )
        self.prefill_chunk = prefill_chunk
        if prefill_budget is not None:
            if prefill_chunk is None:
                raise ValueError(
                    "prefill_budget requires prefill_chunk — without "
                    "chunking, prefill is a single blocking wave and "
                    "the budget would be silently ignored"
                )
            if int(prefill_budget) < 1:
                raise ValueError(f"prefill_budget={prefill_budget} < 1")
        # per-step() prefill token budget (chunked mode): default one
        # chunk's worth — the typical long-prompt arrival streams in at
        # one chunk per decode window, bounding in-flight inter-token
        # latency at roughly one chunk of extra compute
        self._prefill_budget = (
            int(prefill_budget) if prefill_budget is not None
            else (prefill_chunk or 0)
        )

        # -- paged arena knobs (ISSUE 7) -------------------------------
        self.paged = bool(paged)
        if not self.paged:
            if block_size is not None or num_blocks is not None:
                raise ValueError(
                    "block_size/num_blocks require paged=True — the "
                    "fixed arena has no blocks, silently ignoring the "
                    "knobs would misreport capacity"
                )
            if preemption:
                raise ValueError(
                    "preemption requires paged=True — the fixed arena "
                    "has no block pool to swap out of"
                )
            self.block_size = None
            self.num_blocks = None
        else:
            bs = 16 if block_size is None else int(block_size)
            if not 0 < bs <= self.maxlen:
                raise ValueError(
                    f"block_size={bs} outside (0, maxlen={self.maxlen}]"
                )
            self.block_size = bs
            # blocks any single request may need (full maxlen context)
            self.max_blocks_per_slot = blocks_for(self.maxlen, bs)
            # default pool: capacity parity with the fixed arena —
            # every slot could still hold a full-maxlen context; the
            # paged win is that short requests stop RESERVING that
            nb = (
                int(num_blocks) if num_blocks is not None
                else self.num_slots * self.max_blocks_per_slot
            )
            if nb < 1:
                raise ValueError(f"num_blocks={nb} < 1")
            self.num_blocks = nb
            self._tbuckets = table_buckets(self.max_blocks_per_slot)
        self.preemption = bool(preemption)

        # -- quantized paged KV (ISSUE 19) -----------------------------
        # "fp" (default) stores f32 pool blocks — the parity oracle,
        # bit-for-bit the historical engine. "int8"/"int4" store
        # quantized codes + per-(position, head) f32 scales: quantize
        # on write inside the paged programs, dequantize inside the
        # flash span tiles (kv_quant module). Temp-0 exactness holds
        # WITHIN a dtype (offload/resume/migration move quantized
        # blocks bit-identically); cross-dtype quality is gated
        # against the fp oracle (docs/API.md "Quantized KV").
        check_kv_dtype(kv_dtype)
        if kv_dtype != "fp" and not self.paged:
            raise ValueError(
                "kv_dtype requires paged=True — the fixed slot arena "
                "has no quantized storage path; silently serving fp "
                "would misreport the KV byte budget"
            )
        self.kv_dtype = kv_dtype

        # -- speculative decoding knobs (ISSUE 8) ----------------------
        self.speculative = bool(speculative)
        if not self.speculative:
            if spec_k is not None or spec_drafter is not None:
                raise ValueError(
                    "spec_k/spec_drafter require speculative=True — "
                    "silently ignoring the knobs would misreport how "
                    "the engine decodes"
                )
            self.spec_k = None
        else:
            k = 4 if spec_k is None else int(spec_k)
            # the verify window feeds 1 (last token) + k drafts; its
            # widest write lands at position cursor + k, capped by the
            # per-slot draft budget at maxlen - 1 — k itself only needs
            # to leave room for at least one real position
            if not 1 <= k < self.maxlen:
                raise ValueError(
                    f"spec_k={k} outside [1, maxlen={self.maxlen})"
                )
            self.spec_k = k

        # -- attention kernel selection (ISSUE 11) ---------------------
        # "flash" (default) = tiled online-softmax serving programs
        # (ops/flash_serving): O(span) score memory, static causal tile
        # skipping in full-bucket prefill, span-bucketed block-span
        # reads in fixed-arena decode/chunk. "naive" = the seed
        # full-materialized einsum/softmax path, kept selectable as the
        # bitwise parity oracle. Flash output matches naive to float
        # tolerance and temp-0 token streams exactly (documented in
        # docs/API.md "Attention kernels").
        if attention not in ("flash", "naive"):
            raise ValueError(
                f"attention must be 'flash' or 'naive', got "
                f"{attention!r}"
            )
        self.attention = attention
        # fixed-arena span ladder: flash decode/chunk/verify programs
        # attend over cache[:, :span] for a bucketed span covering the
        # live residents — compiled once per touched bucket (a closed
        # set; the floor keeps small models at ONE decode compile)
        self._sbuckets = span_buckets(self.maxlen)

        # -- sequence-parallel long-prompt prefill (ISSUE 11) ----------
        if sp_prefill is not None:
            if not self.paged:
                raise ValueError(
                    "sp_prefill requires paged=True — the SP prefill "
                    "lands K/V into the block pool (the fixed arena "
                    "has no block-granular landing path)"
                )
            if mesh is not None:
                raise ValueError(
                    "sp_prefill requires an UNMESHED engine — the SP "
                    "mesh serves prefill only, and decode proceeds "
                    "unmeshed on the landed blocks (a decode mesh "
                    "would double-shard the pool)"
                )
            if sp_mechanism not in ("ring", "ulysses"):
                raise ValueError(
                    f"sp_mechanism must be 'ring' or 'ulysses', got "
                    f"{sp_mechanism!r}"
                )
            if sp_axis not in sp_prefill.shape:
                raise ValueError(
                    f"sp_axis {sp_axis!r} not in the SP mesh axes "
                    f"{tuple(sp_prefill.shape)}"
                )
            sp_w = int(sp_prefill.shape[sp_axis])
            if sp_w & (sp_w - 1):
                # pad lengths are powers of two (sp_pad_len), and a
                # non-power-of-two shard count divides none of them —
                # the shard_map would raise mid-serve on the first
                # long prompt; fail HERE instead
                raise ValueError(
                    f"sp_prefill axis {sp_axis!r} has size {sp_w} — "
                    f"SP prefill pads prompts to power-of-two "
                    f"lengths, which only tile over a power-of-two "
                    f"shard count; reshape the mesh"
                )
            if sp_mechanism == "ulysses":
                bad = [
                    (name, h) for name, h, _d in (
                        (l.name, int(l.num_heads), int(l.head_dim))
                        for l in flash_layers
                    ) if h % sp_w
                ]
                if bad:
                    raise ValueError(
                        f"ulysses SP prefill needs num_heads divisible "
                        f"by the seq axis size ({sp_w}); offending "
                        f"layers: {bad} — use sp_mechanism='ring'"
                    )
            if sp_threshold is not None and int(sp_threshold) < 1:
                raise ValueError(
                    f"sp_threshold={sp_threshold} < 1"
                )
        elif sp_threshold is not None or sp_axis != "seq" \
                or sp_mechanism != "ring":
            raise ValueError(
                "sp_threshold/sp_axis/sp_mechanism require sp_prefill= "
                "(an SP mesh) — silently ignoring them would misreport "
                "how long prompts prefill"
            )
        self.sp_mesh = sp_prefill
        self.sp_axis = sp_axis
        self.sp_mechanism = sp_mechanism
        # prompts at or above the threshold prefill over the SP mesh;
        # default: half the model's context (the regime where a single
        # device's prefill dominates TTFT)
        self.sp_threshold = (
            int(sp_threshold) if sp_threshold is not None
            else max(1, self.maxlen // 2)
        ) if sp_prefill is not None else None

        # -- SLO admission policy (ISSUE 10) ---------------------------
        if policy is not None and not isinstance(policy, Policy):
            raise TypeError(
                f"policy must be a serving.policy.Policy (or None), "
                f"got {type(policy).__name__} — build one with "
                f"FairSharePolicy(tenants=...) or resolve_policy()"
            )
        self.policy = policy

        if self.paged:
            self.arena = PagedKVPool(
                flash_layers, self.num_blocks, self.block_size,
                mesh=mesh, batch_axes=self.batch_axes,
                model_axis=model_axis, kv_dtype=self.kv_dtype,
            )
        else:
            self.arena = SlotKVCache(
                flash_layers, self.num_slots, self.maxlen,
                mesh=mesh, batch_axes=self.batch_axes,
                model_axis=model_axis,
            )
        # -- telemetry identity captured EARLY so the allocator's gauge
        # shares the engine's label set (release_telemetry retires them
        # together); the metric definitions follow below
        treg = telemetry.registry()
        self._telemetry_registry = treg
        self._tracer = telemetry.tracer()
        eid = telemetry.instance_label()
        self.telemetry_label = eid
        # -- per-request flight recorder + compile watching (ISSUE 12):
        # both captured at construction like the registry/tracer, so an
        # engine built under null mode stays zero-overhead for life.
        # _flight_live holds in-flight records (rid -> dict); finished
        # lifecycles move into the bounded FlightRecorder ring.
        if flight_recorder is not None and int(flight_recorder) < 0:
            raise ValueError(
                f"flight_recorder={flight_recorder} < 0 — use 0/None "
                f"to disable, or a positive record capacity"
            )
        fr_capacity = 0 if flight_recorder is None else int(flight_recorder)
        self._flight = (
            telemetry.FlightRecorder(fr_capacity)
            if fr_capacity and not telemetry.null_mode() else None
        )
        self._flight_live: dict[int, dict] = {}
        # jit-compile spans: each dispatch that grows a program's jit
        # cache is recorded as a named "jit.compile" span, so a
        # mid-serve recompile shows up ON the request timeline instead
        # of being reconstructed by hand (the PR-9 light-tenant TTFT
        # forensics). Off under null mode — the cache-size probe is
        # cheap, but null means null.
        self._trace_compiles = not telemetry.null_mode()

        allocator = None
        if self.paged:
            allocator = BlockAllocator(
                self.num_blocks, self.block_size,
                free_gauge=treg.gauge(
                    "elephas_serving_blocks_free",
                    "Unleased KV pool blocks (paged arena)",
                    labels=("engine",),
                ).labels(engine=eid),
            )
        self.scheduler = Scheduler(
            self.num_slots, buckets or default_buckets(self.maxlen),
            prefix_cache=prefix_cache,
            prefix_min_reuse=prefix_min_reuse,
            allocator=allocator,
            preemption=preemption,
            policy=policy,
        )
        self._rules = rules
        self._seed = int(seed)
        # slots mid-chunked-prefill: slot -> [Admission, progress]
        # (progress = prompt tokens already resident, incl. any copied
        # prefix; the slot joins decode only once progress == len(prompt))
        self._prefilling: dict[int, list] = {}
        # slots whose in-flight prefill straddled a weight refresh —
        # their rows mix weight generations and never become donors
        self._stale_prefill: set[int] = set()
        # completed requests, BOUNDED: a server alive for millions of
        # requests must not grow host memory linearly — callers keep
        # their own Request handles from submit(); this registry only
        # feeds stats()/tests and evicts oldest past the bound
        self.finished: dict[int, Request] = {}
        self._finished_bound = 4096
        self._protected: set[int] = set()
        # warning cadence for _evict_finished: a PLAIN count, never the
        # registry counter (which reads 0 under telemetry null mode)
        self._evictions_seen = 0

        # -- telemetry (ISSUE 5): the registry/tracer captured above
        # are the engine's for life, so an engine built under null mode
        # stays ~zero-overhead even if the global flag flips later.
        # Counters are report-only views (`total_generated` etc. read
        # them back); nothing below drives control flow.
        def _c(name, help_):
            return treg.counter(
                name, help_, labels=("engine",)
            ).labels(engine=eid)

        self._m_tokens = _c(
            "elephas_serving_tokens_generated_total",
            "Generated tokens emitted by the serving engine",
        )
        self._m_finished = _c(
            "elephas_serving_requests_finished_total",
            "Requests that completed (EOS or token budget)",
        )
        self._m_finished_evicted = _c(
            "elephas_serving_finished_evicted_total",
            "Finished requests evicted from the bounded result registry "
            "before the caller consumed them",
        )
        self._m_decode_windows = _c(
            "elephas_serving_decode_windows_total",
            "Arena-wide decode window dispatches",
        )
        self._m_prefill_stalls = _c(
            "elephas_serving_prefill_stall_slots_total",
            "Mid-prefill slots deferred to a later step because the "
            "per-step chunk-token budget was exhausted",
        )
        self._m_ttft = treg.histogram(
            "elephas_serving_ttft_seconds",
            "Submit-to-first-token latency of served requests",
            labels=("engine",),
        ).labels(engine=eid)
        self._m_itl = treg.histogram(
            "elephas_serving_inter_token_seconds",
            "Arrival gap between consecutive tokens of one request",
            labels=("engine",),
        ).labels(engine=eid)
        # paged-arena accounting (ISSUE 7): counters exist in BOTH
        # modes so stats() keys never vary by config — the fixed arena
        # simply never increments them
        self._m_preemptions = _c(
            "elephas_serving_preemptions_total",
            "Requests preempted (blocks offloaded to host) so a "
            "higher-priority arrival could admit",
        )
        self._m_resumes = _c(
            "elephas_serving_resumes_total",
            "Preempted requests restored from host offload",
        )
        self._m_offload_blocks = _c(
            "elephas_serving_offloaded_blocks_total",
            "KV pool blocks swapped to host memory by preemption",
        )
        self._m_rejected = _c(
            "elephas_serving_rejected_total",
            "Requests rejected at submit because prompt + "
            "max_new_tokens can never fit the block pool",
        )
        # speculative decoding (ISSUE 8): counters exist in BOTH modes
        # (keys in stats() never vary by config); a non-speculative
        # engine simply never increments them
        self._m_spec_drafted = _c(
            "elephas_serving_spec_draft_tokens_total",
            "Drafted tokens scored by the speculative verify forward",
        )
        self._m_spec_accepted = _c(
            "elephas_serving_spec_accepted_tokens_total",
            "Drafted tokens accepted by the longest-matching-prefix "
            "rule (each saved one target-model decode step)",
        )
        self._m_spec_rounds = _c(
            "elephas_serving_spec_verify_rounds_total",
            "Batched speculative verify dispatches",
        )
        self._m_spec_throttled = _c(
            "elephas_serving_spec_throttled_total",
            "Times a request's collapsed acceptance rate tripped the "
            "drafting throttle (fell back to plain decode)",
        )
        # SLO scheduling (ISSUE 10): policy admission rejects (distinct
        # from the paged never-fits counter — this one is load shed,
        # not a capacity impossibility), plus tenant-labeled series.
        # Families exist in EVERY mode so stats() keys never vary by
        # config; children materialize per tenant label on first use.
        self._m_admission_rejected = _c(
            "elephas_serving_admission_rejected_total",
            "Requests rejected at submit by the policy's overload "
            "admission control (429 on the gateway)",
        )
        # lifecycle control (ISSUE 14): cancellation + live migration.
        # Counters exist in every mode (stats() keys never vary by
        # config); engines outside a fleet simply never migrate.
        self._m_cancelled = _c(
            "elephas_serving_cancelled_total",
            "In-flight requests cancelled before completion "
            "(slot/blocks reclaimed; gateway client disconnects land "
            "here)",
        )
        self._m_migrated_out = _c(
            "elephas_serving_migrated_out_total",
            "Requests exported off this engine as migration records "
            "(fleet drain / rebalancing)",
        )
        self._m_migrated_in = _c(
            "elephas_serving_migrated_in_total",
            "Requests adopted from another replica's migration record",
        )
        # quantized KV + scoring (ISSUE 19): counters exist in EVERY
        # mode (stats() keys never vary by config) — fp engines count
        # fp-sized offload/export bytes, non-scoring callers simply
        # never increment score requests
        self._m_offload_bytes = _c(
            "elephas_serving_kv_quant_offload_bytes_total",
            "Host bytes written by preemption offload records (KV "
            "block rows + scales at the arena's stored kv_dtype)",
        )
        self._m_export_bytes = _c(
            "elephas_serving_kv_quant_export_bytes_total",
            "Payload bytes of migration/handoff export records "
            "(per-layer arrays at the stored kv_dtype, header "
            "excluded) — the counted wire-size the bench quant "
            "section gates on",
        )
        self._m_score_requests = _c(
            "elephas_serving_score_requests_total",
            "Completions scored through score() / POST /v1/score "
            "(one verify-style forward each, engine state untouched)",
        )

        def _tc(name, help_):
            return treg.counter(name, help_, labels=("engine", "tenant"))

        self._mf_tenant_tokens = _tc(
            "elephas_serving_tenant_tokens_total",
            "Generated tokens emitted, by tenant",
        )
        self._mf_tenant_admitted = _tc(
            "elephas_serving_tenant_admitted_total",
            "Requests admitted into KV slots, by tenant",
        )
        self._mf_tenant_rejected = _tc(
            "elephas_serving_tenant_rejected_total",
            "Requests rejected at submit, by tenant (admission "
            "control and paged never-fit alike)",
        )
        self._mf_slo_met = _tc(
            "elephas_serving_slo_met_total",
            "First tokens that landed within their declared TTFT "
            "deadline, by tenant",
        )
        self._mf_slo_missed = _tc(
            "elephas_serving_slo_missed_total",
            "First tokens that landed after their declared TTFT "
            "deadline, by tenant",
        )
        # per-tenant queue depth: callback gauges reading the live
        # scheduler queue — scrape and stats() see the same truth with
        # zero update plumbing (and zero chance of drift)
        self._mf_tenant_queue = treg.gauge(
            "elephas_serving_tenant_queue_depth",
            "Waiting requests queued, by tenant",
            labels=("engine", "tenant"),
        )
        if self.policy is not None:
            sched = self.scheduler
            for t in self.policy.tenant_names:
                self._mf_tenant_queue.labels(
                    engine=eid, tenant=t
                ).set_function(lambda t=t: sched.waiting_count(t))
                # materialize the zero-valued children now so a scrape
                # before the first request already shows every tenant
                for fam in (
                    self._mf_tenant_tokens, self._mf_tenant_admitted,
                    self._mf_tenant_rejected, self._mf_slo_met,
                    self._mf_slo_missed,
                ):
                    fam.labels(engine=eid, tenant=t)

        # attention-kernel info gauge (ISSUE 11): the kernel rides as a
        # LABEL (value is a constant 1) so dashboards can join "which
        # kernel is this engine on" against any of its other series
        treg.gauge(
            "elephas_serving_attn_kernel",
            "Attention kernel the serving programs run (info gauge: "
            "constant 1, kernel name in the label)",
            labels=("engine", "kernel"),
        ).labels(engine=eid, kernel=self.attention).set(1)
        # kv_dtype info gauge (ISSUE 19): same join-by-label idiom as
        # the kernel gauge — which storage dtype this arena speaks
        treg.gauge(
            "elephas_serving_kv_quant_mode",
            "KV storage dtype of the paged arena (info gauge: "
            "constant 1, dtype name in the label)",
            labels=("engine", "kv_dtype"),
        ).labels(engine=eid, kv_dtype=self.kv_dtype).set(1)
        # weight generation (ISSUE 20): plain value gauge (not an info
        # gauge — generations are ordered and dashboards graph the
        # fleet converging), re-set by every stamped refresh_weights()
        self.weight_version = 0
        self._g_weight_version = treg.gauge(
            "elephas_serving_weight_version",
            "Weight generation the engine currently serves "
            "(0 = unversioned; stamped by refresh_weights(version=))",
            labels=("engine",),
        ).labels(engine=eid)
        self._g_weight_version.set(self.weight_version)
        # per-bucket prefill-token histogram (ISSUE 11): one observation
        # per completed prefill, labeled by the compiled bucket it ran
        # through — Chrome traces say WHERE long prompts spend TTFT,
        # this says how often each bucket is actually exercised
        self._mf_prefill_tokens = treg.histogram(
            "elephas_serving_prefill_tokens",
            "Prompt tokens ingested per completed prefill, by prompt "
            "size class (the prompt-bucket ladder; sp<S> = sequence-"
            "parallel padded length). NOTE: chunked/paged prefills "
            "compile per chunk width, not per prompt bucket — this "
            "label classifies the PROMPT, not the program.",
            labels=("engine", "bucket"),
        )
        treg.gauge(
            "elephas_serving_slots", "KV-cache slots in the arena",
            labels=("engine",),
        ).labels(engine=eid).set(self.num_slots)
        treg.gauge(
            "elephas_serving_kv_arena_bytes",
            "Host-side size estimate of the full KV arena at its "
            "stored dtype (f32, or int8/int4 codes + scales)",
            labels=("engine",),
        ).labels(engine=eid).set(self.arena.nbytes())
        if self.paged:
            # named WITHOUT the _total suffix (ISSUE 12): OpenMetrics
            # reserves _total for counters, and this is a gauge — a
            # spec-strict scraper of the exemplar exposition would
            # reject the whole page over it (was
            # elephas_serving_blocks_total through PR 11)
            treg.gauge(
                "elephas_serving_kv_blocks",
                "KV pool blocks in the paged arena",
                labels=("engine",),
            ).labels(engine=eid).set(self.num_blocks)

        maxlen, arena = self.maxlen, self.arena

        def _constrain_all(caches):
            # leaf-generic over the entry arity: fp (k, v) pairs and
            # quantized (kq, vq, k_scale, v_scale) 4-tuples alike
            heads = {name: h for name, h, _d in arena.specs}
            return {
                name: tuple(
                    arena.constrain(z, heads[name]) for z in leaves
                )
                for name, leaves in caches.items()
            }

        def _vec(z):
            if mesh is None:
                return z
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.lax.with_sharding_constraint(
                z, NamedSharding(mesh, P(self.batch_axes))
            )

        def init_state():
            caches = arena.init()
            lengths = _vec(jnp.zeros((self.num_slots,), jnp.int32))
            last = _vec(jnp.zeros((self.num_slots,), jnp.int32))
            temps = _vec(jnp.zeros((self.num_slots,), jnp.float32))
            return caches, lengths, last, temps

        attn_kernel = self.attention

        def prefill(w, caches, lengths, last, temps, tokens_rows,
                    p_lens, admit, new_temps, key):
            logits, caches = prefill_forward(
                model, w, tokens_rows, caches, admit, maxlen,
                attention=attn_kernel,
            )
            caches = _constrain_all(caches)
            # each row's next-token logits sit at its own prompt end —
            # one-hot contraction over the bucket axis (exact select,
            # and slot-local under the mesh unlike a per-row gather)
            S = tokens_rows.shape[1]
            at_end = (
                (p_lens - 1)[:, None] == jnp.arange(S)[None, :]
            ).astype(logits.dtype)
            last_logits = jnp.einsum("bs,bsv->bv", at_end, logits)
            key, sub = jax.random.split(key)
            firsts = _sample_dynamic(
                last_logits, sub, new_temps, self.top_k, self.top_p
            )
            lengths = _vec(jnp.where(admit, p_lens, lengths))
            last = _vec(jnp.where(admit, firsts, last))
            temps = _vec(jnp.where(admit, new_temps, temps))
            return caches, lengths, last, temps, key, firsts

        # multi-step scheduling (the vLLM/TensorRT-LLM trick): decode
        # `steps_per_sync` tokens per dispatch inside ONE fori_loop, so
        # program-launch + host-sync cost amortizes over the window.
        # Scheduling decisions (admission, reclaim) then happen at
        # window boundaries — k=1 is pure Orca iteration-level
        # scheduling; larger k trades up to k-1 wasted positions on a
        # mid-window finish for far fewer host round-trips. Greedy
        # (temperature-0) tokens are identical across k; sampled
        # streams match only while windows are fully consumed — a
        # drain that abandons a window tail still advanced the key k
        # times, so later temp>0 requests may sample differently than
        # under k=1 (deterministic per (seed, k, schedule) either way).
        k_window = max(1, int(steps_per_sync))
        self.steps_per_sync = k_window

        def decode(w, caches, lengths, last, temps, active, key,
                   span=None):
            # `active` masks idle / mid-chunked-prefill / prefix-donor
            # slots OUT of the cache write and cursor advance — their
            # resident rows must survive the window; active slots' math
            # is untouched (bit-identical to the unmasked program).
            # `span` (STATIC, flash mode): the attended row slice — a
            # span bucket covering every live resident + the window.
            def body(i, carry):
                caches, lengths, last, key, toks = carry
                positions = jnp.minimum(lengths, maxlen - 1)
                logits, caches = token_decode_step(
                    model, w, last, positions, caches, maxlen,
                    active=active, attention=attn_kernel, span=span,
                )
                caches = _constrain_all(caches)
                key, sub = jax.random.split(key)
                sampled = _sample_dynamic(
                    logits, sub, temps, self.top_k, self.top_p
                )
                lengths = _vec(jnp.where(
                    active, jnp.minimum(lengths + 1, maxlen), lengths
                ))
                toks = toks.at[i].set(sampled)
                last = _vec(jnp.where(active, sampled, last))
                return caches, lengths, last, key, toks

            toks0 = jnp.zeros((k_window, self.num_slots), jnp.int32)
            caches, lengths, last, key, toks = jax.lax.fori_loop(
                0, k_window, body, (caches, lengths, last, key, toks0)
            )
            return caches, lengths, last, key, toks

        def chunk_step(w, caches, lengths, last, temps, tokens, offs,
                       clens, act, fin, p_lens, new_temps,
                       src_idx, copy_mask, copy_len, key,
                       has_copy: bool, span=None):
            """One bounded prefill chunk for every slot in ``act`` —
            cold chunked prefill and post-copy suffix prefill alike.
            Slots in ``fin`` end their prompt inside this chunk: their
            first token samples from the prompt-end logits row and they
            join the decode population.

            Prefix-cache transplants FUSE into this program (``src_idx``
            / ``copy_mask`` / ``copy_len``; all-False mask = no copy,
            same compiled shape): a hit admission whose suffix prefills
            immediately pays ONE dispatch, not copy-then-chunk — on
            dispatch-bound backends the launch overhead rivals the tiny
            suffix compute itself. The standalone copy program below
            stays for chunked-queue admissions, where the copy must
            land while the wave still pins the donor but the first
            chunk call may be budget-deferred to a later step.

            ``has_copy`` is STATIC: the donor gather costs O(slots² ·
            maxlen · H · Dh) per layer whether or not the mask selects
            anything (the mask is runtime data XLA cannot elide), so
            copy-free calls — every budgeted chunk in chunked mode —
            trace a variant without it. Two entries per width at most,
            and each mode only ever uses one."""
            if has_copy:
                caches = _constrain_all(prefix_copy(
                    caches, src_idx, copy_mask, copy_len, maxlen
                ))
            logits, caches = chunked_prefill_forward(
                model, w, tokens, caches, offs, clens, act, maxlen,
                attention=attn_kernel, span=span,
            )
            caches = _constrain_all(caches)
            C = tokens.shape[1]
            at_end = (
                (p_lens - offs - 1)[:, None] == jnp.arange(C)[None, :]
            ).astype(logits.dtype)
            last_logits = jnp.einsum("bc,bcv->bv", at_end, logits)
            key, sub = jax.random.split(key)
            firsts = _sample_dynamic(
                last_logits, sub, new_temps, self.top_k, self.top_p
            )
            lengths = _vec(jnp.where(fin, p_lens, lengths))
            last = _vec(jnp.where(fin, firsts, last))
            temps = _vec(jnp.where(fin, new_temps, temps))
            return caches, lengths, last, temps, key, firsts

        def copy_prefix(caches, src_idx, copy_mask, copy_len):
            return _constrain_all(
                prefix_copy(caches, src_idx, copy_mask, copy_len, maxlen)
            )

        # -- paged programs (ISSUE 7): same sampling/advance math as
        # the fixed-arena decode/chunk bodies, with storage indirected
        # through the block tables. Compiled once per table-length
        # bucket (decode) / (chunk width, table bucket) pair — tables
        # ride as a traced [num_slots, T] argument, so only the bucket
        # SHAPE triggers a compile.
        def paged_decode(w, caches, tables, lengths, last, temps,
                         active, key):
            def body(i, carry):
                caches, lengths, last, key, toks = carry
                positions = jnp.minimum(lengths, maxlen - 1)
                logits, caches = paged_token_decode_step(
                    model, w, last, positions, caches, tables,
                    self.block_size, maxlen, active,
                    local=mesh is None, attention=attn_kernel,
                    kv_dtype=self.kv_dtype,
                )
                caches = _constrain_all(caches)
                key, sub = jax.random.split(key)
                sampled = _sample_dynamic(
                    logits, sub, temps, self.top_k, self.top_p
                )
                lengths = _vec(jnp.where(
                    active, jnp.minimum(lengths + 1, maxlen), lengths
                ))
                toks = toks.at[i].set(sampled)
                last = _vec(jnp.where(active, sampled, last))
                return caches, lengths, last, key, toks

            toks0 = jnp.zeros((k_window, self.num_slots), jnp.int32)
            caches, lengths, last, key, toks = jax.lax.fori_loop(
                0, k_window, body, (caches, lengths, last, key, toks0)
            )
            return caches, lengths, last, key, toks

        def paged_chunk_step(w, caches, tables, tokens, offs, clens,
                             act, fin, lengths, last, temps, p_lens,
                             new_temps, key):
            """The ONLY paged prefill program: cold prompts are chunks
            from offset 0, prefix hits start at their shared-block
            boundary — no whole-bucket prefill, no copy program (the
            splice already happened in the host block table)."""
            logits, caches = paged_chunk_forward(
                model, w, tokens, caches, tables, offs, clens, act,
                self.block_size, maxlen, local=mesh is None,
                attention=attn_kernel, kv_dtype=self.kv_dtype,
            )
            caches = _constrain_all(caches)
            C = tokens.shape[1]
            at_end = (
                (p_lens - offs - 1)[:, None] == jnp.arange(C)[None, :]
            ).astype(logits.dtype)
            last_logits = jnp.einsum("bc,bcv->bv", at_end, logits)
            key, sub = jax.random.split(key)
            firsts = _sample_dynamic(
                last_logits, sub, new_temps, self.top_k, self.top_p
            )
            lengths = _vec(jnp.where(fin, p_lens, lengths))
            last = _vec(jnp.where(fin, firsts, last))
            temps = _vec(jnp.where(fin, new_temps, temps))
            return caches, lengths, last, temps, key, firsts

        def offload_rows(caches, ids):
            # read-only: the pool is NOT donated — it survives for the
            # same step's admissions to write into
            return gather_blocks(caches, ids)

        def restore_rows(caches, ids, rows):
            return _constrain_all(scatter_blocks(caches, ids, rows))

        def resume_state(lengths, last, temps, mask, r_len, r_last,
                         r_temps):
            return (
                _vec(jnp.where(mask, r_len, lengths)),
                _vec(jnp.where(mask, r_last, last)),
                _vec(jnp.where(mask, r_temps, temps)),
            )

        # -- speculative verify (ISSUE 8): ONE batched forward scores a
        # whole draft window for every verifying slot — row j of the
        # [num_slots, K+1] sample matrix is the model's own token for
        # position offs+j+1, which the host compares against the drafts
        # (accept the longest matching prefix + one bonus token). The
        # window width is STATIC (spec_k + 1); per-slot shorter drafts
        # ride the same program via the n_fed mask — one verify compile
        # total on the fixed arena, one per table bucket paged. One key
        # split per round covers all window positions (temp>0 streams
        # therefore diverge from plain decode, like chunked prefill;
        # temp-0 rows are argmax and key-free).
        # The round's host-built vectors ride as ONE packed [num_slots,
        # W+3] int32 upload (tokens | offset | n_fed | active) — four
        # separate stage calls measurably taxed the round on
        # dispatch-bound backends, where per-transfer overhead rivals
        # the dispatch itself.
        W_spec = (self.spec_k + 1) if self.speculative else 0

        def _unpack_verify(packed):
            tokens = packed[:, :W_spec]
            offs = packed[:, W_spec]
            n_fed = packed[:, W_spec + 1]
            act = packed[:, W_spec + 2] != 0
            return tokens, offs, n_fed, act

        def _sample_window(logits, temps, key):
            B, C, V = logits.shape
            key, sub = jax.random.split(key)
            sampled = _sample_dynamic(
                logits.reshape(B * C, V), sub,
                jnp.repeat(temps, C), self.top_k, self.top_p,
            ).reshape(B, C)
            return key, sampled

        def spec_verify(w, caches, packed, temps, key, span=None):
            tokens, offs, n_fed, act = _unpack_verify(packed)
            logits, caches = verify_forward(
                model, w, tokens, caches, offs, n_fed, act, maxlen,
                attention=attn_kernel, span=span,
            )
            caches = _constrain_all(caches)
            key, sampled = _sample_window(logits, temps, key)
            return caches, key, sampled

        def paged_spec_verify(w, caches, tables, packed, temps, key):
            tokens, offs, n_fed, act = _unpack_verify(packed)
            logits, caches = paged_verify_forward(
                model, w, tokens, caches, tables, offs, n_fed, act,
                self.block_size, maxlen, local=mesh is None,
                attention=attn_kernel, kv_dtype=self.kv_dtype,
            )
            caches = _constrain_all(caches)
            key, sampled = _sample_window(logits, temps, key)
            return caches, key, sampled

        # -- completion scoring (ISSUE 19): verify-WITHOUT-accept. One
        # chunk/verify-shaped forward feeds prompt+completion[:-1] on
        # lane 0 of a caches pytree that is NOT donated and whose
        # updated copy is DISCARDED — the live arena never changes, so
        # scoring composes with in-flight serving. Paged mode scores
        # through a scratch arange block table (the one-hot writes land
        # in the discarded copy only); row j of the logits scores the
        # token at absolute position j+1, which is exactly the
        # completion logprob/greedy-token oracle the quant bench gates
        # consume. Compiled per (width bucket[, table bucket / span])
        # — the same closed ladders the serving programs use.
        def paged_score(w, caches, tables, tokens, clens, act, targets):
            offs = jnp.zeros((self.num_slots,), jnp.int32)
            logits, _ = paged_chunk_forward(
                model, w, tokens, caches, tables, offs, clens, act,
                self.block_size, maxlen, local=mesh is None,
                attention=attn_kernel, kv_dtype=self.kv_dtype,
            )
            row = logits[0]  # [C, vocab] — the scoring lane
            lp = jax.nn.log_softmax(row, axis=-1)
            tlp = jnp.take_along_axis(lp, targets[:, None], axis=-1)
            greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
            return tlp[:, 0], greedy

        def fixed_score(w, caches, tokens, clens, act, targets,
                        span=None):
            offs = jnp.zeros((self.num_slots,), jnp.int32)
            logits, _ = verify_forward(
                model, w, tokens, caches, offs, clens, act, maxlen,
                attention=attn_kernel, span=span,
            )
            row = logits[0]
            lp = jax.nn.log_softmax(row, axis=-1)
            tlp = jnp.take_along_axis(lp, targets[:, None], axis=-1)
            greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
            return tlp[:, 0], greedy

        # -- SP long-prompt prefill program (ISSUE 11): one whole-
        # prompt forward over the SP mesh returning logits AND every
        # layer's K/V rows, landed straight into the block pool via
        # the same scatter program resume uses, plus the first-token
        # sample — ONE dispatch per long prompt. Compiled per (padded
        # length, table bucket) pair, both closed ladders.
        if self.sp_mesh is not None:
            from elephas_tpu.serving.sp_prefill import sp_prefill_forward

            sp_mesh_, sp_ax_, sp_mech_ = (
                self.sp_mesh, self.sp_axis, self.sp_mechanism
            )

            def sp_step(w, tokens, p_idx):
                """Mesh half of the SP prefill: the sharded forward
                only. K/V rows and the prompt-end logits row hop back
                to the default device on the host side; sampling and
                the block landing run UNMESHED (the scatter program
                resume already owns) — nothing mesh-committed ever
                touches the pool or the key stream, so decode stays
                unmeshed ("proceeds unmeshed" is the contract) and no
                downstream program recompiles."""
                logits, kv = sp_prefill_forward(
                    model, w, tokens, sp_mesh_, sp_ax_, sp_mech_,
                    maxlen,
                )
                row = jax.lax.dynamic_index_in_dim(
                    logits[0], p_idx - 1, axis=0, keepdims=False
                )
                return kv, row

            def sp_sample(row, temp, key):
                key, sub = jax.random.split(key)
                tok = _sample_dynamic(
                    row[None], sub, temp, self.top_k, self.top_p
                )[0]
                return tok, key

            self._sp_jit = jax.jit(sp_step)
            self._sp_sample_jit = jax.jit(sp_sample)
        else:
            self._sp_jit = None
            self._sp_sample_jit = None
        # SP weight staging (mesh-replicated) built lazily on the
        # first long prompt; refresh_weights() drops it
        self._sp_weights = None

        # the fixed program set: ONE decode window + one prefill per
        # prompt bucket (p_lens/admit/new_temps ride as traced vectors,
        # so only the bucket SHAPE triggers a compile), plus ONE prefix
        # copy shape and one chunk program per chunk width (a single
        # width under `prefill_chunk`, suffix buckets otherwise).
        # Paged mode compiles its OWN closed set instead: one decode
        # per table bucket, one chunk per (width, table bucket), one
        # gather/scatter per table bucket (preempt/resume), one
        # resume-state select.
        self._init_jit = jax.jit(init_state)
        if self.paged:
            self._paged_decode_jit = jax.jit(
                paged_decode, donate_argnums=(1, 3, 4, 7)
            )  # args: w, caches, tables, lengths, last, temps,
            #         active, key
            self._paged_chunk_jit = jax.jit(
                paged_chunk_step, donate_argnums=(1, 8, 9, 10, 13)
            )  # args: w, caches, tables, tokens, offs, clens, act,
            #         fin, lengths, last, temps, p_lens, new_temps, key
            self._gather_jit = jax.jit(offload_rows)
            self._scatter_jit = jax.jit(
                restore_rows, donate_argnums=(0,)
            )
            self._resume_state_jit = jax.jit(
                resume_state, donate_argnums=(0, 1, 2)
            )
            self._verify_jit = (
                jax.jit(paged_spec_verify, donate_argnums=(1, 5))
                if self.speculative else None
            )  # args: w, caches, tables, packed, temps, key
            self._score_jit = jax.jit(paged_score)
            # args: w, caches, tables, tokens, clens, act, targets —
            # NOTHING donated: the updated caches are discarded, the
            # live arena survives untouched
        else:
            self._prefill_jit = jax.jit(
                prefill, donate_argnums=(1, 2, 3, 4, 9)
            )  # args: w, caches, lengths, last, temps, rows, p_lens,
            #         admit, new_temps, key
            self._decode_jit = jax.jit(
                decode, donate_argnums=(1, 2, 3, 6),
                static_argnums=(7,),
            )  # trailing STATIC span (flash block-span reads): one
            #   compile per touched span bucket — naive always passes
            #   None, keeping the seed's single decode program
            self._chunk_jit = jax.jit(
                chunk_step, donate_argnums=(1, 2, 3, 4, 15),
                static_argnums=(16, 17),
            )  # args: w, caches, lengths, last, temps, tokens, offs,
            #         clens, act, fin, p_lens, new_temps, src_idx,
            #         copy_mask, copy_len, key, has_copy (static),
            #         span (static)
            self._copy_jit = jax.jit(copy_prefix, donate_argnums=(0,))
            self._verify_jit = (
                jax.jit(
                    spec_verify, donate_argnums=(1, 4),
                    static_argnums=(5,),
                )
                if self.speculative else None
            )  # args: w, caches, packed, temps, key, span (static)
            self._score_jit = jax.jit(
                fixed_score, static_argnums=(6,)
            )  # args: w, caches, tokens, clens, act, targets, span
            #   (static) — nothing donated, updated caches discarded

        self.refresh_weights()
        self._caches, self._lengths, self._last, self._temps = (
            self._init_jit()
        )
        self._key = self._stage(
            np.asarray(jax.random.PRNGKey(self._seed))
        )
        # decode-active mask: host mirror + staged device copy,
        # re-uploaded only when membership changes (admission finalize /
        # reclaim), not every window
        self._active_host = np.zeros((self.num_slots,), bool)
        self._active_dev = self._stage_slots(self._active_host.copy())
        self._active_dirty = False
        # paged staging: device block tables rebuilt only when the
        # scheduler's tables change or the bucket shifts, plus the
        # host store of offloaded (preempted) requests' K/V
        self._tables_cache: tuple | None = None
        self._offloaded: dict[int, _OffloadRecord] = {}
        # speculative host state (ISSUE 8): the drafter, the per-request
        # acceptance throttle, and the device-state dirty flag — verify
        # rounds track positions from HOST truth (resident length =
        # prompt + generated - 1), leaving the device length/last
        # vectors stale; the flag triggers a re-stage before any plain
        # decode window reads them (the all-throttled fallback path)
        self._drafter = (
            resolve_drafter(
                spec_drafter, num_slots=self.num_slots,
                maxlen=self.maxlen, vocab=self.vocab,
            ) if self.speculative else None
        )
        self._spec_throttle = (
            AcceptanceThrottle() if self.speculative else None
        )
        self._spec_dirty = False
        # HTTP/SSE front door (ISSUE 10): attached by
        # ``SparkModel.serve(gateway_port=...)`` (or any host that
        # builds a serving.gateway.Gateway around this engine); the
        # engine's context-manager exit stops it and severs live SSE
        # connections, so ``with model.serve(gateway_port=...) as eng:``
        # can never leak a bound port or a zombie keep-alive handler
        self.gateway = None

    # -- lifecycle ------------------------------------------------------

    def close(self) -> None:
        """Stop the attached gateway (if any): sever live SSE
        connections, release the port, join its threads. Idempotent;
        the engine itself stays usable in-process afterwards."""
        gw = self.gateway
        if gw is not None:
            self.gateway = None
            gw.stop()

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    # -- device staging ------------------------------------------------

    def _stage(self, arr):
        """Host value → device, replicated under the mesh (gang-safe)."""
        import jax.numpy as jnp

        if self.mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from elephas_tpu.parallel.mesh import put_global

        return put_global(np.asarray(arr), NamedSharding(self.mesh, P()))

    def _host(self, leaf) -> np.ndarray:
        if self.mesh is None:
            return np.asarray(leaf)
        from elephas_tpu.parallel.mesh import host_read

        return host_read(leaf, self.mesh)

    def refresh_weights(self, version: int | None = None) -> None:
        """(Re-)upload the model's weights — call after further
        training; the compiled programs take them as arguments, so no
        recompile happens. ``version`` stamps the new weight
        generation (ISSUE 20 deploy subscriber); ``None`` keeps the
        current stamp (ad-hoc in-place refresh, pre-versioned callers).

        Flushes the prefix cache: resident donor K/V was computed
        under the OLD weights, and a donor copy would silently splice
        stale rows into a new-weights request — breaking the engine's
        token-exactness contract with no error. (In-flight requests
        keep their slots and finish on mixed weights, the same
        documented behavior as refreshing mid-decode.)"""
        import jax.numpy as jnp

        if version is not None:
            self.weight_version = int(version)
        elif not hasattr(self, "weight_version"):
            # constructor's first call, before any attribute setup
            self.weight_version = 0
        # lifecycle event (ISSUE 13): a weight push travelling
        # worker → PS → engine ends HERE — emitting under the caller's
        # trace scope stamps the same trace id the push carried, so
        # the deployment is one causal story on the merged timeline.
        # getattr-guarded: the constructor calls refresh_weights()
        # before the telemetry capture exists.
        tracer = getattr(self, "_tracer", None)
        if tracer is not None:
            tracer.emit(
                "serve.refresh_weights", engine=self.telemetry_label,
                weight_version=self.weight_version,
            )
        gauge = getattr(self, "_g_weight_version", None)
        if gauge is not None:
            gauge.set(self.weight_version)
        # guarded for the constructor's first call (scheduler not
        # built yet — nothing cached before weights exist)
        scheduler = getattr(self, "scheduler", None)
        if scheduler is not None:
            scheduler.flush_prefix_cache()
            # slots mid-chunked-prefill hold rows partially computed
            # under the OLD weights: when they finalize they must NOT
            # re-register as donors, or the stale-splice the flush
            # prevents comes back through the side door
            self._stale_prefill = set(self._prefilling)
        # a draft-model drafter re-uploads ITS model's weights and
        # drops its committed frontiers (full re-ingest): the draft
        # model may have been retrained alongside the target — stale
        # draft weights would silently collapse acceptance and turn
        # speculation off through the throttle with no signal
        drafter = getattr(self, "_drafter", None)
        if drafter is not None:
            drafter.refresh_weights()
            # the draft model now serves the SAME generation as the
            # target — without the stamp a mixed-version fleet debug
            # view would show the drafter forever at generation 0
            drafter.weight_version = self.weight_version
        # SP prefill keeps its own mesh-replicated weight staging —
        # drop it so the next long prompt re-stages the new weights
        self._sp_weights = None

        if self.mesh is None:
            self._weights = {
                v.path: jnp.asarray(v.value) for v in self.model.variables
            }
            return
        from elephas_tpu.models.transformer import _decode_shardings
        from elephas_tpu.parallel.mesh import put_global

        var_sh = _decode_shardings(
            list(self.model.variables), self.mesh, self.model_axis,
            self._rules,
        )
        self._weights = {
            v.path: put_global(np.asarray(v.value), s)
            for v, s in zip(self.model.variables, var_sh)
        }

    # -- request API ---------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               temperature: float = 0.0, eos_id: int | None = None,
               on_token=None, priority: int = 0,
               tenant: str | None = None,
               ttft_deadline_ms: float | None = None) -> Request:
        """Queue one generation request (admitted at the next step —
        submission is legal at any time, including mid-flight). Every
        gang process must submit the identical sequence of requests.
        ``on_token(token, done)`` streams tokens to the caller as they
        land; a raising callback fails only ITS request (``req.error``
        set, KV slot reclaimed) — the engine keeps serving.
        ``priority`` matters only with ``preemption=True``: an arrival
        may swap out active requests of strictly lower priority when
        the block pool is exhausted.

        Paged mode: a request whose prompt + budget can NEVER fit the
        block pool is rejected loudly but GRACEFULLY — ``req.error``
        set, ``req.done`` True, never queued — instead of raising or
        (worse) wedging the queue head forever at admission.

        SLO scheduling (ISSUE 10): ``tenant`` accounts the request
        under a policy-declared tenant (fair share, per-tenant stats);
        ``ttft_deadline_ms`` declares its time-to-first-token budget
        (deadline-EDF ordering + SLO attainment counters). Both are
        validated LOUDLY: an unknown tenant, a non-positive deadline,
        or a deadline on an engine whose policy does not read
        deadlines raises ValueError — silently recording either would
        let the caller believe in isolation/urgency the scheduler
        never delivers. A policy with admission control may refuse the
        submit outright: like the paged never-fit case the request
        comes back ``done`` with ``req.error`` set to
        :class:`~elephas_tpu.serving.policy.AdmissionRejected`
        (carrying the Retry-After hint the gateway serves as a 429)."""
        prompt = np.asarray(prompt).reshape(-1)
        p = len(prompt)
        if p < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens} < 1")
        if p + max_new_tokens > self.maxlen:
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the model's maxlen ({self.maxlen})"
            )
        if temperature < 0:
            raise ValueError(f"temperature={temperature} < 0")
        # fail HERE, not mid-flight in the prefill wave (where the
        # request would already hold a leased slot): a custom bucket
        # ladder may top out below the model's maxlen. Chunked prefill
        # never pads to a prompt bucket, so the ladder doesn't bound it.
        if not self.prefill_chunk:
            self.scheduler.bucket_for(p)
        if priority and not self.preemption:
            # ISSUE 8 satellite (knob-validation parity with the paged
            # knobs): only the preemption path ever consults priority —
            # a caller passing it on any other engine is expressing an
            # expectation this engine cannot honor, and silence here
            # would let them believe their high-priority traffic jumps
            # the queue. Warn (not raise): the request itself is valid.
            logger.warning(
                "submit(priority=%d) on an engine without "
                "preemption=True — priority is recorded but IGNORED "
                "(admission stays FIFO); serve with paged=True, "
                "preemption=True for priority scheduling", priority,
            )
        # SLO knob validation (ISSUE 10 satellite) — loud, per the
        # docstring's contract
        if tenant is not None:
            if self.policy is None:
                raise ValueError(
                    f"submit(tenant={tenant!r}) on an engine without a "
                    f"policy — serve with policy=/tenants= to declare "
                    f"tenants before accounting requests under them"
                )
            if not self.policy.knows(tenant):
                raise ValueError(
                    f"unknown tenant {tenant!r} — declared tenants: "
                    f"{sorted(self.policy.tenants) or '[none]'} (plus "
                    f"the implicit {DEFAULT_TENANT!r})"
                )
        if ttft_deadline_ms is not None:
            if not float(ttft_deadline_ms) > 0:
                raise ValueError(
                    f"ttft_deadline_ms={ttft_deadline_ms} must be "
                    f"positive — a deadline at or before submit time "
                    f"can never be met"
                )
            if self.policy is None or not self.policy.reads_deadlines:
                raise ValueError(
                    "submit(ttft_deadline_ms=) needs a deadline-aware "
                    "policy (e.g. FairSharePolicy) — this engine's "
                    "policy never reads deadlines, so the knob would "
                    "be a silent no-op"
                )
        req = self.scheduler.make_request(
            prompt, max_new_tokens, temperature=temperature, eos_id=eos_id,
            on_token=on_token, priority=priority, tenant=tenant,
            ttft_deadline_ms=ttft_deadline_ms,
        )
        req.submit_time = time.perf_counter()
        # trace context minted HERE (ISSUE 12): the rid is the trace
        # identity for every lifecycle event/record downstream (the
        # gateway echoes it back as X-Request-Id and in the SSE/JSON
        # envelopes)
        req.submit_step = self.scheduler._steps
        req.exemplar = {"rid": str(req.rid)}
        rec = self._fr_new(req)
        submit_seq = self._tracer.emit(
            "serve.submit", rid=req.rid,
            tenant=DEFAULT_TENANT if tenant is None else str(tenant),
            prompt_tokens=p, max_new_tokens=int(max_new_tokens),
            step=req.submit_step,
        )
        if rec is not None:
            rec["submit_seq"] = submit_seq
        if self.paged:
            need = blocks_for(p + max_new_tokens, self.block_size)
            if need > self.num_blocks:
                # ISSUE 7 satellite: this request could sit at the
                # queue head forever (admission can never free enough
                # blocks) — reject it now, loudly, without poisoning
                # the engine for everyone behind it
                req.error = RuntimeError(
                    f"request {req.rid} needs {need} KV blocks "
                    f"(prompt {p} + max_new_tokens {max_new_tokens} "
                    f"at block_size {self.block_size}) but the pool "
                    f"only has {self.num_blocks} — it can never be "
                    f"admitted; rejected at submit"
                )
                req.done = True
                self._m_rejected.inc()
                self._tenant_child(self._mf_tenant_rejected, tenant).inc()
                logger.warning("%s", req.error)
                self._fr_finish(req, "rejected_capacity")
                self.finished[req.rid] = req
                self._evict_finished()
                return req
        if self.policy is not None:
            # overload admission control (ISSUE 10): the policy sees
            # the queue's outstanding token debt and may shed THIS
            # request now — loudly, with a deterministic Retry-After —
            # instead of letting it time out at the back of a queue
            # that can only grow
            tenant_debt = self.scheduler.queued_tokens_for(tenant)
            verdict = self.policy.admission_verdict(
                req, self.scheduler.queued_tokens, tenant_debt,
            )
            # verdict event + record (ISSUE 12): the fairness state
            # the decision was made against rides along, so a trace
            # answers "queued behind whose debt?" without replaying
            # the policy
            self._tracer.emit(
                "serve.admission_verdict", rid=req.rid,
                admitted=verdict.admitted, reason=verdict.reason,
                queued_tokens=self.scheduler.queued_tokens,
                tenant_queued_tokens=tenant_debt,
            )
            if rec is not None:
                rec["verdict"] = {
                    "admitted": verdict.admitted,
                    "reason": verdict.reason,
                    "retry_after_s": verdict.retry_after_s,
                    "queued_tokens": self.scheduler.queued_tokens,
                    "tenant_queued_tokens": tenant_debt,
                    "virtual_counters": self.policy.snapshot_counters(),
                }
            if not verdict.admitted:
                req.error = AdmissionRejected(
                    f"request {req.rid} rejected by "
                    f"{type(self.policy).__name__}: {verdict.reason}; "
                    f"retry after {verdict.retry_after_s:.1f}s",
                    retry_after_s=verdict.retry_after_s,
                )
                req.done = True
                self._m_admission_rejected.inc()
                self._tenant_child(self._mf_tenant_rejected, tenant).inc()
                logger.warning("%s", req.error)
                self._fr_finish(req, "rejected_admission")
                self.finished[req.rid] = req
                self._evict_finished()
                return req
        self.scheduler.submit(req)
        return req

    def _tenant_child(self, family, tenant):
        """The tenant-labeled child of ``family`` for this engine."""
        label = DEFAULT_TENANT if tenant is None else str(tenant)
        return family.labels(engine=self.telemetry_label, tenant=label)

    # -- request-scoped tracing (ISSUE 12) ------------------------------

    def _dispatch(self, program: str, fn, *args):
        """Run one compiled-program dispatch; when the call grew the
        program's jit cache (a compile happened inside it) record a
        named ``jit.compile`` span covering the dispatch, so
        mid-serve recompiles land on the same timeline as the request
        lifecycle events. Watch-free (one function call) under null
        mode; report-only always — nothing reads the cache size to
        make a decision."""
        if not self._trace_compiles:
            return fn(*args)
        try:
            before = int(fn._cache_size())
        except Exception:  # jax-version drift: dispatch unwatched
            return fn(*args)
        t0 = time.perf_counter()
        out = fn(*args)
        try:
            grew = int(fn._cache_size()) > before
        except Exception:  # jax-version drift mid-flight
            grew = False
        if grew:
            self._tracer.complete(
                "jit.compile", time.perf_counter() - t0,
                program=program, engine=self.telemetry_label,
            )
        return out

    def _fr(self, rid: int) -> dict | None:
        """The request's lifecycle record — in-flight first, then the
        finished ring (late entries like the spec round that ended the
        request append there). None when recording is off or the
        record was evicted."""
        if self._flight is None:
            return None
        rec = self._flight_live.get(rid)
        if rec is None:
            rec = self._flight.get(rid)
        return rec

    def _fr_new(self, req: Request) -> dict | None:
        """Open one in-flight lifecycle record at submit."""
        if self._flight is None:
            return None
        rec = {
            "rid": req.rid,
            "tenant": req.tenant,
            "prompt_tokens": len(req.prompt),
            "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature,
            "priority": req.priority,
            "ttft_deadline_ms": req.ttft_deadline_ms,
            "submit_step": req.submit_step,
            "submit_seq": -1,  # set from the serve.submit instant
            # generation at submit: a mixed-version fleet is diagnosed
            # from traces — a request whose record says N running on a
            # replica that reports N+1 straddled a deployment
            "weight_version": self.weight_version,
            "verdict": None,
            # first-admission mirrors (the fields explain() names);
            # `admissions` keeps every entry (resume re-admissions)
            "admission_kind": None,
            "reuse_len": 0,
            "queue_wait_steps": None,
            "admissions": [],
            "chunks": [],
            "sp_prefill": None,
            "preemptions": [],
            "resumes": [],
            "spec_rounds": [],
            "first_token": None,
            "token_steps": [],
            "tokens": 0,
            "spec_drafted": 0,
            "spec_accepted": 0,
            "finish": None,
        }
        # every key is pre-seeded HERE and only ever re-assigned, so a
        # lock-free reader (explain() without the engine lock) always
        # sees a fixed-shape dict — deepcopy can never catch the dict
        # growing mid-iteration
        self._flight_live[req.rid] = rec
        return rec

    def _fr_finish(self, req: Request, reason: str) -> None:
        """Close the request's record and file it in the bounded ring;
        also emits the ``serve.finish`` lifecycle instant."""
        seq = self._tracer.emit(
            "serve.finish", rid=req.rid, reason=reason,
            tokens=len(req.tokens), step=self.scheduler._steps,
        )
        if self._flight is None:
            return
        rec = self._flight_live.get(req.rid)
        if rec is None:
            return
        rec["finish"] = {
            "reason": reason,
            "step": self.scheduler._steps,
            "seq": seq,
            "error": None if req.error is None else str(req.error),
        }
        rec["tokens"] = len(req.tokens)
        rec["spec_drafted"] = req.spec_drafted
        rec["spec_accepted"] = req.spec_accepted
        # file into the ring BEFORE dropping the live entry: a
        # lock-free explain() between the two stores must find the
        # record in at least one of them (never a spurious KeyError
        # for a request that exists)
        self._flight.record(req.rid, rec)
        self._flight_live.pop(req.rid, None)

    def _trace_admissions(self, plan) -> None:
        """One ``serve.admit`` instant + record entry per admission in
        the wave: kind (cold / prefix_hit / resume), slot, reuse
        length, and the queue wait in scheduler STEPS (logical — every
        gang process reconstructs the identical number)."""
        step = self.scheduler._steps
        for a in plan:
            if a.resume is not None:
                kind, reuse = "resume", 0
            elif a.donor_slot is not None or a.shared_len:
                kind, reuse = "prefix_hit", (a.reuse_len or a.shared_len)
            else:
                kind, reuse = "cold", 0
            req = a.req
            wait = (
                step - req.submit_step
                if req.submit_step is not None else None
            )
            seq = self._tracer.emit(
                "serve.admit", rid=req.rid, kind=kind, slot=a.slot,
                reuse_len=reuse, step=step, queue_wait_steps=wait,
            )
            rec = self._fr(req.rid)
            if rec is not None:
                rec["admissions"].append({
                    "kind": kind, "slot": a.slot, "reuse_len": reuse,
                    "step": step, "seq": seq,
                })
                if rec["admission_kind"] is None:
                    rec["admission_kind"] = kind
                    rec["reuse_len"] = reuse
                    rec["queue_wait_steps"] = wait

    def _emit(self, req: Request, token: int) -> bool:
        """Record one generated token; reclaim + file the request when
        it finished. Returns done.

        A raising per-token callback fails the request CLEANLY: before
        this guard, the exception unwound through step() after the
        scheduler had recorded the token but before reclaim, leaking
        the KV slot for the engine's lifetime."""
        self._m_tokens.inc()
        slot = req.slot
        now = time.perf_counter()
        req.token_times.append(now)
        rec = self._fr(req.rid) if self._flight is not None else None
        if rec is not None:
            rec["token_steps"].append(self.scheduler._steps)
        # latency histograms feed straight off the per-request arrival
        # times stats() already reports — one recording site, no drift.
        # Observations carry the rid as an exemplar (ISSUE 12): the
        # OpenMetrics scrape links a p99 bucket straight to the trace
        # of the request that landed in it.
        if len(req.token_times) == 1:
            seq = self._tracer.emit(
                "serve.first_token", rid=req.rid,
                step=self.scheduler._steps,
            )
            if req.submit_time is not None:
                ttft = now - req.submit_time
                self._m_ttft.observe(ttft, exemplar=req.exemplar)
                if rec is not None:
                    rec["first_token"] = {
                        "step": self.scheduler._steps, "seq": seq,
                        # wall-derived, EXPORT-ONLY (like every wall
                        # field in the telemetry layer)
                        "ttft_s": ttft,
                    }
                if req.ttft_deadline_ms is not None:
                    # SLO attainment (ISSUE 10): wall-clock TTFT meets
                    # the declared budget HERE and only here — report-
                    # only, never an input to the schedule
                    met = ttft * 1e3 <= req.ttft_deadline_ms
                    self._tenant_child(
                        self._mf_slo_met if met else self._mf_slo_missed,
                        req.tenant,
                    ).inc()
        else:
            self._m_itl.observe(
                now - req.token_times[-2], exemplar=req.exemplar
            )
        if self.policy is not None:
            self.policy.on_token(req)
            self._tenant_child(self._mf_tenant_tokens, req.tenant).inc()
        done = self.scheduler.on_token(slot, token)
        if req.on_token is not None:
            try:
                req.on_token(token, done)
            except Exception as e:
                req.error = e
                req.done = True
                done = True
                logger.warning(
                    "request %d failed in its on_token callback (%r) — "
                    "slot %d reclaimed, engine continues", req.rid, e, slot,
                )
        if done:
            req.finish_time = req.token_times[-1]
            self.scheduler.reclaim(slot)
            self._set_active(slot, False)
            self._m_finished.inc()
            if self.policy is not None:
                self.policy.on_finish(req)
            if self._spec_throttle is not None:
                self._spec_throttle.forget(req.rid)
            if req.error is not None:
                reason = "callback_error"
            elif (
                req.eos_id is not None and req.tokens
                and req.tokens[-1] == req.eos_id
            ):
                reason = "eos"
            else:
                reason = "budget"
            self._fr_finish(req, reason)
            self.finished[req.rid] = req
            self._evict_finished()
        return done

    def _evict_finished(self) -> None:
        """Trim the bounded finished-request registry — LOUDLY but
        RATE-LIMITED (ISSUE 5 satellite): the registry-backed
        ``finished_evicted`` counter keeps EVERY increment for stats
        and scrapes, while the warning fires only on the first eviction
        and every 1024th after — a hot loop evicting per token cannot
        turn the log into the bottleneck. The warning cadence runs on a
        PLAIN count (telemetry never drives control flow — under null
        mode the registry counter reads 0 forever, which would make
        ``0 % 1024 == 0`` fire the warning on EVERY eviction). Requests
        an in-flight :meth:`run` call has yet to return are never
        evicted (the registry may temporarily exceed its bound
        instead)."""
        while len(self.finished) > self._finished_bound:
            if len(self.finished) - len(self._protected) <= 0:
                return  # only protected residents over the bound — a
                # full scan would find no victim (hot path: this runs
                # per token completion during a large run())
            victim = next(
                (rid for rid in self.finished
                 if rid not in self._protected),
                None,
            )
            if victim is None:
                return  # every resident request is protected
            self.finished.pop(victim)
            self._m_finished_evicted.inc()
            self._tracer.emit("serve.evict", rid=victim)
            self._evictions_seen += 1
            evicted = self._evictions_seen
            if evicted == 1 or evicted % 1024 == 0:
                logger.warning(
                    "finished-request registry hit its bound (%d): "
                    "evicted request %d (%d evicted so far) — consume "
                    "results promptly or keep your own Request handles "
                    "from submit()",
                    self._finished_bound, victim, evicted,
                )

    def _fixed_span(self, max_pos_excl: int):
        """Static attended-span bucket for the fixed arena's flash
        programs: the smallest span bucket covering ``max_pos_excl``
        resident positions. ``None`` in naive mode (the seed
        full-``maxlen`` program) and for the paged arena (its span is
        the table bucket already)."""
        if self.attention != "flash" or self.paged:
            return None
        n = max(1, min(self.maxlen, int(max_pos_excl)))
        return span_bucket_for(n, self._sbuckets)

    def _decode_span(self):
        """Span bucket for one decode window: every decoding slot's
        resident length plus the window's worth of new positions."""
        m = 0
        for slot, req in self.scheduler.active.items():
            if slot in self._prefilling:
                continue
            m = max(m, len(req.prompt) + len(req.tokens) - 1)
        return self._fixed_span(m + self.steps_per_sync)

    def _set_active(self, slot: int, value: bool) -> None:
        if bool(self._active_host[slot]) != value:
            self._active_host[slot] = value
            self._active_dirty = True

    def _sync_active(self):
        if self._active_dirty:
            self._active_dev = self._stage_slots(self._active_host.copy())
            self._active_dirty = False
        return self._active_dev

    def _stage_slots(self, arr):
        """Host ``[num_slots, ...]`` value → device, slot axis over the
        batch axes (gang-safe)."""
        import jax.numpy as jnp

        if self.mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from elephas_tpu.parallel.mesh import put_global

        spec = (self.batch_axes,) + (None,) * (np.ndim(arr) - 1)
        return put_global(
            np.asarray(arr), NamedSharding(self.mesh, P(*spec))
        )

    def _prefill_wave(self, admitted: list[Request]) -> None:
        """Prefill one admission wave: ONE program launch per prompt
        bucket covers every request of that bucket in the wave."""
        with self._tracer.span("serve.prefill_wave", reqs=len(admitted)):
            self._prefill_wave_inner(admitted)

    def _prefill_wave_inner(self, admitted: list[Request]) -> None:
        by_bucket: dict[int, list[Request]] = {}
        for req in admitted:
            b = self.scheduler.bucket_for(len(req.prompt))
            by_bucket.setdefault(b, []).append(req)
        for bucket in sorted(by_bucket):
            reqs = by_bucket[bucket]
            rows = np.zeros((self.num_slots, bucket), np.int32)
            p_lens = np.ones((self.num_slots,), np.int32)
            admit = np.zeros((self.num_slots,), bool)
            new_temps = np.zeros((self.num_slots,), np.float32)
            for req in reqs:
                rows[req.slot, : len(req.prompt)] = req.prompt
                p_lens[req.slot] = len(req.prompt)
                admit[req.slot] = True
                new_temps[req.slot] = req.temperature
            (self._caches, self._lengths, self._last, self._temps,
             self._key, firsts) = self._dispatch(
                "prefill", self._prefill_jit,
                self._weights, self._caches, self._lengths, self._last,
                self._temps, self._stage_slots(rows),
                self._stage_slots(p_lens), self._stage_slots(admit),
                self._stage_slots(new_temps), self._key,
            )
            toks = self._host(firsts)
            for req in reqs:
                # prompt rows are resident from here: index them before
                # _emit (a 1-token request reclaims inside _emit, and
                # reclaim only retains slots the cache already knows)
                self.scheduler.on_prefill_complete(req)
                self._set_active(req.slot, True)
                self._note_prefill(req, bucket)
                seq = self._tracer.emit(
                    "serve.prefill", rid=req.rid, bucket=bucket,
                    prompt_tokens=len(req.prompt),
                    step=self.scheduler._steps,
                )
                rec = self._fr(req.rid)
                if rec is not None:
                    # whole-prompt wave: one "chunk" covering it all,
                    # so explain()'s chunk list is the prefill story
                    # on every arena/config
                    rec["chunks"].append({
                        "offset": 0, "take": len(req.prompt),
                        "step": self.scheduler._steps, "seq": seq,
                    })
                self._emit(req, int(toks[req.slot]))

    def _copy_vectors(self, copies):
        """``(src_idx, copy_mask, copy_len)`` staging vectors for a
        wave's donor transplants — shared by the fused (in-chunk) and
        standalone copy program calls so their semantics cannot
        diverge."""
        src = np.zeros((self.num_slots,), np.int32)
        mask = np.zeros((self.num_slots,), bool)
        clen = np.zeros((self.num_slots,), np.int32)
        for a in copies:
            src[a.slot] = a.donor_slot
            mask[a.slot] = True
            clen[a.slot] = a.reuse_len
        return src, mask, clen

    def _run_chunk(self, items: list, width: int, copies=()):
        """One chunk-program call: each ``(admission, progress, take)``
        item advances ``take`` prompt tokens (``<= width``) of its
        slot's prompt from absolute offset ``progress``. Items whose
        prompt completes sample their first token and join decode.
        ``copies`` — admissions whose donor transplant rides fused
        inside this same call (their suffix items must be present too).
        Returns ``(request, token, done)`` emissions of finalized
        requests."""
        with self._tracer.span(
            "serve.chunk", width=width, slots=len(items),
            copies=len(copies),
        ):
            return self._run_chunk_inner(items, width, copies)

    def _run_chunk_inner(self, items: list, width: int, copies=()):
        rows = np.zeros((self.num_slots, width), np.int32)
        offs = np.zeros((self.num_slots,), np.int32)
        clens = np.zeros((self.num_slots,), np.int32)
        act = np.zeros((self.num_slots,), bool)
        fin = np.zeros((self.num_slots,), bool)
        p_lens = np.zeros((self.num_slots,), np.int32)
        new_temps = np.zeros((self.num_slots,), np.float32)
        src, cmask, clen = self._copy_vectors(copies)
        finalized = []
        for adm, progress, take in items:
            req, slot = adm.req, adm.slot
            rows[slot, :take] = req.prompt[progress:progress + take]
            offs[slot] = progress
            clens[slot] = take
            act[slot] = True
            done_prefill = progress + take == len(req.prompt)
            fin[slot] = done_prefill
            p_lens[slot] = len(req.prompt)
            new_temps[slot] = req.temperature
            if done_prefill:
                finalized.append(adm)
            seq = self._tracer.emit(
                "serve.prefill_chunk", rid=req.rid, offset=progress,
                take=take, final=done_prefill,
                step=self.scheduler._steps,
            )
            crec = self._fr(req.rid)
            if crec is not None:
                crec["chunks"].append({
                    "offset": progress, "take": take,
                    "step": self.scheduler._steps, "seq": seq,
                })
        if self.paged:
            # paged chunk: the block tables carry the storage mapping
            # (incl. any spliced prefix blocks) — no copy vectors
            (self._caches, self._lengths, self._last, self._temps,
             self._key, firsts) = self._dispatch(
                "paged_chunk", self._paged_chunk_jit,
                self._weights, self._caches, self._staged_tables(),
                self._stage_slots(rows), self._stage_slots(offs),
                self._stage_slots(clens), self._stage_slots(act),
                self._stage_slots(fin), self._lengths, self._last,
                self._temps, self._stage_slots(p_lens),
                self._stage_slots(new_temps), self._key,
            )
        else:
            # flash block-span read: the attended row slice need only
            # cover this call's deepest written position (queries see
            # the prefix copy + earlier chunks, all below it)
            span = self._fixed_span(
                max(progress + take for _a, progress, take in items)
            ) if items else None
            (self._caches, self._lengths, self._last, self._temps,
             self._key, firsts) = self._dispatch(
                "chunk_prefill", self._chunk_jit,
                self._weights, self._caches, self._lengths, self._last,
                self._temps, self._stage_slots(rows),
                self._stage_slots(offs), self._stage_slots(clens),
                self._stage_slots(act), self._stage_slots(fin),
                self._stage_slots(p_lens), self._stage_slots(new_temps),
                self._stage_slots(src), self._stage_slots(cmask),
                self._stage_slots(clen), self._key, bool(copies), span,
            )
        emitted = []
        if finalized:
            toks = self._host(firsts)
            for adm in finalized:
                req = adm.req
                self._prefilling.pop(adm.slot, None)
                if adm.slot in self._stale_prefill:
                    # prefill straddled refresh_weights(): rows mix
                    # weight generations — decode fine, donate never
                    self._stale_prefill.discard(adm.slot)
                else:
                    self.scheduler.on_prefill_complete(req)
                self._set_active(adm.slot, True)
                self._note_prefill(
                    req, self.scheduler.bucket_for(len(req.prompt))
                )
                self._emit(req, int(toks[adm.slot]))
                emitted.append((req, req.tokens[-1], req.done))
        return emitted

    # -- paged execution (ISSUE 7) -------------------------------------

    def _staged_tables(self):
        """Device copy of the scheduler's block tables, ``[num_slots,
        T]`` for the bucketed ``T`` covering the longest live table —
        rebuilt only when tables mutate or the bucket shifts. Rows pad
        with the sentinel id ``num_blocks`` (matches no pool row);
        idle slots are all-sentinel."""
        sched = self.scheduler
        need = max(
            (len(t) for t in sched.tables.values()), default=1
        )
        T = table_bucket_for(need, self._tbuckets)
        key = (sched.tables_version, T)
        if self._tables_cache is None or self._tables_cache[0] != key:
            arr = np.full((self.num_slots, T), self.num_blocks, np.int32)
            for slot, table in sched.tables.items():
                arr[slot, : len(table)] = table
            self._tables_cache = (key, self._stage_slots(arr))
        return self._tables_cache[1]

    def _pad_ids(self, blocks):
        """Block ids padded to their table bucket with the sentinel —
        gather/scatter programs compile once per bucket, not per
        count."""
        Tb = table_bucket_for(max(1, len(blocks)), self._tbuckets)
        ids = np.full((Tb,), self.num_blocks, np.int32)
        ids[: len(blocks)] = blocks
        return ids

    def _offload(self, pre) -> None:
        """Swap a preemption victim's K/V blocks to host memory. MUST
        run before any pool-writing program of the same step: the
        scheduler already re-leased the blocks on paper, but the device
        rows stay intact until the next write, and the gather is
        dispatched against the CURRENT pool value (the jit data
        dependency keeps it ordered before any donating consumer)."""
        req = pre.req
        with self._tracer.span(
            "serve.preempt", rid=req.rid, blocks=len(pre.blocks),
        ) as sp:
            rec = self._fr(req.rid)
            if rec is not None:
                rec["preemptions"].append({
                    "blocks": len(pre.blocks), "cur_len": pre.cur_len,
                    "step": self.scheduler._steps,
                    "seq": sp.begin_seq,
                })
            ids = self._pad_ids(pre.blocks)
            rows = self._dispatch(
                "offload_gather", self._gather_jit,
                self._caches, self._stage(ids),
            )
            n = len(pre.blocks)
            host = {
                name: tuple(
                    np.asarray(self._host(z))[:n].copy()
                    for z in leaves
                )
                for name, leaves in rows.items()
            }
            store = _OffloadRecord(
                rows=host, n_blocks=n, cur_len=pre.cur_len,
            )
            self._offloaded[req.rid] = store
        self._set_active(pre.slot, False)
        self._m_preemptions.inc()
        self._m_offload_blocks.inc(n)
        self._m_offload_bytes.inc(store.nbytes())
        logger.info(
            "preempted request %d (priority %d): %d blocks offloaded "
            "to host, slot %d freed", req.rid, req.priority, n, pre.slot,
        )

    def _resume(self, adm: Admission) -> None:
        """Restore an offloaded request into its fresh allocation:
        scatter the host rows into the new table's leading blocks and
        re-arm the slot's cursor/last-token/temperature. Bit-exact —
        the restored rows are bitwise the offloaded ones and greedy
        decode is a pure function of (weights, K/V, cursor, last)."""
        req = adm.req
        store = self._offloaded.pop(req.rid)
        with self._tracer.span(
            "serve.resume", rid=req.rid, blocks=store.n_blocks,
        ) as sp:
            rec = self._fr(req.rid)
            if rec is not None:
                rec["resumes"].append({
                    "blocks": store.n_blocks, "cur_len": store.cur_len,
                    "step": self.scheduler._steps,
                    "seq": sp.begin_seq,
                })
            n = store.n_blocks
            ids = self._pad_ids(adm.blocks[:n])
            Tb = len(ids)
            rows = {}
            for name, leaves in store.rows.items():
                staged = []
                for hz in leaves:
                    pz = np.zeros((Tb,) + hz.shape[1:], hz.dtype)
                    pz[:n] = hz
                    staged.append(self._stage(pz))
                rows[name] = tuple(staged)
            self._caches = self._dispatch(
                "resume_scatter", self._scatter_jit,
                self._caches, self._stage(ids), rows,
            )
            mask = np.zeros((self.num_slots,), bool)
            mask[adm.slot] = True
            r_len = np.zeros((self.num_slots,), np.int32)
            r_len[adm.slot] = store.cur_len
            r_last = np.zeros((self.num_slots,), np.int32)
            r_last[adm.slot] = req.tokens[-1]
            r_temps = np.zeros((self.num_slots,), np.float32)
            r_temps[adm.slot] = req.temperature
            self._lengths, self._last, self._temps = self._dispatch(
                "resume_state", self._resume_state_jit,
                self._lengths, self._last, self._temps,
                self._stage_slots(mask), self._stage_slots(r_len),
                self._stage_slots(r_last),
                self._stage_slots(r_temps),
            )
        self._set_active(adm.slot, True)
        self._m_resumes.inc()
        logger.info(
            "resumed request %d into slot %d (%d blocks restored, "
            "cursor %d)", req.rid, adm.slot, n, store.cur_len,
        )

    def _sp_eligible(self, a: Admission) -> bool:
        """Does this fresh admission take the sequence-parallel prefill
        path? Long cold prompts only — a prefix hit's shared blocks
        already paid most of the prefill, and the SP pad length must
        fit the model (else fall back, LOUDLY: silence here would hide
        that the knob the caller reached for is not engaging)."""
        if self.sp_mesh is None or a.shared_len:
            return False
        p = len(a.req.prompt)
        if p < self.sp_threshold:
            return False
        from elephas_tpu.serving.sp_prefill import sp_pad_len

        S = sp_pad_len(p, self.sp_mesh.shape[self.sp_axis], self.maxlen)
        if S is None:
            logger.warning(
                "sp_prefill: prompt of %d tokens has no power-of-two "
                "pad length inside maxlen=%d — falling back to the "
                "single-device prefill path for request %d",
                p, self.maxlen, a.req.rid,
            )
            return False
        return True

    def _sp_staged_weights(self):
        """The engine's weights replicated over the SP mesh (lazy,
        dropped by :meth:`refresh_weights`): engine weights may be
        COMMITTED to the default device (e.g. values assigned off a
        training mesh), and a committed single-device argument refuses
        to enter a program whose shard_map spans the SP mesh."""
        if self._sp_weights is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self.sp_mesh, P())
            self._sp_weights = {
                path: jax.device_put(w, rep)
                for path, w in self._weights.items()
            }
        return self._sp_weights

    def _sp_prefill(self, a: Admission):
        """Prefill one long prompt over the SP mesh: ONE sharded
        forward computes every position's K/V and logits, the rows
        land in the slot's reserved blocks via the resume scatter, and
        the first token samples from the prompt-end logits row. Decode
        then proceeds unmeshed, indistinguishable from a chunk-prefilled
        slot (token-exact at temperature 0)."""
        import jax.numpy as jnp

        from elephas_tpu.serving.sp_prefill import sp_pad_len

        req = a.req
        p = len(req.prompt)
        sp_w = self.sp_mesh.shape[self.sp_axis]
        S = sp_pad_len(p, sp_w, self.maxlen)
        tokens = np.zeros((1, S), np.int32)
        tokens[0, :p] = req.prompt
        n_res = blocks_for(p, self.block_size)
        ids = self._pad_ids(a.blocks[:n_res])
        Tb = len(ids)
        bs = self.block_size
        with self._tracer.span(
            "serve.sp_prefill", rid=req.rid, prompt=p, padded=S,
            shards=int(sp_w), mechanism=self.sp_mechanism,
        ) as sp:
            rec = self._fr(req.rid)
            if rec is not None:
                rec["sp_prefill"] = {
                    "padded": int(S), "shards": int(sp_w),
                    "mechanism": self.sp_mechanism,
                    "step": self.scheduler._steps,
                    "seq": sp.begin_seq,
                }
            kv, row = self._dispatch(
                "sp_prefill", self._sp_jit,
                self._sp_staged_weights(), jnp.asarray(tokens),
                np.int32(p),
            )
            # hop the K/V rows home through HOST memory (exactly how
            # preemption-resume stages its rows) and land them through
            # the UNMESHED scatter program — see sp_step's docstring.
            # The hop must NOT use device_put: that returns COMMITTED
            # arrays, committedness is part of jit cache keys, and one
            # committed leaf reaching the pool recompiles every
            # downstream program on its next dispatch.
            span = Tb * bs
            rows = {}
            for name, (kr, vr) in kv.items():
                hk = np.asarray(kr)
                hv = np.asarray(vr)
                if span <= S:
                    hk, hv = hk[:span], hv[:span]
                else:
                    pad = ((0, span - S), (0, 0), (0, 0))
                    hk = np.pad(hk, pad)
                    hv = np.pad(hv, pad)
                # sentinel-padded ids drop the bucketed tail; garbage
                # rows past the prompt land inside the request's OWN
                # reservation, where rewrite-before-visible covers them
                hk = hk.reshape(Tb, bs, *hk.shape[1:])
                hv = hv.reshape(Tb, bs, *hv.shape[1:])
                if self.kv_dtype == "fp":
                    rows[name] = (self._stage(hk), self._stage(hv))
                else:
                    # quantized arena: the landing rows must be codes
                    # + scales (the pool's stored layout) — host-side
                    # quantization matches the device programs'
                    # write-path math
                    hk, hks = quantize_rows_np(hk, self.kv_dtype)
                    hv, hvs = quantize_rows_np(hv, self.kv_dtype)
                    rows[name] = (
                        self._stage(hk), self._stage(hv),
                        self._stage(hks), self._stage(hvs),
                    )
            self._caches = self._dispatch(
                "resume_scatter", self._scatter_jit,
                self._caches, self._stage(ids), rows,
            )
            tok_dev, self._key = self._dispatch(
                "sp_sample", self._sp_sample_jit,
                self._stage(np.asarray(row)),
                jnp.full((1,), req.temperature, jnp.float32),
                self._key,
            )
            tok = int(np.asarray(tok_dev))
            mask = np.zeros((self.num_slots,), bool)
            mask[a.slot] = True
            r_len = np.zeros((self.num_slots,), np.int32)
            r_len[a.slot] = p
            r_last = np.zeros((self.num_slots,), np.int32)
            r_last[a.slot] = tok
            r_temps = np.zeros((self.num_slots,), np.float32)
            r_temps[a.slot] = req.temperature
            self._lengths, self._last, self._temps = self._dispatch(
                "resume_state", self._resume_state_jit,
                self._lengths, self._last, self._temps,
                self._stage_slots(mask), self._stage_slots(r_len),
                self._stage_slots(r_last),
                self._stage_slots(r_temps),
            )
        self.scheduler.on_prefill_complete(req)
        self._set_active(a.slot, True)
        self._note_prefill(req, f"sp{S}")
        self._emit(req, tok)
        return [(req, req.tokens[-1], req.done)]

    def _note_prefill(self, req: Request, bucket) -> None:
        """One histogram observation per completed prefill, labeled by
        the prompt's SIZE CLASS — the prompt-bucket ladder entry
        covering it, or ``sp<S>`` for an SP prefill (ISSUE 11
        telemetry). Chunked/paged prefills compile per chunk width,
        so this classifies the prompt, not the compiled program."""
        self._mf_prefill_tokens.labels(
            engine=self.telemetry_label, bucket=str(bucket)
        ).observe(len(req.prompt))

    def _admit_wave_paged(self, plan: list[Admission]):
        """Execute one paged admission wave: resumes restore their
        offloaded state (no prefill), fresh admissions prefill their
        un-shared suffix through the paged chunk program — whole
        suffix in one bucketed-width call, or budgeted chunks under
        ``prefill_chunk``. Prefix hits need NO device copy: the shared
        blocks already sit in the slot's table. Long cold prompts take
        the sequence-parallel path when ``sp_prefill`` is armed
        (:meth:`_sp_prefill`) — chunk budgets do not apply to them
        (the SP dispatch IS the bounded unit of work)."""
        emitted: list[tuple[Request, int, bool]] = []
        for a in plan:
            if a.resume is not None:
                self._resume(a)
        fresh = []
        for a in plan:
            if a.resume is not None:
                continue
            if self._sp_eligible(a):
                emitted.extend(self._sp_prefill(a))
            else:
                fresh.append(a)
        if self.prefill_chunk:
            for a in fresh:
                self._prefilling[a.slot] = [a, a.shared_len]
            return emitted
        by_width: dict[int, list] = {}
        for a in fresh:
            suffix = len(a.req.prompt) - a.shared_len
            by_width.setdefault(
                self.scheduler.bucket_for(suffix), []
            ).append((a, a.shared_len, suffix))
        for width in sorted(by_width):
            emitted.extend(self._run_chunk(by_width[width], width))
        return emitted

    def _admit_wave(self, plan: list[Admission]):
        """Execute one admission wave. Without chunking: full-bucket
        prefill for the cold requests (legacy wave), and for prefix
        hits ONE fused copy+suffix-chunk call per suffix bucket. With
        chunking: the wave's copies land NOW in one standalone
        copy-program call (the donors are only pinned through this
        wave — a budget-deferred chunk must not read a maybe-evicted
        donor later), then everything queues for budgeted chunks."""
        emitted: list[tuple[Request, int, bool]] = []
        copies = [a for a in plan if a.donor_slot is not None]
        if self.prefill_chunk:
            if copies:
                src, mask, clen = self._copy_vectors(copies)
                self._caches = self._dispatch(
                    "prefix_copy", self._copy_jit,
                    self._caches, self._stage_slots(src),
                    self._stage_slots(mask), self._stage_slots(clen),
                )
            for a in plan:
                self._prefilling[a.slot] = [a, a.reuse_len]
            return emitted
        cold = [a.req for a in plan if a.donor_slot is None]
        if cold:
            self._prefill_wave(cold)
            emitted.extend(
                (req, req.tokens[-1], req.done) for req in cold
            )
        # fused copy + suffix-only prefill of the hits, one chunk call
        # per suffix bucket (widths stay inside the closed ladder)
        by_width: dict[int, list] = {}
        for a in copies:
            suffix = len(a.req.prompt) - a.reuse_len
            by_width.setdefault(
                self.scheduler.bucket_for(suffix), []
            ).append((a, a.reuse_len, suffix))
        for width in sorted(by_width):
            emitted.extend(self._run_chunk(
                by_width[width], width,
                copies=[a for a, _p, _t in by_width[width]],
            ))
        return emitted

    def _prefill_progress(self):
        """Spend this step's prefill token budget on chunk calls: every
        mid-prefill slot advances by up to ``prefill_chunk`` tokens per
        call, calls repeat until the budget is spent or the queue
        drains. Decode windows run BETWEEN these budgeted slices — the
        whole point: a long prompt streams in without stalling in-flight
        requests' next tokens."""
        emitted: list[tuple[Request, int, bool]] = []
        if not self._prefilling:
            return emitted
        budget = self._prefill_budget
        served: set[int] = set()
        while self._prefilling and budget > 0:
            # the budget caps TOTAL prefill tokens this step, not per
            # call: with several long prompts mid-prefill, slots beyond
            # the budget wait for the next step (lowest slot first,
            # deterministic) — otherwise N concurrent arrivals would
            # cost N×chunk per step and in-flight inter-token latency
            # would scale with arrival count, the exact stall this
            # budget exists to bound
            items = []
            for slot in sorted(self._prefilling):
                if budget <= 0:
                    break
                adm, progress = self._prefilling[slot]
                take = min(
                    self.prefill_chunk, len(adm.req.prompt) - progress
                )
                items.append((adm, progress, take))
                served.add(slot)
                budget -= take
            emitted.extend(self._run_chunk(items, self.prefill_chunk))
            for adm, progress, take in items:
                if adm.slot in self._prefilling:
                    self._prefilling[adm.slot][1] = progress + take
        stalled = sum(1 for s in self._prefilling if s not in served)
        if stalled:
            # chunk-budget stall: slots that got NO chunk this step and
            # wait for the next one — the bounded-latency trade the
            # budget exists to make, but a rising rate means arrivals
            # outpace the budget. Slots that advanced this step are not
            # stalled even if they remain mid-prefill.
            self._m_prefill_stalls.inc(stalled)
        return emitted

    def _note_admissions(self, plan) -> None:
        """Per-tenant admitted counters (ISSUE 10) — fresh admissions
        only; a preemption resume was already counted when it first
        entered a slot."""
        if self.policy is None:
            return
        for a in plan:
            if a.resume is None:
                self._tenant_child(
                    self._mf_tenant_admitted, a.req.tenant
                ).inc()

    def step(self) -> list[tuple[Request, int, bool]]:
        """One engine iteration: admission of waiting requests into
        free slots (prefix-cache copies + prefill — full-wave, or
        budgeted chunks interleaved with decode), then one arena-wide
        decode window of ``steps_per_sync`` steps over the slots whose
        prefill has completed. Returns ``(request, token, done)``
        triples in generation order (a request can appear several
        times: its prefill token plus one per window position); the
        ``done`` flag is per-TOKEN — True only on a request's final
        token, so stream consumers can stop at it without dropping
        tokens."""
        emitted: list[tuple[Request, int, bool]] = []
        if self.paged:
            plan, preempts = self.scheduler.admit_paged(
                prefilling=frozenset(self._prefilling)
            )
            # offloads FIRST: victims' device rows must be read before
            # any admission's prefill (or resume scatter) writes the
            # pool — the gather is dispatched against the current pool
            # value, so ordering here is the whole correctness story
            for pre in preempts:
                self._offload(pre)
            if plan:
                self._note_admissions(plan)
                self._trace_admissions(plan)
                emitted.extend(self._admit_wave_paged(plan))
        else:
            plan = self.scheduler.admit()
            if plan:
                # admission emissions land before any decode token, so
                # req.done there is the prefill token's own flag
                self._note_admissions(plan)
                self._trace_admissions(plan)
                emitted.extend(self._admit_wave(plan))
        emitted.extend(self._prefill_progress())
        if not any(
            slot not in self._prefilling for slot in self.scheduler.active
        ):
            return emitted
        self._m_decode_windows.inc()
        if self.speculative:
            emitted.extend(self._spec_decode_phase())
        else:
            emitted.extend(self._decode_window())
        return emitted

    def _decode_window(self):
        """One arena-wide plain decode window of ``steps_per_sync``
        steps — the non-speculative decode phase, and the speculative
        engine's fallback when no slot drafted this round."""
        if self.speculative and self._spec_dirty:
            self._refresh_decode_state()
        emitted: list[tuple[Request, int, bool]] = []
        with self._tracer.span(
            "serve.decode_window", steps=self.steps_per_sync,
            active=len(self.scheduler.active),
        ):
            if self.paged:
                (self._caches, self._lengths, self._last, self._key,
                 window) = self._dispatch(
                    "paged_decode", self._paged_decode_jit,
                    self._weights, self._caches, self._staged_tables(),
                    self._lengths, self._last, self._temps,
                    self._sync_active(), self._key,
                )
            else:
                (self._caches, self._lengths, self._last, self._key,
                 window) = self._dispatch(
                    "decode", self._decode_jit,
                    self._weights, self._caches, self._lengths,
                    self._last, self._temps, self._sync_active(),
                    self._key, self._decode_span(),
                )
            toks = self._host(window)  # [steps_per_sync, num_slots]
            for i in range(self.steps_per_sync):
                if not self.scheduler.active:
                    break  # window tail decoded garbage for empty slots
                self.scheduler.note_step()
                for slot, req in sorted(self.scheduler.active.items()):
                    if slot in self._prefilling:
                        continue  # mid-prefill: no decode tokens yet
                    done = self._emit(req, int(toks[i, slot]))
                    emitted.append((req, req.tokens[-1], done))
        return emitted

    # -- speculative decoding (ISSUE 8) --------------------------------

    def _refresh_decode_state(self):
        """Re-stage the device length/last vectors from host truth.
        Verify rounds advance positions host-side only (resident length
        = prompt + generated - 1, the invariant preemption's ``cur_len``
        already relies on), so before a plain decode window reads the
        device vectors they must be rebuilt. Mid-prefill and idle slots
        stage zeros — the decode active mask excludes them, and a later
        chunk finalize sets their real state on device."""
        lengths = np.zeros((self.num_slots,), np.int32)
        last = np.zeros((self.num_slots,), np.int32)
        for slot, req in self.scheduler.active.items():
            if slot in self._prefilling or not req.tokens:
                continue
            lengths[slot] = len(req.prompt) + len(req.tokens) - 1
            last[slot] = req.tokens[-1]
        self._lengths = self._stage_slots(lengths)
        self._last = self._stage_slots(last)
        self._spec_dirty = False

    def _spec_decode_phase(self):
        """One speculative decode round: collect drafts for every
        decoding slot (throttle- and budget-capped), then either run
        ONE batched verify forward over the whole window — emitting
        the accepted prefix + bonus token per slot — or, when nobody
        drafted (throttled, no n-gram match, budget exhausted), fall
        back to one plain ``steps_per_sync`` decode window so
        speculation-hostile phases keep the multi-step amortization."""
        items = []
        for slot in sorted(self.scheduler.active):
            if slot in self._prefilling:
                continue
            req = self.scheduler.active[slot]
            remaining = req.max_new_tokens - len(req.tokens)
            cursor = len(req.prompt) + len(req.tokens) - 1
            # the verify window feeds 1 + n_drafts tokens at positions
            # cursor.. and emits at most n_drafts + 1 tokens: drafts
            # are capped so writes stay inside the slot's row (and its
            # paged block reservation) and emissions inside the budget
            k_cap = min(
                self.spec_k, remaining - 1, self.maxlen - 1 - cursor
            )
            if k_cap >= 1 and self._spec_throttle.should_draft(req.rid):
                items.append((slot, req, k_cap))
        proposals = (
            self._drafter.propose_batch(items) if items else {}
        )
        # defend the extension point: a custom drafter returning MORE
        # than its k (which sizes the packed window and the accept
        # loop) or drafts for slots it was never asked about (which
        # would bypass the throttle and the budget/maxlen caps) must
        # not corrupt the round — clip to each item's own cap, drop
        # uninvited slots
        caps = {slot: k for slot, _req, k in items}
        proposals = {
            slot: list(d)[: caps[slot]]
            for slot, d in proposals.items()
            if slot in caps and d
        }
        drafted = sum(len(d) for d in proposals.values())
        if drafted == 0:
            return self._decode_window()
        return self._verify_round(proposals, drafted)

    def _verify_round(self, proposals, drafted: int):
        """Dispatch one batched verify forward and commit its verdict:
        per slot, accept the longest draft prefix matching the model's
        own sampled tokens, emit those plus the bonus token, and roll
        the resident length back over the rejected tail (host-side
        cursor arithmetic — the garbage K/V is rewritten before any
        query can see it; paged tails stay inside already-reserved
        blocks, so the allocator is never touched mid-step)."""
        W = self.spec_k + 1
        # one packed [num_slots, W+3] upload: tokens | offset | n_fed
        # | active — see the program definition for why
        packed = np.zeros((self.num_slots, W + 3), np.int32)
        verifying = []
        for slot in sorted(self.scheduler.active):
            if slot in self._prefilling:
                continue
            req = self.scheduler.active[slot]
            drafts = proposals.get(slot, [])
            packed[slot, 0] = req.tokens[-1]
            packed[slot, 1:1 + len(drafts)] = drafts
            packed[slot, W] = len(req.prompt) + len(req.tokens) - 1
            packed[slot, W + 1] = 1 + len(drafts)
            packed[slot, W + 2] = 1
            verifying.append((slot, req, drafts))
        emitted: list[tuple[Request, int, bool]] = []
        with self._tracer.span(
            "serve.verify", slots=len(verifying), drafted=drafted,
            k=self.spec_k,
        ) as span:
            if self.paged:
                self._caches, self._key, sampled = self._dispatch(
                    "spec_verify", self._verify_jit,
                    self._weights, self._caches, self._staged_tables(),
                    self._stage_slots(packed), self._temps, self._key,
                )
            else:
                # attended span covers the window's deepest write:
                # offset + n_fed over the verifying slots
                att_span = self._fixed_span(max(
                    int(packed[s, W]) + int(packed[s, W + 1])
                    for s, _r, _d in verifying
                )) if verifying else None
                self._caches, self._key, sampled = self._dispatch(
                    "spec_verify", self._verify_jit,
                    self._weights, self._caches,
                    self._stage_slots(packed), self._temps, self._key,
                    att_span,
                )
            toks = self._host(sampled)  # [num_slots, W]
            self.scheduler.note_step()
            accepted_total = 0
            for slot, req, drafts in verifying:
                t = toks[slot]
                a = 0
                while a < len(drafts) and drafts[a] == int(t[a]):
                    a += 1
                # accepted drafts + the model's bonus token, in order;
                # a mid-window EOS finish discards the rest
                n_emitted = 0
                for j in range(a + 1):
                    done = self._emit(req, int(t[j]))
                    emitted.append((req, req.tokens[-1], done))
                    n_emitted += 1
                    if done:
                        break
                # count only accepted drafts that actually EMITTED —
                # an EOS inside the window discards the matched tail,
                # and those drafts saved no decode step (the counter's
                # promise); the throttle gets the same truthful figure
                a = min(a, n_emitted)
                accepted_total += a
                req.spec_drafted += len(drafts)
                req.spec_accepted += a
                tripped = self._spec_throttle.note(
                    req.rid, len(drafts), a
                )
                if tripped:
                    self._m_spec_throttled.inc()
                seq = self._tracer.emit(
                    "serve.spec_verify", rid=req.rid,
                    drafted=len(drafts), accepted=a,
                    throttled=self._spec_throttle.throttled(req.rid),
                    step=self.scheduler._steps,
                )
                rec = self._fr(req.rid)
                if rec is not None:
                    rec["spec_rounds"].append({
                        "drafted": len(drafts), "accepted": a,
                        "throttled": self._spec_throttle.throttled(
                            req.rid
                        ),
                        "step": self.scheduler._steps, "seq": seq,
                    })
                    # a request that FINISHED inside this round was
                    # filed by _fr_finish before these per-round
                    # increments landed — refresh the totals so the
                    # record always agrees with its own spec_rounds
                    # (same dict object whether live or filed)
                    rec["spec_drafted"] = req.spec_drafted
                    rec["spec_accepted"] = req.spec_accepted
            span.set(accepted=accepted_total)
        self._m_spec_drafted.inc(drafted)
        self._m_spec_accepted.inc(accepted_total)
        self._m_spec_rounds.inc()
        self._spec_dirty = True
        return emitted

    def stream(self):
        """Drive the engine until the queue drains, yielding
        ``(request_id, token, done)`` as tokens land — the per-request
        token stream. More requests may be submitted while consuming
        (they join the next admission wave)."""
        while self.scheduler.has_work:
            for req, token, done in self.step():
                yield req.rid, token, done

    def run(self, requests=None) -> dict[int, np.ndarray]:
        """Convenience batch driver: optionally submit ``requests``
        (an iterable of ``(prompt, max_new_tokens)`` pairs or kwargs
        dicts), drive the engine until idle, and return
        ``{request_id: full token sequence (prompt + generated)}``.

        Requests submitted through THIS call are exempt from the
        bounded finished-registry eviction until it returns — a huge
        batch cannot silently lose its own oldest results."""
        submitted: list[Request] = []
        if requests is not None:
            for r in requests:
                if isinstance(r, dict):
                    submitted.append(self.submit(**r))
                else:
                    prompt, max_new = r
                    submitted.append(self.submit(prompt, max_new))
        protected = {r.rid for r in submitted} - self._protected
        self._protected |= protected
        try:
            drained: dict[int, np.ndarray] = {}
            while self.scheduler.has_work:
                for req, _tok, done in self.step():
                    if done:
                        drained[req.rid] = np.asarray(
                            req.full_sequence, np.int32
                        )
        finally:
            self._protected -= protected
            self._evict_finished()  # deferred trim, still loud
        return drained

    # -- lifecycle control: cancel + live migration (ISSUE 14) ---------

    def _detach(self, req: Request, reason: str) -> None:
        """Shared bookkeeping for a request leaving the engine before
        completion (cancel / migration export): policy + spec-throttle
        accounting drop and the flight record files with ``reason``."""
        if self.policy is not None:
            self.policy.on_finish(req)
        if self._spec_throttle is not None:
            self._spec_throttle.forget(req.rid)
        self._fr_finish(req, reason)

    def _find_slot(self, rid: int) -> int | None:
        return next(
            (s for s, r in self.scheduler.active.items()
             if r.rid == rid),
            None,
        )

    def _notify_stream_end(self, req: Request) -> None:
        """Tell a request's live stream it ENDED without a final
        engine token — ``on_token(None, True)``. Without this, a
        consumer blocking on the token stream (the gateway's SSE/JSON
        handlers) waits forever when the request is cancelled or
        migrated away mid-flight: those paths flip ``req.done``
        without ever invoking the callback."""
        cb = req.on_token
        if cb is not None:
            try:
                cb(None, True)
            except BaseException:
                logger.warning(
                    "request %d stream-end callback failed",
                    req.rid, exc_info=True,
                )

    def cancel(self, rid: int) -> bool:
        """Abort one in-flight request and reclaim its slot/blocks
        NOW — a disconnected SSE client's request must not decode to
        completion into a queue nobody reads (the gateway wires client
        aborts here; the router's re-drive path uses it too). Works on
        every engine config: a waiting request just leaves the queue, a
        preempted one drops its host offload record, an active one
        frees its slot (and block table, paged) at the next step
        boundary — deterministic host bookkeeping only, no device
        program runs. Returns True when the rid was live (its
        ``req.done`` flips True with ``req.error`` set to
        :class:`RequestCancelled`; generated-so-far tokens are kept),
        False when it was unknown or already finished.

        Gang contract: like :meth:`submit`, every gang process must
        issue the identical cancel sequence at the identical step
        boundaries — cancellation reshapes the admission schedule."""
        rid = int(rid)
        sched = self.scheduler
        req = sched.remove_waiting(rid)
        if req is not None:
            # a preempted victim waiting to resume also drops its
            # host-offloaded K/V here
            self._offloaded.pop(rid, None)
        else:
            slot = self._find_slot(rid)
            if slot is None:
                return False
            req = sched.active[slot]
            self._prefilling.pop(slot, None)
            self._stale_prefill.discard(slot)
            sched.reclaim(slot)
            self._set_active(slot, False)
        req.done = True
        req.error = RequestCancelled(f"request {rid} cancelled")
        # a live stream must UNBLOCK, not hang: cancel never delivers
        # a final token, so send the explicit end sentinel
        self._notify_stream_end(req)
        self._m_cancelled.inc()
        self._tracer.emit(
            "serve.cancel", rid=rid, tokens=len(req.tokens),
            step=sched._steps,
        )
        self._detach(req, "cancelled")
        self.finished[rid] = req
        self._evict_finished()
        return True

    def score(self, prompt, completion) -> dict:
        """Log-probabilities of ``completion`` given ``prompt`` in ONE
        forward pass (ISSUE 19): scoring is verify-without-accept —
        the sequence ``prompt + completion[:-1]`` feeds through the
        existing verify/chunk program shape on lane 0, and logits row
        ``j`` scores the token at position ``j+1``. The forward runs
        against a NON-donated copy of the live arena whose update is
        discarded, so scoring never perturbs in-flight serving state
        (no allocation, no cursor movement, no PRNG consumption).

        Returns ``{"logprobs": [per-completion-token logprob],
        "total_logprob", "greedy_tokens": [argmax token per position],
        "agreement": fraction of completion tokens matching greedy}``
        — greedy tokens make this the fp-oracle token-agreement probe
        for a quantized pool (temperature-0 caveat: agreement
        compares argmax, so it is exactly what greedy decode would
        emit position-by-position given this prefix).

        Compiled per (width bucket[, table/span bucket]) — the same
        closed ladders the serving programs use, so a scoring workload
        cannot grow the compile set unboundedly. Requires ``prompt``
        and ``completion`` non-empty and their sum within ``maxlen``.
        """
        prompt = [int(t) for t in prompt]
        completion = [int(t) for t in completion]
        if not prompt:
            raise ValueError("score() needs a non-empty prompt")
        if not completion:
            raise ValueError("score() needs a non-empty completion")
        total = len(prompt) + len(completion)
        if total > self.maxlen:
            raise ValueError(
                f"prompt ({len(prompt)}) + completion "
                f"({len(completion)}) exceeds maxlen ({self.maxlen})"
            )
        seq = prompt + completion
        n = total - 1  # fed positions; row j scores seq[j+1]
        width = self.scheduler.bucket_for(n)
        tokens = np.zeros((self.num_slots, width), np.int32)
        tokens[0, :n] = seq[:n]
        targets = np.zeros((width,), np.int32)
        targets[:n] = seq[1:]
        clens = np.zeros((self.num_slots,), np.int32)
        clens[0] = n
        act = np.zeros((self.num_slots,), bool)
        act[0] = True
        if self.paged:
            nb = blocks_for(n, self.block_size)
            if nb > self.num_blocks:
                raise ValueError(
                    f"scoring {n} positions needs {nb} blocks — more "
                    f"than the pool's {self.num_blocks}"
                )
            Tb = table_bucket_for(nb, self._tbuckets)
            # scratch arange table: the one-hot writes land only in
            # the DISCARDED pool copy, so any block ids are safe
            tab = np.full((self.num_slots, Tb), self.num_blocks,
                          np.int32)
            tab[0, :nb] = np.arange(nb, dtype=np.int32)
            tlp, greedy = self._dispatch(
                "score", self._score_jit,
                self._weights, self._caches, self._stage(tab),
                self._stage(tokens), self._stage_slots(clens),
                self._stage_slots(act), self._stage(targets),
            )
        else:
            span = (
                span_bucket_for(n, self._sbuckets)
                if self.attention == "flash" else None
            )
            tlp, greedy = self._dispatch(
                "score", self._score_jit,
                self._weights, self._caches, self._stage(tokens),
                self._stage_slots(clens), self._stage_slots(act),
                self._stage(targets), span,
            )
        tlp = np.asarray(self._host(tlp))
        greedy = np.asarray(self._host(greedy))
        p = len(prompt)
        lps = [float(x) for x in tlp[p - 1:n]]
        g = [int(t) for t in greedy[p - 1:n]]
        agreed = sum(1 for a, b in zip(g, completion) if a == b)
        self._m_score_requests.inc()
        self._tracer.emit(
            "serve.score", prompt_tokens=p,
            completion_tokens=len(completion),
            agreement=agreed / len(completion),
        )
        return {
            "logprobs": lps,
            "total_logprob": float(sum(lps)),
            "greedy_tokens": g,
            "agreement": agreed / len(completion),
        }

    def export_request(self, rid: int, *,
                       notify_stream: bool = False) -> dict:
        """Freeze one live request and hand back its **migration
        record** (ISSUE 14): a host-native dict — prompt, generated
        tokens, budget/sampling/tenant knobs, and (warm path) the
        preemption offload rows (dense per-layer K/V blocks) plus the
        cursor state — that :meth:`import_request` on ANOTHER replica
        resumes bit-exact at temperature 0. PR 7's offload record IS
        the serialization format; this method just detaches it from
        the engine. The request leaves this engine entirely (policy
        accounting dropped, flight record filed as ``migrated`` — it
        is NOT in ``finished``, it lives on elsewhere).

        Warm export (K/V travels) needs a paged engine and a request
        holding at least one generated token; waiting, mid-prefill,
        and tokenless requests export COLD (the target re-prefills —
        nothing resident is worth moving). An in-flight fixed-arena
        request with tokens refuses loudly: the fixed arena has no
        block-granular gather. Raises ``KeyError`` for a rid that is
        not live here. Wire encoding lives in
        :mod:`elephas_tpu.fleet.migration`.

        ``notify_stream=True`` sends the exported request's live
        ``on_token`` stream the ``(None, True)`` end sentinel — the
        wire-migration shape (gateway ``/v1/requests/{rid}/export``),
        where no callback travels and a local consumer blocking on
        the stream would otherwise hang forever. The in-process fleet
        router keeps the default: it re-attaches the SAME stream on
        import, so the tokens must keep flowing to it."""
        rid = int(rid)
        sched = self.scheduler
        store = self._offloaded.pop(rid, None)
        if store is not None:
            # already preempted: its offload record is the migration
            # payload, ready-made (victims always wait in the queue)
            req = sched.remove_waiting(rid)
            assert req is not None  # preempted ⇒ waiting, invariant
            return self._export_payload(
                req, store, notify_stream=notify_stream
            )
        slot = self._find_slot(rid)
        if slot is not None:
            req = sched.active[slot]
            if slot not in self._prefilling and req.tokens:
                if not self.paged:
                    raise ValueError(
                        f"cannot warm-export in-flight request {rid} "
                        f"from a fixed-arena engine — block offload "
                        f"needs paged=True (cancel it or let it finish)"
                    )
                # force-preempt regardless of priority: drain has
                # authority pressure never does. The engine offloads
                # the device rows to host, then the record detaches
                # through the _offloaded branch above.
                pre = sched._preempt(req)
                self._offload(pre)
                return self.export_request(
                    rid, notify_stream=notify_stream
                )
            # mid-prefill / tokenless: partial rows are not a resumable
            # state — cold export, target prefills from scratch
            self._prefilling.pop(slot, None)
            self._stale_prefill.discard(slot)
            sched.reclaim(slot)
            self._set_active(slot, False)
            return self._export_payload(
                req, None, notify_stream=notify_stream
            )
        req = sched.remove_waiting(rid)
        if req is None:
            raise KeyError(f"request {rid} is not live on this engine")
        return self._export_payload(
            req, None, notify_stream=notify_stream
        )

    def _export_payload(self, req: Request, store, *,
                        notify_stream: bool = False) -> dict:
        self._detach(req, "migrated")
        if notify_stream:
            self._notify_stream_end(req)
        self._m_migrated_out.inc()
        self._m_export_bytes.inc(0 if store is None else store.nbytes())
        self._tracer.emit(
            "serve.export", rid=req.rid, warm=store is not None,
            n_blocks=0 if store is None else store.n_blocks,
            tokens=len(req.tokens), step=self.scheduler._steps,
        )
        return {
            # v2 (ISSUE 19): rows travel at the arena's STORED dtype
            # (fp pairs, or quantized code+scale 4-tuples), declared
            # by kv_dtype so an importer can refuse a mismatch before
            # touching array bytes; v1 records remain importable
            # v3 (ISSUE 20): weight_ver declares the K/V's generation —
            # warm rows computed under generation N are garbage under
            # N+1, so the importer refuses a non-zero mismatch loudly
            "version": 3,
            "kv_dtype": self.kv_dtype,
            "weight_ver": self.weight_version,
            "rid": int(req.rid),
            "prompt": [int(t) for t in req.prompt],
            "tokens": [int(t) for t in req.tokens],
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": float(req.temperature),
            "eos_id": None if req.eos_id is None else int(req.eos_id),
            "priority": int(req.priority),
            "tenant": req.tenant,
            "ttft_deadline_ms": req.ttft_deadline_ms,
            # trace context rides the record so the migrated half of
            # the lifecycle joins the same story on a merged timeline
            "trace": telemetry.current_trace(),
            "block_size": self.block_size,
            "cur_len": 0 if store is None else store.cur_len,
            "n_blocks": 0 if store is None else store.n_blocks,
            "rows": {} if store is None else dict(store.rows),
        }

    def import_request(self, record: dict, on_token=None) -> Request:
        """Adopt a migration record exported by another replica
        (ISSUE 14). A warm record (``n_blocks > 0``) re-enters through
        the preemption-resume path: the K/V rows park as a host
        offload record, the request waits at the queue FRONT, and the
        next admission scatters the rows into a fresh block table and
        re-arms the cursor — bit-exact at temperature 0 by the same
        argument as local preempt/resume (greedy decode is a pure
        function of weights + K/V + cursor + last token; replicas
        serve identical weights). A cold record is an ordinary
        re-submission. ``on_token`` re-attaches the caller's stream
        (callbacks never travel on the wire). Temp>0 streams re-key on
        THIS engine's PRNG stream — deterministic per config, but not
        the source engine's continuation (same caveat as chunked
        prefill).

        Validates loudly: version, maxlen fit, rid not already live
        here, tenant known to this engine's policy, and — warm —
        paged target, matching block size/geometry, matching
        ``kv_dtype`` (quantized blocks are only bit-portable between
        arenas storing the same dtype — v1/fp records refuse into a
        quantized arena and vice versa), and the ``cur_len == prompt
        + generated - 1`` resume invariant."""
        if int(record.get("version", -1)) not in (1, 2, 3):
            raise ValueError(
                f"unknown migration record version "
                f"{record.get('version')!r} (this engine speaks "
                f"v1..v3)"
            )
        sched = self.scheduler
        rid = int(record["rid"])
        prompt = tuple(int(t) for t in record["prompt"])
        tokens = [int(t) for t in record["tokens"]]
        max_new = int(record["max_new_tokens"])
        if not prompt:
            raise ValueError("migration record has an empty prompt")
        if len(prompt) + max_new > self.maxlen:
            raise ValueError(
                f"record needs prompt ({len(prompt)}) + budget "
                f"({max_new}) <= maxlen ({self.maxlen})"
            )
        if (
            rid in self._offloaded
            or rid in self.finished
            or any(r.rid == rid for r in sched.waiting)
            or any(r.rid == rid for r in sched.active.values())
        ):
            # exactly-once: live rids always refuse; served rids
            # refuse for as long as the BOUNDED finished registry
            # remembers them (best-effort replay guard — the wire
            # protocol's real guarantee is that export detaches the
            # record from its source exactly once)
            raise ValueError(
                f"request {rid} is already live (or was already "
                f"served) on this engine — a record must be imported "
                f"exactly once"
            )
        tenant = record.get("tenant")
        if tenant is not None and (
            self.policy is None or not self.policy.knows(tenant)
        ):
            raise ValueError(
                f"record carries tenant {tenant!r} unknown to this "
                f"engine's policy — fleet replicas must declare "
                f"identical tenants"
            )
        rows = record.get("rows") or {}
        n_blocks = int(record.get("n_blocks") or 0)
        warm = n_blocks > 0
        if not warm and tokens:
            # a cold import re-prefills the PROMPT only: pre-set
            # generated tokens would interleave with tokens decoded
            # from a context that never saw them, and silently eat
            # the budget — no legitimate export produces this shape
            raise ValueError(
                f"cold record (n_blocks=0) carries {len(tokens)} "
                f"generated tokens — token-holding requests must "
                f"export WARM (K/V travels) or not at all"
            )
        if warm:
            if not self.paged:
                raise ValueError(
                    "warm migration record needs a paged target engine"
                )
            if int(record["block_size"]) != self.block_size:
                raise ValueError(
                    f"record block_size {record['block_size']} != this "
                    f"engine's {self.block_size} — K/V blocks are not "
                    f"geometry-portable"
                )
            rec_dtype = record.get("kv_dtype", "fp")
            if rec_dtype != self.kv_dtype:
                raise ValueError(
                    f"record kv_dtype {rec_dtype!r} != this engine's "
                    f"{self.kv_dtype!r} — quantized KV blocks are "
                    f"bit-portable only between arenas storing the "
                    f"same dtype (re-drive the request cold instead)"
                )
            # weight generation (ISSUE 20, v3): warm rows computed
            # under generation N are garbage under N+1 — resuming them
            # would silently break bit-exactness, the exact failure
            # this field exists to catch. 0 means "unversioned /
            # legacy record, cannot verify" (the shard-identity idiom):
            # refusal needs BOTH sides to claim a generation.
            rec_wver = int(record.get("weight_ver", 0))
            if rec_wver and self.weight_version and (
                rec_wver != self.weight_version
            ):
                raise ValueError(
                    f"record weight_ver {rec_wver} != this engine's "
                    f"weight_version {self.weight_version} — warm K/V "
                    f"from another weight generation cannot resume "
                    f"bit-exact (re-drive the request cold instead)"
                )
            arity = 2 if self.kv_dtype == "fp" else 4
            bad_arity = {
                name: len(leaves) for name, leaves in rows.items()
                if len(leaves) != arity
            }
            if bad_arity:
                raise ValueError(
                    f"record rows carry {bad_arity} arrays per layer "
                    f"— a {self.kv_dtype!r} arena stores {arity} "
                    f"(torn or mis-encoded record)"
                )
            if not tokens:
                raise ValueError(
                    "warm record without generated tokens — the resume "
                    "cursor math (last token re-arm) would be wrong"
                )
            cur_len = int(record["cur_len"])
            if cur_len != len(prompt) + len(tokens) - 1:
                raise ValueError(
                    f"corrupt record: cur_len {cur_len} != prompt "
                    f"({len(prompt)}) + generated ({len(tokens)}) - 1"
                )
            if n_blocks != blocks_for(cur_len, self.block_size):
                raise ValueError(
                    f"corrupt record: {n_blocks} blocks cannot cover "
                    f"cur_len {cur_len} at block_size {self.block_size}"
                )
            expected = {name for name, _h, _d in self.arena.specs}
            if set(rows) != expected:
                raise ValueError(
                    f"record layers {sorted(rows)} != this engine's "
                    f"{sorted(expected)} — different model architecture"
                )
            if blocks_for(
                len(prompt) + max_new, self.block_size
            ) > self.num_blocks:
                raise ValueError(
                    f"record can never fit this pool ({self.num_blocks}"
                    f" blocks) — route it to a larger replica"
                )
        req = Request(
            rid=rid, prompt=prompt, max_new_tokens=max_new,
            temperature=float(record.get("temperature") or 0.0),
            eos_id=(
                None if record.get("eos_id") is None
                else int(record["eos_id"])
            ),
            priority=int(record.get("priority") or 0),
            tenant=tenant,
            ttft_deadline_ms=record.get("ttft_deadline_ms"),
            tokens=tokens,
            on_token=on_token,
        )
        req.submit_step = sched._steps
        # TTFT was (or will be) observed where the request FIRST ran;
        # submit_time stays None here so a migrated request's next
        # token never double-observes the TTFT histogram or SLO
        # counters on the adopting engine
        req.exemplar = {"rid": str(rid)}
        rec = self._fr_new(req)
        seq = self._tracer.emit(
            "serve.import", rid=rid, warm=warm, n_blocks=n_blocks,
            tokens=len(tokens), step=sched._steps,
        )
        if rec is not None:
            rec["submit_seq"] = seq
        if warm:
            host_rows = {
                name: tuple(
                    np.ascontiguousarray(a) for a in leaves
                )
                for name, leaves in rows.items()
            }
            self._offloaded[rid] = _OffloadRecord(
                rows=host_rows, n_blocks=n_blocks,
                cur_len=int(record["cur_len"]),
            )
            sched.adopt_preempted(req, int(record["cur_len"]))
        else:
            # scheduler.submit handles the policy's on_submit hook
            sched.submit(req)
        self._m_migrated_in.inc()
        return req

    # -- introspection -------------------------------------------------

    # Telemetry views (ISSUE 5 satellite): the registry counters are
    # the ONLY store — these attributes read them back, so stats(),
    # scrape(), and the bench can never drift apart. Under null mode
    # they read 0 (telemetry off zeroes reporting, never behavior).

    @property
    def total_generated(self) -> int:
        return int(self._m_tokens.value)

    @property
    def finished_count(self) -> int:
        return int(self._m_finished.value)

    @property
    def finished_evicted(self) -> int:
        return int(self._m_finished_evicted.value)

    def scrape(self, openmetrics: bool = False,
               full: bool = True) -> str:
        """This engine's registry rendered as Prometheus exposition
        text (the in-process scrape surface; the HTTP surface is the
        parameter server's ``GET /metrics``). Empty when the engine was
        constructed under telemetry null mode. ``openmetrics=True``
        renders the OpenMetrics flavor instead — histogram buckets
        carry their rid exemplars (ISSUE 12), so a TTFT p99 spike
        links straight to :meth:`explain`'s record of the request.

        ``full=False`` (ISSUE 14) narrows the exposition to THIS
        engine's own series (its ``engine=`` labels plus its
        scheduler's ``scheduler=`` labels) — the per-replica scrape
        shape a :class:`~elephas_tpu.telemetry.aggregate.FleetScraper`
        wants when several replicas share one process registry (a full
        render would make every instance's fleet view identical sums).
        Same ``only=`` filtering the PR-13 PS scrape-parity satellite
        introduced; no new metrics plumbing."""
        if openmetrics:
            if not full:
                raise ValueError(
                    "full=False is a 0.0.4-flavor filter — the "
                    "OpenMetrics surface renders the whole registry"
                )
            return telemetry.render_openmetrics(self._telemetry_registry)
        if not full:
            reg = self._telemetry_registry
            return telemetry.render(
                reg, only={"engine": self.telemetry_label}
            ) + telemetry.render(
                reg, only={"scheduler": self.scheduler.telemetry_label}
            )
        return telemetry.render(self._telemetry_registry)

    def prefix_warm_probe(self, prompt) -> int:
        """How many leading tokens of ``prompt`` the engine's prefix
        cache would serve without recompute — the pure cache-warmth
        probe (ISSUE 12 satellite; ROADMAP item 3's cache-aware-
        routing primitive). 0 on engines without a prefix cache. Pure
        and side-effect-free (no hit/LRU accounting, same contract as
        ``match()``), so probing at any rate never skews this
        engine's cache behavior, and by construction it equals the
        reuse length admission would then commit. NOT synchronized
        against a concurrently-stepping driver — on a gateway-driven
        engine, probe while holding the gateway's engine lock (the
        wire surfaces already do)."""
        prompt = np.asarray(prompt).reshape(-1)
        idx = self.scheduler.prefix_index
        if idx is not None:
            return idx.match_len(prompt)
        cache = self.scheduler.prefix_cache
        if cache is not None:
            return cache.match_len(prompt)
        return 0

    def explain(self, rid: int) -> dict:
        """The structured lifecycle record of one request (ISSUE 12):
        admission verdict + queue wait, admission kind/reuse length,
        prefill chunks, preempt/offload/resume, spec verify rounds,
        per-token step indices, first token, and finish reason — every
        entry stamped with the scheduler step and tracer sequence
        number it happened at (logical order; wall-derived fields are
        export-only). In-flight requests return their partial record
        (``finish`` is None); finished requests come from the bounded
        flight-recorder ring (last ``flight_recorder=`` lifecycles).

        Raises ``RuntimeError`` when the recorder is off (knob 0/None
        or the engine was built under telemetry null mode) and
        ``KeyError`` for an unknown/evicted rid. Served over the wire
        as ``GET /v1/requests/{rid}/trace``."""
        import copy

        if self._flight is None:
            raise RuntimeError(
                "flight recorder is off (flight_recorder=0/None, or "
                "the engine was built under telemetry null mode) — "
                "explain() has no lifecycle records to read"
            )
        rec = self._fr(int(rid))
        if rec is None:
            raise KeyError(
                f"no lifecycle record for request {rid} — never "
                f"submitted to this engine, or evicted from the "
                f"{self._flight.capacity}-record flight ring"
            )
        return copy.deepcopy(rec)

    def debug_snapshot(self) -> dict:
        """One structured snapshot of live engine state (ISSUE 12 —
        the gateway's ``GET /debug/engine``): slot map, waiting queue
        with per-request policy debt, block-pool occupancy, offloaded
        (preempted) requests, prefix cache/index summary, policy
        state (virtual counters), compiled-program counts, and the
        flight recorder's occupancy. Read-only host work — safe to
        call between steps at any cadence."""
        sched = self.scheduler
        slots = {}
        for slot, req in sorted(sched.active.items()):
            pre = self._prefilling.get(slot)
            slots[str(slot)] = {
                "rid": req.rid,
                "tenant": req.tenant,
                "prompt_tokens": len(req.prompt),
                "generated": len(req.tokens),
                "prefilling": pre is not None,
                "prefill_progress": pre[1] if pre is not None else None,
                "table_blocks": (
                    len(sched.tables.get(slot, ()))
                    if self.paged else None
                ),
            }
        out = {
            "engine": self.telemetry_label,
            "steps": sched._steps,
            "num_slots": self.num_slots,
            "attention": self.attention,
            "kv_dtype": self.kv_dtype,
            "weight_version": self.weight_version,
            "slots": slots,
            "waiting": sched.queue_snapshot(),
            "queued_tokens": sched.queued_tokens,
            "offloaded": {
                str(rid): {"blocks": r.n_blocks, "cur_len": r.cur_len}
                for rid, r in sorted(self._offloaded.items())
            },
            "policy": (
                self.policy.stats() if self.policy is not None else None
            ),
            "compile_stats": self.compile_stats(),
            "flight_recorder": (
                None if self._flight is None else {
                    "capacity": self._flight.capacity,
                    "finished_resident": len(self._flight),
                    "in_flight": len(self._flight_live),
                }
            ),
        }
        if self.paged:
            out["blocks_total"] = self.num_blocks
            out["blocks_free"] = self.scheduler.allocator.free_count
            idx = sched.prefix_index
            out["prefix_index"] = (
                idx.stats() if idx is not None else None
            )
        elif sched.prefix_cache is not None:
            out["prefix_cache"] = sched.prefix_cache.stats()
        return out

    def release_telemetry(self) -> None:
        """Retire this engine's labeled series — its own, its
        scheduler's, and its prefix cache's — from the process
        registry. Hosts that construct engines in a loop (per-request
        test engines) call this when an
        engine is done so scrape output doesn't accumulate dead
        incarnations; never called implicitly, because scraping a
        finished engine's counters is a supported shape. Object-held
        views (``total_generated`` etc.) keep working."""
        telemetry.remove_series(engine=self.telemetry_label)
        self.scheduler.release_telemetry()

    def compile_stats(self) -> dict:
        """Compiled-program counts (the compile-count introspection
        hook): after warmup ``decode_compiles`` must stay at 1 for the
        server's whole life, and ``prefill_compiles`` is bounded by the
        bucket ladder."""

        def n(f):
            try:
                return int(f._cache_size())
            except Exception:  # pragma: no cover - jax-version drift
                return -1

        if self.paged:
            return {
                # paged closed set: one decode per table bucket, one
                # chunk per (width, table bucket), gather/scatter per
                # bucket touched by preemption
                "decode_compiles": n(self._paged_decode_jit),
                "prefill_compiles": 0,
                "chunk_prefill_compiles": n(self._paged_chunk_jit),
                "copy_compiles": 0,  # prefix hits are table splices
                "offload_compiles": n(self._gather_jit),
                "resume_compiles": n(self._scatter_jit),
                "verify_compiles": (
                    n(self._verify_jit) if self.speculative else 0
                ),
                "sp_prefill_compiles": (
                    n(self._sp_jit) if self._sp_jit is not None else 0
                ),
                "score_compiles": n(self._score_jit),
                "buckets": tuple(self.scheduler.buckets),
                "table_buckets": tuple(self._tbuckets),
                "prefill_chunk": self.prefill_chunk,
                "block_size": self.block_size,
                "num_blocks": self.num_blocks,
                "spec_k": self.spec_k,
                "attention": self.attention,
                "kv_dtype": self.kv_dtype,
            }
        return {
            "decode_compiles": n(self._decode_jit),
            "prefill_compiles": n(self._prefill_jit),
            "chunk_prefill_compiles": n(self._chunk_jit),
            "copy_compiles": n(self._copy_jit),
            "verify_compiles": (
                n(self._verify_jit) if self.speculative else 0
            ),
            "score_compiles": n(self._score_jit),
            "buckets": tuple(self.scheduler.buckets),
            # flash block-span reads compile per touched span bucket
            # (closed ladder); naive never leaves the maxlen span, so
            # its decode stays the seed's single program
            "span_buckets": tuple(self._sbuckets),
            "prefill_chunk": self.prefill_chunk,
            "spec_k": self.spec_k,
            "attention": self.attention,
            "kv_dtype": self.kv_dtype,
        }

    def _tenant_stats(self) -> dict:
        """Per-tenant queue depth, admitted/rejected counts, token
        totals, and SLO attainment — registry-backed (ISSUE 10
        satellite). Empty without a policy (no tenants exist)."""
        if self.policy is None:
            return {}
        out = {}
        for t in self.policy.tenant_names:
            met = int(self._tenant_child(self._mf_slo_met, t).value)
            missed = int(
                self._tenant_child(self._mf_slo_missed, t).value
            )
            out[t] = {
                "queue_depth": self.scheduler.waiting_count(t),
                "admitted": int(
                    self._tenant_child(self._mf_tenant_admitted, t).value
                ),
                "rejected": int(
                    self._tenant_child(self._mf_tenant_rejected, t).value
                ),
                "tokens": int(
                    self._tenant_child(self._mf_tenant_tokens, t).value
                ),
                "slo_met": met,
                "slo_missed": missed,
                "slo_attainment": (
                    met / (met + missed) if met + missed else None
                ),
            }
        return out

    @staticmethod
    def _percentiles(xs) -> dict:
        """``{p50, p99, n}`` summary (seconds) of a latency sample."""
        if not xs:
            return {"p50": None, "p99": None, "n": 0}
        return {
            "p50": float(np.percentile(xs, 50)),
            "p99": float(np.percentile(xs, 99)),
            "n": len(xs),
        }

    def stats(self) -> dict:
        """Serving counters: aggregate generated tokens,
        decode steps, mean slot occupancy, per-request whole-request
        latencies, TTFT (submit→first token) and inter-token arrival
        percentiles of finished requests (ISSUE 4 — the chunked-prefill
        and prefix-reuse wins read straight off these counters), plus
        prefix-cache hit/eviction counters when the cache is on."""
        finished = list(self.finished.values())
        lat = [
            r.finish_time - r.submit_time
            for r in finished
            if r.finish_time is not None and r.submit_time is not None
        ]
        ttfts = [r.ttft for r in finished if r.ttft is not None]
        itls = [d for r in finished for d in r.inter_token_times]
        # decode-only tok/s (ISSUE 8 satellite): per-token speed with
        # TTFT excluded — from each finished request's first-to-last
        # token arrival window, the same token_times the percentiles
        # already read. This is the figure speculation moves; aggregate
        # tok/s confounds it with batching and admission effects.
        d_toks = sum(
            len(r.token_times) - 1
            for r in finished if len(r.token_times) > 1
        )
        d_secs = sum(
            r.token_times[-1] - r.token_times[0]
            for r in finished if len(r.token_times) > 1
        )
        drafted = int(self._m_spec_drafted.value)
        accepted = int(self._m_spec_accepted.value)
        out = {
            "total_generated": self.total_generated,
            # which attention kernel the programs run (ISSUE 11) —
            # same truth the elephas_serving_attn_kernel info gauge
            # labels, so dashboards and stats() can never disagree
            "attention": self.attention,
            "decode_steps": self.scheduler._steps,
            "occupancy": self.scheduler.occupancy,
            "latencies": lat,
            "finished": self.finished_count,
            "finished_evicted": self.finished_evicted,
            "num_slots": self.num_slots,
            "ttft_s": self._percentiles(ttfts),
            "inter_token_s": self._percentiles(itls),
            # ISSUE 7 satellite: gauge/counter-backed so stats() and a
            # /metrics scrape can never drift (one store, two views)
            "queue_depth": int(self.scheduler._m_waiting.value),
            "preemptions": int(self._m_preemptions.value),
            "resumes": int(self._m_resumes.value),
            "rejected": int(self._m_rejected.value),
            "decode_tok_s": (d_toks / d_secs) if d_secs > 0 else None,
            # speculative decoding (ISSUE 8): registry-backed like the
            # paged counters — stats() and a /metrics scrape read the
            # SAME series; the acceptance rate is derived at read time
            "spec_draft_tokens": drafted,
            "spec_accepted_tokens": accepted,
            "spec_acceptance_rate": (
                accepted / drafted if drafted else None
            ),
            "spec_verify_rounds": int(self._m_spec_rounds.value),
            "spec_throttled": int(self._m_spec_throttled.value),
            # SLO scheduling (ISSUE 10): same one-store contract — the
            # per-tenant section reads the registry children and the
            # live scheduler queue, so stats() and a /metrics scrape
            # can never drift
            "admission_rejected": int(self._m_admission_rejected.value),
            "tenants": self._tenant_stats(),
            # lifecycle control (ISSUE 14): registry-backed like the
            # rest — stats() and a /metrics scrape read the same series
            "cancelled": int(self._m_cancelled.value),
            "migrated_out": int(self._m_migrated_out.value),
            "migrated_in": int(self._m_migrated_in.value),
            # quantized KV (ISSUE 19): the stored dtype plus the
            # counted wire/offload byte totals the bench's compression
            # gate reads — registry-backed, one store, two views
            "kv_dtype": self.kv_dtype,
            "kv_quant_offload_bytes": int(self._m_offload_bytes.value),
            "kv_quant_export_bytes": int(self._m_export_bytes.value),
            "score_requests": int(self._m_score_requests.value),
            # continuous deployment (ISSUE 20): the generation this
            # engine serves — same truth the weight_version gauge holds
            "weight_version": self.weight_version,
        }
        if self.policy is not None:
            out["policy"] = self.policy.stats()
        if self.paged:
            alloc = self.scheduler.allocator
            out["blocks_total"] = self.num_blocks
            out["blocks_free"] = alloc.free_count
            out["offloaded_blocks"] = int(self._m_offload_blocks.value)
            idx = self.scheduler.prefix_index
            out["prefix_blocks_shared"] = (
                idx.shared_blocks if idx is not None else 0
            )
            if idx is not None:
                out["prefix_cache"] = idx.stats()
        if self.scheduler.prefix_cache is not None:
            out["prefix_cache"] = self.scheduler.prefix_cache.stats()
        return out
