"""Pipeline-parallel serving: continuous batching over a PP×TP mesh
(ISSUE 15).

Every serving path before this module tops out at one TP/DP chip
group: the whole model's weights must fit the group, so model DEPTH is
the one scaling axis the engine cannot buy hardware for. This module
runs the continuous-batching loop over the pre-seed pipeline ring
(:mod:`elephas_tpu.parallel.pipeline_runner`'s stage planner and the
``ppermute`` ring :mod:`elephas_tpu.ops.pipeline` certified for
training and one-shot ring decode): the causal LM depth-shards into
``S`` stages over a ``('stages',)`` mesh axis (width-sharding each
stage over a trailing ``('model',)`` axis under PP×TP), each stage
holds ONLY its layers' weights and its OWN paged KV pool, and decode
runs as **microbatched waves that fill the pipeline bubble**
(GPipe-style microbatching, Huang et al. 2019, composed with
iteration-level continuous batching, Orca, Yu et al. 2022):

- the slot arena partitions STATICALLY into ``S`` waves of
  ``wave_slots`` slots each (slot ``i`` belongs to wave
  ``i // wave_slots``);
- one decode **window** is a single compiled dispatch of
  ``S·k + S − 1`` ring ticks (``k = steps_per_wave``): at tick ``t``
  stage ``s`` decodes wave ``(t − s) mod S``, so while wave ``w``
  crosses stage ``s``, wave ``w+1`` occupies stage ``s−1`` — in steady
  state every stage is busy every tick and the window emits ``S·k``
  wave-tokens for ``S·k + S − 1`` ticks (bubble fraction
  ``(S−1)/(S·k+S−1)``, amortized by ``k``);
- the sampled token of wave ``w`` rides the ring's wrap edge (stage
  ``S−1`` → stage ``0``) and seeds the SAME wave's next position one
  tick later — with ``waves == stages`` the hand-off is exact, so the
  token loop closes entirely on device and the host syncs once per
  window (admission, EOS/budget reclaim, mid-flight arrivals);
- prefill is the same ring with a chunk per wave: one dispatch walks
  an admission wave's (bucket-padded) prompts through all stages,
  landing each stage's K/V in its own pool and sampling first tokens
  on the last stage;
- **bubble-filling chunked prefill** (ISSUE 16, ``bubble_fill=True``):
  at tick ``t`` stage ``s`` is idle whenever wave ``(t − s) mod S``
  has no decode work — an admission landing in such an EMPTY wave
  becomes a *filler*: its prompt prefills chunk-by-chunk
  (``bubble_chunk`` positions per ring round) through exactly those
  idle ticks of the SAME compiled decode window, Sarathi-style
  (Agrawal et al., 2024) piggybacking on a pipeline ring, so a
  mid-flight long-prompt arrival reaches its first token without a
  standalone prefill dispatch between windows;
- **cross-stage prefix sharing** (``prefix_cache=True``): the
  scheduler's :class:`PagedPrefixIndex` spans the per-stage pools for
  free — ONE allocator leases each block id on EVERY stage, so a
  prefix-hit splice makes the shared prompt's K/V resident on all
  stages at once and both the prefill ring and the fill path start at
  the shared offset (a shared system prompt pays one cold prefill
  fleet-wide).

Kept invariants (the standing serving contracts):

- **no wall clock near ordering** — the schedule is a pure function of
  the submission sequence; gang processes derive identical waves;
- **closed compile set** — programs key on (chunk-width bucket ×
  table bucket); the decode ring compiles once per table bucket;
- **temp-0 token-exactness** vs one-shot ``generate()`` (the stage
  replay reuses the paged arena's attention math; under TP the
  head-split psum reassociates floats exactly like the GSPMD TP
  serving path — argmax parity on trained models, the same tested
  contract);
- **telemetry observes, never drives** — per-window bubble-fraction
  and per-wave occupancy gauges plus ``serve.wave`` spans ride along,
  and nothing reads them back.

Per-stage KV: every stage's pool is ``[L_s, num_blocks, block_size,
H, Dh]`` per K/V (``L_s = num_layers / num_stages`` — the planner
refuses an uneven split), stacked ``[S, L_s, ...]`` and sharded over
the stage axis; ONE block allocator leases block *ids* per slot and
every stage stores its layers' rows at those ids in its own pool, so
the block tables replicate and preemption offload gathers **per
stage** (the offload record is the stage-stacked dense rows).

Not in this engine (serve through :class:`~elephas_tpu.serving.\
engine.InferenceEngine` for these): speculative decoding, SLO
policies, SP prefill, migration export/import. Preemption
offload/resume IS here — pool pressure is where PP serving lives —
and so are the paged prefix cache, bubble-fill chunked prefill, and
``cancel()`` (ISSUE 16).
"""

from __future__ import annotations

import logging
import time

import numpy as np

from elephas_tpu import telemetry
from elephas_tpu.serving.blocks import BlockAllocator
from elephas_tpu.serving.paged_kv import (
    blocks_for,
    table_bucket_for,
    table_buckets,
)
from elephas_tpu.serving.scheduler import (
    Request,
    Scheduler,
    default_buckets,
)

logger = logging.getLogger(__name__)


class _StageOffload:
    """Host K/V of a preempted request, PER STAGE: dense block rows
    ``[S, L_s, n_blocks, block_size, H, Dh]`` for K and V plus the
    cursor needed for a bit-exact resume."""

    __slots__ = ("k_rows", "v_rows", "n_blocks", "cur_len")

    def __init__(self, k_rows, v_rows, n_blocks, cur_len):
        self.k_rows = k_rows
        self.v_rows = v_rows
        self.n_blocks = int(n_blocks)
        self.cur_len = int(cur_len)


def _replay_nodes(nodes, in_kt, out_kt, x, handler):
    """Run a stage's node program on ``x`` — the per-stage sibling of
    :func:`~elephas_tpu.serving.kv_cache._graph_replay`: same handler
    contract, but over a node SUBSET with an explicit boundary input
    instead of the whole model's ``_run_through_graph``."""
    from keras import tree as ktree

    tensors = {id(in_kt): x}
    for node in nodes:
        args, kwargs = node.arguments.fill_in(tensors)
        out = handler(node.operation)(*args, **kwargs)
        for kt, val in zip(node.outputs, ktree.flatten(out)):
            tensors[id(kt)] = val
    return tensors[id(out_kt)]


class PPEngine:
    """Continuous-batching serving engine over a pipeline-parallel
    (optionally ×TP) mesh.

    ``num_stages`` depth stages over ``('stages',)`` (one device group
    per stage; ``model_parallel`` width-shards attention heads over a
    trailing ``('model',)`` axis — ``num_heads % model_parallel`` must
    be 0). ``wave_slots`` slots per wave, ``num_stages`` waves (the
    wave count equals the stage count so the ring's wrap edge hands a
    wave's sampled token straight back to stage 0), so the arena holds
    ``num_stages · wave_slots`` slots. ``steps_per_wave`` tokens per
    wave per decode window (the PP analogue of ``steps_per_sync`` —
    larger windows amortize the ``S−1``-tick pipeline fill).

    The KV storage is always paged (``block_size``/``num_blocks``
    as in ``InferenceEngine(paged=True)``; ``num_blocks`` counts
    blocks PER STAGE — every stage's pool has the same geometry).
    ``preemption=True`` arms priority preempt → per-stage host
    offload → bit-exact resume. Submission/driving API mirrors
    ``InferenceEngine``: :meth:`submit`, :meth:`step`,
    :meth:`stream`, :meth:`run`, :meth:`cancel`, :meth:`stats`.

    ``bubble_fill=True`` arms bubble-filling chunked prefill (ISSUE
    16): an admission whose wave-aware slot lands in a wave with no
    decode-active occupant (while another wave decodes) prefills
    ``bubble_chunk`` prompt positions per ring round through that
    wave's otherwise-idle decode-window ticks; ``bubble_budget`` caps
    concurrent fill slots. Off by default: the combined window
    program carries a per-wave fill/decode branch, so the default
    engine keeps PR 15's program byte-for-byte. At temp 0 filled and
    unfilled schedules are token-exact (argmax reads no PRNG); at
    temp>0 they sample DIFFERENT streams — the window consumes one
    key split per tick either way, but fill changes WHICH window
    serves a token, hence which split it reads.

    ``prefix_cache=True`` turns on the scheduler's refcounted
    :class:`PagedPrefixIndex` over the per-stage pools: one block id
    is resident on every stage, so a prefix hit splices shared
    blocks fleet-wide and prefill (standalone or fill) starts at the
    shared offset. ``prefix_min_reuse`` floors the match depth.

    Gang contract: like every serving surface, all gang processes must
    construct the engine identically and submit the identical request
    sequence; the schedule contains no wall clock, so all derive the
    same waves and read the same tokens.
    """

    def __init__(self, model, num_stages: int = 2, wave_slots: int = 2,
                 mesh=None, model_parallel: int = 1,
                 block_size: int | None = None,
                 num_blocks: int | None = None,
                 steps_per_wave: int = 4,
                 top_k: int | None = None, top_p: float | None = None,
                 seed: int = 0, buckets=None,
                 preemption: bool = False,
                 attention: str = "flash",
                 bubble_fill: bool = False,
                 bubble_chunk: int | None = None,
                 bubble_budget: int | None = None,
                 prefix_cache: bool = False,
                 prefix_min_reuse: int = 1):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from elephas_tpu.models.transformer import (
            validate_token_decode_model,
        )
        from elephas_tpu.ops.pipeline import pipeline_mesh
        from elephas_tpu.parallel.pipeline_runner import (
            plan_serving_stages,
        )

        flash_layers, _stock, _gqa = validate_token_decode_model(
            model,
            what="the PP serving engine",
            hint="use InferenceEngine on a TP/DP mesh",
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
        if attention not in ("flash", "naive"):
            raise ValueError(
                f"attention must be 'flash' or 'naive', got "
                f"{attention!r}"
            )
        self.attention = attention

        S = int(num_stages)
        mp = max(1, int(model_parallel))
        self.num_stages = S
        self.model_parallel = mp
        self.plan = plan_serving_stages(model, S)
        geoms = {
            (int(l.num_heads), int(l.head_dim)) for l in flash_layers
        }
        if len(geoms) != 1:
            raise ValueError(
                f"PP serving stacks per-stage KV pools into one "
                f"buffer, which needs uniform attention geometry — "
                f"model mixes {sorted(geoms)}"
            )
        (self.num_heads, self.head_dim), = geoms
        if mp > 1 and self.num_heads % mp:
            raise ValueError(
                f"model_parallel={mp} needs num_heads "
                f"({self.num_heads}) divisible by it (heads split "
                f"over the model axis)"
            )
        self.layers_per_stage = len(self.plan.flash[0])

        if mesh is None:
            mesh = pipeline_mesh(S, model_parallel=mp)
        if mesh.shape.get("stages", 0) != S:
            raise ValueError(
                f"mesh axis 'stages' has size "
                f"{mesh.shape.get('stages', 0)}, need {S}"
            )
        if mesh.shape.get("model", 1) != mp:
            raise ValueError(
                f"mesh axis 'model' has size "
                f"{mesh.shape.get('model', 1)} but "
                f"model_parallel={mp}"
            )
        self.mesh = mesh

        ws = int(wave_slots)
        if ws < 1:
            raise ValueError(f"wave_slots={wave_slots} < 1")
        self.wave_slots = ws
        self.num_slots = S * ws
        k = int(steps_per_wave)
        if k < 1:
            raise ValueError(f"steps_per_wave={steps_per_wave} < 1")
        self.steps_per_wave = k

        bs = 16 if block_size is None else int(block_size)
        if not 0 < bs <= self.maxlen:
            raise ValueError(
                f"block_size={bs} outside (0, maxlen={self.maxlen}]"
            )
        self.block_size = bs
        self.max_blocks_per_slot = blocks_for(self.maxlen, bs)
        nb = (
            int(num_blocks) if num_blocks is not None
            else self.num_slots * self.max_blocks_per_slot
        )
        if nb < 1:
            raise ValueError(f"num_blocks={nb} < 1")
        self.num_blocks = nb
        self._tbuckets = table_buckets(self.max_blocks_per_slot)
        self.preemption = bool(preemption)

        # bubble-fill knobs (ISSUE 16): the chunk width is the fill
        # path's per-round position count — it sizes the window ring
        # buffer (ws·C·D_max), so the OFF engine pins C=1 and keeps
        # PR 15's program byte-for-byte
        self.bubble_fill = bool(bubble_fill)
        C = bs if bubble_chunk is None else int(bubble_chunk)
        if not 0 < C <= self.maxlen:
            raise ValueError(
                f"bubble_chunk={C} outside (0, maxlen={self.maxlen}]"
            )
        self.bubble_chunk = C
        self._C = C if self.bubble_fill else 1
        if bubble_budget is not None and int(bubble_budget) < 1:
            raise ValueError(f"bubble_budget={bubble_budget} < 1")
        self.bubble_budget = (
            None if bubble_budget is None else int(bubble_budget)
        )

        # -- telemetry captured at construction (the standing serving
        # contract: null-built engines stay inert for life) -----------
        treg = telemetry.registry()
        self._telemetry_registry = treg
        self._tracer = telemetry.tracer()
        eid = telemetry.instance_label()
        self.telemetry_label = eid

        def _c(name, help_):
            return treg.counter(
                name, help_, labels=("engine",)
            ).labels(engine=eid)

        # shared serving families (same name+help as InferenceEngine's
        # so the catalog stays one family per concept; this engine is
        # just another engine= child)
        self._m_tokens = _c(
            "elephas_serving_tokens_generated_total",
            "Generated tokens emitted by the serving engine",
        )
        self._m_finished = _c(
            "elephas_serving_requests_finished_total",
            "Requests that completed (EOS or token budget)",
        )
        self._m_decode_windows = _c(
            "elephas_serving_decode_windows_total",
            "Arena-wide decode window dispatches",
        )
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
        self._m_cancelled = _c(
            "elephas_serving_cancelled_total",
            "In-flight requests cancelled before completion "
            "(slot/blocks reclaimed; gateway client disconnects land "
            "here)",
        )
        # bubble-fill + cross-stage prefix telemetry (ISSUE 16) —
        # report-only like every serving series
        self._m_fill_tokens = _c(
            "elephas_pp_fill_tokens_total",
            "Prompt tokens prefilled through idle decode-window ring "
            "ticks (bubble fill)",
        )
        self._m_fill_rounds = _c(
            "elephas_pp_fill_rounds_total",
            "Fill-wave ring rounds carried inside decode windows "
            "(idle ticks that did prefill work instead)",
        )
        self._m_prefix_shared = _c(
            "elephas_pp_prefix_shared_tokens_total",
            "Prompt tokens served by cross-stage prefix-block "
            "splices (shared block ids resident on every stage)",
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
        treg.gauge(
            "elephas_serving_slots", "KV-cache slots in the arena",
            labels=("engine",),
        ).labels(engine=eid).set(self.num_slots)
        treg.gauge(
            "elephas_serving_kv_blocks",
            "KV pool blocks in the paged arena",
            labels=("engine",),
        ).labels(engine=eid).set(self.num_blocks)
        # PP-specific report-only series (ISSUE 15): the pipeline-fill
        # overhead of the last decode window — scheduled stage-ticks
        # that carried no wave work (ramp/drain plus EMPTY waves) over
        # all scheduled stage-ticks — and per-wave live-slot occupancy.
        # Report-only by contract: nothing reads these back.
        self._m_bubble = treg.gauge(
            "elephas_pp_bubble_fraction",
            "Pipeline-bubble fraction of the last decode window "
            "(idle stage-ticks / scheduled stage-ticks; ramp + drain "
            "+ empty waves; bubble-filled ticks count as useful)",
            labels=("engine",),
        ).labels(engine=eid)
        self._mf_wave_active = treg.gauge(
            "elephas_pp_wave_active_slots",
            "Live (decoding) slots per pipeline wave at the last "
            "window boundary",
            labels=("engine", "wave"),
        )
        for w in range(S):
            self._mf_wave_active.labels(engine=eid, wave=str(w)).set(0)

        allocator = BlockAllocator(
            self.num_blocks, bs,
            free_gauge=treg.gauge(
                "elephas_serving_blocks_free",
                "Unleased KV pool blocks (paged arena)",
                labels=("engine",),
            ).labels(engine=eid),
        )
        self.scheduler = Scheduler(
            self.num_slots, buckets or default_buckets(self.maxlen),
            allocator=allocator, preemption=preemption,
            wave_slots=ws, prefix_cache=bool(prefix_cache),
            prefix_min_reuse=prefix_min_reuse,
        )
        self._seed = int(seed)
        self.finished: dict[int, Request] = {}
        self._finished_bound = 4096
        self._protected: set[int] = set()
        self._offloaded: dict[int, _StageOffload] = {}
        self._active_host = np.zeros((self.num_slots,), bool)
        self._tables_cache: tuple | None = None
        self._last_bubble = 0.0
        # bubble-fill host state: slot → next prompt offset to fill
        # (in-progress fillers), and slots whose prompt finished
        # filling but whose WAVE still has fillers in flight
        # (whole-wave graduation — see _decode_window)
        self._filling: dict[int, int] = {}
        self._fill_done: set[int] = set()
        # cumulative ring accounting (stage-ticks scheduled vs
        # carrying work, windows AND standalone prefill dispatches) —
        # stats()['bubble_cumulative'], report-only
        self._ticks_sched = 0
        self._ticks_useful = 0
        self._trace_compiles = not telemetry.null_mode()

        # -- stage weights: per-stage (per-rank under TP) {path: value}
        # pytrees raveled into ONE stacked f32 buffer sharded over the
        # stage (× model) axes — the GPipeTrainer storage pattern, so
        # no device ever holds more than its stage's (rank's) share
        self._build_stage_weights()

        # -- per-stage pools: [S, L_s, N, bs, H, Dh] per K/V, stage
        # axis sharded, head axis sharded under TP
        model_ax = "model" if mp > 1 else None
        self._pool_spec = P("stages", None, None, None, model_ax, None)
        self._pool_sh = NamedSharding(mesh, self._pool_spec)
        self._param_spec = (
            P("stages", "model") if mp > 1 else P("stages",)
        )
        self._rep_sh = NamedSharding(mesh, P())
        # per-DEVICE local pool shape (stage axis 1, heads rank-local);
        # the zeros build through a shard_map with the SAME out_specs
        # as the ring programs, so the initial pools carry the
        # identical sharding object shape the ring outputs do — a
        # plain out_shardings= jit produced an equivalent-but-distinct
        # sharding whose first ring dispatch minted a SECOND executable
        # cache entry (found via the closed-compile-set test)
        local_shape = (
            1, self.layers_per_stage, self.num_blocks, bs,
            self.num_heads // mp, self.head_dim,
        )

        def _init_pools():
            def per_device():
                z = jnp.zeros(local_shape, jnp.float32)
                return z, jnp.zeros(local_shape, jnp.float32)

            return jax.shard_map(
                per_device, mesh=mesh, in_specs=(),
                out_specs=(self._pool_spec, self._pool_spec),
                check_vma=False,
            )()

        self._pk, self._pv = jax.jit(_init_pools)()

        self._build_programs()
        self._key = self._stage_host(
            np.asarray(jax.random.PRNGKey(self._seed))
        )

    # -- staging helpers ------------------------------------------------

    def _stage_host(self, arr):
        """Host value → device, replicated over the PP mesh
        (gang-safe: every process materializes its own shards)."""
        from elephas_tpu.parallel.mesh import put_global

        return put_global(np.asarray(arr), self._rep_sh)

    def _host(self, leaf) -> np.ndarray:
        from elephas_tpu.parallel.mesh import host_read

        return host_read(leaf, self.mesh)

    # -- weights --------------------------------------------------------

    def _stage_var_value(self, layer, v, rank: int):
        """Rank ``rank``'s storage shard of one variable: FlashMHA
        qkv/proj split Megatron-style (head slices), everything else
        replicated — the serving TP plan (attention is where both the
        FLOPs and the KV live; MLP/LN/embedding run replicated inside
        the stage's model group)."""
        from elephas_tpu.parallel.pipeline_runner import _tp_slice_var

        mp = self.model_parallel
        val = np.asarray(v.value)
        if mp == 1:
            return val
        from elephas_tpu.models.transformer import _flash_mha_layer

        if isinstance(layer, _flash_mha_layer()):
            if v is layer.qkv.kernel:
                return _tp_slice_var(
                    val, ("split_qkv", self.num_heads, self.head_dim),
                    rank, mp,
                )
            if v is layer.proj.kernel:
                return _tp_slice_var(val, ("split", 0), rank, mp)
        return val

    def _stage_weight_dict(self, s: int, rank: int) -> dict:
        """Stage ``s``'s ``{var.path: np value}`` dict for one model
        rank. Dropout layers are identity in the serving replay, so
        their (integer RNG) state never enters the f32 flat buffer."""
        import keras

        out = {}
        for layer in self.plan.layers[s]:
            if isinstance(layer, keras.layers.Dropout):
                continue
            for v in layer.variables:
                if not np.issubdtype(
                    np.dtype(v.dtype), np.floating
                ):
                    raise ValueError(
                        f"PP serving packs stage weights into one f32 "
                        f"buffer; variable {v.path} is {v.dtype}"
                    )
                out[v.path] = self._stage_var_value(
                    layer, v, rank
                ).astype(np.float32)
        return out

    def _build_stage_weights(self) -> None:
        """(Re)build the stacked flat stage-weight buffer from the
        model's current variables — also the :meth:`refresh_weights`
        body."""
        from jax.flatten_util import ravel_pytree

        from elephas_tpu.parallel.mesh import put_global

        S, mp = self.num_stages, self.model_parallel
        flats = []  # [S][mp] np flat vectors
        unravels, sizes = [], []
        for s in range(S):
            rank_flats = []
            for r in range(mp):
                flat, unravel = ravel_pytree(
                    self._stage_weight_dict(s, r)
                )
                rank_flats.append(np.asarray(flat, np.float32))
            flats.append(rank_flats)
            unravels.append(unravel)  # same structure across ranks
            sizes.append(int(rank_flats[0].size))
        self._unravels = tuple(unravels)
        self._p_sizes = tuple(sizes)
        self.P_max = max(sizes)
        if mp > 1:
            stacked = np.stack([
                np.stack([
                    np.pad(f, (0, self.P_max - f.size))
                    for f in rank_flats
                ])
                for rank_flats in flats
            ])  # [S, mp, P_max]
        else:
            stacked = np.stack([
                np.pad(flats[s][0], (0, self.P_max - flats[s][0].size))
                for s in range(S)
            ])  # [S, P_max]
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P("stages", "model") if mp > 1 else P("stages",)
        self._wflat = put_global(
            stacked, NamedSharding(self.mesh, spec)
        )

    def refresh_weights(self) -> None:
        """Re-upload the model's weights after further training (the
        compiled ring programs take them as arguments — no
        recompile). Flushes the prefix index: rows indexed under the
        old weights are stale K/V for the new ones."""
        tracer = getattr(self, "_tracer", None)
        if tracer is not None:
            tracer.emit(
                "serve.refresh_weights", engine=self.telemetry_label,
            )
        self._build_stage_weights()
        sched = getattr(self, "scheduler", None)
        if sched is not None:
            sched.flush_prefix_cache()

    # -- stage branch construction --------------------------------------

    def _make_attn_closure(self, op, li: int, mode: str, ctx):
        """The per-layer attention closure of one stage branch:
        ``mode='decode'`` is one token per wave slot at per-slot
        positions, ``mode='chunk'`` a whole (padded) prompt chunk —
        the per-stage mirrors of ``paged_token_decode_step`` /
        ``paged_chunk_forward``'s local fast path (shard_map bodies
        are manual SPMD, so native gather/scatter is always legal
        here). K/V lands in THIS stage's pool slice at the slot's
        leased block ids; under TP the heads are rank-local and the
        output projection psums over the model axis."""
        import jax
        import jax.numpy as jnp

        from elephas_tpu.models.transformer import (
            _apply_rope,
            _rope_tables,
        )
        from elephas_tpu.ops.flash_serving import (
            flash_span_chunk,
            flash_span_decode,
        )
        from elephas_tpu.serving.kv_cache import (
            _rows_at_position_matrix,
            _rows_at_positions,
        )

        mp = self.model_parallel
        Hl = self.num_heads // mp
        Dh = self.head_dim
        bs = self.block_size
        N = self.num_blocks
        maxlen = self.maxlen
        attention = self.attention
        qkv_path = op.qkv.kernel.path
        proj_path = op.proj.kernel.path
        bias_path = op.proj.bias.path

        def _proj_out(o, w):
            out = o @ w[proj_path]
            if mp > 1:
                out = jax.lax.psum(out, "model")
            return out + w[bias_path]

        if mode == "decode":

            def attn(x, *_a, **_k):
                w, pk, pv, updated = ctx["w"], ctx["pk"], ctx["pv"], \
                    ctx["updated"]
                pos_w, act_w, tab_w = (
                    ctx["pos"], ctx["act"], ctx["tables"]
                )
                lk, lv = pk[li], pv[li]  # [N, bs, Hl, Dh]
                ws_n = x.shape[0]
                T = tab_w.shape[1]
                qkv = x @ w[qkv_path]
                q, kk, vv = jnp.split(
                    qkv.reshape(ws_n, 3, Hl, Dh), 3, axis=1
                )
                q, kk, vv = q[:, 0], kk[:, 0], vv[:, 0]
                if getattr(op, "rope", False):
                    cos_np, sin_np = _rope_tables(maxlen, Dh)
                    cos_t = _rows_at_positions(
                        jnp.asarray(cos_np), pos_w
                    )[:, None, :]
                    sin_t = _rows_at_positions(
                        jnp.asarray(sin_np), pos_w
                    )[:, None, :]
                    q = _apply_rope(q, cos_t, sin_t)
                    kk = _apply_rope(kk, cos_t, sin_t)
                blk_idx = pos_w // bs
                offp = pos_w % bs
                blk = jnp.take_along_axis(
                    tab_w, jnp.clip(blk_idx, 0, T - 1)[:, None],
                    axis=1,
                )[:, 0]
                # cursor overrun past the whole bucket routes to the
                # sentinel (the paged engine's block-0 scribble fix);
                # in-bucket overrun lands on the table's own sentinel
                # padding by construction
                blk = jnp.where(blk_idx < T, blk, N)
                blk_safe = jnp.where(act_w, blk, N)
                lk = lk.at[blk_safe, offp].set(
                    kk.astype(lk.dtype), mode="drop"
                )
                lv = lv.at[blk_safe, offp].set(
                    vv.astype(lv.dtype), mode="drop"
                )
                gk = jnp.take(lk, tab_w, axis=0, mode="clip")
                gk = gk.reshape(ws_n, T * bs, Hl, Dh)
                gv = jnp.take(lv, tab_w, axis=0, mode="clip")
                gv = gv.reshape(ws_n, T * bs, Hl, Dh)
                if attention == "flash":
                    o = flash_span_decode(
                        q, gk, gv, pos_w, scale=Dh**-0.5
                    ).reshape(ws_n, Hl * Dh)
                else:
                    # flash-lint: allow — the selectable naive oracle
                    att = jnp.einsum("bhd,bshd->bhs", q, gk) * (
                        Dh**-0.5
                    )
                    visible = (
                        jnp.arange(T * bs)[None, None, :]
                        <= pos_w[:, None, None]
                    )
                    att = jax.nn.softmax(
                        jnp.where(visible, att, -jnp.inf), axis=-1
                    )
                    # flash-lint: allow — naive oracle att@V
                    o = jnp.einsum(
                        "bhs,bshd->bhd", att, gv
                    ).reshape(ws_n, Hl * Dh)
                updated[li] = (lk, lv)
                return _proj_out(o, w)

            return attn

        def attn(x, *_a, **_k):  # mode == "chunk"
            w, pk, pv, updated = ctx["w"], ctx["pk"], ctx["pv"], \
                ctx["updated"]
            pos_mat, valid, tab_w = (
                ctx["pos_mat"], ctx["valid"], ctx["tables"]
            )
            lk, lv = pk[li], pv[li]
            ws_n, C = x.shape[0], x.shape[1]
            T = tab_w.shape[1]
            qkv = jnp.reshape(
                x @ w[qkv_path], (ws_n, C, 3, Hl, Dh)
            )
            qkv = jnp.transpose(qkv, (2, 0, 3, 1, 4))
            q, kk, vv = qkv[0], qkv[1], qkv[2]  # [ws, Hl, C, Dh]
            if getattr(op, "rope", False):
                cos_np, sin_np = _rope_tables(maxlen, Dh)
                cos = _rows_at_position_matrix(
                    jnp.asarray(cos_np), pos_mat
                )[:, None]
                sin = _rows_at_position_matrix(
                    jnp.asarray(sin_np), pos_mat
                )[:, None]
                q = _apply_rope(q, cos, sin)
                kk = _apply_rope(kk, cos, sin)
            k_rows = jnp.transpose(kk, (0, 2, 1, 3))  # [ws, C, Hl, Dh]
            v_rows = jnp.transpose(vv, (0, 2, 1, 3))
            blk_idx = pos_mat // bs
            off_mat = pos_mat % bs
            blk_mat = jnp.take_along_axis(
                tab_w, jnp.clip(blk_idx, 0, T - 1), axis=1
            )
            blk_mat = jnp.where(blk_idx < T, blk_mat, N)
            blk_safe = jnp.where(valid, blk_mat, N)
            lk = lk.at[blk_safe, off_mat].set(
                k_rows.astype(lk.dtype), mode="drop"
            )
            lv = lv.at[blk_safe, off_mat].set(
                v_rows.astype(lv.dtype), mode="drop"
            )
            gk = jnp.take(lk, tab_w, axis=0, mode="clip")
            gk = gk.reshape(ws_n, T * bs, Hl, Dh)
            gv = jnp.take(lv, tab_w, axis=0, mode="clip")
            gv = gv.reshape(ws_n, T * bs, Hl, Dh)
            if attention == "flash":
                o = flash_span_chunk(
                    q, gk, gv, pos_mat, scale=Dh**-0.5
                )
            else:
                # flash-lint: allow — the selectable naive oracle
                att = jnp.einsum(
                    "bhcd,bshd->bhcs", q, gk
                ) * (Dh**-0.5)
                visible = (
                    jnp.arange(T * bs)[None, None, None, :]
                    <= pos_mat[:, None, :, None]
                )
                att = jax.nn.softmax(
                    jnp.where(visible, att, -jnp.inf), axis=-1
                )
                # flash-lint: allow — naive oracle att@V
                o = jnp.einsum("bhcs,bshd->bhcd", att, gv)
            o = jnp.reshape(
                jnp.transpose(o, (0, 2, 1, 3)), (ws_n, C, Hl * Dh)
            )
            updated[li] = (lk, lv)
            return _proj_out(o, w)

        return attn

    def _make_stage_handler(self, s: int, mode: str, ctx):
        """The node-op handler of stage ``s``'s replay — FlashMHA
        routes to the paged attention closure, Dropout is identity,
        every other op runs stateless on the stage's unraveled
        weights, with concrete graph constants (positional tables)
        re-sliced to the wave's positions."""
        import keras

        from elephas_tpu.models.transformer import _flash_mha_layer
        from elephas_tpu.serving.kv_cache import (
            _slice_seq_at_position_matrix,
            _slice_seq_at_positions,
        )

        FlashMHA = _flash_mha_layer()
        flash_idx = {
            id(l): i for i, l in enumerate(self.plan.flash[s])
        }
        maxlen = self.maxlen

        def slice_fn(a):
            if mode == "decode":
                return _slice_seq_at_positions(a, ctx["pos"], maxlen)
            return _slice_seq_at_position_matrix(
                a, ctx["pos_mat"], maxlen
            )

        def handler(op):
            if isinstance(op, FlashMHA):
                return self._make_attn_closure(
                    op, flash_idx[id(op)], mode, ctx
                )
            if isinstance(op, keras.layers.Dropout):
                return lambda x, *a, **k: x
            if isinstance(op, keras.Layer) and op.variables:
                def stateless(*args, _op=op, **kwargs):
                    if kwargs.get("training"):
                        kwargs["training"] = False
                    args = [slice_fn(a) for a in args]
                    w = ctx["w"]
                    tv = [w[v.path] for v in _op.trainable_variables]
                    ntv = [
                        w[v.path]
                        for v in _op.non_trainable_variables
                    ]
                    out, _ = _op.stateless_call(tv, ntv, *args, **kwargs)
                    return out

                return stateless

            def weightless(*args, _op=op, **kwargs):
                args = [slice_fn(a) for a in args]
                kwargs = {
                    kk: slice_fn(vv) for kk, vv in kwargs.items()
                }
                return _op(*args, **kwargs)

            return weightless

        return handler

    # -- compiled ring programs -----------------------------------------

    def _build_programs(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from elephas_tpu.serving.engine import _sample_dynamic

        S, ws, k = self.num_stages, self.wave_slots, self.steps_per_wave
        num_slots, maxlen = self.num_slots, self.maxlen
        mesh = self.mesh
        mp = self.model_parallel
        top_k, top_p = self.top_k, self.top_p
        plan = self.plan
        unravels, p_sizes = self._unravels, self._p_sizes
        # the ring buffer carries per-position hidden rows between
        # stages and sampled tokens on the wrap edge; logits never
        # cross (sampling happens ON the last stage), so the buffer is
        # sized by the widest hidden boundary, not the vocab. With
        # bubble-fill armed a fill round moves ws·C hidden rows per
        # tick (C = bubble_chunk); the OFF engine's C is pinned to 1,
        # so its window buffer — and whole program — stays PR 15's
        # byte-for-byte
        D_max = plan.max_boundary_dim
        enable_fill = self.bubble_fill
        Cf = self._C
        B_dec = ws * Cf * D_max
        param_spec = self._param_spec
        pool_spec = self._pool_spec

        def make_decode_branch(s: int):
            nodes, in_kt, out_kt = plan.programs[s]
            first, last = s == 0, s == S - 1
            D_in = None if first else plan.boundary_dims[s - 1]
            unravel, p_size = unravels[s], p_sizes[s]

            def branch(p, tok_in, recv, pk, pv, pos_w, act_w,
                       temps_w, tab_w, sub):
                ctx = {
                    "w": unravel(p[:p_size]),
                    "pk": pk, "pv": pv, "updated": {},
                    "pos": pos_w, "act": act_w, "tables": tab_w,
                }
                handler = self._make_stage_handler(s, "decode", ctx)
                x = tok_in if first else (
                    recv[: ws * D_in].reshape(ws, D_in)
                )
                out = _replay_nodes(nodes, in_kt, out_kt, x, handler)
                for li, (nk, nv) in sorted(ctx["updated"].items()):
                    pk = pk.at[li].set(nk)
                    pv = pv.at[li].set(nv)
                if last:
                    toks = _sample_dynamic(
                        out, sub, temps_w, top_k, top_p
                    )
                    flat = toks.astype(jnp.float32)
                else:
                    flat = out.reshape(-1)
                return (
                    jnp.pad(flat, (0, B_dec - flat.size)), pk, pv,
                )

            return branch

        def make_fill_branch(s: int):
            # the window-resident sibling of make_chunk_branch: one
            # Cf-wide chunk of a filler wave's prompts per ring round,
            # positions (fill offset + round·Cf) + arange(Cf), valid
            # while inside the prompt; the LAST valid position samples
            # the first token exactly like the prefill ring's at_end
            # row, and it rides the same outputs[(round, slot)] write
            nodes, in_kt, out_kt = plan.programs[s]
            first, last = s == 0, s == S - 1
            D_in = None if first else plan.boundary_dims[s - 1]
            unravel, p_size = unravels[s], p_sizes[s]

            def branch(p, rows, recv, pk, pv, offs_w, act_w,
                       p_lens_w, temps_w, tab_w, sub):
                pos_mat = offs_w[:, None] + jnp.arange(Cf)[None, :]
                valid = act_w[:, None] & (
                    pos_mat < p_lens_w[:, None]
                )
                ctx = {
                    "w": unravel(p[:p_size]),
                    "pk": pk, "pv": pv, "updated": {},
                    "pos_mat": pos_mat, "valid": valid,
                    "tables": tab_w,
                }
                handler = self._make_stage_handler(s, "chunk", ctx)
                x = rows if first else (
                    recv[: ws * Cf * D_in].reshape(ws, Cf, D_in)
                )
                out = _replay_nodes(nodes, in_kt, out_kt, x, handler)
                for li, (nk, nv) in sorted(ctx["updated"].items()):
                    pk = pk.at[li].set(nk)
                    pv = pv.at[li].set(nv)
                if last:
                    at_end = (
                        valid & (pos_mat == (p_lens_w - 1)[:, None])
                    ).astype(out.dtype)
                    row = jnp.einsum("wc,wcv->wv", at_end, out)
                    firsts = _sample_dynamic(
                        row, sub, temps_w, top_k, top_p
                    )
                    flat = firsts.astype(jnp.float32)
                else:
                    flat = out.reshape(-1)
                return (
                    jnp.pad(flat, (0, B_dec - flat.size)), pk, pv,
                )

            return branch

        def make_combined_branch(s: int):
            dec = make_decode_branch(s)
            fil = make_fill_branch(s)

            def branch(p, tok_in, recv, pk, pv, pos_w, act_w,
                       temps_w, tab_w, sub, fw, f_rows, f_offs_w,
                       f_plens_w):
                # waves are pure (all-fill or all-decode — the
                # scheduler and _demote_stranded enforce it), so one
                # cond per (stage, tick) picks the wave's mode; the
                # decode side is the PR 15 branch untouched
                return jax.lax.cond(
                    fw,
                    lambda: fil(
                        p, f_rows, recv, pk, pv, f_offs_w, act_w,
                        f_plens_w, temps_w, tab_w, sub,
                    ),
                    lambda: dec(
                        p, tok_in, recv, pk, pv, pos_w, act_w,
                        temps_w, tab_w, sub,
                    ),
                )

            return branch

        decode_branches = [make_decode_branch(s) for s in range(S)]
        combined_branches = [
            make_combined_branch(s) for s in range(S)
        ]

        def ring_decode(wflat, pk, pv, tables, lengths0, last0,
                        temps, active, fill_wave, fill_offs,
                        fill_tokens, fill_plens, key):
            T = int(tables.shape[1])

            def per_device(wflat, pk, pv, tables, lengths0, last0,
                           temps, active, fill_wave, fill_offs,
                           fill_tokens, fill_plens, key):
                stage = jax.lax.axis_index("stages")
                p = wflat.reshape(wflat.shape[-1])
                pk, pv = pk[0], pv[0]

                def one_tick(carry, t):
                    recv, pk, pv, outputs, key = carry
                    w_idx = (t - stage) % S
                    j = (t - stage) // S
                    jc = jnp.clip(j, 0, k - 1)
                    processing = (t >= stage) & (j < k)
                    off = w_idx * ws
                    lens_w = jax.lax.dynamic_slice(
                        lengths0, (off,), (ws,)
                    )
                    act_w = jax.lax.dynamic_slice(
                        active, (off,), (ws,)
                    ) & processing
                    temps_w = jax.lax.dynamic_slice(
                        temps, (off,), (ws,)
                    )
                    last_w = jax.lax.dynamic_slice(
                        last0, (off,), (ws,)
                    )
                    tab_w = jax.lax.dynamic_slice(
                        tables, (off, 0), (ws, T)
                    )
                    pos_w = jnp.minimum(lens_w + j, maxlen - 1)
                    # wave w's token j-1, sampled by the last stage
                    # one tick ago, arrives on the ring's wrap edge
                    # EXACTLY when stage 0 needs it (waves == stages)
                    tok_in = jnp.where(
                        j == 0, last_w, recv[:ws].astype(jnp.int32)
                    )
                    key, sub = jax.random.split(key)
                    if enable_fill:
                        fw = fill_wave[w_idx]
                        f_offs_w = jax.lax.dynamic_slice(
                            fill_offs, (off,), (ws,)
                        ) + jc * Cf
                        f_rows = jax.lax.dynamic_slice(
                            fill_tokens, (off, jc * Cf), (ws, Cf)
                        )
                        f_plens_w = jax.lax.dynamic_slice(
                            fill_plens, (off,), (ws,)
                        )
                        out_flat, pk, pv = jax.lax.switch(
                            stage,
                            [
                                (lambda *a, _br=br: _br(*a))
                                for br in combined_branches
                            ],
                            p, tok_in, recv, pk, pv, pos_w, act_w,
                            temps_w, tab_w, sub, fw, f_rows,
                            f_offs_w, f_plens_w,
                        )
                    else:
                        out_flat, pk, pv = jax.lax.switch(
                            stage,
                            [
                                (lambda *a, _br=br: _br(*a))
                                for br in decode_branches
                            ],
                            p, tok_in, recv, pk, pv, pos_w, act_w,
                            temps_w, tab_w, sub,
                        )
                    toks = out_flat[:ws].astype(jnp.int32)
                    upd = jax.lax.dynamic_update_slice(
                        outputs, toks[None, :], (jc, off)
                    )
                    outputs = jnp.where(
                        (stage == S - 1) & processing, upd, outputs
                    )
                    recv = jax.lax.ppermute(
                        out_flat, "stages",
                        [(i, (i + 1) % S) for i in range(S)],
                    )
                    return (recv, pk, pv, outputs, key), None

                recv0 = jnp.zeros((B_dec,), jnp.float32)
                out0 = jnp.zeros((k, num_slots), jnp.int32)
                (recv, pk, pv, outputs, key), _ = jax.lax.scan(
                    one_tick, (recv0, pk, pv, out0, key),
                    jnp.arange(S * k + S - 1),
                )
                return pk[None], pv[None], outputs[None], key

            return jax.shard_map(
                per_device,
                mesh=mesh,
                in_specs=(param_spec, pool_spec, pool_spec,
                          P(), P(), P(), P(), P(), P(), P(), P(),
                          P(), P()),
                out_specs=(pool_spec, pool_spec, P("stages"), P()),
                check_vma=False,
            )(wflat, pk, pv, tables, lengths0, last0, temps, active,
              fill_wave, fill_offs, fill_tokens, fill_plens, key)

        self._decode_ring_jit = jax.jit(
            ring_decode, donate_argnums=(1, 2)
        )

        # -- prefill ring: one chunk per wave walks all stages --------

        def make_chunk_branch(s: int, C: int):
            nodes, in_kt, out_kt = plan.programs[s]
            first, last = s == 0, s == S - 1
            D_in = None if first else plan.boundary_dims[s - 1]
            unravel, p_size = unravels[s], p_sizes[s]
            B_pre = ws * C * D_max

            def branch(p, rows, recv, pk, pv, offs_w, clens_w,
                       act_w, p_lens_w, temps_w, tab_w, sub):
                pos_mat = offs_w[:, None] + jnp.arange(C)[None, :]
                valid = act_w[:, None] & (
                    jnp.arange(C)[None, :] < clens_w[:, None]
                )
                ctx = {
                    "w": unravel(p[:p_size]),
                    "pk": pk, "pv": pv, "updated": {},
                    "pos_mat": pos_mat, "valid": valid,
                    "tables": tab_w,
                }
                handler = self._make_stage_handler(s, "chunk", ctx)
                x = rows if first else (
                    recv[: ws * C * D_in].reshape(ws, C, D_in)
                )
                out = _replay_nodes(nodes, in_kt, out_kt, x, handler)
                for li, (nk, nv) in sorted(ctx["updated"].items()):
                    pk = pk.at[li].set(nk)
                    pv = pv.at[li].set(nv)
                if last:
                    at_end = (
                        (p_lens_w - offs_w - 1)[:, None]
                        == jnp.arange(C)[None, :]
                    ).astype(out.dtype)
                    row = jnp.einsum("wc,wcv->wv", at_end, out)
                    firsts = _sample_dynamic(
                        row, sub, temps_w, top_k, top_p
                    )
                    flat = firsts.astype(jnp.float32)
                else:
                    flat = out.reshape(-1)
                return (
                    jnp.pad(flat, (0, B_pre - flat.size)), pk, pv,
                )

            return branch

        def ring_prefill(wflat, pk, pv, tables, tokens, offs, clens,
                         act, p_lens, temps, key):
            C = int(tokens.shape[1])
            T = int(tables.shape[1])
            B_pre = ws * C * D_max
            branches = [make_chunk_branch(s, C) for s in range(S)]

            def per_device(wflat, pk, pv, tables, tokens, offs,
                           clens, act, p_lens, temps, key):
                stage = jax.lax.axis_index("stages")
                p = wflat.reshape(wflat.shape[-1])
                pk, pv = pk[0], pv[0]

                def one_tick(carry, t):
                    recv, pk, pv, firsts, key = carry
                    w_idx = (t - stage) % S
                    processing = (t >= stage) & (t - stage < S)
                    off = w_idx * ws
                    rows = jax.lax.dynamic_slice(
                        tokens, (off, 0), (ws, C)
                    )
                    offs_w = jax.lax.dynamic_slice(
                        offs, (off,), (ws,)
                    )
                    clens_w = jax.lax.dynamic_slice(
                        clens, (off,), (ws,)
                    )
                    act_w = jax.lax.dynamic_slice(
                        act, (off,), (ws,)
                    ) & processing
                    p_lens_w = jax.lax.dynamic_slice(
                        p_lens, (off,), (ws,)
                    )
                    temps_w = jax.lax.dynamic_slice(
                        temps, (off,), (ws,)
                    )
                    tab_w = jax.lax.dynamic_slice(
                        tables, (off, 0), (ws, T)
                    )
                    key, sub = jax.random.split(key)
                    out_flat, pk, pv = jax.lax.switch(
                        stage,
                        [
                            (lambda *a, _br=br: _br(*a))
                            for br in branches
                        ],
                        p, rows, recv, pk, pv, offs_w, clens_w,
                        act_w, p_lens_w, temps_w, tab_w, sub,
                    )
                    toks = out_flat[:ws].astype(jnp.int32)
                    upd = jax.lax.dynamic_update_slice(
                        firsts, toks, (off,)
                    )
                    firsts = jnp.where(
                        (stage == S - 1) & processing, upd, firsts
                    )
                    recv = jax.lax.ppermute(
                        out_flat, "stages",
                        [(i, (i + 1) % S) for i in range(S)],
                    )
                    return (recv, pk, pv, firsts, key), None

                recv0 = jnp.zeros((B_pre,), jnp.float32)
                f0 = jnp.zeros((num_slots,), jnp.int32)
                (recv, pk, pv, firsts, key), _ = jax.lax.scan(
                    one_tick, (recv0, pk, pv, f0, key),
                    jnp.arange(2 * S - 1),
                )
                return pk[None], pv[None], firsts[None], key

            return jax.shard_map(
                per_device,
                mesh=mesh,
                in_specs=(param_spec, pool_spec, pool_spec, P(), P(),
                          P(), P(), P(), P(), P(), P()),
                out_specs=(pool_spec, pool_spec, P("stages"), P()),
                check_vma=False,
            )(wflat, pk, pv, tables, tokens, offs, clens, act,
              p_lens, temps, key)

        self._prefill_ring_jit = jax.jit(
            ring_prefill, donate_argnums=(1, 2)
        )

        # -- per-stage offload gather / resume scatter -----------------

        def gather_rows(pk, pv, ids):
            def per_device(pk, pv, ids):
                pk, pv = pk[0], pv[0]
                gk = jnp.take(pk, ids, axis=1, mode="clip")
                gv = jnp.take(pv, ids, axis=1, mode="clip")
                return gk[None], gv[None]

            return jax.shard_map(
                per_device, mesh=mesh,
                in_specs=(pool_spec, pool_spec, P()),
                out_specs=(pool_spec, pool_spec),
                check_vma=False,
            )(pk, pv, ids)

        def scatter_rows(pk, pv, ids, rk, rv):
            def per_device(pk, pv, ids, rk, rv):
                pk, pv, rk, rv = pk[0], pv[0], rk[0], rv[0]
                pk = pk.at[:, ids].set(rk, mode="drop")
                pv = pv.at[:, ids].set(rv, mode="drop")
                return pk[None], pv[None]

            return jax.shard_map(
                per_device, mesh=mesh,
                in_specs=(pool_spec, pool_spec, P(), pool_spec,
                          pool_spec),
                out_specs=(pool_spec, pool_spec),
                check_vma=False,
            )(pk, pv, ids, rk, rv)

        self._gather_jit = jax.jit(gather_rows)
        self._scatter_jit = jax.jit(
            scatter_rows, donate_argnums=(0, 1)
        )

    # -- dispatch + compile accounting ----------------------------------

    def _dispatch(self, program: str, fn, *args):
        """Cache-size-watched dispatch (the ISSUE 12 pattern): a call
        that grew the program's jit cache records a ``jit.compile``
        span. Report-only; unwatched under null mode."""
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
        except Exception:
            grew = False
        if grew:
            self._tracer.complete(
                "jit.compile", time.perf_counter() - t0,
                program=program, engine=self.telemetry_label,
            )
        return out

    # -- request API ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               temperature: float = 0.0, eos_id: int | None = None,
               on_token=None, priority: int = 0) -> Request:
        """Queue one generation request (admitted at the next window
        boundary — mid-flight submission joins the next wave). Same
        shape as ``InferenceEngine.submit`` minus the policy/tenant
        knobs this engine does not carry; ``priority`` matters only
        with ``preemption=True``."""
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
        self.scheduler.bucket_for(p)  # fail here, not mid-wave
        if priority and not self.preemption:
            logger.warning(
                "submit(priority=%d) on a PP engine without "
                "preemption=True — priority is recorded but IGNORED",
                priority,
            )
        req = self.scheduler.make_request(
            prompt, max_new_tokens, temperature=temperature,
            eos_id=eos_id, on_token=on_token, priority=priority,
        )
        req.submit_time = time.perf_counter()
        req.submit_step = self.scheduler._steps
        req.exemplar = {"rid": str(req.rid)}
        self._tracer.emit(
            "serve.submit", rid=req.rid, prompt_tokens=p,
            max_new_tokens=int(max_new_tokens),
            step=req.submit_step,
        )
        need = blocks_for(p + max_new_tokens, self.block_size)
        if need > self.num_blocks:
            req.error = RuntimeError(
                f"request {req.rid} needs {need} KV blocks per stage "
                f"(prompt {p} + max_new_tokens {max_new_tokens} at "
                f"block_size {self.block_size}) but each stage pool "
                f"only has {self.num_blocks} — it can never be "
                f"admitted; rejected at submit"
            )
            req.done = True
            self._m_rejected.inc()
            logger.warning("%s", req.error)
            self.finished[req.rid] = req
            self._evict_finished()
            return req
        self.scheduler.submit(req)
        return req

    def _evict_finished(self) -> None:
        while len(self.finished) > self._finished_bound:
            victim = next(
                (rid for rid in self.finished
                 if rid not in self._protected),
                None,
            )
            if victim is None:
                return
            self.finished.pop(victim)
            self._tracer.emit("serve.evict", rid=victim)

    def _emit(self, req: Request, token: int) -> bool:
        """Record one generated token; reclaim the slot when the
        request finished (EOS / budget / raising callback)."""
        self._m_tokens.inc()
        slot = req.slot
        now = time.perf_counter()
        req.token_times.append(now)
        if len(req.token_times) == 1:
            self._tracer.emit(
                "serve.first_token", rid=req.rid,
                step=self.scheduler._steps,
            )
            if req.submit_time is not None:
                self._m_ttft.observe(
                    now - req.submit_time, exemplar=req.exemplar
                )
        else:
            self._m_itl.observe(
                now - req.token_times[-2], exemplar=req.exemplar
            )
        done = self.scheduler.on_token(slot, token)
        if req.on_token is not None:
            try:
                req.on_token(token, done)
            except Exception as e:
                req.error = e
                req.done = True
                done = True
                logger.warning(
                    "request %d failed in its on_token callback (%r) "
                    "— slot %d reclaimed, engine continues",
                    req.rid, e, slot,
                )
        if done:
            req.finish_time = req.token_times[-1]
            self.scheduler.reclaim(slot)
            self._active_host[slot] = False
            self._m_finished.inc()
            if req.error is not None:
                reason = "callback_error"
            elif (
                req.eos_id is not None and req.tokens
                and req.tokens[-1] == req.eos_id
            ):
                reason = "eos"
            else:
                reason = "budget"
            self._tracer.emit(
                "serve.finish", rid=req.rid, reason=reason,
                tokens=len(req.tokens), step=self.scheduler._steps,
            )
            self.finished[req.rid] = req
            self._evict_finished()
        return done

    # -- device staging of host truth -----------------------------------

    def _staged_tables(self):
        """Device copy of the block tables, ``[num_slots, T]`` for the
        bucketed ``T`` — sentinel-padded, rebuilt only on mutation or
        bucket shift (the paged engine's caching pattern)."""
        sched = self.scheduler
        need = max(
            (len(t) for t in sched.tables.values()), default=1
        )
        T = table_bucket_for(need, self._tbuckets)
        key = (sched.tables_version, T)
        if self._tables_cache is None or self._tables_cache[0] != key:
            arr = np.full(
                (self.num_slots, T), self.num_blocks, np.int32
            )
            for slot, table in sched.tables.items():
                arr[slot, : len(table)] = table
            self._tables_cache = (key, self._stage_host(arr))
        return self._tables_cache[1]

    def _pad_ids(self, blocks):
        Tb = table_bucket_for(max(1, len(blocks)), self._tbuckets)
        ids = np.full((Tb,), self.num_blocks, np.int32)
        ids[: len(blocks)] = blocks
        return ids

    # -- preemption offload / resume ------------------------------------

    def _offload(self, pre) -> None:
        """Per-stage offload: gather the victim's blocks from EVERY
        stage's pool in one stage-sharded program, host-read the
        stacked rows, and park them until resume. Runs before any
        pool-writing program of the same step (the jit data dependency
        orders the gather against the current pool value)."""
        req = pre.req
        with self._tracer.span(
            "serve.preempt", rid=req.rid, blocks=len(pre.blocks),
        ):
            ids = self._pad_ids(pre.blocks)
            gk, gv = self._dispatch(
                "pp_offload_gather", self._gather_jit,
                self._pk, self._pv, self._stage_host(ids),
            )
            n = len(pre.blocks)
            k_rows = np.ascontiguousarray(self._host(gk)[:, :, :n])
            v_rows = np.ascontiguousarray(self._host(gv)[:, :, :n])
            self._offloaded[req.rid] = _StageOffload(
                k_rows=k_rows, v_rows=v_rows, n_blocks=n,
                cur_len=pre.cur_len,
            )
        self._active_host[pre.slot] = False
        self._m_preemptions.inc()
        self._m_offload_blocks.inc(n * self.num_stages)
        logger.info(
            "PP-preempted request %d: %d blocks/stage offloaded "
            "across %d stages, slot %d freed",
            req.rid, n, self.num_stages, pre.slot,
        )

    def _resume(self, adm) -> None:
        """Scatter the parked per-stage rows into the fresh allocation
        and re-arm host state — bit-exact: greedy decode is a pure
        function of (weights, K/V, cursor, last token), and the
        restored rows are bitwise the offloaded ones on every
        stage."""
        from elephas_tpu.parallel.mesh import put_global

        req = adm.req
        store = self._offloaded.pop(req.rid)
        with self._tracer.span(
            "serve.resume", rid=req.rid, blocks=store.n_blocks,
        ):
            n = store.n_blocks
            ids = self._pad_ids(adm.blocks[:n])
            Tb = len(ids)
            S = self.num_stages
            shape = (
                S, self.layers_per_stage, Tb, self.block_size,
                self.num_heads, self.head_dim,
            )
            rk = np.zeros(shape, np.float32)
            rv = np.zeros(shape, np.float32)
            rk[:, :, :n] = store.k_rows
            rv[:, :, :n] = store.v_rows
            self._pk, self._pv = self._dispatch(
                "pp_resume_scatter", self._scatter_jit,
                self._pk, self._pv, self._stage_host(ids),
                put_global(rk, self._pool_sh),
                put_global(rv, self._pool_sh),
            )
        self._active_host[adm.slot] = True
        self._m_resumes.inc()  # admission kind counted by admit_paged
        logger.info(
            "PP-resumed request %d into slot %d (%d blocks/stage, "
            "cursor %d)", req.rid, adm.slot, n, store.cur_len,
        )

    # -- execution ------------------------------------------------------

    def _account_ring(self, stage_ticks: int, useful: int) -> None:
        """Cumulative ring-time accounting (report-only): every ring
        dispatch — decode window or standalone prefill — schedules
        ``stage_ticks`` stage-ticks of which ``useful`` carried wave
        work; ``stats()['bubble_cumulative']`` is the lifetime idle
        fraction, the number to compare across a filled and an unfilled
        arm (a filled arm simply never schedules the standalone
        prefill dispatch's mostly-idle ticks)."""
        self._ticks_sched += int(stage_ticks)
        self._ticks_useful += int(useful)

    def _prefill_wave(self, fresh):
        """Prefill an admission wave through the ring: one dispatch
        per prompt-width bucket walks every admitted slot's prompt
        through all stages (wave by wave), lands each stage's K/V in
        its own pool, and samples first tokens on the last stage.

        Suffix-bucketed (ISSUE 16): a prefix-index hit spliced blocks
        that are ALREADY resident on every stage (one allocator, one
        id fleet-wide), so the ring starts at the shared offset and
        buckets by the remaining suffix — the flat engine's rule."""
        items = []
        for a in fresh:
            if a.shared_len:
                self._m_prefix_shared.inc(a.shared_len)
            items.append((a.req, a.slot, a.shared_len))
        return self._ring_prefill_items(items, demoted=False)

    def _ring_prefill_items(self, items, demoted: bool):
        """Run the offset-capable prefill ring over ``(req, slot,
        offset)`` items — admission waves (offset = shared prefix) and
        bubble-fill demotions (offset = fill progress) share one
        dispatch path, so both complete the prompt, register it with
        the prefix index, and emit the first token identically."""
        emitted = []
        sched = self.scheduler
        S, ws = self.num_stages, self.wave_slots
        by_width: dict[int, list] = {}
        for req, slot, off in items:
            by_width.setdefault(
                sched.bucket_for(len(req.prompt) - off), []
            ).append((req, slot, off))
        for width in sorted(by_width):
            group = by_width[width]
            tokens = np.zeros((self.num_slots, width), np.int32)
            offs = np.zeros((self.num_slots,), np.int32)
            clens = np.zeros((self.num_slots,), np.int32)
            act = np.zeros((self.num_slots,), bool)
            p_lens = np.ones((self.num_slots,), np.int32)
            temps = np.zeros((self.num_slots,), np.float32)
            for req, slot, off in group:
                suffix = req.prompt[off:]
                tokens[slot, : len(suffix)] = suffix
                offs[slot] = off
                clens[slot] = len(suffix)
                act[slot] = True
                p_lens[slot] = len(req.prompt)
                temps[slot] = req.temperature
            # a standalone prefill dispatch is ring time too: 2S−1
            # ticks in which only the occupied waves carry work
            waves_used = len({slot // ws for _r, slot, _o in group})
            self._account_ring(S * (2 * S - 1), waves_used * S)
            with self._tracer.span(
                "serve.prefill_wave", reqs=len(group), width=width,
                demoted=bool(demoted),
            ):
                (self._pk, self._pv, firsts, self._key) = (
                    self._dispatch(
                        "pp_ring_prefill", self._prefill_ring_jit,
                        self._wflat, self._pk, self._pv,
                        self._staged_tables(),
                        self._stage_host(tokens),
                        self._stage_host(offs),
                        self._stage_host(clens),
                        self._stage_host(act),
                        self._stage_host(p_lens),
                        self._stage_host(temps), self._key,
                    )
                )
                toks = self._host(firsts)[self.num_stages - 1]
            for req, slot, off in group:
                self._active_host[slot] = True
                self._tracer.emit(
                    "serve.prefill", rid=req.rid, bucket=width,
                    prompt_tokens=len(req.prompt), offset=int(off),
                    step=sched._steps,
                )
                # register the completed prompt with the prefix index
                # BEFORE emitting: a budget-1 request reclaims its
                # table inside _emit, and insert() needs it live
                sched.on_prefill_complete(req)
                self._emit(req, int(toks[slot]))
                emitted.append((req, req.tokens[-1], req.done))
        return emitted

    def _decode_window(self):
        """One compiled window of ``S·k + S − 1`` ring ticks: every
        wave advances ``k`` tokens, stages overlap on different waves
        (the bubble-filling schedule), host state re-arms from truth
        at the boundary.

        Bubble-fill (ISSUE 16): waves flagged in ``fill_wave`` run
        the CHUNK branch on their ticks instead of decode — each of
        the window's ``k`` rounds advances every filler in the wave
        by ``bubble_chunk`` prompt positions (from its ``_filling``
        offset). A filler whose remaining prompt fits this window
        samples its first token at round ``ceil(remaining/C) − 1``
        and parks in ``_fill_done`` until the WHOLE wave finished
        filling (whole-wave graduation — graduating one slot early
        would flip the wave to decode and strand its co-fillers
        mid-prompt); then the wave decodes normally from the next
        window."""
        sched = self.scheduler
        S, ws, k = self.num_stages, self.wave_slots, self.steps_per_wave
        C = self._C
        filling = dict(self._filling)
        skip = set(filling) | self._fill_done
        lengths0 = np.zeros((self.num_slots,), np.int32)
        last0 = np.zeros((self.num_slots,), np.int32)
        temps = np.zeros((self.num_slots,), np.float32)
        for slot, req in sched.active.items():
            temps[slot] = req.temperature
            if slot in skip:
                continue  # fillers have no sampled token yet
            lengths0[slot] = len(req.prompt) + len(req.tokens) - 1
            last0[slot] = req.tokens[-1]
        active = self._active_host.copy()
        fill_wave = np.zeros((S,), bool)
        fill_offs = np.zeros((self.num_slots,), np.int32)
        fill_tokens = np.zeros((self.num_slots, k * C), np.int32)
        fill_plens = np.ones((self.num_slots,), np.int32)
        fill_rounds = [0] * S
        fill_toks = 0
        for slot, off in sorted(filling.items()):
            req = sched.active[slot]
            w = slot // ws
            pl = len(req.prompt)
            fill_wave[w] = True
            fill_offs[slot] = off
            seg = req.prompt[off: off + k * C]
            fill_tokens[slot, : len(seg)] = seg
            fill_plens[slot] = pl
            active[slot] = True
            rounds = min(-(-(pl - off) // C), k)
            fill_rounds[w] = max(fill_rounds[w], rounds)
            fill_toks += len(seg)
        for slot in self._fill_done:
            # completed co-fillers keep their wave in fill mode (the
            # chunk branch idles them via the valid mask) until the
            # whole wave graduates
            fill_wave[slot // ws] = True
        # report-only wave occupancy + bubble fraction: ramp/drain
        # ticks plus whole-window ticks of EMPTY waves carry no wave
        # work — but rounds a fill wave spends on prefill chunks DO;
        # telemetry observes, never drives
        wave_live = [
            int(self._active_host[w * ws:(w + 1) * ws].sum())
            for w in range(S)
        ]
        nonempty = sum(1 for n in wave_live if n)
        ticks = S * k + S - 1
        useful = nonempty * S * k + sum(fill_rounds) * S
        bubble = 1.0 - useful / float(S * ticks)
        self._last_bubble = bubble
        self._m_bubble.set(bubble)
        self._account_ring(S * ticks, useful)
        for w, n in enumerate(wave_live):
            self._mf_wave_active.labels(
                engine=self.telemetry_label, wave=str(w)
            ).set(n)
        if fill_toks:
            self._m_fill_tokens.inc(fill_toks)
            self._m_fill_rounds.inc(sum(fill_rounds))
        emitted = []
        with self._tracer.span(
            "serve.wave", waves=S, steps=k,
            active=len(sched.active), bubble=round(bubble, 4),
            fill_slots=len(filling), fill_tokens=fill_toks,
        ):
            self._m_decode_windows.inc()
            (self._pk, self._pv, outputs, self._key) = self._dispatch(
                "pp_ring_decode", self._decode_ring_jit,
                self._wflat, self._pk, self._pv,
                self._staged_tables(), self._stage_host(lengths0),
                self._stage_host(last0), self._stage_host(temps),
                self._stage_host(active),
                self._stage_host(fill_wave),
                self._stage_host(fill_offs),
                self._stage_host(fill_tokens),
                self._stage_host(fill_plens),
                self._key,
            )
            toks = self._host(outputs)[S - 1]  # [k, num_slots]
            for i in range(k):
                if not sched.active:
                    break
                sched.note_step()
                for slot, req in sorted(sched.active.items()):
                    if slot in skip:
                        continue
                    done = self._emit(req, int(toks[i, slot]))
                    emitted.append((req, req.tokens[-1], done))
        # fill advancement/completion at the window boundary
        for slot in sorted(filling):
            req = sched.active.get(slot)
            if req is None or slot not in self._filling:
                continue  # cancelled mid-window
            off = self._filling[slot]
            pl = len(req.prompt)
            rounds = -(-(pl - off) // C)  # ceil
            if rounds <= k:
                del self._filling[slot]
                self._tracer.emit(
                    "serve.fill_complete", rid=req.rid, slot=slot,
                    prompt_tokens=pl, step=sched._steps,
                )
                sched.on_prefill_complete(req)
                done = self._emit(req, int(toks[rounds - 1, slot]))
                emitted.append((req, req.tokens[-1], done))
                if not done:
                    self._fill_done.add(slot)
            else:
                self._filling[slot] = off + k * C
        # whole-wave graduation: a fill wave with no in-progress
        # fillers left starts decoding at the NEXT window
        for slot in sorted(self._fill_done):
            w = slot // ws
            if not any(s // ws == w for s in self._filling):
                self._fill_done.discard(slot)
                self._active_host[slot] = True
        return emitted

    def _start_fill(self, a) -> None:
        """Arm bubble-fill for one admission: the prompt (its suffix,
        after a prefix-hit splice — shared blocks are resident on
        every stage already) prefills through the slot's wave during
        coming decode windows' idle ticks instead of a standalone
        ring dispatch. The slot stays decode-inactive until its wave
        graduates."""
        req = a.req
        if a.shared_len:
            self._m_prefix_shared.inc(a.shared_len)
        self._filling[a.slot] = int(a.shared_len)
        self._tracer.emit(
            "serve.fill_admit", rid=req.rid, slot=a.slot,
            prompt_tokens=len(req.prompt), shared=int(a.shared_len),
            step=self.scheduler._steps,
        )

    def _demote_stranded(self):
        """Bubble-fill liveness: fillers whose wave can no longer run
        as a PURE fill wave finish their prompts NOW through the
        offset prefill ring. Two strandings exist: (a) a decode-
        active occupant landed in the fill wave (a resume's wave-
        aware slot, or a budget-overflow admission) — each ring tick
        runs ONE branch per wave, so mixed waves are unschedulable;
        (b) no decode-active wave remains anywhere, so no window
        would ever carry the fill. Demotion re-enters the standing
        prefill path at the CURRENT offset — chunk K/V already
        written stays valid, only the remaining suffix rings."""
        if not self._filling and not self._fill_done:
            return []
        ws = self.wave_slots
        decode_waves = {
            int(s) // ws for s in np.flatnonzero(self._active_host)
        }
        demote = [
            slot for slot in sorted(self._filling)
            if slot // ws in decode_waves or not decode_waves
        ]
        emitted = []
        if demote:
            items = []
            for slot in demote:
                off = self._filling.pop(slot)
                req = self.scheduler.active[slot]
                self._tracer.emit(
                    "serve.fill_demote", rid=req.rid, slot=slot,
                    offset=int(off), step=self.scheduler._steps,
                )
                items.append((req, slot, off))
            emitted = self._ring_prefill_items(items, demoted=True)
        # completed fillers in a wave that is decoding anyway (or
        # whose last in-progress filler just demoted) graduate now
        for slot in sorted(self._fill_done):
            w = slot // ws
            if w in decode_waves or not any(
                s // ws == w for s in self._filling
            ):
                self._fill_done.discard(slot)
                self._active_host[slot] = True
        return emitted

    def step(self):
        """One engine iteration: paged admission (preemption offloads
        first, resumes restored, bubble-fill admissions armed, fresh
        admissions ring-prefilled, stranded fillers demoted), then
        one microbatched decode window. Returns ``(request, token,
        done)`` triples in generation order."""
        emitted = []
        fillers = frozenset(self._filling) | frozenset(self._fill_done)
        plan, preempts = self.scheduler.admit_paged(
            prefilling=fillers,
            bubble_fill=self.bubble_fill,
            fill_budget=self.bubble_budget,
        )
        for pre in preempts:
            self._offload(pre)
            # a parked-but-complete filler may be a victim; its fill
            # state dies with the slot (the offload record has the
            # full prompt K/V — resume restores it as plain decode)
            self._fill_done.discard(pre.slot)
        if plan:
            for a in plan:
                if a.resume is not None:
                    self._resume(a)
            for a in plan:
                if a.resume is None and a.fill:
                    self._start_fill(a)
            fresh = [
                a for a in plan if a.resume is None and not a.fill
            ]
            if fresh:
                emitted.extend(self._prefill_wave(fresh))
        emitted.extend(self._demote_stranded())
        if self.scheduler.active:
            emitted.extend(self._decode_window())
        return emitted

    def _notify_stream_end(self, req: Request) -> None:
        """Tell a request's live stream it ENDED without a final
        engine token — ``on_token(None, True)``; without it a
        consumer blocking on the stream (the gateway's SSE handlers)
        waits forever on cancel."""
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
        """Abort one in-flight request and reclaim its wave slot and
        blocks at the window boundary — flat-engine parity (the
        gateway's ``POST /v1/requests/{rid}/cancel`` route and SSE
        disconnects call this on whichever engine serves). A waiting
        request leaves the queue (a preempted one also drops its
        per-stage offload record); an active one frees its slot and
        table, clearing any bubble-fill state. Returns True when the
        rid was live (``req.done`` flips with ``req.error`` set to
        :class:`RequestCancelled`), False when unknown or already
        finished.

        Gang contract: every gang process must issue the identical
        cancel sequence at the identical step boundaries."""
        from elephas_tpu.serving.engine import RequestCancelled

        rid = int(rid)
        sched = self.scheduler
        req = sched.remove_waiting(rid)
        if req is not None:
            self._offloaded.pop(rid, None)
        else:
            slot = next(
                (s for s, r in sched.active.items() if r.rid == rid),
                None,
            )
            if slot is None:
                return False
            req = sched.active[slot]
            self._filling.pop(slot, None)
            self._fill_done.discard(slot)
            sched.reclaim(slot)
            self._active_host[slot] = False
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
        self.finished[rid] = req
        self._evict_finished()
        return True

    def stream(self):
        while self.scheduler.has_work:
            for req, token, done in self.step():
                yield req.rid, token, done

    def run(self, requests=None) -> dict[int, np.ndarray]:
        """Batch driver, shaped like ``InferenceEngine.run``."""
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
            self._evict_finished()
        return drained

    # -- introspection --------------------------------------------------

    @property
    def total_generated(self) -> int:
        return int(self._m_tokens.value)

    @property
    def finished_count(self) -> int:
        return int(self._m_finished.value)

    def compile_stats(self) -> dict:
        """Compiled-program counts — the closed-set contract: the
        decode ring compiles once per table bucket, the prefill ring
        once per (width bucket, table bucket), gather/scatter once
        per touched table bucket. A second identical workload must
        leave this dict unchanged."""

        def n(f):
            try:
                return int(f._cache_size())
            except Exception:  # pragma: no cover - jax-version drift
                return -1

        return {
            "ring_decode_compiles": n(self._decode_ring_jit),
            "ring_prefill_compiles": n(self._prefill_ring_jit),
            "offload_compiles": n(self._gather_jit),
            "resume_compiles": n(self._scatter_jit),
            "buckets": tuple(self.scheduler.buckets),
            "table_buckets": tuple(self._tbuckets),
            "num_stages": self.num_stages,
            "wave_slots": self.wave_slots,
            "steps_per_wave": self.steps_per_wave,
            "block_size": self.block_size,
            "num_blocks": self.num_blocks,
            "model_parallel": self.model_parallel,
            "attention": self.attention,
            "bubble_fill": self.bubble_fill,
            "bubble_chunk": self._C,
            "prefix_cache": self.scheduler.prefix_index is not None,
        }

    def stats(self) -> dict:
        finished = list(self.finished.values())
        lat = [
            r.finish_time - r.submit_time
            for r in finished
            if r.finish_time is not None and r.submit_time is not None
        ]
        ttfts = [r.ttft for r in finished if r.ttft is not None]
        itls = [d for r in finished for d in r.inter_token_times]
        d_toks = sum(
            len(r.token_times) - 1
            for r in finished if len(r.token_times) > 1
        )
        d_secs = sum(
            r.token_times[-1] - r.token_times[0]
            for r in finished if len(r.token_times) > 1
        )
        from elephas_tpu.serving.engine import InferenceEngine

        pct = InferenceEngine._percentiles
        return {
            "total_generated": self.total_generated,
            "finished": self.finished_count,
            "decode_steps": self.scheduler._steps,
            "occupancy": self.scheduler.occupancy,
            "latencies": lat,
            "num_slots": self.num_slots,
            "num_stages": self.num_stages,
            "wave_slots": self.wave_slots,
            "steps_per_wave": self.steps_per_wave,
            "attention": self.attention,
            "ttft_s": pct(ttfts),
            "inter_token_s": pct(itls),
            "decode_tok_s": (d_toks / d_secs) if d_secs > 0 else None,
            "queue_depth": int(self.scheduler._m_waiting.value),
            "preemptions": int(self._m_preemptions.value),
            "resumes": int(self._m_resumes.value),
            "rejected": int(self._m_rejected.value),
            "offloaded_blocks": int(self._m_offload_blocks.value),
            "blocks_total": self.num_blocks,
            "blocks_free": self.scheduler.allocator.free_count,
            "bubble_fraction": self._last_bubble,
            "bubble_cumulative": (
                1.0 - self._ticks_useful / self._ticks_sched
                if self._ticks_sched else None
            ),
            "fill_tokens": int(self._m_fill_tokens.value),
            "fill_rounds": int(self._m_fill_rounds.value),
            "prefix_shared_tokens": int(self._m_prefix_shared.value),
            "cancelled": int(self._m_cancelled.value),
        }

    def scrape(self, full: bool = True) -> str:
        """Prometheus exposition of this engine's series (the
        ``InferenceEngine.scrape`` shape, 0.0.4 flavor)."""
        if not full:
            reg = self._telemetry_registry
            return telemetry.render(
                reg, only={"engine": self.telemetry_label}
            ) + telemetry.render(
                reg, only={"scheduler": self.scheduler.telemetry_label}
            )
        return telemetry.render(self._telemetry_registry)

    def release_telemetry(self) -> None:
        telemetry.remove_series(engine=self.telemetry_label)
        self.scheduler.release_telemetry()
