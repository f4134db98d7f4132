"""Paged KV arena: block-pool K/V + block-table attention (ISSUE 7).

The fixed slot arena (:mod:`~elephas_tpu.serving.kv_cache`) prices
every slot at the model's worst-case length — one ``[num_slots,
max_len, H, Dh]`` row pair per layer, so a single long-context slot's
reservation caps admission depth for everyone. This module is the
PagedAttention-style (vLLM, Kwon et al. 2023) replacement: a global
**block pool** ``[num_blocks, block_size, H, Dh]`` per layer plus
per-slot **block tables** mapping logical position ``p`` to physical
row ``(table[p // block_size], p % block_size)``. Requests reserve
``ceil((prompt + max_new_tokens) / block_size)`` blocks — their OWN
worst case, not the model's — so short requests stop paying for long
ones, freed blocks recycle at block granularity, and full prompt-prefix
blocks can be SHARED by refcount (copy-free prefix hits, no donor
transplant program at all).

The repo's serving invariants carry over unchanged:

- **one-hot slot-local writes** — block/offset targets are one-hot
  contractions, never dynamic scatters, so writes stay exact (each
  pool row receives exactly one ``1.0·value`` against ``0.0`` terms)
  and mesh-safe;
- **a closed compiled-shape set** — programs compile per bucketed
  block-TABLE length (:func:`table_buckets`: powers of two in blocks,
  capped at ``ceil(maxlen / block_size)``), not per request: the decode
  program's attention span is ``T·block_size`` for the bucketed ``T``
  covering the longest live table, so a short-context steady state
  attends over a short span instead of ``maxlen``;
- **temperature-0 token-exactness** — attention runs the same
  einsum/softmax math over the same visible position set as the fixed
  arena, including under TP meshes (heads shard over the model axis;
  the block axis stays REPLICATED — blocks have no slot affinity, so
  unlike the slot arena there is no batch-axis sharding that keeps a
  gather local; the one-hot contractions remain exact regardless).

Padding convention: block-table rows pad with the SENTINEL id
``num_blocks`` — a one-hot against ``arange(num_blocks)`` that matches
nothing, so padded entries neither write (a cursor beyond a slot's
table maps to no pool row) nor gather (they contribute exact zero rows,
masked off by position visibility). Padding with 0 would alias block 0.

:func:`gather_blocks` / :func:`scatter_blocks` are the device half of
preempt/resume: gather reads a victim's blocks into dense rows for
host offload (``jax.device_get``), scatter writes them back into a
fresh allocation bit-exactly. One compile per table bucket each.

The sentinel/table conventions here (pad with ``num_blocks``, route
cursor overrun to the sentinel, ``table_buckets`` ladder) are shared
verbatim by the pipeline-parallel engine's per-stage pools
(:mod:`elephas_tpu.serving.pp_engine`, ISSUE 15) — its stage-local
attention closures mirror this module's ``local=True`` fast path
inside ``shard_map``, where native gather/scatter is always legal.
"""

from __future__ import annotations

from elephas_tpu.models.transformer import (
    _apply_rope,
    _rope_tables,
)
from elephas_tpu.serving.kv_cache import (
    _graph_replay,
    _rows_at_position_matrix,
    _rows_at_positions,
    _slice_seq_at_position_matrix,
    _slice_seq_at_positions,
)

__all__ = [
    "PagedKVPool",
    "blocks_for",
    "table_buckets",
    "table_bucket_for",
    "paged_token_decode_step",
    "paged_chunk_forward",
    "paged_verify_forward",
    "gather_blocks",
    "scatter_blocks",
]


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` positions (ceil division)."""
    if tokens <= 0:
        return 0
    return -(-int(tokens) // int(block_size))


def table_buckets(max_blocks: int) -> tuple[int, ...]:
    """Power-of-two block-table length ladder ``[1, 2, 4, ..]`` capped
    at (and always including) ``max_blocks`` — the paged analogue of
    the prompt bucket ladder: programs compile once per bucket, so the
    compiled-shape set stays closed no matter the request mix."""
    if max_blocks <= 0:
        raise ValueError(
            f"max_blocks must be positive, got {max_blocks}"
        )
    buckets, b = [], 1
    while b < max_blocks:
        buckets.append(b)
        b *= 2
    buckets.append(int(max_blocks))
    return tuple(buckets)


def table_bucket_for(n_blocks: int, buckets) -> int:
    """Smallest table bucket holding ``n_blocks`` blocks."""
    for b in buckets:
        if b >= n_blocks:
            return int(b)
    raise ValueError(
        f"block table of {n_blocks} blocks exceeds the largest table "
        f"bucket {max(buckets)}"
    )


class PagedKVPool:
    """Specs + sharding rules for the paged block pool of one model.

    The paged sibling of :class:`~elephas_tpu.serving.kv_cache.\
SlotKVCache`: host-side metadata only, the arrays are functional state
    threaded through the engine's jitted steps. Buffers are
    ``[num_blocks, block_size, H, Dh]`` per layer; heads shard over the
    model axis when they tile (same rule as the slot arena), but the
    BLOCK axis is replicated — a block belongs to whichever slot the
    allocator leased it to, so there is no batch-axis layout that keeps
    a table gather shard-local the way the slot arena's slot==batch
    alignment did. Under a DP mesh this costs pool replication per
    replica and a cross-replica reduction per write (exact: one-hot
    partial sums are zero everywhere but the owning row); TP meshes
    pay nothing new."""

    def __init__(self, flash_layers, num_blocks: int, block_size: int,
                 mesh=None, batch_axes=("data",), model_axis=None,
                 kv_dtype: str = "fp"):
        from elephas_tpu.serving.kv_quant import check_kv_dtype

        self.specs = [
            (l.name, int(l.num_heads), int(l.head_dim))
            for l in flash_layers
        ]
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.mesh = mesh
        self.batch_axes = tuple(
            (batch_axes,) if isinstance(batch_axes, str) else batch_axes
        )
        self.model_axis = model_axis
        self.kv_dtype = check_kv_dtype(kv_dtype)

    def nbytes(self) -> int:
        """Host-side size of the full block pool at its STORED dtype
        — f32 values for ``kv_dtype="fp"``, int8/int4-packed codes
        plus per-(position, head) f32 scales when quantized. This is
        the per-device KV price an equal-bytes comparison divides
        by."""
        from elephas_tpu.serving.kv_quant import pool_bytes_per_pos

        return self.num_blocks * self.block_size * pool_bytes_per_pos(
            self.specs, self.kv_dtype
        )

    def constrain(self, z, heads: int):
        """``[num_blocks, block_size, H, Dh]`` buffers (and their
        3-D ``[num_blocks, block_size, H]`` scale planes): block axis
        replicated, heads over the model axis when they tile."""
        if self.mesh is None:
            return z
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        ax = (
            self.model_axis
            if self.model_axis is not None
            and self.mesh.shape.get(self.model_axis, 1) > 1
            and heads % self.mesh.shape[self.model_axis] == 0
            else None
        )
        spec = (
            P(None, None, ax, None) if z.ndim == 4 else P(None, None, ax)
        )
        return jax.lax.with_sharding_constraint(
            z, NamedSharding(self.mesh, spec)
        )

    def init(self) -> dict:
        """The zeroed pool: ``{layer_name: (k, v)}`` float32 for
        ``kv_dtype="fp"``; ``{layer_name: (kq, vq, k_scale, v_scale)}``
        when quantized — int8 ``[num_blocks, block_size, H, Dhp]``
        codes (``Dhp`` = packed head dim) beside f32 ``[num_blocks,
        block_size, H]`` scales. Zero codes with zero scales dequantize
        to exact zeros, so the sentinel-row convention is unchanged."""
        import jax.numpy as jnp

        from elephas_tpu.serving.kv_quant import packed_head_dim

        if self.kv_dtype == "fp":
            return {
                name: tuple(
                    self.constrain(
                        jnp.zeros(
                            (self.num_blocks, self.block_size, h, d),
                            jnp.float32,
                        ),
                        h,
                    )
                    for _ in range(2)
                )
                for name, h, d in self.specs
            }
        out = {}
        for name, h, d in self.specs:
            dp = packed_head_dim(d, self.kv_dtype)
            qz = lambda: self.constrain(
                jnp.zeros(
                    (self.num_blocks, self.block_size, h, dp), jnp.int8
                ),
                h,
            )
            sz = lambda: self.constrain(
                jnp.zeros(
                    (self.num_blocks, self.block_size, h), jnp.float32
                ),
                h,
            )
            out[name] = (qz(), qz(), sz(), sz())
        return out


def _exact_onehot_einsum(eq, sels, x, out_dtype):
    """One-hot contraction that stays EXACT for integer operands:
    int8 pool codes contract in int32 (each output element is a single
    nonzero term, so no overflow and no rounding) and cast back; float
    operands keep the existing f32 path bit-for-bit."""
    import jax.numpy as jnp

    if jnp.issubdtype(jnp.dtype(out_dtype), jnp.integer):
        ops = [s.astype(jnp.int32) for s in sels]
        ops.append(x.astype(jnp.int32))
        return jnp.einsum(eq, *ops).astype(out_dtype)
    ops = [s.astype(out_dtype) for s in sels]
    ops.append(x.astype(out_dtype))
    return jnp.einsum(eq, *ops)


def paged_token_decode_step(model, w, tok, positions, pool, tables,
                            block_size, maxlen, active, local=False,
                            attention="naive", kv_dtype="fp"):
    """One decode step over the whole slot population, paged: slot
    ``b`` consumes ``tok[b]`` at absolute position ``positions[b]``,
    writes that position's K/V into pool row ``(tables[b, p // bs],
    p % bs)``, and attends over its table's gathered blocks (positions
    ``<= positions[b]``).

    Same per-row math as the fixed arena's :func:`~elephas_tpu.serving.\
kv_cache.token_decode_step` — einsum strings and operation order kept
    identical so paged tokens match the fixed arena (and one-shot
    ``generate()``) exactly at temperature 0; only the storage indexing
    changes. ``tables`` is ``[num_slots, T]`` for a bucketed ``T``
    (compile per bucket); sentinel entries (``num_blocks``) match no
    pool row. ``active`` is REQUIRED here (unlike the fixed step):
    an inactive slot's stale cursor may map outside its table, and the
    sentinel only protects the table's padded tail, not a row another
    slot now owns.

    ``local=True`` (no mesh) swaps the one-hot contractions for native
    gather/scatter — bitwise the same rows land and load (a scatter
    writes the identical value the one-hot selected; garbage gathered
    through clipped sentinel ids only ever feeds visibility-masked
    lanes), but the gather work drops from O(B·T·num_blocks) to
    O(B·T) rows per step. Under a mesh the one-hots stay: dynamic
    gathers/scatters on sharded operands make GSPMD emit collectives
    inside the decode loop (the measured ~15x hazard the fixed arena
    also avoids).

    ``attention="flash"`` (ISSUE 11) runs the gathered table span
    through the tiled online-softmax kernel
    (:mod:`elephas_tpu.ops.flash_serving`) instead of materializing
    the ``[B, H, S]`` score row — float-tolerance parity, temp-0
    token-exact, same visible position set.

    ``kv_dtype`` ``"int8"``/``"int4"`` (ISSUE 19): the pool entry is a
    4-tuple ``(kq, vq, k_scale, v_scale)`` and this token's K/V rows
    QUANTIZE ON WRITE (:mod:`elephas_tpu.serving.kv_quant`) — codes
    and per-(position, head) scales land through the same one-hot /
    native-scatter machinery (integer contractions run in int32, so
    they stay exact), the table gather moves quantized bytes, and
    dequantization happens inside the flash tile loop (or over the
    full gathered span for the naive oracle). ``kv_dtype="fp"`` is
    bit-for-bit the historical program.

    Returns ``(logits [num_slots, vocab], new_pool)``."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops.flash_serving import flash_span_decode
    from elephas_tpu.serving.kv_quant import (
        dequantize_rows,
        quantize_rows,
    )

    bs = int(block_size)
    T = int(tables.shape[1])
    S = T * bs
    ctx_new = {}
    blk_idx = positions // bs
    off = positions % bs
    # the slot's CURRENT block id, via a one-hot over its table row
    # (tables is data — a dynamic gather would be per-row). Cursors
    # with blk_idx >= T (a finished slot still device-active for the
    # rest of a steps_per_sync window keeps advancing past its
    # reservation — and past the whole bucket when every live table is
    # small) match NO table column, and the where/sum would resolve to
    # 0 — a REAL block id, owned by whichever request leased block 0.
    # Route them to the sentinel explicitly; in-bucket overrun lands on
    # the table's sentinel padding by construction.
    t_onehot = blk_idx[:, None] == jnp.arange(T)[None, :]
    blk = jnp.sum(jnp.where(t_onehot, tables, 0), axis=1)  # [B]
    N_sentinel = next(iter(pool.values()))[0].shape[0]
    blk = jnp.where(blk_idx < T, blk, N_sentinel)

    quant = kv_dtype != "fp"

    def attn_for(op):
        def attn(x, *_a, **_k):
            entry = pool[op.name]
            if quant:
                pk, pv, sk, sv = entry
            else:
                pk, pv = entry
                sk = sv = None
            N = int(pk.shape[0])
            H, Dh = op.num_heads, op.head_dim
            Dhs = int(pk.shape[-1])  # stored width (packed for int4)
            B = x.shape[0]
            qkv = x @ w[op.qkv.kernel.path]  # [B, 3·H·Dh]
            q, k, v = jnp.split(
                qkv.reshape(B, 3, H, Dh), 3, axis=1
            )
            q, k, v = q[:, 0], k[:, 0], v[:, 0]  # [B, H, Dh]
            if getattr(op, "rope", False):
                cos_np, sin_np = _rope_tables(maxlen, Dh)
                cos_t = _rows_at_positions(
                    jnp.asarray(cos_np), positions
                )[:, None, :]
                sin_t = _rows_at_positions(
                    jnp.asarray(sin_np), positions
                )[:, None, :]
                q = _apply_rope(q, cos_t, sin_t)
                k = _apply_rope(k, cos_t, sin_t)
            if quant:
                # quantize-on-write: the row's codes + scales are what
                # lands; fp k/v die with this trace
                k, ks = quantize_rows(k, kv_dtype)
                v, vs = quantize_rows(v, kv_dtype)
            gks = gvs = None
            if local:
                # unmeshed fast path: scatter this token's row at
                # (blk, off) — inactive/overrun cursors route to the
                # sentinel index and DROP — then gather the table's
                # rows natively (sentinel ids clip; they only feed
                # masked lanes)
                blk_safe = jnp.where(active, blk, N)
                pk = pk.at[blk_safe, off].set(
                    k.astype(pk.dtype), mode="drop"
                )
                pv = pv.at[blk_safe, off].set(
                    v.astype(pv.dtype), mode="drop"
                )
                gk = jnp.take(pk, tables, axis=0, mode="clip")
                gk = gk.reshape(B, S, H, Dhs)
                gv = jnp.take(pv, tables, axis=0, mode="clip")
                gv = gv.reshape(B, S, H, Dhs)
                if quant:
                    sk = sk.at[blk_safe, off].set(ks, mode="drop")
                    sv = sv.at[blk_safe, off].set(vs, mode="drop")
                    gks = jnp.take(
                        sk, tables, axis=0, mode="clip"
                    ).reshape(B, S, H)
                    gvs = jnp.take(
                        sv, tables, axis=0, mode="clip"
                    ).reshape(B, S, H)
            else:
                # write: one token per active slot lands at (blk, off)
                # — factored one-hot contraction over (block, offset);
                # the sentinel id N matches nothing, so a padded/
                # overrun cursor writes nowhere
                wsel = (blk[:, None] == jnp.arange(N)[None, :]) \
                    & active[:, None]  # [B, N]
                osel = off[:, None] == jnp.arange(bs)[None, :]  # [B,bs]
                new_k = _exact_onehot_einsum(
                    "bn,bo,bhd->nohd", (wsel, osel), k, pk.dtype
                )
                new_v = _exact_onehot_einsum(
                    "bn,bo,bhd->nohd", (wsel, osel), v, pv.dtype
                )
                covered = (
                    jnp.einsum(
                        "bn,bo->no",
                        wsel.astype(jnp.int32), osel.astype(jnp.int32),
                    ) > 0
                )[:, :, None, None]
                pk = jnp.where(covered, new_k, pk)
                pv = jnp.where(covered, new_v, pv)
                # gather each slot's blocks into its dense [S, H, Dh]
                # view (sentinel table entries contribute exact zero
                # rows, all masked off by visibility)
                gsel = (
                    tables[:, :, None] == jnp.arange(N)[None, None, :]
                )  # [B, T, N]
                gk = _exact_onehot_einsum(
                    "btn,nohd->btohd", (gsel,), pk, pk.dtype
                ).reshape(B, S, H, Dhs)
                gv = _exact_onehot_einsum(
                    "btn,nohd->btohd", (gsel,), pv, pv.dtype
                ).reshape(B, S, H, Dhs)
                if quant:
                    new_ks = jnp.einsum(
                        "bn,bo,bh->noh",
                        wsel.astype(sk.dtype), osel.astype(sk.dtype),
                        ks,
                    )
                    new_vs = jnp.einsum(
                        "bn,bo,bh->noh",
                        wsel.astype(sv.dtype), osel.astype(sv.dtype),
                        vs,
                    )
                    sk = jnp.where(covered[..., 0], new_ks, sk)
                    sv = jnp.where(covered[..., 0], new_vs, sv)
                    gks = jnp.einsum(
                        "btn,noh->btoh", gsel.astype(sk.dtype), sk
                    ).reshape(B, S, H)
                    gvs = jnp.einsum(
                        "btn,noh->btoh", gsel.astype(sv.dtype), sv
                    ).reshape(B, S, H)
            if attention == "flash":
                o = flash_span_decode(
                    q, gk, gv, positions, scale=Dh**-0.5,
                    kv_dtype=kv_dtype,
                    kv_scales=(gks, gvs) if quant else None,
                ).reshape(B, H * Dh)
            else:
                if quant:
                    # naive oracle: dequantize the gathered span once
                    # (it materializes [B, H, S] scores anyway)
                    gk = dequantize_rows(gk, gks, kv_dtype, Dh)
                    gv = dequantize_rows(gv, gvs, kv_dtype, Dh)
                # flash-lint: allow — the selectable naive oracle
                att = jnp.einsum("bhd,bshd->bhs", q, gk) * (Dh**-0.5)
                visible = (
                    jnp.arange(S)[None, None, :]
                    <= positions[:, None, None]
                )
                att = jax.nn.softmax(
                    jnp.where(visible, att, -jnp.inf), axis=-1
                )
                # flash-lint: allow — naive oracle att@V
                o = jnp.einsum("bhs,bshd->bhd", att, gv).reshape(
                    B, H * Dh
                )
            ctx_new[op.name] = (
                (pk, pv, sk, sv) if quant else (pk, pv)
            )
            return (
                o @ w[op.proj.kernel.path] + w[op.proj.bias.path]
            )

        return attn

    logits = _graph_replay(
        model, w, tok, attn_for,
        lambda a: _slice_seq_at_positions(a, positions, maxlen),
    )
    return logits, {
        name: ctx_new.get(name, pool[name]) for name in pool
    }


def paged_chunk_forward(model, w, tokens_chunk, pool, tables, offsets,
                        chunk_lens, active, block_size, maxlen,
                        local=False, attention="naive",
                        kv_dtype="fp"):
    """Prefill a bounded chunk of each active slot's prompt into its
    block-table rows — the ONLY prefill program paged mode needs: a
    cold prompt is one full-width chunk from offset 0 (or several under
    ``prefill_chunk``), a prefix hit starts at its shared-block
    boundary, so there is no separate whole-bucket prefill and no copy
    program at all.

    The paged analogue of :func:`~elephas_tpu.serving.kv_cache.\
chunked_prefill_forward`: this chunk's K/V rows land in the pool FIRST
    (one-hot over (block, offset) via the table), then queries attend
    over the gathered table span — shared prefix blocks, earlier
    chunks, and the chunk's own causal part. Compiled per (chunk width
    ``C``, table bucket ``T``) pair — both from closed ladders.
    ``local``/``attention``/``kv_dtype`` as in
    :func:`paged_token_decode_step` — quantized pools land this
    chunk's codes + scales through the same write machinery and
    dequantize inside the flash tiles (or over the gathered span for
    the naive oracle).

    Returns ``(logits [num_slots, C, vocab], new_pool)``."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops.flash_serving import flash_span_chunk
    from elephas_tpu.serving.kv_quant import (
        dequantize_rows,
        quantize_rows,
    )

    bs = int(block_size)
    C = int(tokens_chunk.shape[1])
    T = int(tables.shape[1])
    S = T * bs
    ctx_new = {}
    pos_mat = offsets[:, None] + jnp.arange(C)[None, :]  # [B, C]
    valid = (
        active[:, None] & (jnp.arange(C)[None, :] < chunk_lens[:, None])
    )  # [B, C]
    blk_idx_mat = pos_mat // bs
    off_mat = pos_mat % bs
    t_onehot = (
        blk_idx_mat[:, :, None] == jnp.arange(T)[None, None, :]
    )  # [B, C, T]
    blk_mat = jnp.sum(
        jnp.where(t_onehot, tables[:, None, :], 0), axis=2
    )  # [B, C]

    quant = kv_dtype != "fp"

    def attn_for(op):
        def attn(x, *_a, **_k):
            entry = pool[op.name]
            if quant:
                pk, pv, sk, sv = entry
            else:
                pk, pv = entry
                sk = sv = None
            N = int(pk.shape[0])
            H, Dh = op.num_heads, op.head_dim
            Dhs = int(pk.shape[-1])  # stored width (packed for int4)
            B = x.shape[0]
            qkv = jnp.reshape(
                x @ w[op.qkv.kernel.path], (B, C, 3, H, Dh)
            )
            qkv = jnp.transpose(qkv, (2, 0, 3, 1, 4))  # [3,B,H,C,Dh]
            q, k, v = qkv[0], qkv[1], qkv[2]
            if getattr(op, "rope", False):
                cos_np, sin_np = _rope_tables(maxlen, Dh)
                cos = _rows_at_position_matrix(
                    jnp.asarray(cos_np), pos_mat
                )[:, None]  # [B, 1, C, Dh]
                sin = _rows_at_position_matrix(
                    jnp.asarray(sin_np), pos_mat
                )[:, None]
                q = _apply_rope(q, cos, sin)
                k = _apply_rope(k, cos, sin)
            k_rows = jnp.transpose(k, (0, 2, 1, 3))  # [B, C, H, Dh]
            v_rows = jnp.transpose(v, (0, 2, 1, 3))
            if quant:
                # quantize-on-write: codes + per-(pos, head) scales
                # are what lands; fp rows die with this trace
                k_rows, ks_rows = quantize_rows(k_rows, kv_dtype)
                v_rows, vs_rows = quantize_rows(v_rows, kv_dtype)
            gks = gvs = None
            if local:
                # unmeshed fast path: scatter the chunk's rows at
                # (blk, off) — padded/inactive lanes route to the
                # sentinel index and DROP — then gather natively
                blk_safe = jnp.where(valid, blk_mat, N)
                pk = pk.at[blk_safe, off_mat].set(
                    k_rows.astype(pk.dtype), mode="drop"
                )
                pv = pv.at[blk_safe, off_mat].set(
                    v_rows.astype(pv.dtype), mode="drop"
                )
                gk = jnp.take(pk, tables, axis=0, mode="clip")
                gk = gk.reshape(B, S, H, Dhs)
                gv = jnp.take(pv, tables, axis=0, mode="clip")
                gv = gv.reshape(B, S, H, Dhs)
                if quant:
                    sk = sk.at[blk_safe, off_mat].set(
                        ks_rows, mode="drop"
                    )
                    sv = sv.at[blk_safe, off_mat].set(
                        vs_rows, mode="drop"
                    )
                    gks = jnp.take(
                        sk, tables, axis=0, mode="clip"
                    ).reshape(B, S, H)
                    gvs = jnp.take(
                        sv, tables, axis=0, mode="clip"
                    ).reshape(B, S, H)
            else:
                # land the chunk's rows first: factored one-hot over
                # (block, offset); `valid` rides the block select so a
                # padded chunk tail (blk_mat resolved to 0) writes
                # nowhere
                nsel = (
                    blk_mat[:, :, None] == jnp.arange(N)[None, None, :]
                ) & valid[:, :, None]  # [B, C, N]
                osel = (
                    off_mat[:, :, None]
                    == jnp.arange(bs)[None, None, :]
                )  # [B, C, bs]
                scat_k = _exact_onehot_einsum(
                    "bcn,bco,bchd->nohd", (nsel, osel), k_rows,
                    pk.dtype,
                )
                scat_v = _exact_onehot_einsum(
                    "bcn,bco,bchd->nohd", (nsel, osel), v_rows,
                    pv.dtype,
                )
                covered = (
                    jnp.einsum(
                        "bcn,bco->no",
                        nsel.astype(jnp.int32),
                        osel.astype(jnp.int32),
                    ) > 0
                )[:, :, None, None]
                pk = jnp.where(covered, scat_k, pk)
                pv = jnp.where(covered, scat_v, pv)
                gsel = (
                    tables[:, :, None] == jnp.arange(N)[None, None, :]
                )  # [B, T, N]
                gk = _exact_onehot_einsum(
                    "btn,nohd->btohd", (gsel,), pk, pk.dtype
                ).reshape(B, S, H, Dhs)
                gv = _exact_onehot_einsum(
                    "btn,nohd->btohd", (gsel,), pv, pv.dtype
                ).reshape(B, S, H, Dhs)
                if quant:
                    scat_ks = jnp.einsum(
                        "bcn,bco,bch->noh",
                        nsel.astype(sk.dtype), osel.astype(sk.dtype),
                        ks_rows,
                    )
                    scat_vs = jnp.einsum(
                        "bcn,bco,bch->noh",
                        nsel.astype(sv.dtype), osel.astype(sv.dtype),
                        vs_rows,
                    )
                    sk = jnp.where(covered[..., 0], scat_ks, sk)
                    sv = jnp.where(covered[..., 0], scat_vs, sv)
                    gks = jnp.einsum(
                        "btn,noh->btoh", gsel.astype(sk.dtype), sk
                    ).reshape(B, S, H)
                    gvs = jnp.einsum(
                        "btn,noh->btoh", gsel.astype(sv.dtype), sv
                    ).reshape(B, S, H)
            if attention == "flash":
                o = flash_span_chunk(
                    q, gk, gv, pos_mat, scale=Dh**-0.5,
                    kv_dtype=kv_dtype,
                    kv_scales=(gks, gvs) if quant else None,
                )
            else:
                if quant:
                    # naive oracle: dequantize the gathered span once
                    gk = dequantize_rows(gk, gks, kv_dtype, Dh)
                    gv = dequantize_rows(gv, gvs, kv_dtype, Dh)
                # flash-lint: allow — the selectable naive oracle
                att = jnp.einsum(
                    "bhcd,bshd->bhcs", q, gk
                ) * (Dh**-0.5)
                visible = (
                    jnp.arange(S)[None, None, None, :]
                    <= pos_mat[:, None, :, None]
                )
                att = jax.nn.softmax(
                    jnp.where(visible, att, -jnp.inf), axis=-1
                )
                # flash-lint: allow — naive oracle att@V
                o = jnp.einsum("bhcs,bshd->bhcd", att, gv)
            o = jnp.reshape(
                jnp.transpose(o, (0, 2, 1, 3)), (B, C, H * Dh)
            )
            ctx_new[op.name] = (
                (pk, pv, sk, sv) if quant else (pk, pv)
            )
            return (
                o @ w[op.proj.kernel.path] + w[op.proj.bias.path]
            )

        return attn

    logits = _graph_replay(
        model, w, tokens_chunk, attn_for,
        lambda a: _slice_seq_at_position_matrix(a, pos_mat, maxlen),
    )
    return logits, {
        name: ctx_new.get(name, pool[name]) for name in pool
    }


def paged_verify_forward(model, w, tokens_window, pool, tables,
                         offsets, n_fed, active, block_size, maxlen,
                         local=False, attention="naive",
                         kv_dtype="fp"):
    """Batched K-token speculative verify over the PAGED arena (ISSUE
    8) — the block-table analogue of :func:`~elephas_tpu.serving.\
kv_cache.verify_forward`: slot ``b`` feeds its last sampled token plus
    drafted guesses at positions ``offsets[b] ..``, K/V lands in the
    slot's table blocks, and a logits row comes back per window
    position for the engine's accept-longest-matching-prefix rule.

    Delegates to :func:`paged_chunk_forward` (generated tokens instead
    of prompt tokens; same writes-land-first causal attention), so
    there is exactly one verify program per (window width ``K``,
    table bucket) pair — both from closed ladders. Rollback is free:
    a rejected tail's garbage rows live INSIDE blocks the request
    already reserved up front (``ceil((prompt + max_new) / bs)``),
    so rolling the cursor back never touches the allocator, and the
    rows are rewritten before any query can see them."""
    return paged_chunk_forward(
        model, w, tokens_window, pool, tables, offsets, n_fed, active,
        block_size, maxlen, local=local, attention=attention,
        kv_dtype=kv_dtype,
    )


def gather_blocks(pool, ids):
    """Read pool blocks ``ids`` (``[T]`` int32, sentinel-padded) into
    dense per-layer rows of shape ``[T, block_size, ...]`` — the
    device half of preemption offload: the caller ``device_get``s the
    result and frees the blocks. One-hot over the block axis (exact,
    mesh-safe — integer pool leaves contract in int32); sentinel rows
    read zeros and are sliced off on the host. LEAF-GENERIC over the
    pool's tuple arity: fp entries stay ``(k, v)``, quantized entries
    move all four of ``(kq, vq, k_scale, v_scale)`` — offloaded
    blocks stay quantized, values and scales travel together. The
    pool is NOT consumed."""
    import jax.numpy as jnp

    out = {}
    for name, leaves in pool.items():
        N = int(leaves[0].shape[0])
        sel = ids[:, None] == jnp.arange(N)[None, :]  # [T, N]
        out[name] = tuple(
            _exact_onehot_einsum("tn,n...->t...", (sel,), z, z.dtype)
            for z in leaves
        )
    return out


def scatter_blocks(pool, ids, rows):
    """Write dense rows back into pool blocks ``ids`` — the resume
    half of preempt/offload: restored rows are bitwise the offloaded
    ones (quantized codes and scales included — bit-exact WITHIN a
    kv_dtype), so the resumed request's attention sees exactly the
    K/V it had. Sentinel ids write nowhere. Leaf-generic like
    :func:`gather_blocks`. Returns the new pool."""
    import jax.numpy as jnp

    out = {}
    for name, leaves in pool.items():
        rleaves = rows[name]
        N = int(leaves[0].shape[0])
        sel = ids[:, None] == jnp.arange(N)[None, :]  # [T, N]
        covered = jnp.any(sel, axis=0)  # [N]
        merged = []
        for z, r in zip(leaves, rleaves):
            new_z = _exact_onehot_einsum(
                "tn,t...->n...", (sel,), r.astype(z.dtype), z.dtype
            )
            cov = covered.reshape((N,) + (1,) * (z.ndim - 1))
            merged.append(jnp.where(cov, new_z, z))
        out[name] = tuple(merged)
    return out
