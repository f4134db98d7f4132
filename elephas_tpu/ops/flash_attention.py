"""Flash attention — blockwise online-softmax attention for TPU.

Forward is a Pallas kernel (see /opt/skills/guides/pallas_guide.md):
q/k/v blocks stream HBM→VMEM, scores hit the MXU tile-by-tile, and the
softmax runs online (running max ``m``, normalizer ``l``, accumulator
``acc`` live in VMEM scratch across the KV grid axis) — attention never
materializes the ``[S, S]`` score matrix in HBM, so memory is O(S·D)
instead of O(S²).

Backward uses the standard flash recurrences (dV = Pᵀ dO, dS = P∘(dP − Δ),
…) over O(S·D) residuals (just q/k/v/out/LSE). In the ``[BH, S, D]``
layout the forward kernel's two results carry the names ``OUT_NAME``
and ``LSE_NAME``, so that a caller whose own ``jax.checkpoint``
recomputes the whole op can keep them by policy and leave the forward
kernel out of its backward pass (q, k and v it computes again; the
attention layers of ``models.qwen3_next``, ``models.deepseek_v3`` and
``models.smallthinker`` do); to every other caller the names are
nothing. In that layout the recurrences are two Pallas kernels in the
forward's style (dK/dV, then dQ): score blocks stay in VMEM, operands reach the MXU in the inputs'
dtype, causally empty block pairs are skipped (their copies too, in
all three kernels: the index maps stay on the last visible block), and a
key/value head that several query heads share is read in place. A
``window`` narrows the causal mask to a band (a query sees itself and
the ``window - 1`` keys before it), and the grid to the band: the inner
axis of each kernel's grid is as long as the most blocks a band holds
and counts from the first of them (``band_grid``), so the pairs below
the band are not stepped over at all. Where a
caller names no block each kernel takes the largest measured blocks that
divide the sequences and fit VMEM, a query block no longer than the band
first (``_BLOCK_TABLE``, ``_resolve_blocks``). The packed qkv
layout still evaluates them blockwise under ``lax.scan``, XLA-fused
(:func:`_flash_backward`, also the tests' oracle for the kernels). The
whole op carries a ``jax.custom_vjp`` so it drops into any ``jax.grad``
training step.

On the ``cpu`` backend, and only there, the same kernel runs in Pallas
interpreter mode (tests), keeping one code path; on every other backend
it is compiled and a compiler refusal is an error
(:func:`elephas_tpu.utils.backend_guard.pallas_interpret`).

Reference parity note: the reference has no attention op of its own (its
models call Keras layers); this op backs the transformer model family and
the sequence-parallel path (ring_attention), which SURVEY.md §5 lists as
absent upstream — a TPU-native extension, not a port.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elephas_tpu import telemetry
from elephas_tpu.utils import backend_guard

NEG_INF = -1e30
# the names (``jax.ad_checkpoint.checkpoint_name``) of the ``[BH, S, D]``
# forward kernel's result and of its rows' log-sum-exp, for a caller
# whose ``jax.checkpoint`` policy keeps them instead of running the
# forward kernel again in the backward pass
OUT_NAME = "flash_out"
LSE_NAME = "flash_lse"


# -- forward kernel ----------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                window: int | None = None, nk: int | None = None):
    # refs arrive squeezed to [BQ, D] / [BK, D] / [BQ, D] / [1, BQ]
    # (BlockSpec ``None`` dims), so one kernel serves both the separate
    # [BH, S, D] layout and the packed [B, S, 3, H, D] qkv layout
    step = pl.program_id(2)
    last_step = pl.num_programs(2) - 1

    @pl.when(step == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # read outside the conditional body below: the interpreter has no
    # program_id inside one
    i = pl.program_id(1)
    j = step
    if window is not None:
        # the grid holds a band's key blocks alone (of ``nk``): a query
        # block counts them from the first it sees
        j += _band_ends("fwd", i, block_q, block_k, window, nk)[0]

    def _accumulate():
        q = q_ref[:]  # [BQ, D]
        k = k_ref[:]  # [BK, D]
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK]

        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(_seen(rows, cols, window), s, NEG_INF)

        m_prev = m_ref[:]  # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)  # [BQ, BK]
        # fully-masked-so-far rows: m_new is still NEG_INF and s - m_new
        # == 0 would make p == 1, accumulating phantom mass (the row
        # would output mean(V) instead of zeros). Zero p so l stays 0
        # for those rows.
        p = jnp.where(m_new <= NEG_INF * 0.5, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)  # [BQ, 1]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # a float32 product at default precision reaches the MXU in one
        # bfloat16 pass: casting p to v's dtype instead gave the same
        # output and the same time on the chip (PR 36's micro record)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v_ref[:].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    if causal:
        # a key block wholly ahead of the query block, or wholly behind
        # its window, adds nothing (its scores are all masked, so the
        # branch above leaves the accumulators as they are): skip its
        # two products
        seen = _pair_seen(i, j, block_q, block_k, window)
        if window is not None:
            # past the last block there is the index map stays on it
            seen &= j < nk
        pl.when(seen)(_accumulate)
    else:
        _accumulate()

    @pl.when(step == last_step)
    def _finalize():
        l = l_ref[:]
        # fully-masked rows kept l == 0 via the p guard above; they output
        # zeros with lse == NEG_INF (zero weight in ring-attention merges)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, :] = (m_ref[:] + jnp.log(safe_l))[:, 0]


# -- the grid: blocks from the shapes, maps that skip with the mask -------

# Where a caller names no block a kernel takes the first pair (block_q,
# block_k) of its row that divides the sequences and fits VMEM. The
# order is what a v5e chip gave at 8192 causal positions for 192- and
# 256-wide bfloat16 heads (benchmarks/results/flash-forward-micro-PR36
# .json): a grid step costs about a microsecond whatever it multiplies,
# so the largest blocks won in all three kernels (forward 54 ms at
# blocks of 256, 26 at 512, 16 at 1024), and a long key block more than
# a long query block in the forward kernel. Under a window the grid
# holds the band's pairs alone and the row's order is read again from
# the band (_resolve_blocks; benchmarks/results/flash-band-micro-PR45
# .json): a step that computes costs 0.5 (128-blocks) to 6 (1024-blocks)
# microseconds there, and few are empty.
_BLOCK_TABLE = {
    "fwd": ((1024, 1024), (512, 1024), (512, 512), (256, 256), (128, 128)),
    "bwd": ((1024, 1024), (512, 512), (256, 256), (128, 128)),
    # no cell runs these two, so they keep the block they had: the
    # lane-grouped forward kernel, and the packed layout's backward
    # pass, whose float32 blocks XLA holds in HBM for every head at once
    "fwd_grouped": ((128, 128),),
    "blockwise": ((128, 128),),
}
# under a band the rule prefers no query block shorter than this: the
# shortest the chip measured ahead of the row's order, on the grid over
# every pair (PR 44) and on the grid of the band alone (PR 45)
_SHORTEST_BAND_BLOCK = 512
# the scoped VMEM each call asks for; the rule fills half of it and
# leaves the rest to what it does not count (masks, the exponent's
# temporaries, Mosaic's own scratch)
_VMEM_LIMIT = 32 * 2**20
_MOSAIC = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _vmem_bytes(block_q, block_k, d, dv, itemsize):
    """One grid step's working set, for any of the three kernels: the
    operands and results of both blocks at both widths double-buffered,
    a float32 accumulator of the larger block (with the forward's
    lane-padded ``m`` and ``l``), and the float32 score and probability
    blocks. Widths count as VMEM holds them, in whole 128-lane tiles."""
    wide = -(-d // 128) * 128 + -(-dv // 128) * 128
    return (2 * itemsize * (block_q + block_k) * wide
            + 4 * max(block_q, block_k) * (wide + 2 * 128)
            + 2 * 4 * block_q * block_k)


def _resolve_blocks(block_q, block_k, s_q, s_k, d, dv, itemsize, kernel,
                    window=None):
    """The kernel's blocks. A block the caller names rules; one it does
    not comes from the first pair of the kernel's row that divides the
    sequences and fits VMEM, and a sequence that no such block divides
    is one block if that fits. Under a ``window`` the row's pairs whose
    query block reaches past the band come after those whose does not,
    the shortest overreach first, and a band shorter than 512 keys
    counts as 512: no band puts a query block under 512 ahead of the
    row's order. Measured on the chip on the grid of the band alone
    (72 heads over 8 at 8192 positions under a 512-key band,
    ``benchmarks/results/flash-band-micro-PR45.json``): a query block
    of the band's 512 wins in each kernel (forward ``(512, 1024)``
    11.3 ms against 12.8 at 1024-blocks and 13.1 at 512-blocks; dK/dV
    and dQ at 512-blocks 9.6 and 8.4 against 14.7 and 13.1 at
    1024-blocks), and small blocks still lose with the empty steps
    gone, by their count of computing steps (256-blocks 18.1, 13.0 and
    10.8 ms, 128-blocks 30.5, 23.6 and 22.5). A band of 1024 keys or
    more leaves the row in its order (4096 keys at 16384 positions:
    1024-blocks 34.7 ms for the three kernels, every pair with a 512 in
    it 36.6 to 41.9); no band under 512 was measured. On the grid over
    every pair of blocks, which a band had until PR 45, a skipped step
    cost 0.10 to 0.17 microseconds (``flash-band-micro-PR44.json``:
    256-blocks 98 ms for the three kernels, 128-blocks 296)."""
    named = bool(block_q and block_k)
    row = _BLOCK_TABLE[kernel]
    if window is not None:
        band = max(window, _SHORTEST_BAND_BLOCK)
        row = tuple(sorted(row, key=lambda pair: max(pair[0] - band, 0)))
    for bq, bk in row + ((s_q, s_k),):
        bq, bk = min(block_q or bq, s_q), min(block_k or bk, s_k)
        if s_q % bq == 0 and s_k % bk == 0 and (
                named
                or _vmem_bytes(bq, bk, d, dv, itemsize) <= _VMEM_LIMIT // 2):
            return bq, bk
    raise ValueError(
        f"sequence lengths ({s_q}, {s_k}) must be multiples of the "
        f"block sizes ({block_q}, {block_k}), and blocks the op chooses "
        f"must fit VMEM: pad the sequences to a multiple of 128"
    )


def _seen(rows, cols, window):
    """The causal mask over positions ``rows`` (queries) and ``cols``
    (keys), narrowed to the band of ``window`` keys where one is set."""
    seen = cols <= rows
    if window is not None:
        seen &= rows - cols < window
    return seen


def _pair_seen(i, j, block_q, block_k, window):
    """Whether the causal mask (and the band) leaves anything of the
    block pair ``(i, j)``."""
    seen = j * block_k < (i + 1) * block_q
    if window is not None:
        seen &= (j + 1) * block_k + window - 1 > i * block_q
    return seen


def _visible_maps(causal, block_q, block_k, nq):
    """``(first_i, last_j)``: for a step ``(i, j)`` of the grid over
    every pair of blocks (no ``window``) the nearest query block that
    sees key block ``j`` and the nearest key block that query block
    ``i`` sees. An index map that goes through them stays where it is
    over the steps the causal mask empties, and a block that does not
    change is not fetched again: a skipped pair's copies are skipped
    too."""
    if not causal:
        return (lambda i, j: i), (lambda i, j: j)

    def first_i(i, j):
        return jnp.minimum(jnp.maximum(i, j * block_k // block_q), nq - 1)

    def last_j(i, j):
        return jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)

    return first_i, last_j


def _band_ends(kernel, outer, block_q, block_k, window, n_inner):
    """``(first, last)``: the inner blocks of a kernel's grid that the
    band of ``window`` keys leaves something of under its ``outer``
    block, every block between them too: the key blocks a query block
    sees (``"fwd"``, ``"dq"``), the query blocks that see a key block
    (``"dkv"``), of ``n_inner``; ``last < first`` where there is none.
    Python integers give integers (the grid's extent), traced ones the
    index maps' and the kernels' arithmetic."""
    least, most = ((max, min) if isinstance(outer, int)
                   else (jnp.maximum, jnp.minimum))
    if kernel == "dkv":
        first = outer * block_k // block_q
        # the last query that sees the block's last key
        last = ((outer + 1) * block_k + window - 2) // block_q
    else:
        # the first key that the block's first query sees, if any
        first = least(outer * block_q - window + 1, 0) // block_k
        last = ((outer + 1) * block_q - 1) // block_k
    return first, most(last, n_inner - 1)


def _grid_blocks(kernel, s_q, s_k, block_q, block_k):
    """``(n_outer, n_inner)``: the blocks a kernel's grid walks, the
    key blocks outermost in dK/dV and the query blocks in the others."""
    nq, nk = s_q // block_q, s_k // block_k
    return (nk, nq) if kernel == "dkv" else (nq, nk)


def band_grid(kernel, s_q, s_k, block_q, block_k, window):
    """``(steps, computing)`` of one head and sequence under the causal
    mask: the steps of ``kernel``'s grid (``"fwd"``, ``"dkv"``,
    ``"dq"``) and those among them whose block pair holds something.
    With no ``window`` the grid steps over every pair of blocks. With
    one its inner axis is as long as the most blocks the band holds
    under any outer block, and a step counts from the first of them
    (``_band_ends``): the three ``pallas_call`` s take their inner
    extent from here, ``steps`` over the outer blocks."""
    n_outer, n_inner = _grid_blocks(kernel, s_q, s_k, block_q, block_k)
    # no window counts as one that reaches every key
    reach = s_q + s_k if window is None else window
    held = [
        max(last - first + 1, 0) for first, last in (
            _band_ends(kernel, outer, block_q, block_k, reach, n_inner)
            for outer in range(n_outer))]
    span = n_inner if window is None else max(held)
    return n_outer * max(span, 1), sum(held)


def _band_maps(kernel, s_q, s_k, block_q, block_k, window):
    """``(span, inner)`` of a windowed kernel's grid: its inner extent
    and the index map ``inner(outer, step)`` onto the blocks the band
    holds, which stays on the last of them over the steps a shorter
    row leaves (nothing is fetched for a step that computes nothing).
    Emits the ``flash.grid`` event, once a ``pallas_call`` that is
    traced."""
    n_outer, n_inner = _grid_blocks(kernel, s_q, s_k, block_q, block_k)
    steps, computing = band_grid(kernel, s_q, s_k, block_q, block_k, window)
    telemetry.emit(
        "flash.grid", kernel=kernel, window=window, block_q=block_q,
        block_k=block_k, steps=steps, computing=computing)

    def inner(outer, step):
        first, last = _band_ends(
            kernel, outer, block_q, block_k, window, n_inner)
        return jnp.minimum(first + step, last)

    return steps // n_outer, inner


def _cost(bh, s_q, s_k, d, itemsize, dv=None):
    """Forward cost: scores over the ``d``-wide queries and keys, the
    sum over the ``dv``-wide values (``d`` where not given)."""
    dv = d if dv is None else dv
    # keras symbolic builds trace with a polymorphic batch dim
    # (_DimExpr); CostEstimate requires concrete ints
    if not all(type(t) is int for t in (bh, s_q, s_k, d, dv)):
        return None
    return pl.CostEstimate(
        flops=2 * bh * s_q * s_k * (d + dv),
        bytes_accessed=bh * (s_q + s_k) * (d + dv) * itemsize,
        transcendentals=bh * s_q * s_k,
    )


def _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret,
                   window=None):
    """[BH, S, D] inputs → (out [BH, S, Dv], lse [BH, S]); ``k`` and
    ``v`` may have a whole fraction of ``q``'s heads (``[BH / G, S, D]``),
    and ``v`` a width of its own (``[BH / G, S, Dv]``)."""
    bh, s_q, d = q.shape
    s_k, dv = k.shape[1], v.shape[-1]
    # grouped-query attention: ``group`` consecutive query heads read
    # one key/value head, through the index map (no repeated copy)
    group = bh // k.shape[0]
    block_q, block_k = _resolve_blocks(
        block_q, block_k, s_q, s_k, d, dv, q.dtype.itemsize, "fwd", window)
    nq, nk = s_q // block_q, s_k // block_k
    if window is None:
        span = nk
        _, last_j = _visible_maps(causal, block_q, block_k, nq)
    else:
        span, last_j = _band_maps("fwd", s_q, s_k, block_q, block_k, window)
    grid = (bh, nq, span)
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        window=window,
        nk=nk,
    )
    kv_side = lambda b, i, j: (b // group, last_j(i, j), 0)  # noqa: E731
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), kv_side),
            pl.BlockSpec((None, block_k, dv), kv_side),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, dv), lambda b, i, j: (b, i, 0)),
            # lse rides as [BH, 1, S] so the trailing block dims (1, block_q)
            # meet Mosaic's (equal-dim, 128-divisible) tiling rule
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        cost_estimate=_cost(bh, s_q, s_k, d, q.dtype.itemsize, dv),
        compiler_params=_MOSAIC,
        interpret=interpret,
    )(q, k, v)
    return out, lse[:, 0, :]


def _fwd_kernel_grouped(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                        m_ref, l_ref, *, scale: float, causal: bool,
                        block_q: int, block_k: int, hp: int, d: int):
    """Online-softmax forward over a GROUP of ``hp`` lane-packed heads.

    Blocks arrive ``[BQ, hp·d]`` — ``hp`` heads side by side filling a
    128-lane tile (r5, VERDICT r4 #3c: head_dim-64 models previously
    fell back to the transposed layout and paid its copy kernels).
    Heads stay separate WITHOUT lane reshapes (Mosaic rejects the
    vector shape cast): each head's score dot runs over all ``hp·d``
    lanes with the OTHER heads' k lanes zeroed — mathematically the
    head's own ``d``-deep contraction, and the same MXU occupancy the
    transposed fallback gets from a ``d``-deep dot. Per-head softmax
    state lives in ``[hp, BQ, 1]`` scratch; the accumulator stays in
    the packed ``[BQ, hp·d]`` layout with per-head rescaling applied
    through lane masks."""
    j = pl.program_id(2)
    last_j = pl.num_programs(2) - 1
    w = hp * d

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[:]  # [BQ, hp·d]
    k = k_ref[:]  # [BK, hp·d]
    v = v_ref[:]
    lanes_k = jax.lax.broadcasted_iota(jnp.int32, (block_k, w), 1)
    lanes_q = jax.lax.broadcasted_iota(jnp.int32, (block_q, w), 1)
    if causal:
        i = pl.program_id(1)
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        visible = cols <= rows

    for t in range(hp):
        sel_k = (lanes_k >= t * d) & (lanes_k < (t + 1) * d)
        sel_q = (lanes_q >= t * d) & (lanes_q < (t + 1) * d)
        k_t = jnp.where(sel_k, k, 0)
        # zeroed foreign lanes contribute nothing: this IS q_t · k_tᵀ
        s = jax.lax.dot_general(
            q, k_t,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK]
        if causal:
            s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[t]  # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # fully-masked-so-far rows would accumulate phantom mass (see
        # _fwd_kernel) — zero them so l stays 0
        p = jnp.where(m_new <= NEG_INF * 0.5, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[t] = l_ref[t] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[t] = m_new
        v_t = jnp.where(sel_k, v, 0).astype(jnp.float32)
        contrib = jax.lax.dot_general(
            p, v_t,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, hp·d], nonzero only on head t's lanes
        acc_ref[:] = jnp.where(
            sel_q, acc_ref[:] * alpha + contrib, acc_ref[:]
        )

    @pl.when(j == last_j)
    def _finalize():
        l_packed = jnp.ones((block_q, w), jnp.float32)
        for t in range(hp):
            sel_q = (lanes_q >= t * d) & (lanes_q < (t + 1) * d)
            l_t = l_ref[t]
            l_packed = jnp.where(
                sel_q, jnp.where(l_t == 0.0, 1.0, l_t), l_packed
            )
            safe_l = jnp.where(l_t == 0.0, 1.0, l_t)
            lse_ref[t, :] = (m_ref[t] + jnp.log(safe_l))[:, 0]
        o_ref[:] = (acc_ref[:] / l_packed).astype(o_ref.dtype)


def _flash_forward_packed(qkv, h, d, scale, causal, block_q, block_k,
                          interpret):
    """Packed qkv → (out ``[B, S, H·D]``, lse ``[B·H, S]``).

    ``qkv``: ``[B, S, 3·H·D]`` — the fused projection's output, as
    produced. The kernel reads q/k/v via three index maps over the ONE
    flat array (head ``h`` of q/k/v lives at last-dim block index
    ``h`` / ``H+h`` / ``2H+h`` in D-sized blocks), so the
    [B,S,H,D]→[B,H,S,D] transposes — the top copy kernels in the r4
    trace — never materialize, and the output lands sequence-major
    ready for the out-projection. Mosaic's tiling rule needs the last
    BLOCK dim 128-divisible: ``D % 128 == 0`` uses per-head blocks;
    smaller head dims with ``128 % D == 0`` lane-pack ``128 // D``
    heads per block (r5) via :func:`_fwd_kernel_grouped`; callers gate
    on ``packed_layout_supported``."""
    if d % 128:
        # head_dim 64: two heads lane-packed per 128-wide block
        return _flash_forward_packed_grouped(
            qkv, h, d, scale, causal, block_q, block_k, interpret
        )
    b, s, fused = qkv.shape
    assert fused == 3 * h * d, (qkv.shape, h, d)
    block_q, block_k = _resolve_blocks(
        block_q, block_k, s, s, d, d, qkv.dtype.itemsize, "fwd")
    grid = (b * h, s // block_q, s // block_k)

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
    )
    _, last_j = _visible_maps(causal, block_q, block_k, grid[1])
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (None, block_q, d), lambda bh, i, j, h=h: (bh // h, i, bh % h)
            ),
            pl.BlockSpec(
                (None, block_k, d),
                lambda bh, i, j, h=h: (bh // h, last_j(i, j), h + bh % h),
            ),
            pl.BlockSpec(
                (None, block_k, d),
                lambda bh, i, j, h=h: (
                    bh // h, last_j(i, j), 2 * h + bh % h),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, block_q, d), lambda bh, i, j, h=h: (bh // h, i, bh % h)
            ),
            pl.BlockSpec((None, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), qkv.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        cost_estimate=_cost(b * h, s, s, d, qkv.dtype.itemsize),
        compiler_params=_MOSAIC,
        interpret=interpret,
    )(qkv, qkv, qkv)
    return out, lse[:, 0, :]


def _flash_forward_packed_grouped(qkv, h, d, scale, causal, block_q,
                                  block_k, interpret):
    """Packed forward for small head dims: ``hp = 128 // d`` heads ride
    each 128-lane block (r5). Same index-map structure as the per-head
    path with groups in place of heads; heads within a group are
    contiguous in the fused layout, so the output flattens straight to
    ``[B, S, H·D]`` and lse to ``[B·H, S]``."""
    b, s, fused = qkv.shape
    assert fused == 3 * h * d, (qkv.shape, h, d)
    assert 128 % d == 0 and h % (128 // d) == 0, (
        "grouped layout needs 128 % head_dim == 0 and an even group "
        "split — gate callers on packed_layout_supported", h, d,
    )
    hp = 128 // d
    ng = h // hp  # lane groups per q/k/v region
    block_q, block_k = _resolve_blocks(
        block_q, block_k, s, s, hp * d, hp * d, qkv.dtype.itemsize,
        "fwd_grouped")
    grid = (b * ng, s // block_q, s // block_k)

    kernel = functools.partial(
        _fwd_kernel_grouped,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        hp=hp,
        d=d,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (None, block_q, hp * d),
                lambda bg, i, j, ng=ng: (bg // ng, i, bg % ng),
            ),
            pl.BlockSpec(
                (None, block_k, hp * d),
                lambda bg, i, j, ng=ng: (bg // ng, j, ng + bg % ng),
            ),
            pl.BlockSpec(
                (None, block_k, hp * d),
                lambda bg, i, j, ng=ng: (bg // ng, j, 2 * ng + bg % ng),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, block_q, hp * d),
                lambda bg, i, j, ng=ng: (bg // ng, i, bg % ng),
            ),
            pl.BlockSpec((None, hp, block_q), lambda bg, i, j: (bg, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), qkv.dtype),
            jax.ShapeDtypeStruct((b * ng, hp, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hp * d), jnp.float32),
            pltpu.VMEM((hp, block_q, 1), jnp.float32),
            pltpu.VMEM((hp, block_q, 1), jnp.float32),
        ],
        cost_estimate=_cost(b * h, s, s, d, qkv.dtype.itemsize),
        interpret=interpret,
    )(qkv, qkv, qkv)
    # [B·NG, hp, S] → [B·H, S]: group-major × within-group IS the head
    # order (heads of a group are lane-contiguous in the fused layout)
    return out, lse.reshape(b * h, s)


def packed_layout_supported(d: int, h: int) -> bool:
    """Can the packed-qkv kernels express this (head_dim, heads)?
    128-multiples use per-head blocks; head_dim 64 lane-packs 2 heads
    per block (even head counts). Smaller head dims would multiply the
    masked-dot MAC waste past the fallback's copy cost, so they take
    the transposed layout."""
    return d % 128 == 0 or (d == 64 and h % 2 == 0)


# -- blockwise backward (flash recurrences, XLA-fused): packed layout ----


def _causal_mask(i, j, block_q, block_k, window=None):
    rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return _seen(rows, cols, window)


def _flash_backward(scale, causal, block_q, block_k, residuals, g,
                    window=None):
    q, k, v, out, lse = residuals
    bh, s_q, d = q.shape
    s_k, dv = k.shape[1], v.shape[-1]  # v, out and g are ``dv`` wide
    block_q, block_k = _resolve_blocks(
        block_q, block_k, s_q, s_k, d, dv, 4, "blockwise", window)
    nq, nk = s_q // block_q, s_k // block_k
    f32 = jnp.float32

    qb = q.reshape(bh, nq, block_q, d).astype(f32)
    kb = k.reshape(bh, nk, block_k, d).astype(f32)
    vb = v.reshape(bh, nk, block_k, dv).astype(f32)
    gb = g.reshape(bh, nq, block_q, dv).astype(f32)
    lseb = lse.reshape(bh, nq, block_q)
    # Δ_i = rowsum(dO ∘ O)
    delta = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1).reshape(
        bh, nq, block_q
    )

    def p_block(i, j, qi, kj, li):
        s = jnp.einsum("bqd,bkd->bqk", qi, kj, preferred_element_type=f32) * scale
        if causal:
            s = jnp.where(
                _causal_mask(i, j, block_q, block_k, window)[None], s, NEG_INF)
        p = jnp.exp(s - li[..., None])  # [bh, BQ, BK]
        # fully-masked rows carry lse == NEG_INF; exp(s - lse) would be 1
        return jnp.where(li[..., None] <= NEG_INF * 0.5, 0.0, p)

    # dq: for each query block, scan KV blocks
    def dq_for_block(i, qi, gi, li, di):
        def body(acc, j):
            kj, vj = kb[:, j], vb[:, j]
            p = p_block(i, j, qi, kj, li)
            dp = jnp.einsum("bqd,bkd->bqk", gi, vj, preferred_element_type=f32)
            ds = p * (dp - di[..., None])
            return acc + jnp.einsum(
                "bqk,bkd->bqd", ds, kj, preferred_element_type=f32
            ) * scale, None

        acc0 = jnp.zeros((bh, block_q, d), f32)
        acc, _ = jax.lax.scan(body, acc0, jnp.arange(nk))
        return acc

    dq = jax.vmap(dq_for_block, in_axes=(0, 1, 1, 1, 1), out_axes=1)(
        jnp.arange(nq), qb, gb, lseb, delta
    ).reshape(bh, s_q, d)

    # dk/dv: for each KV block, scan query blocks
    def dkv_for_block(j, kj, vj):
        def body(carry, i):
            dk_acc, dv_acc = carry
            qi, gi, li, di = qb[:, i], gb[:, i], lseb[:, i], delta[:, i]
            p = p_block(i, j, qi, kj, li)
            dv_acc = dv_acc + jnp.einsum(
                "bqk,bqd->bkd", p, gi, preferred_element_type=f32
            )
            dp = jnp.einsum("bqd,bkd->bqk", gi, vj, preferred_element_type=f32)
            ds = p * (dp - di[..., None])
            dk_acc = dk_acc + jnp.einsum(
                "bqk,bqd->bkd", ds, qi, preferred_element_type=f32
            ) * scale
            return (dk_acc, dv_acc), None

        zeros = (jnp.zeros((bh, block_k, d), f32),
                 jnp.zeros((bh, block_k, dv), f32))
        (dk_acc, dv_acc), _ = jax.lax.scan(body, zeros, jnp.arange(nq))
        return dk_acc, dv_acc

    d_k, d_v = jax.vmap(dkv_for_block, in_axes=(0, 1, 1), out_axes=1)(
        jnp.arange(nk), kb, vb
    )
    d_k = d_k.reshape(bh, s_k, d)
    d_v = d_v.reshape(bh, s_k, dv)
    return dq.astype(q.dtype), d_k.astype(k.dtype), d_v.astype(v.dtype)


def _flash_backward_packed(scale, causal, block_q, block_k, residuals, g):
    """Flash backward for the packed layout: the head-free
    :func:`_flash_backward` vmapped over the head axis of the
    ``[B, S, H, D]`` views — identical recurrences (one copy of the
    numerically delicate math), batched einsums, no bhsd transposes
    materialized. Returns ``(d(qkv) [B, S, 3, H, D],)``."""
    qkv, out, lse = residuals
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B, S, H, D]

    def per_head(q_, k_, v_, o_, l_, g_):
        return _flash_backward(
            scale, causal, block_q, block_k, (q_, k_, v_, o_, l_), g_
        )

    dq, dk, dv = jax.vmap(
        per_head, in_axes=(2, 2, 2, 2, 1, 2), out_axes=2
    )(q, k, v, out, lse, g)
    return (jnp.stack([dq, dk, dv], axis=2),)


# -- backward kernels ([BH, S, D] layout) -------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal: bool, block_q: int, block_k: int,
                    window: int | None = None, nq: int | None = None):
    """dK and dV of one key block of one key/value head, summed over the
    query heads that share it (grid axis 2) and the query blocks (axis
    3; under a ``window`` those of ``nq`` that the band holds, counted
    from the first that sees the key block). Scores are held
    transposed, ``[BK, BQ]``: ``lse`` and ``delta`` then broadcast from
    the ``[1, BQ]`` rows they arrive as, and both accumulating products
    contract the leading query axis of ``do`` and ``q`` as they lie."""
    j, g, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last_g, last_step = pl.num_programs(2) - 1, pl.num_programs(3) - 1

    @pl.when((g == 0) & (step == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    i = step
    if window is not None:
        i += _band_ends("dkv", j, block_q, block_k, window, nq)[0]

    def _accumulate():
        q, do = q_ref[:], do_ref[:]  # [BQ, D]
        k, v = k_ref[:], v_ref[:]  # [BK, D]
        nt = (((1,), (1,)), ((), ()))
        nn = (((1,), (0,)), ((), ()))
        st = jax.lax.dot_general(
            k, q, nt, preferred_element_type=jnp.float32) * scale
        if causal:
            keys = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            queries = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(_seen(queries, keys, window), st, NEG_INF)
        lse = lse_ref[:]  # [1, BQ]
        # fully-masked rows carry lse == NEG_INF; exp(s - lse) would be 1
        pt = jnp.where(lse <= NEG_INF * 0.5, 0.0, jnp.exp(st - lse))
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, nn, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, nt, preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[:])
        dk_acc[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, nn, preferred_element_type=jnp.float32)

    if causal:
        # a query block wholly behind the key block, or wholly past its
        # window, sees none of it
        seen = _pair_seen(i, j, block_q, block_k, window)
        if window is not None:
            seen &= i < nq
        pl.when(seen)(_accumulate)
    else:
        _accumulate()

    @pl.when((g == last_g) & (step == last_step))
    def _finalize():
        dk_ref[:] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, lse_col, delta_col, *, scale: float,
                   causal: bool, block_q: int, block_k: int,
                   window: int | None = None, nk: int | None = None):
    """dQ of one query block, summed over the key blocks (grid axis 2;
    under a ``window`` those of ``nk`` that the band holds, as in the
    forward kernel). ``lse`` and ``delta`` arrive as ``[1, BQ]`` rows
    and are turned to ``[BQ, 1]`` columns once a query block."""
    i, step = pl.program_id(1), pl.program_id(2)
    last_step = pl.num_programs(2) - 1

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        lse_col[:] = jnp.transpose(lse_ref[:])
        delta_col[:] = jnp.transpose(delta_ref[:])

    j = step
    if window is not None:
        j += _band_ends("dq", i, block_q, block_k, window, nk)[0]

    def _accumulate():
        q, do = q_ref[:], do_ref[:]  # [BQ, D]
        k, v = k_ref[:], v_ref[:]  # [BK, D]
        nt = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            q, k, nt, preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(
                _causal_mask(i, j, block_q, block_k, window), s, NEG_INF)
        lse = lse_col[:]  # [BQ, 1]
        p = jnp.where(lse <= NEG_INF * 0.5, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(
            do, v, nt, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_col[:])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        seen = _pair_seen(i, j, block_q, block_k, window)
        if window is not None:
            # past the last block there is the index map stays on it
            seen &= j < nk
        pl.when(seen)(_accumulate)
    else:
        _accumulate()

    @pl.when(step == last_step)
    def _finalize():
        dq_ref[:] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_backward_kernels(scale, causal, block_q, block_k, interpret,
                            residuals, g, window=None):
    """The flash recurrences of :func:`_flash_backward` as two Pallas
    kernels. Operands reach the MXU in the dtype the inputs came in and
    every product accumulates in float32; a block pair the causal mask
    empties is skipped, and under a ``window`` the grids hold the band's
    pairs alone; a key/value head shared by ``group`` query heads is
    read in place and its gradient summed in the kernel."""
    q, k, v, out, lse = residuals
    bh, s_q, d = q.shape
    bkv, s_k, _ = k.shape
    dv = v.shape[-1]  # v, out, dO and dV; q, k, dQ and dK are ``d`` wide
    group = bh // bkv
    # the backward kernels' own blocks: the residuals depend on none
    block_q, block_k = _resolve_blocks(
        block_q, block_k, s_q, s_k, d, dv, q.dtype.itemsize, "bwd", window)
    nq, nk = s_q // block_q, s_k // block_k
    f32 = jnp.float32
    # Δ_i = rowsum(dO ∘ O)
    delta = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1)
    lse, delta = lse[:, None, :], delta[:, None, :]  # [BH, 1, S] rows
    params = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, window=window)
    if window is None:
        span_q, span_k = nq, nk
        first_i, last_j = _visible_maps(causal, block_q, block_k, nq)
    else:
        band = (s_q, s_k, block_q, block_k, window)
        span_q, inner_q = _band_maps("dkv", *band)
        span_k, last_j = _band_maps("dq", *band)
        first_i = lambda i, j: inner_q(j, i)  # noqa: E731
    concrete = all(type(t) is int for t in (bh, s_q, s_k, d, dv))

    def cost(score_wide, value_wide):
        """``score_wide`` products contract or give the ``d``-wide
        side, ``value_wide`` the ``dv``-wide one."""
        if not concrete:
            return None
        return pl.CostEstimate(
            flops=2 * bh * s_q * s_k * (score_wide * d + value_wide * dv),
            bytes_accessed=(bh * s_q * (2 * d + dv)
                            + bkv * s_k * (2 * d + 2 * dv))
            * q.dtype.itemsize,
            transcendentals=bh * s_q * s_k,
        )

    q_side = lambda b, j, g_, i: (b * group + g_, first_i(i, j), 0)  # noqa: E731
    kv_side = lambda b, j, g_, i: (b, j, 0)  # noqa: E731
    row = lambda b, j, g_, i: (b * group + g_, 0, first_i(i, j))  # noqa: E731
    d_k, d_v = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, **params),
        grid=(bkv, nk, group, span_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_side),
            pl.BlockSpec((None, block_k, d), kv_side),
            pl.BlockSpec((None, block_k, dv), kv_side),
            pl.BlockSpec((None, block_q, dv), q_side),
            pl.BlockSpec((None, 1, block_q), row),
            pl.BlockSpec((None, 1, block_q), row),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), kv_side),
            pl.BlockSpec((None, block_k, dv), kv_side),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), f32),
            pltpu.VMEM((block_k, dv), f32),
        ],
        cost_estimate=cost(2, 2),
        compiler_params=_MOSAIC,
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    q_side = lambda b, i, j: (b, i, 0)  # noqa: E731
    kv_side = lambda b, i, j: (b // group, last_j(i, j), 0)  # noqa: E731
    row = lambda b, i, j: (b, 0, i)  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **params),
        grid=(bh, nq, span_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_side),
            pl.BlockSpec((None, block_k, d), kv_side),
            pl.BlockSpec((None, block_k, dv), kv_side),
            pl.BlockSpec((None, block_q, dv), q_side),
            pl.BlockSpec((None, 1, block_q), row),
            pl.BlockSpec((None, 1, block_q), row),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), q_side),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), f32),
            pltpu.VMEM((block_q, 1), f32),
            pltpu.VMEM((block_q, 1), f32),
        ],
        cost_estimate=cost(2, 1),
        compiler_params=_MOSAIC,
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, d_k, d_v


# -- public op ---------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_bhsd(q, k, v, scale, causal, block_q, block_k, interpret,
                          window=None):
    out, _ = _flash_forward(
        q, k, v, scale, causal, block_q, block_k, interpret, window)
    return out


def _fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret, window):
    out, lse = _flash_forward(
        q, k, v, scale, causal, block_q, block_k, interpret, window)
    out, lse = checkpoint_name(out, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return out, (q, k, v, out, lse)


def _bwd_rule(scale, causal, block_q, block_k, interpret, window, residuals,
              g):
    return _flash_backward_kernels(
        scale, causal, block_q, block_k, interpret, residuals, g, window
    )


_flash_attention_bhsd.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _flash_attention_packed(qkv, scale, causal, block_q, block_k, interpret):
    b, s, _, h, d = qkv.shape
    out, _ = _flash_forward_packed(
        qkv.reshape(b, s, 3 * h * d), h, d, scale, causal, block_q,
        block_k, interpret,
    )
    return out.reshape(b, s, h, d)


def _fwd_rule_packed(qkv, scale, causal, block_q, block_k, interpret):
    b, s, _, h, d = qkv.shape
    out, lse = _flash_forward_packed(
        qkv.reshape(b, s, 3 * h * d), h, d, scale, causal, block_q,
        block_k, interpret,
    )
    out = out.reshape(b, s, h, d)
    return out, (qkv, out, lse.reshape(b, h, s))


def _bwd_rule_packed(scale, causal, block_q, block_k, interpret, residuals, g):
    return _flash_backward_packed(
        scale, causal, block_q, block_k, residuals, g
    )


_flash_attention_packed.defvjp(_fwd_rule_packed, _bwd_rule_packed)


def flash_attention_qkv(
    qkv,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """Self-attention straight from a fused qkv projection.

    ``qkv``: ``[B, S, 3, H, D]`` — the packed output of one
    ``Dense(3·H·D)`` reshaped, exactly as produced. Returns
    ``[B, S, H, D]``. Numerically identical to
    ``flash_attention(q, k, v)`` on the unpacked slices, but the kernel
    reads q/k/v via three index maps over the ONE packed array and
    writes output in the sequence-major layout the next projection
    consumes — the [B,S,·,H,D]→[·,B,H,S,D] transpose copies (the
    largest copy kernels in the r4 transformer trace, fwd and bwd)
    never exist. Differentiable (custom VJP in the same layout)."""
    if scale is None:
        scale = qkv.shape[-1] ** -0.5
    if interpret is None:
        interpret = backend_guard.pallas_interpret()
    b, s, _, h, d = qkv.shape
    if not packed_layout_supported(int(d), int(h)):
        # Mosaic's tiling rule needs 128-divisible last-dim blocks.
        # D % 128 == 0 → per-head blocks; divisors of 128 lane-pack
        # 128//D heads per block (r5 — head_dim-64 models no longer pay
        # the transpose copies); anything else (or an odd head count)
        # takes the transposed layout — same math, with the copy cost
        # the packed path avoids
        qkv_t = jnp.transpose(qkv, (2, 0, 3, 1, 4))  # [3, B, H, S, D]
        out = _flash_attention_bhsd(
            qkv_t[0].reshape(b * h, s, d),
            qkv_t[1].reshape(b * h, s, d),
            qkv_t[2].reshape(b * h, s, d),
            float(scale),
            bool(causal),
            block_q and int(block_q),
            block_k and int(block_k),
            bool(interpret),
        )
        return jnp.transpose(out.reshape(b, h, s, d), (0, 2, 1, 3))
    return _flash_attention_packed(
        qkv,
        float(scale),
        bool(causal),
        block_q and int(block_q),
        block_k and int(block_k),
        bool(interpret),
    )


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
):
    """Blockwise attention. ``q/k/v``: ``[batch, heads, seq, head_dim]``
    (or ``[bh, seq, head_dim]``). Differentiable; O(seq) memory.
    ``k`` and ``v`` may have fewer heads than ``q`` (grouped-query
    attention): ``heads_q / heads_kv`` consecutive query heads then
    share one key/value head. ``v`` may have a head width of its own
    (latent attention scores with wider heads than it sums): the
    result then has ``v``'s. ``window`` (causal attention only) is a
    sliding window: query ``i`` sees the keys ``j <= i`` with ``i - j <
    window``, so ``window`` keys with its own; the kernels' grids hold
    the block pairs of the band alone, forward and backward
    (``band_grid``). None is plain causal attention, on the grid over
    every pair of blocks that it has always had.

    A ``block_q``/``block_k`` the caller names rules the forward
    kernel and both backward kernels. Where none is named each kernel
    takes its own from the shapes: the largest measured blocks that
    divide the sequences and fit VMEM, under a ``window`` those whose
    query block is no longer than the band first (``_BLOCK_TABLE``,
    ``_resolve_blocks``); the result does not depend on them beyond
    the order of float32 sums."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = backend_guard.pallas_interpret()
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[None], k[None], v[None]
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    if h % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"{h} query heads cannot share {k.shape[1]} key and "
            f"{v.shape[1]} value heads"
        )
    if k.shape[-1] != d:
        raise ValueError(
            f"queries are {d} wide and keys {k.shape[-1]}: a score needs "
            f"one width (the values' may differ)"
        )
    if window is not None and (not causal or int(window) < 1):
        raise ValueError(
            f"window {window!r}: a sliding window is a positive number "
            f"of keys under the causal mask"
        )
    merged = lambda t, s: t.reshape(-1, s, t.shape[-1])  # noqa: E731
    out = _flash_attention_bhsd(
        merged(q, s_q),
        merged(k, s_k),
        merged(v, s_k),
        float(scale),
        bool(causal),
        block_q and int(block_q),
        block_k and int(block_k),
        bool(interpret),
        window and int(window),
    )
    out = out.reshape(b, h, s_q, v.shape[-1])
    return out[0] if squeeze else out


def attention_reference(q, k, v, causal: bool = False,
                        scale: float | None = None,
                        window: int | None = None):
    """Naive O(S²)-memory attention — the correctness oracle for tests.
    ``window``: as :func:`flash_attention`'s."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = s.shape[-2], s.shape[-1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        s = jnp.where(_seen(rows, cols, window), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32)).astype(q.dtype)
