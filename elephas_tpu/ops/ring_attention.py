"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context support the reference lacks entirely (SURVEY.md §5
"long-context: absent"): sequence length there is bounded by one worker's
memory. Here the sequence dimension is sharded over a mesh axis; each
device keeps its query shard resident and the K/V shards rotate around
the ring via ``lax.ppermute`` (XLA lowers neighbor permutes onto ICI
neighbor links), with the online-softmax partial results merged by
log-sum-exp. Peak memory per device is O(S/W · D) and the permute of the
next chunk overlaps with compute of the current one under XLA's async
collectives — the blockwise/ring-attention construction.

Forward chunks run the Pallas flash kernel
(:mod:`elephas_tpu.ops.flash_attention`), so the hot op stays hand-tiled
for the MXU. The op carries a ``jax.custom_vjp`` whose backward is a
second ring pass: dK/dV accumulators rotate *with* their K/V chunks so
after W steps each device's gradients arrive back home — communication
stays neighbor-to-neighbor, memory stays O(S/W).

Causality across shards uses global positions: a chunk wholly in the
future is skipped, the diagonal chunk applies the in-kernel causal mask,
and past chunks run unmasked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from elephas_tpu.ops.flash_attention import _flash_forward, NEG_INF
from elephas_tpu.utils import backend_guard


def _merge(o1, lse1, o2, lse2):
    """Merge two attention partials by log-sum-exp of their normalizers."""
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    return o1 * w1 + o2 * w2, lse


def _ring_forward(q, k, v, axis_name, causal, scale, block_q, block_k, interpret):
    """Returns (out, lse) for the local shard; kv chunks rotate the ring.

    ``axis_index`` is taken ONLY on the causal path (where the switch
    consumes it): a dead ``axis_index`` in the non-causal scan body
    survives DCE into the lowered module, and XLA's SPMD partitioner
    refuses the orphaned ``PartitionId`` outside a manual region
    ("PartitionId instruction is not supported for SPMD
    partitioning...") — the root cause of the seed's non-causal
    SP failures (jit'd evaluate/predict under a sequence scope;
    regression-pinned in tests/test_sequence_parallel.py)."""
    w = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name) if causal else None
    bh, s_local, d = q.shape
    f32 = jnp.float32

    chunk = functools.partial(
        _flash_forward,
        scale=float(scale),
        block_q=min(block_q, s_local),
        block_k=min(block_k, k.shape[1]),
        interpret=interpret,
    )

    def full_chunk(q, kc, vc):
        return chunk(q, kc, vc, causal=False)

    def diag_chunk(q, kc, vc):
        return chunk(q, kc, vc, causal=True)

    def skip_chunk(q, kc, vc):
        return (
            jnp.zeros((bh, s_local, d), q.dtype),
            jnp.full((bh, s_local), NEG_INF, f32),
        )

    perm = [(i, (i + 1) % w) for i in range(w)]

    def step(carry, t):
        o, lse, kc, vc = carry
        if causal:
            src = (me - t) % w
            case = jnp.where(src == me, 1, jnp.where(src > me, 2, 0))
            oc, lsec = jax.lax.switch(
                case, (full_chunk, diag_chunk, skip_chunk), q, kc, vc
            )
        else:
            oc, lsec = full_chunk(q, kc, vc)
        o, lse = _merge(o.astype(f32), lse, oc.astype(f32), lsec)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (o, lse, kc, vc), None

    o0 = jnp.zeros((bh, s_local, d), f32)
    lse0 = jnp.full((bh, s_local), NEG_INF, f32)
    (o, lse, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v), jnp.arange(w))
    return o.astype(q.dtype), lse


def _chunk_grads(q, kc, vc, g, lse, delta, scale, mask):
    """Flash-backward recurrences for one (q-shard × kv-chunk) pair.

    ``lse``/``delta`` are the *global* log-sum-exp and rowsum(dO∘O) for the
    local q rows, so per-chunk probabilities p = exp(s − lse) are exact
    global attention weights. ``mask`` is the [S_q, S_k] validity mask.
    """
    f32 = jnp.float32
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(f32), kc.astype(f32),
        preferred_element_type=f32,
    ) * scale
    s = jnp.where(mask[None], s, NEG_INF)
    p = jnp.exp(s - lse[..., None])  # zero where masked or skipped
    # rows whose *global* lse is NEG_INF (every position masked) would
    # otherwise get p = exp(NEG_INF - NEG_INF) = 1
    p = jnp.where(lse[..., None] <= NEG_INF * 0.5, 0.0, p)
    dp = jnp.einsum(
        "bqd,bkd->bqk", g.astype(f32), vc.astype(f32), preferred_element_type=f32
    )
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bqk,bkd->bqd", ds, kc.astype(f32)) * scale
    dk = jnp.einsum("bqk,bqd->bkd", ds, q.astype(f32)) * scale
    dv = jnp.einsum("bqk,bqd->bkd", p, g.astype(f32))
    return dq, dk, dv


def _ring_backward(axis_name, causal, scale, block_q, block_k, interpret,
                   residuals, g):
    q, k, v, out, lse = residuals
    w = jax.lax.axis_size(axis_name)
    # causal-only, as in _ring_forward: a dead axis_index in the
    # non-causal body lowers to an orphaned PartitionId (see there)
    me = jax.lax.axis_index(axis_name) if causal else None
    bh, s_local, d = q.shape
    f32 = jnp.float32
    delta = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1)  # [bh, S_local]

    rows = jax.lax.broadcasted_iota(jnp.int32, (s_local, s_local), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (s_local, s_local), 1)

    perm = [(i, (i + 1) % w) for i in range(w)]

    def step(carry, t):
        dq, dk_rot, dv_rot, kc, vc = carry
        if causal:
            src = (me - t) % w
            # global positions: my rows at me*S, chunk cols at src*S
            mask = (rows + me * s_local) >= (cols + src * s_local)
        else:
            mask = jnp.ones((s_local, s_local), bool)
        dq_c, dk_c, dv_c = _chunk_grads(q, kc, vc, g, lse, delta, scale, mask)
        dq = dq + dq_c
        dk_rot = dk_rot + dk_c
        dv_rot = dv_rot + dv_c
        # kv and their gradient accumulators travel together; after w
        # steps the accumulators land back on the chunk's home device
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dk_rot = jax.lax.ppermute(dk_rot, axis_name, perm)
        dv_rot = jax.lax.ppermute(dv_rot, axis_name, perm)
        return (dq, dk_rot, dv_rot, kc, vc), None

    z = jnp.zeros((bh, s_local, d), f32)
    (dq, dk, dv, _, _), _ = jax.lax.scan(
        step, (z, z, z, k, v), jnp.arange(w)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_attention(q, k, v, axis_name, causal, scale, block_q, block_k, interpret):
    out, _ = _ring_forward(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    return out


def _ring_fwd(q, k, v, axis_name, causal, scale, block_q, block_k, interpret):
    out, lse = _ring_forward(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, scale, block_q, block_k, interpret, residuals, g):
    return _ring_backward(
        axis_name, causal, scale, block_q, block_k, interpret, residuals, g
    )


_ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(
    q,
    k,
    v,
    axis_name: str,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """Sequence-parallel attention; call INSIDE ``shard_map``/``pmap``.

    ``q/k/v``: the local sequence shard, ``[bh, S_local, D]`` (sequence
    axis sharded over ``axis_name``; batch*heads merged). Returns the
    local output shard ``[bh, S_local, D]``. Differentiable (custom
    ring-pass VJP).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = backend_guard.pallas_interpret()
    return _ring_attention(
        q, k, v, axis_name, bool(causal), float(scale),
        int(block_q), int(block_k), bool(interpret),
    )


def ring_attention_sharded(
    q,
    k,
    v,
    mesh,
    axis_name: str = "workers",
    causal: bool = False,
    scale: float | None = None,
    interpret: bool | None = None,
):
    """Global-array convenience wrapper: shards the sequence axis of
    ``[bh, S, D]`` inputs over ``mesh[axis_name]`` and runs
    :func:`ring_attention` under ``shard_map``."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        ring_attention,
        axis_name=axis_name,
        causal=causal,
        scale=scale,
        interpret=interpret,
    )
    spec = P(None, axis_name, None)
    sharded = jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return sharded(q, k, v)
