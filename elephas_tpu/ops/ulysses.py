"""Ulysses sequence parallelism — all-to-all head↔sequence resharding.

The second of the two standard SP families (SURVEY.md §2a lists both as
absent upstream; TPU-native extension, not a port):

- **Ring** (:mod:`elephas_tpu.ops.ring_attention`): queries stay put,
  KV shards rotate via ``ppermute`` — S/W communication per hop, W hops.
- **Ulysses** (this module, after DeepSpeed-Ulysses): two
  ``lax.all_to_all`` reshards instead. Tokens arrive sequence-sharded
  ``[B, H, S/W, D]``; the first all-to-all trades the sequence split
  for a HEAD split (``[B, H/W, S, D]``), every device runs ordinary
  full-sequence attention over its own heads (here: the Pallas flash
  kernel), and the second all-to-all restores the sequence split.

Trade-offs, honestly: Ulysses moves each activation exactly twice
regardless of W (cheaper than the ring's rotating KV traffic for large
W), but requires ``num_heads % W == 0`` and materializes full-length
sequences per head group (O(S) per device rather than O(S/W)); the
ring has no head-count constraint and keeps O(S/W) activations. Both
are exact attention; pick by head count and memory budget.

Differentiable end-to-end with no custom VJP: ``all_to_all`` is linear
(its transpose is the reverse all-to-all) and the flash kernel carries
its own VJP.

Call :func:`ulysses_attention` INSIDE ``shard_map`` with the sequence
axis sharded over ``axis_name``; :func:`ulysses_attention_sharded` is
the global-array convenience wrapper (mirrors
``ring_attention_sharded``).
"""

from __future__ import annotations

import functools

import jax

from elephas_tpu.ops.flash_attention import flash_attention


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str,
    causal: bool = False,
    scale: float | None = None,
    interpret: bool | None = None,
):
    """Sequence-parallel attention; call INSIDE ``shard_map``.

    ``q/k/v``: the local sequence shard ``[B, H, S_local, D]`` (the
    sequence axis sharded over ``axis_name``; heads NOT sharded —
    ``H % axis_size == 0`` required). Returns ``[B, H, S_local, D]``.
    """
    w = jax.lax.axis_size(axis_name)
    b, h, s_local, d = q.shape
    if h % w:
        raise ValueError(
            f"Ulysses needs num_heads ({h}) divisible by the sequence "
            f"axis size ({w}) — use ring attention for odd head counts"
        )

    import jax.numpy as jnp

    # ONE stacked all_to_all for q/k/v (as DeepSpeed-Ulysses does)
    # instead of three collective launches per attention:
    # [3, B, H, S/W, D] -> [3, B, H/W, S, D] — each device gets ALL the
    # sequence for a slice of the heads
    qh, kh, vh = jax.lax.all_to_all(
        jnp.stack((q, k, v)), axis_name, split_axis=2, concat_axis=3,
        tiled=True,
    )
    out = flash_attention(
        qh, kh, vh, causal=causal, scale=scale, interpret=interpret
    )
    # [B, H/W, S, D] -> [B, H, S/W, D]: restore the sequence split
    return jax.lax.all_to_all(
        out, axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def ulysses_attention_sharded(
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
    ``[B, H, S, D]`` inputs over ``mesh[axis_name]`` and runs
    :func:`ulysses_attention` under ``shard_map``."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        ulysses_attention,
        axis_name=axis_name,
        causal=causal,
        scale=scale,
        interpret=interpret,
    )
    spec = P(None, None, axis_name, None)
    sharded = jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return sharded(q, k, v)
