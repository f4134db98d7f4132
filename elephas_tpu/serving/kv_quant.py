"""Quantized paged-KV block codec (ISSUE 19).

The paged arena (:mod:`~elephas_tpu.serving.paged_kv`) prices
admission in BYTES: every resident position costs ``2 · H · Dh``
float32 values per layer, and on a fixed per-device KV budget that
byte price is exactly what caps concurrency. This module is the
KIVI/KVQuant-style answer: store pool blocks as **int8 or packed int4
with per-(position, head) float32 scales**, quantize on write inside
the serving programs, and dequantize inside the flash span tiles —
fp rows never materialize outside one ``[B, block_k, H, Dh]`` tile.

Scale granularity is per (pool row position, head) — NOT one scale
per block — deliberately: each token's write touches only its own
``(block, offset)`` row, so quantize-on-write needs no read-modify-
write of a shared block statistic, both the one-hot contraction and
``local=True`` native-scatter write paths stay exact and incremental,
and an offloaded/migrated block is a self-contained byte string
(values + scales move together, bit-identically).

Symmetric quantization, zero-point-free::

    scale = max(|x|) / qmax        (qmax: 127 for int8, 7 for int4)
    q     = round(x / scale)  in [-qmax, qmax]
    x'    = q * scale

An all-zero row quantizes to ``scale == 0`` and dequantizes to exact
zeros (``q * 0``) — sentinel-padded pool rows stay exact zeros through
the round-trip, which the paged gather math relies on.

int4 packs two signed nibbles per int8 byte along the head_dim axis
(lo nibble = even index, hi nibble = odd index; odd ``Dh`` zero-pads
the last nibble). Unpacking is two arithmetic shifts — sign extension
for free, no lookup tables.

Every helper has a numpy twin (``*_np``) for the host side: stage-
parallel prefill handoffs land host fp rows into a quantized pool, and
the wire/refusal tests exercise the codec without a device.

Temp-0 exactness CANNOT survive quantization — the parity contract
changes shape (see docs/API.md "Quantized KV"): ``kv_dtype="fp"`` is
the selectable parity oracle (exactly like ``attention="naive"``),
bit-exactness is asserted WITHIN a kv_dtype (quantized blocks offload,
migrate, and resume bit-identically), and cross-dtype quality is gated
by token agreement / logprob deltas against the fp oracle.
"""

from __future__ import annotations

__all__ = [
    "KV_DTYPES",
    "QMAX",
    "packed_head_dim",
    "pool_bytes_per_pos",
    "quantize_rows",
    "dequantize_rows",
    "pack_int4",
    "unpack_int4",
    "quantize_rows_np",
    "dequantize_rows_np",
]

KV_DTYPES = ("fp", "int8", "int4")

QMAX = {"int8": 127.0, "int4": 7.0}


def check_kv_dtype(kv_dtype: str) -> str:
    """Validate a ``kv_dtype`` knob value loudly (engine/serve() and
    the wire importer both refuse unknown dtypes up front)."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}"
        )
    return kv_dtype


def packed_head_dim(head_dim: int, kv_dtype: str) -> int:
    """STORED last-axis width for one ``head_dim``-wide row: ``Dh``
    int8 bytes for int8, ``ceil(Dh / 2)`` packed bytes for int4."""
    if kv_dtype == "int4":
        return -(-int(head_dim) // 2)
    return int(head_dim)


def pool_bytes_per_pos(specs, kv_dtype: str) -> int:
    """Bytes one resident position costs across all layers (K and V):
    the honest per-device KV price an equal-bytes comparison of
    admitted concurrency divides by. ``specs`` is ``[(name, heads, head_dim), ...]``."""
    if kv_dtype == "fp":
        return sum(h * d for _, h, d in specs) * 2 * 4
    # quantized: 1 byte per stored value + one f32 scale per head
    return sum(
        h * packed_head_dim(d, kv_dtype) + h * 4 for _, h, d in specs
    ) * 2


def pack_int4(q):
    """Pack signed int4 values (int8 storage, range [-7, 7]) two per
    byte along the LAST axis: even index → lo nibble, odd index → hi
    nibble; odd-length axes zero-pad the final hi nibble. ``[..., D]``
    int8 → ``[..., ceil(D/2)]`` int8."""
    import jax.numpy as jnp

    d = q.shape[-1]
    if d % 2:
        pad = [(0, 0)] * (q.ndim - 1) + [(0, 1)]
        q = jnp.pad(q, pad)
    lo = q[..., 0::2]
    hi = q[..., 1::2]
    return ((lo & 0x0F) | (hi << 4)).astype(jnp.int8)


def unpack_int4(p, head_dim: int):
    """Inverse of :func:`pack_int4`: ``[..., ceil(D/2)]`` int8 →
    ``[..., head_dim]`` int8 via sign-extending arithmetic shifts
    (``(p << 4) >> 4`` recovers the lo nibble, ``p >> 4`` the hi)."""
    import jax.numpy as jnp

    p = p.astype(jnp.int8)
    lo = (p << 4) >> 4
    hi = p >> 4
    out = jnp.stack([lo, hi], axis=-1).reshape(
        p.shape[:-1] + (2 * p.shape[-1],)
    )
    return out[..., : int(head_dim)]


def quantize_rows(x, kv_dtype: str):
    """Quantize fp rows ``[..., H, Dh]`` → ``(q, scale)``: ``q`` int8
    ``[..., H, Dhp]`` (int4 packed when asked), ``scale`` float32
    ``[..., H]``. Symmetric per-(row, head); all-zero rows get
    ``scale == 0`` and round-trip to exact zeros."""
    import jax.numpy as jnp

    qmax = QMAX[kv_dtype]
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)  # [..., H]
    scale = amax / qmax
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(
        jnp.round(x / safe[..., None]), -qmax, qmax
    ).astype(jnp.int8)
    if kv_dtype == "int4":
        q = pack_int4(q)
    return q, scale


def dequantize_rows(q, scale, kv_dtype: str, head_dim: int):
    """Inverse of :func:`quantize_rows`: ``(q [..., H, Dhp] int8,
    scale [..., H] f32)`` → float32 ``[..., H, head_dim]``. This is
    the in-tile seam — flash callers hand it ONE K/V tile at a time,
    so fp never materializes beyond a tile."""
    import jax.numpy as jnp

    if kv_dtype == "int4":
        q = unpack_int4(q, head_dim)
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def quantize_rows_np(x, kv_dtype: str):
    """Host (numpy) twin of :func:`quantize_rows` — bit-identical
    quantization decisions (same symmetric scale, same round-half-to-
    even), used when stage-parallel prefill lands host fp rows into a
    quantized pool and by the codec tests."""
    import numpy as np

    qmax = QMAX[kv_dtype]
    x = np.asarray(x, dtype=np.float32)
    amax = np.max(np.abs(x), axis=-1)
    scale = (amax / qmax).astype(np.float32)
    safe = np.where(scale > 0.0, scale, 1.0)
    q = np.clip(
        np.round(x / safe[..., None]), -qmax, qmax
    ).astype(np.int8)
    if kv_dtype == "int4":
        d = q.shape[-1]
        if d % 2:
            pad = [(0, 0)] * (q.ndim - 1) + [(0, 1)]
            q = np.pad(q, pad)
        q = ((q[..., 0::2] & 0x0F) | (q[..., 1::2] << 4)).astype(
            np.int8
        )
    return q, scale


def dequantize_rows_np(q, scale, kv_dtype: str, head_dim: int):
    """Host (numpy) twin of :func:`dequantize_rows`."""
    import numpy as np

    q = np.asarray(q, dtype=np.int8)
    if kv_dtype == "int4":
        lo = (q << 4) >> 4
        hi = q >> 4
        q = np.stack([lo, hi], axis=-1).reshape(
            q.shape[:-1] + (2 * q.shape[-1],)
        )[..., : int(head_dim)]
    return q.astype(np.float32) * np.asarray(
        scale, dtype=np.float32
    )[..., None]
