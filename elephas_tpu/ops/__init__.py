"""Hand-written TPU kernels and distributed ops.

The reference delegates all compute to TF kernels (SURVEY.md §2: "no
native code in the reference itself — TF kernels in C++/CUDA are the
delegated native layer"). Here the delegated layer is XLA, and this
package holds the ops where hand-scheduling beats the compiler:

- :mod:`elephas_tpu.ops.flash_attention` — blockwise online-softmax
  attention (Pallas, MXU-tiled, O(S) memory).
- :mod:`elephas_tpu.ops.ring_attention` — sequence-parallel attention
  over a mesh axis via ``ppermute`` (KV blocks rotate over ICI while
  each device computes its local query block).
- :mod:`elephas_tpu.ops.gated_delta` — the gated delta rule of
  recurrent-state (linear-attention) layers, chunked, forward and
  backward.
- :mod:`elephas_tpu.ops.ssd` — the selective scan of state-space
  (Mamba-2) layers, chunked, forward and backward.
- :mod:`elephas_tpu.ops.moe` — expert-parallel and dropless
  held-experts mixture-of-experts FFNs.
"""

from elephas_tpu.ops.flash_attention import flash_attention
from elephas_tpu.ops.ring_attention import ring_attention
from elephas_tpu.ops.ulysses import ulysses_attention
from elephas_tpu.ops.gated_delta import (
    gated_delta_rule,
    gated_delta_rule_recurrent,
)
from elephas_tpu.ops.ssd import ssd_chunked, ssd_recurrent
from elephas_tpu.ops.moe import grouped_matmul, held_experts_ffn

__all__ = [
    "flash_attention", "ring_attention", "ulysses_attention",
    "gated_delta_rule", "gated_delta_rule_recurrent",
    "ssd_chunked", "ssd_recurrent",
    "grouped_matmul", "held_experts_ffn",
]
