"""The kernels of the main path through the chip's compiler, without the
chip: each is lowered at a real width for a described ``v5e:2x2`` and
must come out as a Mosaic kernel (``tpu_custom_call``), not interpreted
and not refused. Interpret-mode tests cannot see what this sees — a
slice off the tiling, too much fast memory, a kernel that cannot be
partitioned. A compile that passes here is not a chip run.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from elephas_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_qkv,
    packed_layout_supported,
)
from elephas_tpu.ops.layer_norm import layer_norm
from elephas_tpu.ops.ring_attention import ring_attention_sharded

TOPO = None  # the described v5e:2x2, set by the fixture below


@pytest.fixture(scope="module", autouse=True)
def _described_chip():
    """Describes the chip once a module, inside a test's set-up and
    never at import: only one process at a time may load the TPU's
    library, and under several test workers every worker imports this
    file. Where no v5e:2x2 can be described the file's tests skip.

    A compile for a described chip is written to JAX's persistent
    cache but cannot be read back without the chip (the next run warns
    and compiles again), so the cache stays off around these."""
    global TOPO
    try:
        from jax.experimental import topologies

        TOPO = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this machine
        pytest.skip(f"cannot describe a v5e:2x2 here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def on_chip(shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(TOPO.devices[0])
    )


def kernels_in(fn, *args) -> int:
    """Compile ``fn`` for the described chip; count its Mosaic calls."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30
    return compiled.as_text().count("tpu_custom_call")


DTYPES = [jnp.bfloat16, jnp.float32]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("head_dim", [128, 64])
def test_flash_forward_transposed_layout(head_dim, dtype):
    q = on_chip((4, 8, 1024, head_dim), dtype)
    assert kernels_in(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False
        ),
        q, q, q,
    ) == 1


@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_forward_packed_layout(dtype):
    assert packed_layout_supported(128, 8)
    qkv = on_chip((4, 256, 3, 8, 128), dtype)
    assert kernels_in(
        lambda t: flash_attention_qkv(t, causal=True, interpret=False),
        qkv,
    ) == 1


@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_forward_lane_grouped_layout_gpt2_small(dtype):
    """H=12 D=64 S=1024: two heads share a 128-lane block."""
    assert packed_layout_supported(64, 12)
    qkv = on_chip((2, 1024, 3, 12, 64), dtype)
    assert kernels_in(
        lambda t: flash_attention_qkv(t, causal=True, interpret=False),
        qkv,
    ) == 1


def test_grad_through_the_packed_op():
    """What the LM's fit epoch differentiates: Pallas forward, scanned
    XLA backward."""
    qkv = on_chip((2, 1024, 3, 12, 64), jnp.bfloat16)

    def loss(t):
        out = flash_attention_qkv(t, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    assert kernels_in(jax.grad(loss), qkv) >= 1


@pytest.mark.parametrize("dtype", DTYPES)
def test_layer_norm_forward(dtype):
    x = on_chip((32768, 1024), dtype)
    g = on_chip((1024,), jnp.float32)
    assert kernels_in(
        lambda x, g, b: layer_norm(x, g, b, interpret=False), x, g, g
    ) == 1


def test_layer_norm_backward():
    x = on_chip((32768, 1024), jnp.bfloat16)
    g = on_chip((1024,), jnp.float32)

    def loss(x, g, b):
        y = layer_norm(x, g, b, interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    # forward (for the saved statistics) and the one-pass backward
    assert kernels_in(jax.grad(loss, argnums=(0, 1, 2)), x, g, g) == 2


def test_ring_attention_over_the_four_described_chips():
    """The sequence-parallel kernel inside ``shard_map`` on a mesh
    built from the topology's devices: Mosaic calls around the ring's
    collective-permutes."""
    mesh = Mesh(np.array(TOPO.devices), ("workers",))
    q = jax.ShapeDtypeStruct(
        (16, 4096, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, "workers", None)),
    )
    compiled = jax.jit(
        lambda q, k, v: ring_attention_sharded(
            q, k, v, mesh, causal=True, interpret=False
        )
    ).lower(q, q, q).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


@pytest.mark.parametrize("meshed", [False, True])
def test_serving_decode_step_at_gpt2_small_width(meshed):
    """The engine's own paged decode program (width of GPT-2-small,
    depth cut to two layers, a full 64-block table) for the described
    chip. Unmeshed it takes native gather/scatter; on the one-device
    mesh ``SparkModel.serve`` builds, the one-hot contractions. The
    engine places arrays as it is built, which a described device
    cannot hold, so the meshed case stops construction at the first
    staging call: by then every program exists."""
    from elephas_tpu.models import transformer_lm
    from elephas_tpu.serving import InferenceEngine

    model = transformer_lm(
        vocab_size=50257, maxlen=1024, d_model=768, num_heads=12,
        num_layers=2, dropout=0.0, seed=0,
    )
    slots, blocks, table = 8, 512, 64
    kw = dict(num_slots=slots, paged=True, block_size=16,
              num_blocks=blocks, prefix_cache=True)
    if meshed:
        class Built(Exception):
            pass

        class ProgramsOnly(InferenceEngine):
            def refresh_weights(self, version=None):
                raise Built

        mesh = Mesh(np.array(TOPO.devices[:1]), ("workers",))
        engine = ProgramsOnly.__new__(ProgramsOnly)
        with pytest.raises(Built):
            engine.__init__(model, mesh=mesh, batch_axes=("workers",), **kw)
        whole = NamedSharding(mesh, P())
        by_slot = NamedSharding(mesh, P("workers"))
    else:
        engine = InferenceEngine(model, **kw)
        whole = by_slot = SingleDeviceSharding(TOPO.devices[0])

    def shape(dims, dtype, sharding=whole):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    weights = {
        v.path: shape(tuple(v.shape), jnp.float32) for v in model.variables
    }
    pool = jax.tree.map(
        lambda a: shape(a.shape, a.dtype), jax.eval_shape(engine.arena.init)
    )
    compiled = engine._paged_decode_jit.lower(
        weights, pool, shape((slots, table), jnp.int32, by_slot),
        shape((slots,), jnp.int32, by_slot),
        shape((slots,), jnp.int32, by_slot),
        shape((slots,), jnp.float32, by_slot),
        shape((slots,), jnp.bool_, by_slot),
        shape((2,), jnp.uint32),
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes < 2**34
    engine.release_telemetry()


# -- the hybrid MoE block's kernels at the published widths (ISSUE 27) ----


def test_flash_forward_grouped_query_at_8192_positions():
    """16 query heads over 2 key/value heads, head size 256, causal,
    8192 positions: the key/value head is found through the index map."""
    q = on_chip((2, 16, 8192, 256), jnp.bfloat16)
    kv = on_chip((2, 2, 8192, 256), jnp.bfloat16)
    assert kernels_in(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512, interpret=False
        ),
        q, kv, kv,
    ) == 1


def test_flash_backward_grouped_query_at_8192_positions():
    """The gradient of the same attention at blocks of 256: the forward
    kernel, dK/dV and dQ. The key/value head is read in place on the
    way back too: the program's temporaries stay under one float32
    ``[32, 8192, 256]`` array, what a repeated copy of ``k`` or ``v``
    alone would take (the output and the softmax statistics are
    half of that)."""
    q = on_chip((2, 16, 8192, 256), jnp.bfloat16)
    kv = on_chip((2, 2, 8192, 256), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, block_q=256, block_k=256, interpret=False
        )
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    repeated_f32 = 2 * 16 * 8192 * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < repeated_f32


def test_flash_kernels_at_latent_attentions_two_widths():
    """Latent attention at the published widths (ISSUE 31): 32 heads
    score with 192-wide queries and keys and sum 128-wide values over
    8192 causal positions. The forward kernel, dK/dV and dQ take the
    two widths as they are: three Mosaic calls, gradients in their
    operands' shapes, no operand padded to a common width in HBM
    (nothing of the program is 256 wide, and ``v``, ``o`` and ``dV``
    stay 128). The temporaries are XLA's four layout copies of the
    192-wide operands and gradients (it keeps a free-standing 192-wide
    array sequence-minor; 100 MB each), which hold the output, its
    gradient and the softmax statistics in turn: bounded by what
    float32 copies of ``q`` and ``k`` at 256 wide would take."""
    q = on_chip((32, 8192, 192), jnp.bfloat16)
    v = on_chip((32, 8192, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, block_q=256, block_k=256, interpret=False
        )
        assert out.shape == v.shape
        return jnp.sum(out.astype(jnp.float32))

    grads = jax.jit(jax.grad(loss, (0, 1, 2)))
    compiled = grads.lower(q, q, v).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "[32,8192,256]" not in text
    assert [g.shape for g in jax.eval_shape(grads, q, q, v)] == [
        q.shape, q.shape, v.shape]
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2 * 32 * 8192 * 256 * 4)


@pytest.mark.parametrize("heads,kv_heads,d,dv", [
    pytest.param(64, 64, 192, 128, id="latent-64x192-128"),
    pytest.param(32, 4, 256, 256, id="gated-32-over-4x256"),
])
def test_flash_kernels_at_the_rules_blocks(heads, kv_heads, d, dv):
    """The two LM cells' attention of one step with no block named
    (ISSUE 36): the op takes blocks of 1024 from the shapes for the
    forward kernel, dK/dV and dQ, and Mosaic takes all three inside the
    scoped VMEM the calls ask for. The temporaries keep the bound they
    had at blocks of 256: blocks live in VMEM."""
    from elephas_tpu.ops.flash_attention import _resolve_blocks

    assert [
        _resolve_blocks(None, None, 8192, 8192, d, dv, 2, kernel)
        for kernel in ("fwd", "bwd")
    ] == [(1024, 1024), (1024, 1024)]
    q = on_chip((heads, 8192, d), jnp.bfloat16)
    k = on_chip((kv_heads, 8192, d), jnp.bfloat16)
    v = on_chip((kv_heads, 8192, dv), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < (
        heads * 8192 * 256 * 4 * (2 if d != dv else 1))


@pytest.mark.parametrize("window", [4096, None])
def test_flash_kernels_under_a_window_at_16384_positions(window):
    """The window-and-full-attention LM's attention of one step (ISSUE
    37): 28 query heads over 4 key/value heads of 128 at 16384
    positions, with the 4096-key band and without. Blocks of 1024 by
    the rule, the clamped maps' integer arithmetic inside Mosaic's
    index maps, and all three kernels compile; the temporaries stay
    under one float32 copy of the queries."""
    q = on_chip((28, 16384, 128), jnp.bfloat16)
    k = on_chip((4, 16384, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, window=window, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, k).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < 28 * 16384 * 128 * 4


@pytest.mark.parametrize("heads,window", [(72, 512), (48, None)])
def test_flash_kernels_at_head_counts_that_differ_by_layer(heads, window):
    """The Laguna cell's attention of one sequence (ISSUE 44): 72 query
    heads behind a 512-key band and 48 behind full attention, over 8
    key/value heads of 128 at 8192 positions (9 and 6 query heads a
    key/value head). The band takes the rule's shorter blocks, and all
    three kernels compile with them; the temporaries stay under one
    float32 copy of the queries."""
    q = on_chip((heads, 8192, 128), jnp.bfloat16)
    k = on_chip((8, 8192, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, window=window, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, k).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < heads * 8192 * 128 * 4


def test_grouped_matmul_over_held_experts_forward_and_backward():
    """20480 slot rows over 32 held experts at hidden 2048 and twice
    the expert width: the grouped product and both of its gradients
    are Mosaic kernels."""
    from elephas_tpu.ops.moe import grouped_matmul

    rows = on_chip((20480, 2048), jnp.bfloat16)
    experts = on_chip((32, 2048, 1024), jnp.bfloat16)
    sizes = on_chip((32,), jnp.int32)

    def loss(rows, experts, sizes):
        out = grouped_matmul(rows, experts, sizes, kernel=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    assert kernels_in(jax.grad(loss, (0, 1)), rows, experts, sizes) >= 3


def test_chunked_gated_delta_rule_fits_at_published_widths():
    """32 value heads of 128 x 128 state over 2 x 8192 tokens in chunks
    of 64, forward and backward: compiles, and under 16 GiB."""
    from elephas_tpu.ops.gated_delta import gated_delta_rule

    qk = on_chip((2, 8192, 32, 128), jnp.bfloat16)
    gate = on_chip((2, 8192, 32), jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(gated_delta_rule(q, k, v, g, beta)[0].astype(
            jnp.float32))

    kernels_in(jax.grad(jax.checkpoint(loss), (0, 1, 2, 3, 4)),
               qk, qk, qk, gate, gate)


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)])
def test_grouped_matmul_at_widths_that_are_no_power_of_two(k, n):
    """12288 slot rows over 8 held experts at hidden 2688 and expert
    width 1856 (21 x 128 and 14.5 x 128): the tiles are multiples of
    128 that divide 2688 or pad 1856 least, laid over both widths by
    the backward products, and all three kernels compile (a tile of 64,
    the power-of-two rule's, or of the whole 1856 is refused)."""
    from elephas_tpu.ops.moe import _lane_tile, grouped_matmul

    assert (_lane_tile(2688, 1024), _lane_tile(1856, 1024)) == (896, 640)
    # the widths of the three LMs the benchmark held before keep theirs
    assert [_lane_tile(w, 1024) for w in (2048, 512, 768, 2560)] == [
        1024, 512, 256, 512]
    rows = on_chip((12288, k), jnp.bfloat16)
    experts = on_chip((8, k, n), jnp.bfloat16)
    sizes = on_chip((8,), jnp.int32)

    def loss(rows, experts, sizes):
        out = grouped_matmul(rows, experts, sizes, kernel=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    assert kernels_in(jax.grad(loss, (0, 1)), rows, experts, sizes) >= 3


@pytest.mark.parametrize("rows,width,weighted", [
    (20480, 2048, True), (24576, 2560, False), (12288, 2688, True),
    (12288, 6, True)])
def test_rows_summed_by_token_on_the_mxu(rows, width, weighted):
    """The sparse block's row-to-token sum at the cells' buffers and
    widths (2688 is 21 x 128; 6 is a weight gradient's ``k`` columns,
    padded to a lane tile): one Mosaic kernel, with the weights as three
    bfloat16 terms or without."""
    from elephas_tpu.ops.moe import RoutePlan, _sum_rows_by_token

    tokens = 16384
    ints = lambda *shape: on_chip(shape, jnp.int32)  # noqa: E731
    plan = RoutePlan(
        ints(rows), on_chip((rows,), jnp.float32), ints(8), ints(rows),
        ints(rows), ints(rows), on_chip((rows,), jnp.float32),
        ints(tokens // 128))

    def summed(values, plan):
        return _sum_rows_by_token(
            values, plan, tokens, dtype=jnp.bfloat16, kernel=True,
            weights=plan.weight_of_rank if weighted else None)

    assert kernels_in(summed, on_chip((rows, width), jnp.bfloat16), plan) == 1


def test_chunked_ssd_scan_fits_at_published_widths():
    """64 heads of 64 over a 128-wide state, B and C in 8 groups, 2 x
    8192 tokens in chunks of 128, forward and backward under a
    checkpoint: compiles, and its temporaries stay under 4 GiB (a
    layer's decay factors are 537 MB in float32)."""
    from elephas_tpu.ops.ssd import ssd_chunked

    x = on_chip((2, 8192, 64, 64), jnp.bfloat16)
    dt = on_chip((2, 8192, 64), jnp.float32)
    head = on_chip((64,), jnp.float32)
    bc = on_chip((2, 8192, 8, 128), jnp.bfloat16)

    def loss(x, dt, a_neg, b_in, c_in, d_skip):
        return jnp.sum(ssd_chunked(
            x, dt, a_neg, b_in, c_in, d_skip, 128)[0].astype(jnp.float32))

    compiled = jax.jit(jax.grad(jax.checkpoint(loss), range(6))).lower(
        x, dt, head, bc, bc, head).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30
