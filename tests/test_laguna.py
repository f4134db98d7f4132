"""The Laguna block (``laguna_lm``) against the benchmark's plain
reference, at a small size on the CPU: widths cut, ratios kept (18 and
12 query heads over 2 key/value heads, 9 and 6 a head as 72 and 48 over
8; a window of 8 keys over 24 positions; 10 of 64 experts a token with
2 held, a 32nd; layer 0 full attention and a dense SwiGLU, layer 1 a
sliding window and the sparse block, layer 2 full attention and the
sparse block; the rotations' parameters as published)."""

import hashlib
import importlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "laguna-s-2.1-ep32"

ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
}
CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 3,
    "intermediate_size": 48, "num_attention_heads": 12,
    "num_attention_heads_per_layer": [12, 18, 12, 18],
    "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["full_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "sliding_window": 8, "rope_parameters": ROPE, "gating": "per-head",
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "num_experts": 64, "num_experts_per_tok": 10,
    "moe_intermediate_size": 12, "shared_expert_intermediate_size": 12,
    "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5,
    "moe_apply_router_weight_on_input": False,
    "moe_router_logit_softcapping": 0,
    "num_experts_held": 2, "experts_held_first": 4,
    "sequence_length": 24, "remat": True, "dtype": "float32",
    "assumed": {"initializer_range": 0.2,
                "attention_gate": "sigmoid_of_layer_input_per_head",
                "scoring_func": "sigmoid", "band": "sliding_window"},
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9},
}
SEQ, HIDDEN = CFG["sequence_length"], CFG["hidden_size"]
HELD = (4, 6)
FAULTS = {"attention_gate": "none", "scoring_func": "softmax",
          "band": "none"}


def _load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + kind + "_" + re.sub(r"[^a-z0-9]", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference", NAME)


@pytest.fixture(scope="module")
def builder():
    return _load("builders", "keras_laguna")


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max(), scale)


def _mm(ref):
    return lambda a, w: jnp.matmul(a, w, precision=ref.HI)


def _ident(t):
    return t


def _faulty(key):
    return dict(CFG, assumed=dict(CFG["assumed"], **{key: FAULTS[key]}))


def _layer_params(ref, prefix, seed=0, cfg=CFG):
    params = ref.init_params(cfg, seed)
    return {k: v for k, v in params.items() if k.startswith(prefix)}


def _attn_layer(i, remat=False, **more):
    from elephas_tpu.models import laguna
    from elephas_tpu.models import lm_mixers as zoo

    kind = CFG["layer_types"][i]
    args = dict(
        window=CFG["sliding_window"] if kind == "sliding_attention" else None,
        gating=CFG["gating"], init_std=0.2, remat=remat,
        name=f"layer{i}_attn",
        **laguna.rotation_of(ROPE[kind], CFG["head_dim"]))
    args.update(more)
    return zoo.BandedAttention(
        CFG["num_attention_heads_per_layer"][i], CFG["num_key_value_heads"],
        CFG["head_dim"], **args)


def _moe_layer(held=HELD, remat=False, name="layer1_moe"):
    from elephas_tpu.models import lm_blocks as zoo

    return zoo.SparseMoeBlock(
        CFG["num_experts"], CFG["num_experts_per_tok"],
        CFG["moe_intermediate_size"], CFG["shared_expert_intermediate_size"],
        held, scoring_func="sigmoid",
        routed_scaling_factor=CFG["moe_routed_scaling_factor"],
        gated_shared_expert=False, remat=remat, name=name)


def _stateless(layer, params, *inputs):
    """``(result, non-trainable variables after the call)`` with every
    variable the reference names taken from ``params``."""
    tv = [params[v.path] for v in layer.trainable_variables]
    ntv = [params.get(v.path, v.value)
           for v in layer.non_trainable_variables]
    return layer.stateless_call(tv, ntv, *inputs)


def _inputs(seed=5, rows=2):
    return jax.random.normal(jax.random.key(seed), (rows, SEQ, HIDDEN))


# -- the rotation's tables -----------------------------------------------------


def test_the_yarn_table_is_the_closed_form(ref):
    """``_rope_tables`` under the published full-attention group
    against the closed form written out here (64 rotated dimensions,
    base 500000, factor 128 from 8192 positions, the ramp between the
    pairs that turn 32 times and once), and against the reference's
    float64 table; the stated ``attention_factor`` is the standard
    rule's ``0.1 ln 128 + 1``."""
    from elephas_tpu.models.lm_mixers import YARN_KEYS
    from elephas_tpu.models.transformer import _rope_tables

    group = ROPE["full_attention"]
    yarn = tuple(group[k] for k in YARN_KEYS)
    cos, sin = _rope_tables(4096, 64, 500000.0, yarn)
    assert cos.shape == sin.shape == (4096, 64)
    i = np.arange(32)
    f = 500000.0 ** (2 * i / 64)
    c = lambda n: 64 * np.log(8192 / (2 * np.pi * n)) / (  # noqa: E731
        2 * np.log(500000.0))
    low, high = np.floor(c(32)), np.ceil(c(1))
    assert (low, high) == (9, 18)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    freq = ramp / (128 * f) + (1 - ramp) / f
    angle = np.arange(4096)[:, None] * freq[None, :]
    factor = 1.4852030263919618
    assert factor == pytest.approx(0.1 * np.log(128) + 1, abs=1e-15)
    for got, want in ((cos, np.cos(angle)), (sin, np.sin(angle))):
        np.testing.assert_allclose(got[:, :32], factor * want, atol=2e-6)
        np.testing.assert_array_equal(got[:, :32], got[:, 32:])
    ref_cos, ref_sin = ref.rope_tables(4096, group, 128)
    np.testing.assert_allclose(cos[:, :32], ref_cos, atol=2e-6)
    np.testing.assert_allclose(sin[:, :32], ref_sin, atol=2e-6)
    # the factor left to the rule is the published one
    ruled = _rope_tables(4096, 64, 500000.0, yarn[:4] + (None,))
    np.testing.assert_allclose(ruled[0], cos, atol=1e-7)


def test_yarn_at_factor_one_is_the_plain_table():
    from elephas_tpu.models.transformer import _rope_tables

    plain = _rope_tables(512, 64, 500000.0)
    same = _rope_tables(512, 64, 500000.0, (1, 8192, 32, 1, None))
    scaled = _rope_tables(512, 64, 500000.0, (128, 8192, 32, 1, 1.0))
    for got, want, other in zip(same, plain, scaled):
        np.testing.assert_array_equal(got, want)
        # the fastest pairs keep their frequency, the slowest do not
        np.testing.assert_array_equal(other[:, :9], want[:, :9])
        assert np.abs(other[:, 18:32] - want[:, 18:32]).max() > 1e-3


# -- the gate and the grouped heads ----------------------------------------


def test_zero_gate_weights_halve_every_heads_result(ref):
    """``sigmoid(0) = 1/2`` a head and token: with ``W_g`` zero the
    gated layer gives half of what the same layer gives without its
    gate, and with large ``W_g`` columns for some heads those heads
    pass whole."""
    x = _inputs(6)
    params = _layer_params(ref, "layer1_attn/", seed=1)
    gated, plain = _attn_layer(1), _attn_layer(1, gating=None)
    gated.build(x.shape), plain.build(x.shape)
    assert {v.path for v in gated.variables} == set(params)
    assert {v.path for v in plain.variables} == set(params) - {
        "layer1_attn/g_proj"}
    zero = dict(params, **{"layer1_attn/g_proj": jnp.zeros((HIDDEN, 18))})
    whole = jax.jit(lambda p, x: _stateless(plain, p, x)[0])(params, x)
    through_gate = jax.jit(lambda p, x: _stateless(gated, p, x)[0])
    _close(through_gate(zero, x), 0.5 * whole, 1e-6)
    assert np.abs(np.asarray(through_gate(params, x))
                  - 0.5 * np.asarray(whole)).max() > 1e-3


@pytest.mark.parametrize("group", [9, 6])
def test_grouped_heads_under_a_512_band(group):
    """9 and 6 query heads a key/value head (72 and 48 over 8) through
    the flash kernels at a 512-key band, forward and gradients, at
    several pairs of blocks and at the rule's own, against the plain
    attention; the band is what the op computes (full causal attention
    is further off)."""
    from elephas_tpu.ops.flash_attention import (
        attention_reference, flash_attention)

    ks = jax.random.split(jax.random.key(group), 4)
    s, d = 1024, 32
    q = jax.random.normal(ks[0], (1, group, s, d))
    k = jax.random.normal(ks[1], (1, 1, s, d))
    v = jax.random.normal(ks[2], (1, 1, s, d))
    g = jax.random.normal(ks[3], (1, group, s, d))
    want_fn = lambda q, k, v, w=512: attention_reference(  # noqa: E731
        q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1), causal=True,
        window=w)
    with jax.default_matmul_precision("highest"):
        want = want_fn(q, k, v)
        want_grads = jax.grad(
            lambda *a: jnp.sum(want_fn(*a) * g), (0, 1, 2))(q, k, v)
        assert np.abs(np.asarray(want - want_fn(q, k, v, None))).max() > 0.1
        for blocks in ((128, 256), (256, 128), (None, None)):
            got_fn = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=True, window=512, block_q=blocks[0],
                block_k=blocks[1])
            _close(got_fn(q, k, v), want, 1e-5)
            grads = jax.grad(
                lambda *a: jnp.sum(got_fn(*a) * g), (0, 1, 2))(q, k, v)
            for got, w in zip(grads, want_grads):
                _close(got, w, 1e-5)


# -- the block rule --------------------------------------------------------


def test_the_block_rule_reads_the_band():
    """One rule from the sequences, VMEM and the band: a 4096-key band
    at 16384 positions keeps the blocks the sequences alone give (the
    SmallThinker cell's), under a 512-key band no query block is longer
    than the band (the forward kernel keeps its long key block: what
    the chip measured fastest), a shorter band takes the 512-key band's
    blocks (nothing under 512 goes ahead of the table's order: the chip
    put 256-blocks at twice and 128-blocks at six times the time), and
    a named block still rules."""
    fa = importlib.import_module("elephas_tpu.ops.flash_attention")
    resolve = lambda s, window, kernel, named=None: fa._resolve_blocks(  # noqa: E731
        named, named, s, s, 128, 128, 2, kernel, window)
    for kernel in ("fwd", "bwd"):
        assert resolve(16384, 4096, kernel) == resolve(
            16384, None, kernel) == (1024, 1024)
        assert resolve(8192, 1024, kernel) == (1024, 1024)
        for band in (100, 256):
            assert resolve(8192, band, kernel) == resolve(8192, 512, kernel)
        assert resolve(8192, 512, kernel, named=1024) == (1024, 1024)
    assert resolve(8192, 512, "fwd") == (512, 1024)
    assert resolve(8192, 512, "bwd") == (512, 512)
    # the scores a head and sequence computes: 15 pairs of 1024-blocks
    # hold nearly four times the band's, the rule's blocks fewer
    scores = lambda q, k: q * k * sum(  # noqa: E731
        bool(fa._pair_seen(i, j, q, k, 512))
        for i in range(8192 // q) for j in range(8192 // k))
    assert scores(1024, 1024) > 3.5 * 8192 * 512
    assert scores(512, 1024) < scores(1024, 1024)
    assert scores(512, 512) < 2 * 8192 * 512


# recorded at PR 44's parent (5e30c99) under jax 0.9.0: sha256 of the
# text of the jaxpr and of its gradient's (addresses blanked), of the
# attention layers as the other two cells' models build them and of the
# flash op under SmallThinker's band, at those cells' shapes. PR 45 put
# the windowed kernels on a grid of the band alone, which had to move
# the two digests with a window in them and no other: they were
# recorded again at its tree ("smallthinker-window" 0a7a0c2ec877d225
# before, FLASH_4096_AT_16384 d809d767066f0883); the two full-attention
# digests were PR 44's parent's still, the proof that no window traces as
# it did. PR 46 made the layer name and keep q, k and v
# (``BandedAttention.kept``), which had to move the three layers' digests
# (the forward text gains three ``name`` equations, the gradient's loses
# the recomputed projections) and not the flash op's: the three were
# recorded again at its tree ("smallthinker-window" c57378832976a18c,
# "smallthinker-full" cdfae97de721dad7, "nemotron-full" 2125495016be3b34
# before); FLASH_4096_AT_16384 is PR 45's still, the proof that the op
# every other caller shares traces as it did
PARENT_JAXPRS = {
    "smallthinker-window": (
        (28, 4, 128, 4096, True, 1500000), (1, 16384, 2560),
        "6aa3e7c2d5a8bd20"),
    "smallthinker-full": (
        (28, 4, 128, None, False, 1500000), (1, 16384, 2560),
        "6bfc33aff6e3158a"),
    "nemotron-full": (
        (32, 2, 128, None, False), (2, 8192, 2688), "4694e49773c83f64"),
}
FLASH_4096_AT_16384 = "ed6ffb2287124f22"


def _digest(fn, *args):
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32))  # noqa: E731
    wrt = tuple(range(len(args)))
    text = str(jax.make_jaxpr(fn)(*args)) + str(
        jax.make_jaxpr(jax.grad(loss, wrt))(*args))
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("model", sorted(PARENT_JAXPRS))
def test_the_other_cells_attention_layers_trace_as_before(model):
    """With the arguments ``smallthinker_lm`` and ``nemotron_h_lm``
    pass, ``BandedAttention`` and its gradient trace to the program
    they traced to before the layer had a gate, a rotated width or
    YaRN: naming the defaults changes nothing, and (under the jax the
    digest was recorded with) the text is the parent's."""
    from elephas_tpu.models import lm_mixers as zoo

    args, shape, digest = PARENT_JAXPRS[model]
    x = jnp.zeros(shape, jnp.bfloat16)

    def traced(**more):
        layer = zoo.BandedAttention(
            *args, init_std=0.02, remat=True, name="a", **more)
        layer.build(shape)
        tv = [v.value for v in layer.trainable_variables]
        return _digest(
            lambda tv, x: layer.stateless_call(tv, [], x)[0], tv, x)

    got = traced()
    assert got == traced(gating=None, rotary_dim=None, yarn=None)
    assert got != traced(gating="per-head")
    if jax.__version__ == "0.9.0":
        assert got == digest


def test_smallthinkers_band_keeps_its_flash_program():
    """``flash_attention(window=4096)`` at 16384 positions, 28 heads
    over 4: the program the SmallThinker cell runs (recorded at PR 45,
    on the band's grid), the one that names 1024-blocks: the rule that
    reads the band left its blocks alone."""
    from elephas_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 28, 16384, 128), jnp.bfloat16)
    k = jnp.zeros((1, 4, 16384, 128), jnp.bfloat16)
    ruled = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=4096, interpret=False)
    got = _digest(ruled, q, k, k)
    if jax.__version__ == "0.9.0":
        assert got == FLASH_4096_AT_16384
    named = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=4096, interpret=False, block_q=1024,
        block_k=1024)
    assert _digest(named, q, k, k) == got
    short = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=512, interpret=False)
    assert _digest(short, q, k, k) != got


# -- what a recomputed layer keeps -------------------------------------------

KEPT_KINDS = {  # (layer of CFG, the gate)
    "banded-gated": (1, "per-head"), "banded": (1, None),
    "full-gated": (2, "per-head"), "full": (2, None)}


def _kept_layer(kind, remat=True):
    """``(layer, loss(params, x), params, x)``: a layer of its own for
    each trace."""
    i, gating = KEPT_KINDS[kind]
    x = _inputs()
    layer = _attn_layer(i, remat, gating=gating)
    layer.build(x.shape)
    params = [0.2 * jax.random.normal(jax.random.key(n), v.shape)
              for n, v in enumerate(layer.trainable_variables)]
    loss = lambda p, x: jnp.sum(  # noqa: E731
        jnp.sin(3.0 * layer.stateless_call(p, [], x)[0]))
    return layer, loss, params, x


@pytest.mark.parametrize("kind", sorted(KEPT_KINDS))
def test_a_recomputed_layer_projects_once(kind, monkeypatch, capsys):
    """Under ``remat`` the layer keeps q, k and v as it hands them to the
    flash kernels (heads first, k and v at their own head count) beside
    the forward kernel's two results: the gradient's program holds the
    products of x by ``q_proj``, ``k_proj`` and ``v_proj`` once, three
    products fewer than with the two results alone kept (PR 45's
    ``kept``, under which the backward pass projected again); the gate's
    small product still runs twice."""
    from elephas_tpu.models import lm_mixers as zoo
    from elephas_tpu.ops.flash_attention import LSE_NAME, OUT_NAME

    def products():
        _layer, loss, params, x = _kept_layer(kind)
        text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x))
        return text.count("dot_general"), text

    layer, loss, params, x = _kept_layer(kind)
    assert layer.kept == (
        zoo.Q_NAME, zoo.K_NAME, zoo.V_NAME, OUT_NAME, LSE_NAME)
    now, text = products()
    for name in layer.kept:
        assert f"name={name}" in text
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(loss, params, x)
    saved = capsys.readouterr().out
    b, s = x.shape[:2]
    h, hk, d = layer.num_heads, layer.num_kv_heads, layer.head_dim
    for shape, times in (((b, h, s, d), 1), ((b, hk, s, d), 2)):
        assert saved.count("f32[" + ",".join(map(str, shape)) + "]") == times
    assert f"f32[{b * h},{s},{d}]" in saved  # the forward kernel's result
    assert f"named '{LSE_NAME}'" in saved
    monkeypatch.setattr(type(layer), "kept", (OUT_NAME, LSE_NAME))
    before, _ = products()
    assert before - now == 3


@pytest.mark.parametrize("kind", sorted(KEPT_KINDS))
def test_a_recomputed_layers_gradients_are_the_plain_layers(kind):
    _layer, loss, params, x = _kept_layer(kind, remat=True)
    got = jax.jit(jax.grad(loss, (0, 1)))(params, x)
    _layer, plain, _params, _x = _kept_layer(kind, remat=False)
    want = jax.jit(jax.grad(plain, (0, 1)))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 5e-4)


@pytest.mark.parametrize("shape,heads", [
    ((2, 24, 32), (18, 2, 16)), ((1, 64, 48), (28, 4, 8))])
def test_the_kept_event_counts_bytes_from_the_shapes(shape, heads):
    """Tracing a recomputed layer emits one ``remat.kept`` event: the
    names it keeps, and the bytes of the three the layer itself names:
    q, k and v in bfloat16 hold ``B x S x (H + 2 Hk) x D x 2``; a layer
    that recomputes nothing emits none."""
    from elephas_tpu import telemetry
    from elephas_tpu.models import lm_mixers as zoo

    (b, s, _), (h, hk, d) = shape, heads
    x = jnp.zeros(shape, jnp.bfloat16)

    def events(remat):
        layer = zoo.BandedAttention(
            h, hk, d, window=8, dtype="bfloat16", remat=remat,
            name=f"kept{b}x{s}")
        layer.build(shape)
        tv = [v.value.astype(jnp.bfloat16)
              for v in layer.trainable_variables]
        since = telemetry.default_tracer().seq
        jax.make_jaxpr(lambda tv, x: layer.stateless_call(tv, [], x)[0])(
            tv, x)
        return telemetry.default_tracer().events(
            since_seq=since, name="remat.kept")

    assert events(remat=False) == []
    (event,) = events(remat=True)
    args = event["args"]
    assert args["layer"] == f"kept{b}x{s}"
    assert args["kept"] == [
        zoo.Q_NAME, zoo.K_NAME, zoo.V_NAME, "flash_out", "flash_lse"]
    held = args["bytes"]
    assert sum(held[n] for n in (zoo.Q_NAME, zoo.K_NAME, zoo.V_NAME)) == (
        b * s * (h + 2 * hk) * d * 2)
    assert held == {zoo.Q_NAME: b * s * h * d * 2,
                    zoo.K_NAME: b * s * hk * d * 2,
                    zoo.V_NAME: b * s * hk * d * 2}


# -- each layer kind against the reference ----------------------------------


@pytest.mark.parametrize("kind,remat", [
    ("sliding", False), ("sliding", True), ("full", True), ("moe", True),
    ("dense", True)])
def test_layer_forward_and_gradients(ref, kind, remat):
    x = _inputs()
    if kind == "moe":
        layer, prefix = _moe_layer(remat=remat), "layer1_moe/"
        want_fn = lambda p, x: ref._sparse_block(  # noqa: E731
            p, prefix, x, CFG, _ident, _mm(ref))
    elif kind == "dense":
        from elephas_tpu.models import lm_blocks as zoo

        layer = zoo.DenseMLP(CFG["intermediate_size"], 0.2, remat=remat,
                             name="layer0_mlp")
        prefix = "layer0_mlp/"
        want_fn = lambda p, x: ref._swiglu(  # noqa: E731
            x, p[prefix + "gate_up"], p[prefix + "down"], _ident, _mm(ref))
    else:
        i = 1 if kind == "sliding" else 2
        layer, prefix = _attn_layer(i, remat), f"layer{i}_attn/"
        want_fn = lambda p, x: ref._attention(  # noqa: E731
            p, prefix, x, CFG, kind == "sliding",
            CFG["num_attention_heads_per_layer"][i], _ident, _mm(ref))
    layer.build(x.shape)
    params = _layer_params(ref, prefix)
    assert {v.path for v in layer.variables
            if not v.path.endswith("/route_counts")} == set(params)
    got_fn = lambda p, x: _stateless(layer, p, x)[0]  # noqa: E731
    _close(jax.jit(got_fn)(params, x), jax.jit(want_fn)(params, x))
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(3.0 * f(p, x)))  # noqa: E731
    got = jax.jit(jax.grad(loss(got_fn), (0, 1)))(params, x)
    want = jax.jit(jax.grad(loss(want_fn), (0, 1)))(params, x)
    _close(got[1], want[1], 5e-4)
    for path in params:
        _close(got[0][path], want[0][path], 5e-4)


def test_each_fault_changes_the_references_layer(ref):
    """The three readings the reference takes as faults are other
    functions: the gate left out, the band left out, a softmax score."""
    x = _inputs(8)
    attn = _layer_params(ref, "layer1_attn/")
    sound = ref._attention(
        attn, "layer1_attn/", x, CFG, True, 18, _ident, _mm(ref))
    for key in ("attention_gate", "band"):
        other = ref._attention(
            attn, "layer1_attn/", x, _faulty(key), True, 18, _ident, _mm(ref))
        assert np.abs(np.asarray(other - sound)).max() > 1e-2
    moe = _layer_params(ref, "layer1_moe/")
    weights, chosen = ref.route(
        x.reshape(-1, HIDDEN), moe["layer1_moe/router"], CFG)
    other, _ = ref.route(x.reshape(-1, HIDDEN), moe["layer1_moe/router"],
                         _faulty("scoring_func"))
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(other.sum(-1), 2.5, rtol=1e-6)
    assert chosen.shape == (2 * SEQ, 10)
    assert np.abs(np.asarray(other - weights)).max() > 1e-3
    with pytest.raises(ValueError, match="attention_gate"):
        ref._attention(
            attn, "layer1_attn/", x,
            dict(CFG, assumed=dict(CFG["assumed"], attention_gate="tanh")),
            True, 18, _ident, _mm(ref))


# -- the share of a deployment ------------------------------------------------


def test_shares_add_up_to_the_uncut_layer(ref):
    """The routed parts of all 32 shares (2 of the 64 experts each)
    plus the shared expert, which every chip computes alike, counted
    once, add up to what the uncut reference (all 64 held) gives for
    the layer at this rule: sigmoid scores, 10 chosen, normalised,
    times 2.5."""
    whole_cfg = dict(CFG, num_experts_held=64, experts_held_first=0)
    params = _layer_params(ref, "layer1_moe/", seed=3, cfg=whole_cfg)
    x = _inputs(10, rows=1)
    mm = _mm(ref)
    want = ref._sparse_block(params, "layer1_moe/", x, whole_cfg, _ident, mm)
    shared = ref._swiglu(
        x.reshape(-1, HIDDEN), params["layer1_moe/shared_expert/gate_up"],
        params["layer1_moe/shared_expert/down"], _ident, mm).reshape(x.shape)
    total, routed_slots = -31.0 * shared, 0
    for share in range(32):
        first = 2 * share
        layer = _moe_layer((first, first + 2))
        layer.build(x.shape)
        mine = dict(params)
        for name in ("experts_gate_up", "experts_down"):
            mine["layer1_moe/" + name] = params[
                "layer1_moe/" + name][first:first + 2]
        out, ntv = _stateless(layer, mine, x)
        total = total + out
        counts = [v for v in ntv if v.dtype == jnp.int32]
        routed_slots += int(counts[0][0])
    _close(total, want)
    assert routed_slots == SEQ * 10  # every slot is some share's


# -- the whole model through SparkModel.fit -----------------------------------


def _tokens(seed, rows=4):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CFG["vocab_size"], size=(rows, SEQ + 1))
    tok = tok.astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def test_model_forward_is_the_references_and_not_a_faults(ref, builder):
    """Logits of the whole model against the reference's forward pass;
    the reference under each of the three faults is further off than
    that, and the builder builds none of the faults it could be asked
    for."""
    params = ref.init_params(CFG, 6)
    model = builder.build(dict(CFG), params)
    assert [model.get_layer(f"layer{i}_attn").num_heads
            for i in range(3)] == [12, 18, 12]
    assert [model.get_layer(f"layer{i}_attn").window
            for i in range(3)] == [None, 8, None]
    assert model.get_layer("layer0_attn").rotary_dim == 8
    assert model.get_layer("layer0_attn").yarn["factor"] == 128
    assert model.get_layer("layer1_attn").yarn is None
    x, _ = _tokens(6, rows=2)
    got = np.asarray(jax.jit(lambda x: model(x))(x))
    forward = lambda cfg: np.asarray(jax.jit(  # noqa: E731
        lambda p, x: ref.forward(p, x, cfg))(params, x))
    _close(got, forward(CFG))
    for key in FAULTS:
        want = forward(_faulty(key))
        assert np.abs(got - want).max() > 1e-2 * np.abs(want).max(), key
    for key in ("attention_gate", "band"):
        with pytest.raises(ValueError, match=key):
            builder.build(_faulty(key), params)


@pytest.fixture(scope="module")
def fitted(ref, builder):
    """Two SGD steps (one epoch of 4 sequences, 2 a step) through
    ``SparkModel.fit`` from the reference's seeded weights."""
    from elephas_tpu import SparkModel, telemetry
    from elephas_tpu.data import SparkContext
    from elephas_tpu.utils import rdd_utils

    params = ref.init_params(CFG, 7)
    model = builder.build(dict(CFG), params)
    x, y = _tokens(7)
    rdd = rdd_utils.to_simple_rdd(SparkContext("local[1]"), x, y,
                                  num_partitions=1)
    since = telemetry.default_tracer().seq
    history = SparkModel(model, mode="synchronous", num_workers=1).fit(
        rdd, epochs=1, batch_size=2)
    events = telemetry.default_tracer().events(since, name="fit.counters")
    want = ref.follow(CFG, 7, [(x[:2], y[:2]), (x[2:], y[2:])])
    return {"model": model, "history": history, "want": want,
            "start": {k: np.asarray(v) for k, v in params.items()},
            "events": events}


def test_fit_step_loss_matches_reference(fitted):
    got = fitted["history"]["loss"][0]
    assert abs(got - np.mean(fitted["want"]["losses"])) < 2e-4 * got


def test_fit_step_momenta_and_change_match_reference(fitted):
    model, want = fitted["model"], fitted["want"]
    norm = lambda a: float(np.sqrt(np.sum(np.square(  # noqa: E731
        np.asarray(a, np.float64)))))
    momenta = {v.path: np.asarray(v.value)
               for v in model.optimizer.variables}
    variables = {v.path: np.asarray(v.value) for v in model.variables}
    assert set(want["velocity_norm"]) == {
        v.path for v in model.trainable_variables}
    floors = {k: float(np.median(list(want[k].values())))
              for k in ("velocity_norm", "change_norm")}
    for path, ref_norm in want["velocity_norm"].items():
        got = norm(momenta["SGD/" + path.replace("/", "_") + "_momentum"])
        assert abs(got - ref_norm) <= 2e-3 * max(
            ref_norm, floors["velocity_norm"]), path
        got = norm(variables[path] - fitted["start"][path])
        want_change = want["change_norm"][path]
        assert abs(got - want_change) <= 2e-3 * max(
            want_change, floors["change_norm"]), path


def test_fit_emits_one_counters_event_an_epoch(fitted):
    events = fitted["events"]
    assert len(events) == 1 and events[0]["mono_ns"] is not None
    layers = events[0]["args"]["layers"]
    assert sorted(layers) == ["layer1_moe", "layer2_moe"]
    for counts in layers.values():
        assert counts["slots"] == 4 * SEQ * 10
        assert 0 < counts["max_expert_tokens"] <= counts["held_slots"]
        assert counts["held_slots"] <= counts["slots"]


def test_builder_assign_checks_paths_and_zeroes_counters(fitted, ref, builder):
    model = fitted["model"]
    params = ref.init_params(CFG, 8)
    builder.assign(model, params)
    for var in model.variables:
        if var.path.endswith("/route_counts"):
            assert not np.asarray(var.value).any()
        else:
            np.testing.assert_array_equal(var.value, params[var.path])
    with pytest.raises(ValueError, match="differ"):
        builder.assign(model, {k: v for k, v in params.items()
                               if "g_proj" not in k})
    wrong = dict(params)
    wrong["layer1_attn/q_proj"] = params["layer1_attn/q_proj"][:, :-1]
    with pytest.raises(ValueError, match="q_proj"):
        builder.assign(model, wrong)


def test_reference_param_count_and_flops(builder, ref):
    """The published widths by shape arithmetic alone: layer 0
    157,440,000, a sliding layer 148,862,976, layer 4 129,914,880, the
    cell's 811,017,216 in all; 60 TFLOP a step of 16384 tokens forward
    and backward by the exact count of visible keys, 45% of it
    attention's projections."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    shapes = ref.param_shapes(cfg)
    size = lambda keep: sum(  # noqa: E731
        int(np.prod(shape)) for path, (shape, _kind) in shapes.items()
        if keep(path))
    assert size(lambda p: p.startswith("layer0_")) == 157_440_000
    assert size(lambda p: p.startswith("layer1_")) == 148_862_976
    assert size(lambda p: p.startswith("layer4_")) == 129_914_880
    assert size(lambda p: True) == cfg["parameters"] == 811_017_216
    assert ref.layer_kinds(cfg) == [
        (False, True, 48), (True, False, 72), (True, False, 72),
        (True, False, 72), (False, False, 48)]
    # the published per-layer lists, whole
    assert cfg["layer_types"] == [
        "sliding_attention" if l % 4 else "full_attention"
        for l in range(48)]
    assert cfg["num_attention_heads_per_layer"] == [
        72 if l % 4 else 48 for l in range(48)]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    traffic = {"sequence_length": 8192, "batch_size": 2}
    macs = builder.forward_macs_per_token(cfg, 8192)
    step = 2 * builder.train_flops_per_example(cfg, traffic)
    assert step == 2 * 6 * macs * 8192
    assert 59.5e12 < step < 60.5e12
    assert builder.visible_keys(8192) == 4096.5
    assert builder.visible_keys(8192, 512) == pytest.approx(496.03, abs=0.01)
    projections = 2 * (2 * 3072 * 6144 + 2 * 3072 * 1024) + 3 * (
        2 * 3072 * 9216 + 2 * 3072 * 1024)
    assert 0.44 < projections / macs < 0.47
    experts = builder.moe_experts_step_cost(cfg, traffic, 8 * 640)
    assert experts["flops"] == 3 * 2 * 3 * 3072 * 1024 * 8 * 640
    assert experts["bytes"] > 4 * 8 * 3 * 3072 * 1024 * 8


def test_the_references_layerwise_step_is_the_gradient_of_its_loss(ref):
    """``follow`` takes a sequence's gradient a layer at a time into
    the velocity (so that it fits the chip): after one step from rest
    the velocity is ``-lr`` times ``jax.grad`` of the whole loss."""
    x, y = _tokens(10, rows=2)
    params = ref.init_params(CFG, 10)
    grads = jax.jit(jax.grad(
        lambda p: ref.loss_fn(p, x, y, CFG, False)))(params)
    got = ref.follow(CFG, 10, [(x, y)])
    lr = CFG["optimizer"]["learning_rate"]
    for path, norm in got["velocity_norm"].items():
        want = lr * float(jnp.sqrt(jnp.sum(jnp.square(grads[path]))))
        assert abs(norm - want) <= 1e-4 * max(want, 1e-6), path


def test_the_references_attention_in_blocks_is_the_whole_square(
        ref, monkeypatch):
    """The reference takes a head's attention a block of queries at a
    time (so that 8192 positions fit): in blocks of 8 it gives what one
    block of all 24 gives, sliding or full."""
    x = _inputs(12)
    for i, sliding in ((1, True), (2, False)):
        params = _layer_params(ref, f"layer{i}_attn/")
        fn = lambda: ref._attention(  # noqa: E731
            params, f"layer{i}_attn/", x, CFG, sliding,
            CFG["num_attention_heads_per_layer"][i], _ident, _mm(ref))
        whole = fn()
        monkeypatch.setattr(ref, "ATTN_ROWS", 8)
        _close(fn(), whole, 1e-6)
        monkeypatch.undo()


def test_control_one_precision_down_moves_a_layer(ref):
    """The reference with fp8 where the configuration holds bfloat16
    (the control that the limits of ``correct`` must refuse), on a
    sliding layer with its sparse block: finite, and another result and
    other gradients than the float32 reference's."""
    x = _inputs(13)
    params = ref._of_layer(ref.init_params(CFG, 13), 1)

    def grads(lower):
        loss = lambda p, x: jnp.sum(jnp.sin(ref._layer(  # noqa: E731
            p, x, CFG, True, False, 18, lower)))
        return jax.jit(jax.grad(loss))(params, x)

    sound, lower = grads(False), grads(True)
    for path, want in sound.items():
        got = np.asarray(lower[path])
        assert np.all(np.isfinite(got)), path
        norm = float(np.linalg.norm(np.asarray(want)))
        assert np.linalg.norm(got - np.asarray(want)) > 1e-3 * norm, path
