"""The hybrid linear-attention MoE block (``qwen3_next_lm``) against the
benchmark's plain reference, at a small size on the CPU: widths cut,
structure whole (16 experts with 4 held, top-2, two key heads over four
value heads, chunk 4, sequence 24)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 1e7, "rms_norm_eps": 1e-6, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "num_experts": 16, "num_experts_per_tok": 2, "num_experts_held": 4,
    "experts_held_first": 4, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "sequence_length": 24,
    "chunk_size": 4, "remat": True, "dtype": "float32",
    "assumed": {"initializer_range": 0.2},
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9},
}
SEQ = CFG["sequence_length"]


def _load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + kind + "_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference", "qwen3-next-80b-a3b-ep16")


@pytest.fixture(scope="module")
def builder():
    return _load("builders", "keras_qwen3_next")


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max(), scale)


# -- the chunked scan against the token-by-token recurrence ---------------


def _scan_inputs(s, heads=3, dk=8, dv=8, b=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, s, heads, dk))
    k = jax.random.normal(ks[1], (b, s, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, heads, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("s,chunk", [(22, 4), (24, 4), (7, 64), (33, 8)])
def test_chunked_scan_forward(s, chunk):
    from elephas_tpu.ops import gated_delta_rule, gated_delta_rule_recurrent

    args = _scan_inputs(s)
    want, want_state = gated_delta_rule_recurrent(*args)
    got, got_state = gated_delta_rule(*args, chunk_size=chunk)
    _close(got, want, 1e-5)
    _close(got_state, want_state, 1e-5)


@pytest.mark.parametrize("s,chunk", [(22, 4), (24, 4), (7, 64)])
def test_chunked_scan_backward(s, chunk):
    from elephas_tpu.ops import gated_delta_rule, gated_delta_rule_recurrent

    args = _scan_inputs(s, seed=1)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)[0]))  # noqa: E731
    want = jax.grad(loss(gated_delta_rule_recurrent), (0, 1, 2, 3, 4))(*args)
    got = jax.grad(
        loss(lambda *a: gated_delta_rule(*a, chunk_size=chunk)),
        (0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        _close(g, w, 1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s", [192, 150])
def test_chunked_scan_at_the_published_head_width(s, dtype):
    """128-wide heads in chunks of 64, what the benchmark's cell runs:
    three chunks, and a sequence that ends inside its third; four heads
    of which two share a ``q`` and ``k`` as the model's ``jnp.repeat``
    leaves them. ``o``, the final state and the five gradients, under a
    loss that reads both results, against the token-by-token rule.

    float32 inputs hold 1e-5 of the largest element. bfloat16 inputs
    hold 2^-6: every operand of every product is rounded to 8 bits
    (2^-9 of itself), a dozen such roundings stand between the inputs
    and a gradient, and the float32 oracle has none of them."""
    from elephas_tpu.ops import gated_delta_rule, gated_delta_rule_recurrent

    q, k = _scan_inputs(s, heads=2, dk=128, dv=128, seed=2)[:2]
    v, g, beta = _scan_inputs(s, heads=4, dk=128, dv=128, seed=3)[2:]
    q, k = jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2)
    args = tuple(t.astype(dtype) for t in (q, k, v)) + (g, beta)
    exact = tuple(t.astype(jnp.float32) for t in args)

    def results(f, a):
        def loss(*a):
            out, state = f(*a)
            return (jnp.sum(jnp.sin(out.astype(jnp.float32)))
                    + jnp.sum(jnp.cos(state)))
        return f(*a) + jax.grad(loss, (0, 1, 2, 3, 4))(*a)

    got = results(gated_delta_rule, args)
    want = results(gated_delta_rule_recurrent, exact)
    assert got[0].dtype == jnp.dtype(dtype)
    tol = 1e-5 if dtype == "float32" else 2.0 ** -6
    for ours, oracle in zip(got, want):
        _close(ours, oracle, tol)


# -- grouped-query flash attention ------------------------------------------


@pytest.mark.parametrize("heads,kv", [(4, 2), (8, 1), (4, 4)])
def test_flash_attention_grouped_query(heads, kv):
    from elephas_tpu.ops.flash_attention import (
        attention_reference, flash_attention,
    )

    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (2, heads, 32, 8))
    k = jax.random.normal(ks[1], (2, kv, 32, 8))
    v = jax.random.normal(ks[2], (2, kv, 32, 8))
    ours = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=8, block_k=8)
    plain = lambda q, k, v: attention_reference(  # noqa: E731
        q, jnp.repeat(k, heads // kv, 1), jnp.repeat(v, heads // kv, 1),
        causal=True)
    _close(ours(q, k, v), plain(q, k, v), 1e-5)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
    got = jax.grad(loss(ours), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        _close(g, w, 1e-5)


def test_flash_attention_refuses_uneven_groups():
    from elephas_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 4, 8, 8))
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(q, q[:, :3], q[:, :3])


# -- each layer kind against the reference ----------------------------------


def _layer_params(ref, prefix, seed=0):
    params = ref.init_params(CFG, seed)
    return {k: v for k, v in params.items() if k.startswith(prefix)}


def _keras_layer(kind, remat=False):
    from elephas_tpu import models as zoo

    if kind == "attn":
        return zoo.GatedAttention(
            CFG["num_attention_heads"], CFG["num_key_value_heads"],
            CFG["head_dim"], 4, CFG["rope_theta"], remat=remat,
            name="layer3_attn")
    if kind == "gdn":
        return zoo.GatedDeltaNet(
            CFG["linear_num_key_heads"], CFG["linear_num_value_heads"],
            CFG["linear_key_head_dim"], CFG["linear_value_head_dim"],
            CFG["linear_conv_kernel_dim"], CFG["chunk_size"], remat=remat,
            name="layer0_gdn")
    return zoo.SparseMoeBlock(
        CFG["num_experts"], CFG["num_experts_per_tok"],
        CFG["moe_intermediate_size"], CFG["shared_expert_intermediate_size"],
        (4, 8), remat=remat, name="layer0_moe")


REF_FN = {"attn": "_attention", "gdn": "_gated_delta_net",
          "moe": "_sparse_block"}
PREFIX = {"attn": "layer3_attn/", "gdn": "layer0_gdn/", "moe": "layer0_moe/"}


def _stateless(layer, params, x):
    tv = [params[v.path] for v in layer.trainable_variables]
    ntv = [v.value for v in layer.non_trainable_variables]
    out, _ntv = layer.stateless_call(tv, ntv, x)
    return out


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("kind", ["attn", "gdn", "moe"])
def test_layer_forward_and_gradients(ref, kind, remat):
    layer = _keras_layer(kind, remat)
    x = jax.random.normal(jax.random.key(5), (2, SEQ, CFG["hidden_size"]))
    layer.build(x.shape)
    params = _layer_params(ref, PREFIX[kind])
    assert {v.path for v in layer.trainable_variables} == set(params)
    plain = getattr(ref, REF_FN[kind])
    mm = lambda a, w: jnp.matmul(a, w, precision=ref.HI)  # noqa: E731

    def want_fn(p, x):
        return plain(p, PREFIX[kind], x, CFG, lambda t: t, mm)

    def got_fn(p, x):
        return _stateless(layer, p, x)

    _close(got_fn(params, x), want_fn(params, x))
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(3.0 * f(p, x)))  # noqa: E731
    got = jax.grad(loss(got_fn), (0, 1))(params, x)
    want = jax.grad(loss(want_fn), (0, 1))(params, x)
    _close(got[1], want[1])
    for path in params:
        _close(got[0][path], want[0][path], 5e-4)


def test_rematerialised_mixer_keeps_its_triangular_inverses(ref, monkeypatch):
    """A Gated DeltaNet layer under ``remat`` solves each chunk's
    triangular system once: its ``jax.checkpoint`` keeps the inverses
    (``GatedDeltaNet.kept``), so the gradient's program holds fewer
    float32 products at ``highest`` (the solve's and its backward
    rule's, nothing else in the layer asks for that precision) than it
    does with nothing kept, where the forward's are there twice; the
    gradients are the same numbers."""
    x = jax.random.normal(jax.random.key(6), (2, SEQ, CFG["hidden_size"]))
    params = _layer_params(ref, PREFIX["gdn"])

    def gradient_and_solves():
        layer = _keras_layer("gdn", remat=True)
        layer.build(x.shape)
        grad = jax.jit(jax.grad(
            lambda p, x: jnp.sum(jnp.sin(3.0 * _stateless(layer, p, x)))))
        solves = grad.lower(params, x).as_text().count("HIGHEST, HIGHEST")
        return grad(params, x), solves

    kept, solves_kept = gradient_and_solves()
    monkeypatch.setattr(type(_keras_layer("gdn")), "kept", ())
    recomputed, solves_recomputed = gradient_and_solves()
    assert 0 < solves_kept < solves_recomputed
    for path in params:
        np.testing.assert_array_equal(kept[path], recomputed[path])


def _attention_layer(kind):
    """One rematerialised attention layer of each model's kind, at this
    file's widths."""
    from elephas_tpu.models import lm_mixers

    if kind == "gated":
        return lm_mixers.GatedAttention(4, 2, 16, 4, remat=True, name="attn")
    if kind == "latent":
        return lm_mixers.LatentAttention(
            4, 16, 8, 16, 16, remat=True, name="attn")
    windowed = kind == "banded-window"
    return lm_mixers.BandedAttention(
        4, 2, 16, 6 if windowed else None, windowed, remat=True, name="attn")


@pytest.mark.parametrize(
    "kind", ["gated", "latent", "banded-window", "banded-full"])
def test_rematerialised_attention_runs_the_flash_forward_kernel_once(
        kind, monkeypatch):
    """An attention layer under ``remat`` keeps the flash forward
    kernel's result and log-sum-exp (its ``kept``): the gradient's
    program, lowered for the TPU, holds that kernel once, and twice with
    nothing kept, where the backward pass runs it again for the same two
    arrays; either way both backward kernels are there once and the
    gradients are the same numbers. ``BandedAttention`` keeps q, k and
    v too (since PR 46): the side it is compared with keeps those three
    and not the kernel's two, so that both sides hand the kernels the
    same tensors and the kernel's count alone differs."""
    from elephas_tpu.ops.flash_attention import LSE_NAME, OUT_NAME
    from elephas_tpu.utils import backend_guard

    x = jax.random.normal(jax.random.key(6), (2, SEQ, CFG["hidden_size"]))

    def layer_loss():  # a layer and a function of its own for each trace
        layer = _attention_layer(kind)
        layer.build(x.shape)
        return layer, lambda p, x: jnp.sum(
            jnp.sin(3.0 * layer.stateless_call(p, [], x)[0]))

    def gradient_and_kernels():
        layer, loss = layer_loss()
        params = [0.2 * jax.random.normal(jax.random.key(i), v.shape)
                  for i, v in enumerate(layer.trainable_variables)]
        gradient = jax.jit(jax.grad(loss, (0, 1)))(params, x)
        with monkeypatch.context() as compiled:  # kernels, not interpreted
            compiled.setattr(backend_guard, "pallas_interpret", lambda: False)
            text = jax.jit(jax.grad(layer_loss()[1], (0, 1))).trace(
                params, x).lower(lowering_platforms=("tpu",)).as_text()
        return gradient, [
            text.count(f'kernel_name = "{kernel}"') for kernel in
            ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel")]

    kept, kernels_kept = gradient_and_kernels()
    cls = type(_attention_layer(kind))
    monkeypatch.setattr(cls, "kept", tuple(
        name for name in cls.kept if name not in (OUT_NAME, LSE_NAME)))
    recomputed, kernels_recomputed = gradient_and_kernels()
    assert kernels_kept == [1, 1, 1]
    assert kernels_recomputed == [2, 1, 1]
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(recomputed)):
        np.testing.assert_array_equal(a, b)


def test_norm_and_swiglu(ref):
    from elephas_tpu.models import lm_blocks as zoo

    x = jax.random.normal(jax.random.key(6), (2, 5, 32))
    norm = zoo.ZeroCentredRMSNorm(name="n")
    norm.build(x.shape)
    w = jax.random.normal(jax.random.key(7), (32,)) * 0.1
    got, _ = norm.stateless_call([w], [], x)
    _close(got, ref._rms(x, 1e-6) * (1.0 + w), 1e-5)
    mlp = zoo.SwiGLU(16, name="m")
    mlp.build(x.shape)
    gate_up = jax.random.normal(jax.random.key(8), (32, 32)) * 0.2
    down = jax.random.normal(jax.random.key(9), (16, 32)) * 0.2
    got, _ = mlp.stateless_call([gate_up, down], [], x)
    gate, up = jnp.split(x @ gate_up, 2, axis=-1)
    _close(got, (jax.nn.silu(gate) * up) @ down, 1e-5)


# -- the share of a deployment ------------------------------------------------


def test_shares_add_up_to_the_uncut_layer(ref):
    """The routed parts of all four shares (4 of the 16 experts each),
    with the shared expert counted once, add up to what the uncut
    reference (all 16 held) gives for the layer."""
    from elephas_tpu.models import lm_blocks as zoo

    whole_cfg = dict(CFG, num_experts_held=16, experts_held_first=0)
    params = {k: v for k, v in ref.init_params(whole_cfg, 3).items()
              if k.startswith("layer0_moe/")}
    x = jax.random.normal(jax.random.key(10), (2, SEQ, 32))
    mm = lambda a, w: jnp.matmul(a, w, precision=ref.HI)  # noqa: E731
    want = ref._sparse_block(params, "layer0_moe/", x, whole_cfg,
                             lambda t: t, mm)
    no_shared = dict(params)
    no_shared["layer0_moe/shared_expert/down"] = jnp.zeros_like(
        params["layer0_moe/shared_expert/down"])
    total, routed_slots = 0.0, 0
    for share in range(4):
        first = 4 * share
        layer = zoo.SparseMoeBlock(16, 2, 16, 16, (first, first + 4),
                                   name="layer0_moe")
        layer.build(x.shape)
        mine = dict(params if share == 0 else no_shared)
        for name in ("experts_gate_up", "experts_down"):
            mine["layer0_moe/" + name] = params[
                "layer0_moe/" + name][first:first + 4]
        tv = [mine[v.path] for v in layer.trainable_variables]
        out, ntv = layer.stateless_call(
            tv, [v.value for v in layer.non_trainable_variables], x)
        total = total + out
        routed_slots += int(ntv[0][0])
    _close(total, want)
    assert routed_slots == 2 * SEQ * 2  # every slot is some share's


def test_no_token_dropped_when_all_choose_one_held_expert(ref):
    """A router that sends every token to held expert 5 (and its second
    choice anywhere): 48 rows on one expert, four times the mean of a
    uniform router over the four held, and still the reference's
    result."""
    layer = _keras_layer("moe")
    x = jax.random.normal(jax.random.key(11), (2, SEQ, 32))
    layer.build(x.shape)
    params = _layer_params(ref, "layer0_moe/", seed=4)
    # a constant input feature drives expert 5's score past all others
    x = x.at[..., 0].set(1.0)
    router = params["layer0_moe/router"].at[0, 5].set(60.0)
    params = dict(params, **{"layer0_moe/router": router})
    mm = lambda a, w: jnp.matmul(a, w, precision=ref.HI)  # noqa: E731
    want = ref._sparse_block(params, "layer0_moe/", x, CFG, lambda t: t, mm)
    tv = [params[v.path] for v in layer.trainable_variables]
    got, ntv = layer.stateless_call(
        tv, [v.value for v in layer.non_trainable_variables], x)
    _close(got, want)
    held_slots, slots, fullest = (int(v) for v in ntv[0][:3])
    assert slots == 2 * SEQ * 2 and fullest == 2 * SEQ
    assert held_slots >= 2 * SEQ


@pytest.mark.parametrize("held,bias", [((4, 8), 0.0), ((4, 8), 60.0),
                                       ((0, 16), 0.0), ((12, 16), 0.0)])
def test_held_experts_ffn_paths(held, bias):
    """The one-buffer path, the blocked path (past twice the uniform
    load) and the all-held path give the plain sum over held experts,
    forward and backward."""
    from elephas_tpu.ops import held_experts_ffn

    t, d, e, width, k = 48, 16, 16, 8, 2
    ks = jax.random.split(jax.random.key(12), 4)
    x = jax.random.normal(ks[0], (t, d)).at[:, 0].set(1.0)
    router = jax.random.normal(ks[1], (d, e)).at[0, 5].add(bias)
    n = held[1] - held[0]
    gate_up = jax.random.normal(ks[2], (n, d, 2 * width)) * 0.3
    down = jax.random.normal(ks[3], (n, width, d)) * 0.3

    def plain(x, router, gate_up, down):
        p = jax.nn.softmax(x @ router, -1)
        top, chosen = jax.lax.top_k(p, k)
        top = top / top.sum(-1, keepdims=True)
        y = 0.0
        for i in range(n):
            w = jnp.sum(jnp.where(chosen == held[0] + i, top, 0.0), -1)
            gate, up = jnp.split(x @ gate_up[i], 2, -1)
            y = y + w[:, None] * ((jax.nn.silu(gate) * up) @ down[i])
        return y

    args = (x, router, gate_up, down)
    got, counts = held_experts_ffn(*args, held, k)
    _close(got, plain(*args), 1e-5)
    assert int(counts[1]) == t * k
    if bias:
        assert int(counts[0]) > 2 * t * k * n // e  # the blocked path ran
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
    got_g = jax.grad(loss(lambda *a: held_experts_ffn(*a, held, k)[0]),
                     (0, 1, 2, 3))(*args)
    want_g = jax.grad(loss(plain), (0, 1, 2, 3))(*args)
    for g, w in zip(got_g, want_g):
        _close(g, w, 1e-5)


def _brute_force_plan(local, weights, rows, held):
    """The plan slot by slot on the host: the held slots in the order of
    their experts, token order within an expert."""
    tokens, k = local.shape
    slots = [(int(local[t, j]), t, j) for t in range(tokens)
             for j in range(k) if local[t, j] < held]
    row_of_slot = {}
    token_of_row, weight_of_row = np.zeros(rows, int), np.zeros(rows)
    for row, (_, t, j) in enumerate(sorted(slots)):  # stable in (t, j)
        row_of_slot[t, j], token_of_row[row] = row, t
        weight_of_row[row] = weights[t, j]
    row_of_rank, token_of_rank = np.full(rows, rows), np.full(rows, tokens)
    choice_of_rank, weight_of_rank = np.zeros(rows, int), np.zeros(rows)
    tile_ranks = np.zeros(-(-tokens // 128), int)
    for rank, (_, t, j) in enumerate(slots):  # token order
        row_of_rank[rank], token_of_rank[rank] = row_of_slot[t, j], t
        choice_of_rank[rank], weight_of_rank[rank] = j, weights[t, j]
        tile_ranks[t // 128] += 1
    sizes = np.bincount(local.reshape(-1), minlength=held + 1)[:held]
    return dict(
        token_of_row=token_of_row, weight_of_row=weight_of_row,
        group_sizes=sizes, row_of_rank=row_of_rank,
        token_of_rank=token_of_rank, choice_of_rank=choice_of_rank,
        weight_of_rank=weight_of_rank, tile_ranks=tile_ranks), len(slots)


@pytest.mark.parametrize("case", [
    "mixed", "one-token-holds-all-k", "one-held-expert", "no-slot-held",
    "buffer-of-every-slot"])
def test_route_plan_against_brute_force(case):
    """The slot buffer's plan (rows by expert, token order within one;
    the buffer's order and token order inverse to each other;
    ``group_sizes`` a count) for tokens with every slot held, with none,
    a held range of one expert and no held slot at all."""
    from elephas_tpu.ops.moe import _route_plan

    tokens, k, experts, held, first = 150, 4, 12, 5, 3
    rng = np.random.default_rng(5)
    chosen = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    if case == "one-token-holds-all-k":
        chosen[7] = first + np.arange(k)[::-1]
        chosen[8:20] = np.arange(k) + first + held  # and twelve hold none
    elif case == "one-held-expert":
        held = 1
    elif case == "no-slot-held":
        chosen = np.where(
            (chosen >= first) & (chosen < first + held), experts, chosen)
    local = np.where((chosen >= first) & (chosen < first + held),
                     chosen - first, held).astype(np.int32)
    weights = rng.random((tokens, k)).astype(np.float32)
    routed = int((local < held).sum())
    rows = tokens * k if case == "buffer-of-every-slot" else routed + 3
    want, n = _brute_force_plan(local, weights, rows, held)
    got = jax.jit(_route_plan, static_argnums=(2, 3))(
        jnp.asarray(local), jnp.asarray(weights), rows, held)._asdict()
    assert n == routed and (n == 0) == (case == "no-slot-held")
    # past the slots held a row or a rank has no weight, whatever it names
    for name in ("token_of_row", "choice_of_rank"):
        got[name], want[name] = got[name][:n], want[name][:n]
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    # the buffer's order and token order are inverse to each other
    assert sorted(got["row_of_rank"][:n]) == list(range(n))
    np.testing.assert_array_equal(
        got["token_of_row"][got["row_of_rank"][:n]],
        got["token_of_rank"][:n])


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("k", [2, 6, 10])
def test_held_experts_ffn_gradients_with_a_router_tensor(k, gated):
    """Forward and every gradient (tokens, the router's tensor, router,
    both expert stacks) against the plain sum over the held experts, for
    2, 6 and 10 experts a token, gated and ungated."""
    from elephas_tpu.ops import held_experts_ffn

    t, d, e, width, held = 64, 16, 32, 8, (8, 16)
    n = held[1] - held[0]
    ks = jax.random.split(jax.random.key(13 + k), 5)
    x = jax.random.normal(ks[0], (t, d))
    seen = jax.random.normal(ks[1], (t, d))
    router = jax.random.normal(ks[2], (d, e))
    w_in = jax.random.normal(ks[3], (n, d, (2 if gated else 1) * width)) * 0.3
    down = jax.random.normal(ks[4], (n, width, d)) * 0.3
    act = "silu" if gated else "relu2"

    def plain(x, seen, router, w_in, down):
        p = jax.nn.softmax(seen @ router, -1)
        top, chosen = jax.lax.top_k(p, k)
        top = top / top.sum(-1, keepdims=True)
        y = 0.0
        for i in range(n):
            w = jnp.sum(jnp.where(chosen == held[0] + i, top, 0.0), -1)
            if gated:
                gate, up = jnp.split(x @ w_in[i], 2, -1)
                hidden = jax.nn.silu(gate) * up
            else:
                hidden = jnp.square(jax.nn.relu(x @ w_in[i]))
            y = y + w[:, None] * (hidden @ down[i])
        return y

    def ours(x, seen, router, w_in, down):
        return held_experts_ffn(x, router, w_in, down, held, k,
                                route_from=seen, activation=act,
                                gated=gated)[0]

    args = (x, seen, router, w_in, down)
    _close(ours(*args), plain(*args), 1e-5)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
    for g, w in zip(jax.grad(loss(ours), range(5))(*args),
                    jax.grad(loss(plain), range(5))(*args)):
        _close(g, w, 1e-5)


def test_rematerialised_sparse_block_routes_once():
    """A sparse block under ``remat`` keeps what its routing decided
    (its ``kept``): the gradient's program holds one top-k, one sort of
    every token slot (the plan's; the chip sorts them in less time than
    it counts them by scatter) and no ``k`` passes of ``[T, D]``
    gathers; with nothing kept it chooses and sorts twice, to the same
    numbers."""
    import re

    from elephas_tpu.models import lm_blocks as zoo

    tokens, d, experts, k = 256, 32, 64, 6  # a buffer of 384 rows
    x = jax.random.normal(jax.random.key(3), (1, tokens, d))

    def gradient_and_text():
        layer = zoo.SparseMoeBlock(experts, k, 16, 16, (8, 16), remat=True,
                                   name="moe")
        layer.build(x.shape)
        params = [0.2 * jax.random.normal(jax.random.key(i), v.shape)
                  for i, v in enumerate(layer.trainable_variables)]
        counts = [v.value for v in layer.non_trainable_variables]
        loss = lambda p, x: jnp.sum(  # noqa: E731
            jnp.sin(layer.stateless_call(p, counts, x)[0]))
        fn = jax.jit(jax.grad(loss, (0, 1)))
        return fn(params, x), fn.lower(params, x).as_text()

    def sorts_of_every_slot(text):
        return [
            int(np.prod([int(n) for n in m.group(1).split("x")]))
            for m in re.finditer(
                r'"stablehlo\.sort"\(.*?\(tensor<([0-9x]+)x[a-z]', text, re.S)
        ].count(tokens * k)

    kept, text = gradient_and_text()
    assert text.count("chlo.top_k") == 1
    assert sorts_of_every_slot(text) == 1
    wide = re.findall(
        rf'"stablehlo\.gather".*-> tensor<{tokens}x{d}x', text)
    assert len(wide) <= 4  # two a direction
    with pytest.MonkeyPatch.context() as nothing_kept:
        nothing_kept.setattr(zoo.SparseMoeBlock, "kept", ())
        recomputed, text = gradient_and_text()
    assert text.count("chlo.top_k") == 2
    assert sorts_of_every_slot(text) == 2
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(recomputed)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("bias,blocked", [(0.0, 0), (60.0, 1)],
                         ids=["uniform", "all-choose-one-held-expert"])
def test_sparse_block_counts_its_calls_and_the_blocked_ones(bias, blocked):
    """``route_counts`` says how often the block ran and how often more
    slots were routed to the held experts than the one buffer holds:
    never under a uniform router, every call where all tokens choose
    one held expert (the router of the test above)."""
    from elephas_tpu.models import lm_blocks as zoo

    layer = _keras_layer("moe")
    x = jax.random.normal(jax.random.key(11), (2, SEQ, 32)).at[..., 0].set(1.0)
    layer.build(x.shape)
    names = [v.path.split("/")[-1] for v in layer.trainable_variables]
    params = [0.2 * jax.random.normal(jax.random.key(i), v.shape)
              for i, v in enumerate(layer.trainable_variables)]
    router = names.index("router")
    params[router] = params[router].at[0, 5].add(bias)
    counts = [v.value for v in layer.non_trainable_variables]
    for calls in (1, 2):
        _, counts = layer.stateless_call(params, counts, x)
        counted = dict(zip(zoo.COUNTER_NAMES, np.asarray(counts[0])))
        assert counted["calls"] == calls
        assert counted["blocked_calls"] == calls * blocked
        assert counted["slots"] == calls * 2 * SEQ * 2
    usual = 2 * (2 * SEQ * 2) * 4 // 16
    assert (counted["held_slots"] > calls * usual) == bool(blocked)


def test_sparse_block_refuses_a_range_outside_the_experts():
    from elephas_tpu.models import lm_blocks as zoo

    with pytest.raises(ValueError, match="experts_held"):
        zoo.SparseMoeBlock(16, 2, 16, 16, (12, 20))
    with pytest.raises(ValueError, match="experts a token"):
        zoo.SparseMoeBlock(4, 8, 16, 16)


# -- the whole model through SparkModel.fit -----------------------------------


def _tokens(seed, rows=4):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CFG["vocab_size"], size=(rows, SEQ + 1))
    tok = tok.astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


@pytest.fixture(scope="module")
def fitted(ref, builder):
    """Two SGD steps (one epoch of 4 sequences, 2 a step) through
    ``SparkModel.fit`` from the reference's seeded weights."""
    from elephas_tpu import SparkModel, telemetry
    from elephas_tpu.data import SparkContext
    from elephas_tpu.utils import rdd_utils

    params = ref.init_params(CFG, 7)
    model = builder.build(CFG, params)
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
    floor = float(np.median(list(want["velocity_norm"].values())))
    for path, ref_norm in want["velocity_norm"].items():
        got = norm(momenta["SGD/" + path.replace("/", "_") + "_momentum"])
        assert abs(got - ref_norm) <= 2e-3 * max(ref_norm, floor), path
        got = norm(variables[path] - fitted["start"][path])
        want_change = want["change_norm"][path]
        assert abs(got - want_change) <= 2e-3 * max(
            want_change, float(np.median(list(want["change_norm"].values())))
        ), path


def test_fit_emits_one_counters_event_an_epoch(fitted):
    events = fitted["events"]
    assert len(events) == 1 and events[0]["mono_ns"] is not None
    layers = events[0]["args"]["layers"]
    assert sorted(layers) == [f"layer{i}_moe" for i in range(4)]
    for counts in layers.values():
        assert counts["slots"] == 4 * SEQ * 2
        assert 0 < counts["max_expert_tokens"] <= counts["held_slots"]
        assert counts["held_slots"] <= counts["slots"]


def test_fit_with_a_metric_never_runs_the_master_model_op_by_op(
        ref, builder, monkeypatch):
    """Compiled with a metric, and with the master parked on another
    device than the worker's (as beside a chip): the metric's variables
    are built from the model's output spec, so every forward pass is a
    traced one, and the history carries the metric."""
    import keras

    from elephas_tpu import SparkModel
    from elephas_tpu import models as zoo

    far = jax.devices()[-1]
    original = jax.local_devices
    monkeypatch.setattr(
        jax, "local_devices",
        lambda *a, backend=None, **k: [far] if backend == "cpu"
        else original(*a, backend=backend, **k))
    for kind in ("GatedAttention", "GatedDeltaNet", "SparseMoeBlock"):
        cls = getattr(zoo, kind)
        plain = cls._forward

        def traced_only(self, x, plain=plain):
            assert isinstance(x, jax.core.Tracer), "an eager forward pass"
            return plain(self, x)

        monkeypatch.setattr(cls, "_forward", traced_only)
    model = builder.build(CFG, ref.init_params(CFG, 7))
    model.compile(
        optimizer=model.optimizer, loss=zoo.next_token_loss,
        metrics=[keras.metrics.SparseCategoricalAccuracy(name="accuracy")])
    x, y = _tokens(7)
    history = SparkModel(model, mode="synchronous", num_workers=1).fit(
        (x, y), epochs=2, batch_size=2)
    assert len(history["accuracy"]) == 2
    assert all(0.0 <= a <= 1.0 for a in history["accuracy"])
    assert {d for v in model.variables for d in v.value.devices()} == {
        jax.devices()[0]}


def test_a_layer_names_its_own_counter_variable():
    """The epoch runner knows no layer's variable by name: a layer's
    ``epoch_counters`` maps the attribute that holds the variable to
    its entries' names."""
    import keras

    from elephas_tpu import SparkModel, telemetry

    class CountsRows(keras.layers.Layer):
        epoch_counters = {"seen": ("rows", "calls")}

        def build(self, input_shape):
            self.seen = self.add_weight(
                name="seen", shape=(2,), dtype="int32",
                initializer="zeros", trainable=False)

        def call(self, x):
            self.seen.assign(
                self.seen.value + jnp.array([x.shape[0], 1], jnp.int32))
            return x

    model = keras.Sequential([
        keras.layers.Input((8,)), CountsRows(name="counting"),
        keras.layers.Dense(2, activation="softmax")])
    model.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    rng = np.random.default_rng(3)
    x = rng.standard_normal((32, 8)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)
    since = telemetry.default_tracer().seq
    SparkModel(model, mode="synchronous", num_workers=2).fit(
        (x, y), epochs=2, batch_size=4)
    events = telemetry.default_tracer().events(since, name="fit.counters")
    assert [e["args"]["layers"] for e in events] == [
        {"counting": {"rows": 32, "calls": 8}}] * 2


def test_builder_assign_checks_paths_and_zeroes_counters(fitted, ref, builder):
    model = fitted["model"]
    params = ref.init_params(CFG, 8)
    builder.assign(model, params)
    for var in model.variables:
        if var.path.endswith("/route_counts"):
            assert not np.asarray(var.value).any()
    with pytest.raises(ValueError, match="differ"):
        builder.assign(model, {k: v for k, v in params.items()
                               if "router" not in k})


def test_reference_param_count_and_flops(builder, ref):
    """The published widths: 625.7M parameters held here, about 1.4
    GFLOP a token forward and backward."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3-next-80b-a3b-ep16.json")) as f:
        cfg = json.load(f)
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(shape)) for shape, _kind in shapes.values())
    assert count == cfg["parameters"]
    assert 620e6 < count < 630e6
    traffic = {"sequence_length": 8192, "batch_size": 2}
    per_token = builder.train_flops_per_example(cfg, traffic) / 8192
    assert 1.2e9 < per_token < 1.6e9
    scan = builder.gdn_scan_step_cost(cfg, traffic)
    experts = builder.moe_experts_step_cost(cfg, traffic, 4 * 10240)
    assert scan["flops"] > 0 and scan["bytes"] > 0
    assert experts["flops"] == 3 * 2 * 3 * 2048 * 512 * 4 * 10240


def test_control_one_precision_down_moves_the_loss(ref):
    x, y = _tokens(9, rows=2)
    sound = ref.follow(CFG, 9, [(x, y)])
    lower = ref.follow(CFG, 9, [(x, y)], lower=True)
    assert np.isfinite(lower["losses"][0])
    gaps = [abs(lower["velocity_norm"][p] - n) / max(n, 1e-12)
            for p, n in sound["velocity_norm"].items()]
    assert max(gaps) > 1e-3
