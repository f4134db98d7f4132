"""The latent-attention MoE block (``deepseek_v3_lm``) against the
benchmark's plain reference, at a small size on the CPU: widths cut,
ratios kept (queries and keys 24 wide, 16 without position and 8
rotated, over 16-wide values, as 192/128/64 over 128; 6 of 16 experts a
token with 4 held; a leading dense layer and a sparse one; sequence
24)."""

import importlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kanana-2-30b-a3b-ep8"

CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "intermediate_size": 96,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 16, "q_lora_rank": None,
    "rope_theta": 1e6, "rms_norm_eps": 1e-6, "n_routed_experts": 16,
    "n_group": 1, "num_experts_per_tok": 6, "num_experts_held": 4,
    "experts_held_first": 4, "moe_intermediate_size": 12,
    "n_shared_experts": 2, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "sequence_length": 24, "remat": True,
    "dtype": "float32",
    "assumed": {"initializer_range": 0.2, "select_bias_std": 0.05},
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9},
}
SEQ, HIDDEN = CFG["sequence_length"], CFG["hidden_size"]
BIAS = "/e_score_correction_bias"


def _load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + kind + "_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference", NAME)


@pytest.fixture(scope="module")
def builder():
    return _load("builders", "keras_deepseek_v3")


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max(), scale)


def _mm(ref):
    return lambda a, w: jnp.matmul(a, w, precision=ref.HI)


# -- each layer kind against the reference ----------------------------------


def _layer_params(ref, prefix, seed=0, cfg=CFG):
    params = ref.init_params(cfg, seed)
    return {k: v for k, v in params.items() if k.startswith(prefix)}


def _moe_layer(held=(4, 8), remat=False):
    from elephas_tpu.models import lm_blocks as zoo

    return zoo.SparseMoeBlock(
        CFG["n_routed_experts"], CFG["num_experts_per_tok"],
        CFG["moe_intermediate_size"],
        CFG["n_shared_experts"] * CFG["moe_intermediate_size"], held,
        scoring_func="sigmoid", selection_bias=True,
        routed_scaling_factor=CFG["routed_scaling_factor"],
        gated_shared_expert=False, remat=remat, name="layer1_moe")


def _keras_layer(kind, remat=False):
    from elephas_tpu import models as zoo

    if kind == "attn":
        return zoo.LatentAttention(
            CFG["num_attention_heads"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"], CFG["kv_lora_rank"],
            CFG["rope_theta"], remat=remat, name="layer1_attn")
    if kind == "mlp":
        return zoo.DenseMLP(
            CFG["intermediate_size"], remat=remat, name="layer0_mlp")
    return _moe_layer(remat=remat)


PREFIX = {"attn": "layer1_attn/", "mlp": "layer0_mlp/", "moe": "layer1_moe/"}


def _reference_layer(ref, kind, cfg=CFG):
    ident, mm = (lambda t: t), _mm(ref)
    if kind == "attn":
        return lambda p, x: ref._latent_attention(
            p, PREFIX[kind], x, cfg, ident, mm)
    if kind == "mlp":
        return lambda p, x: ref._swiglu(
            x, p["layer0_mlp/gate_up"], p["layer0_mlp/down"], ident, mm)
    return lambda p, x: ref._sparse_block(p, PREFIX[kind], x, cfg, ident, mm)


def _stateless(layer, params, x):
    """``(result, non-trainable variables after the call)`` with every
    variable the reference names taken from ``params``."""
    tv = [params[v.path] for v in layer.trainable_variables]
    ntv = [params.get(v.path, v.value)
           for v in layer.non_trainable_variables]
    return layer.stateless_call(tv, ntv, x)


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("kind", ["attn", "mlp", "moe"])
def test_layer_forward_and_gradients(ref, kind, remat):
    layer = _keras_layer(kind, remat)
    x = jax.random.normal(jax.random.key(5), (2, SEQ, HIDDEN))
    layer.build(x.shape)
    params = _layer_params(ref, PREFIX[kind])
    assert {v.path for v in layer.variables
            if not v.path.endswith("/route_counts")} == set(params)
    want_fn = _reference_layer(ref, kind)
    got_fn = lambda p, x: _stateless(layer, p, x)[0]  # noqa: E731
    _close(jax.jit(got_fn)(params, x), jax.jit(want_fn)(params, x))
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(3.0 * f(p, x)))  # noqa: E731
    got = jax.jit(jax.grad(loss(got_fn), (0, 1)))(params, x)
    want = jax.jit(jax.grad(loss(want_fn), (0, 1)))(params, x)
    _close(got[1], want[1])
    for path in params:
        _close(got[0][path], want[0][path], 5e-4)
        if path.endswith(BIAS):  # chooses, and is no parameter
            assert not np.asarray(got[0][path]).any()


def test_latent_attention_sums_values_narrower_than_its_scores(
        ref, monkeypatch):
    """The layer hands the flash kernels 24-wide queries and keys and
    16-wide values, and the rotated key part is one head's, shared."""
    # the module, not the function ``elephas_tpu.ops`` exports by its name
    fa = importlib.import_module("elephas_tpu.ops.flash_attention")
    layer = _keras_layer("attn")
    x = jax.random.normal(jax.random.key(6), (2, SEQ, HIDDEN))
    layer.build(x.shape)
    seen = {}
    plain = fa.flash_attention

    def watched(q, k, v, **kwargs):
        seen.update(q=q.shape, k=k.shape, v=v.shape, k_rope=k[..., 16:])
        return plain(q, k, v, **kwargs)

    monkeypatch.setattr(fa, "flash_attention", watched)
    out, _ = _stateless(layer, _layer_params(ref, PREFIX["attn"]), x)
    assert (seen["q"], seen["k"], seen["v"]) == (
        (2, 4, SEQ, 24), (2, 4, SEQ, 24), (2, 4, SEQ, 16))
    assert out.shape == x.shape
    np.testing.assert_array_equal(seen["k_rope"][:, 0], seen["k_rope"][:, 3])


def test_rms_norm(ref):
    from elephas_tpu.models import lm_blocks as zoo

    x = jax.random.normal(jax.random.key(6), (2, 5, 32))
    norm = zoo.RMSNorm(name="n")
    norm.build(x.shape)
    assert np.asarray(norm.weight.value).tolist() == [1.0] * 32
    w = 1.0 + jax.random.normal(jax.random.key(7), (32,)) * 0.1
    got, _ = norm.stateless_call([w], [], x)
    _close(got, ref._rms(x, 1e-6) * w, 1e-5)


# -- the router's rule --------------------------------------------------------


def test_the_bias_changes_the_choice_and_not_the_weights(ref):
    from elephas_tpu.ops.moe import route_top_k

    ks = jax.random.split(jax.random.key(8), 3)
    x = jax.random.normal(ks[0], (40, HIDDEN))
    router = jax.random.normal(ks[1], (HIDDEN, 16)) * 0.3
    small = 0.05 * jax.random.normal(ks[2], (16,))
    rule = dict(score="sigmoid", scale=2.448)
    for bias in (jnp.zeros(16), small, small.at[11].set(5.0)):
        weights, chosen = route_top_k(x, router, 6, select_bias=bias, **rule)
        want_w, want_c = ref.route(x, router, bias, CFG)
        np.testing.assert_array_equal(chosen, want_c)
        _close(weights, want_w, 1e-6)
        # the chosen scores without the bias, renormalised, then scaled
        scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=ref.HI))
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        _close(weights, 2.448 * picked / picked.sum(-1, keepdims=True), 1e-6)
        _close(weights.sum(-1), np.full(40, 2.448), 1e-6)
    # a bias of 5 puts expert 11 among every token's six, at its own score
    assert bool(jnp.all(jnp.any(chosen == 11, axis=-1)))
    unbiased = route_top_k(x, router, 6, select_bias=jnp.zeros(16), **rule)[1]
    assert not bool(jnp.all(jnp.any(unbiased == 11, axis=-1)))
    assert not np.array_equal(
        route_top_k(x, router, 6, select_bias=small, **rule)[1], unbiased)


def test_the_softmax_rule_is_the_hybrid_lms_bit_for_bit():
    from elephas_tpu.ops.moe import route_top_k

    ks = jax.random.split(jax.random.key(9), 2)
    x = jax.random.normal(ks[0], (64, HIDDEN))
    router = jax.random.normal(ks[1], (HIDDEN, 16))
    logits = jnp.matmul(x, router, precision=jax.lax.Precision.HIGHEST)
    top, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 2)
    weights, experts = route_top_k(x, router, 2)
    np.testing.assert_array_equal(experts, chosen)
    np.testing.assert_array_equal(
        weights, top / jnp.sum(top, axis=-1, keepdims=True))
    with pytest.raises(KeyError):
        route_top_k(x, router, 2, score="tanh")


def test_sparse_block_refuses_an_unknown_score():
    from elephas_tpu.models import lm_blocks as zoo

    with pytest.raises(ValueError, match="scoring_func"):
        zoo.SparseMoeBlock(16, 2, 16, 16, scoring_func="tanh")


# -- the share of a deployment ------------------------------------------------


def test_shares_add_up_to_the_uncut_layer(ref):
    """The routed parts of all four shares (4 of the 16 experts each),
    with the two shared experts counted once, add up to what the uncut
    reference (all 16 held) gives for the layer."""
    whole_cfg = dict(CFG, num_experts_held=16, experts_held_first=0)
    params = _layer_params(ref, "layer1_moe/", seed=3, cfg=whole_cfg)
    x = jax.random.normal(jax.random.key(10), (2, SEQ, HIDDEN))
    want = _reference_layer(ref, "moe", whole_cfg)(params, x)
    no_shared = dict(params)
    no_shared["layer1_moe/shared_expert/down"] = jnp.zeros_like(
        params["layer1_moe/shared_expert/down"])
    total, routed_slots = 0.0, 0
    for share in range(4):
        first = 4 * share
        layer = _moe_layer((first, first + 4))
        layer.build(x.shape)
        mine = dict(params if share == 0 else no_shared)
        for name in ("experts_gate_up", "experts_down"):
            mine["layer1_moe/" + name] = params[
                "layer1_moe/" + name][first:first + 4]
        out, ntv = _stateless(layer, mine, x)
        total = total + out
        counts = [v for v in ntv if v.dtype == jnp.int32]
        routed_slots += int(counts[0][0])
    _close(total, want)
    assert routed_slots == 2 * SEQ * 6  # every slot is some share's


def test_no_token_dropped_when_all_choose_one_held_expert(ref):
    """A router that sends every token to held expert 5 (and its other
    five choices anywhere): 48 rows on one expert, several times the
    mean of a uniform router over the four held, and still the
    reference's result."""
    layer = _moe_layer()
    x = jax.random.normal(jax.random.key(11), (2, SEQ, HIDDEN))
    layer.build(x.shape)
    params = _layer_params(ref, "layer1_moe/", seed=4)
    # a constant input feature drives expert 5's score past all others
    x = x.at[..., 0].set(1.0)
    router = params["layer1_moe/router"].at[0, 5].set(60.0)
    params = dict(params, **{"layer1_moe/router": router})
    want = _reference_layer(ref, "moe")(params, x)
    got, ntv = _stateless(layer, params, x)
    _close(got, want)
    counts = [v for v in ntv if v.dtype == jnp.int32][0]
    held_slots, slots, fullest = (int(v) for v in counts[:3])
    assert slots == 2 * SEQ * 6 and fullest == 2 * SEQ
    assert held_slots >= 2 * SEQ


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
    model = builder.build(dict(CFG, **{"sequence_length": SEQ}), params)
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
    floor = float(np.median(list(want["velocity_norm"].values())))
    for path, ref_norm in want["velocity_norm"].items():
        got = norm(momenta["SGD/" + path.replace("/", "_") + "_momentum"])
        assert abs(got - ref_norm) <= 2e-3 * max(ref_norm, floor), path
        got = norm(variables[path] - fitted["start"][path])
        want_change = want["change_norm"][path]
        assert abs(got - want_change) <= 2e-3 * max(
            want_change, float(np.median(list(want["change_norm"].values())))
        ), path


def test_the_selection_bias_leaves_fit_as_it_came(fitted):
    """A float variable that is no parameter rides the runner's state:
    no momentum slot, no update, and the write-back returns it."""
    model = fitted["model"]
    biases = [v for v in model.variables if v.path.endswith(BIAS)]
    assert len(biases) == 1 and not biases[0].trainable
    for var in biases:
        assert np.asarray(var.value).any()
        np.testing.assert_array_equal(var.value, fitted["start"][var.path])
    slots = {v.path for v in model.optimizer.variables}
    assert not any("e_score_correction_bias" in path for path in slots)
    assert not any(path.endswith(BIAS)
                   for path in fitted["want"]["velocity_norm"])


def test_fit_emits_one_counters_event_an_epoch(fitted):
    events = fitted["events"]
    assert len(events) == 1 and events[0]["mono_ns"] is not None
    layers = events[0]["args"]["layers"]
    assert sorted(layers) == ["layer1_moe"]
    for counts in layers.values():
        assert counts["slots"] == 4 * SEQ * 6
        assert 0 < counts["max_expert_tokens"] <= counts["held_slots"]
        assert counts["held_slots"] <= counts["slots"]


def test_builder_assign_checks_paths_and_zeroes_counters(fitted, ref, builder):
    model = fitted["model"]
    params = ref.init_params(CFG, 8)
    builder.assign(model, params)
    for var in model.variables:
        if var.path.endswith("/route_counts"):
            assert not np.asarray(var.value).any()
        if var.path.endswith(BIAS):
            np.testing.assert_array_equal(var.value, params[var.path])
    with pytest.raises(ValueError, match="differ"):
        builder.assign(model, {k: v for k, v in params.items()
                               if "kv_b_proj" not in k})
    wrong = dict(params)
    wrong["layer1_attn/q_proj"] = params["layer1_attn/q_proj"][:, :-1]
    with pytest.raises(ValueError, match="q_proj"):
        builder.assign(model, wrong)


def test_reference_param_count_and_flops(builder, ref):
    """The published widths: latent attention 26,345,984 parameters a
    layer, the configuration's own count held here, about 3.3 GFLOP a
    token forward and backward, half of it attention at 8192
    positions."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    shapes = ref.param_shapes(cfg)
    size = lambda keep: sum(  # noqa: E731
        int(np.prod(shape)) for path, (shape, _kind) in shapes.items()
        if keep(path))
    assert size(lambda p: p.startswith("layer1_attn/")) == 26_345_984
    assert size(lambda p: True) == cfg["parameters"]
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert size(lambda p: p.endswith(BIAS)) == 128 * sparse
    assert size(lambda p: p.startswith("layer0_")) == 64_098_816
    assert size(lambda p: p.startswith("layer1_")) == 111_547_008
    traffic = {"sequence_length": 8192, "batch_size": 2}
    per_token = builder.train_flops_per_example(cfg, traffic) / 8192
    macs = builder.forward_macs_per_token(cfg, 8192)
    attention = cfg["num_hidden_layers"] * 32 * (192 + 128) * 4096
    assert per_token == 6 * macs and 0.4 < attention / macs < 0.55
    experts = builder.moe_experts_step_cost(cfg, traffic, 5 * 12288)
    assert experts["flops"] == 3 * 2 * 3 * 2048 * 768 * 5 * 12288
    assert experts["bytes"] > 0


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
    assert not any(path.endswith(BIAS) for path in got["change_norm"])


def test_control_one_precision_down_moves_the_loss(ref, fitted):
    """The reference with fp8 where the configuration holds bfloat16,
    over the fitted steps, against the float32 reference's."""
    x, y = _tokens(7)
    sound = fitted["want"]
    lower = ref.follow(CFG, 7, [(x[:2], y[:2]), (x[2:], y[2:])], lower=True)
    assert np.all(np.isfinite(lower["losses"]))
    assert lower["losses"] != sound["losses"]
    gaps = [abs(lower["velocity_norm"][p] - n) / max(n, 1e-12)
            for p, n in sound["velocity_norm"].items()]
    assert max(gaps) > 1e-3
