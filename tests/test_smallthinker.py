"""The window-and-full-attention MoE block (``smallthinker_lm``) against
the benchmark's plain reference, at a small size on the CPU: widths
cut, ratios kept (14 query heads over 2 key/value heads, 7 a head as 28
over 4; a window of 8 keys over 24 positions; 6 of 16 experts a token
with 2 held, an eighth; layer 0 full and rotation-free, layer 1
windowed and rotated)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "smallthinker-21b-a3b-ep8"

CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window_size": 8, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "rope_theta": 1.5e6, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "moe_num_primary_experts": 16,
    "moe_num_active_primary_experts": 6, "moe_ffn_hidden_size": 12,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_experts_held": 2, "experts_held_first": 2,
    "sequence_length": 24, "remat": True, "dtype": "float32",
    "assumed": {"initializer_range": 0.2, "router_input": "layer_input"},
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9},
}
SEQ, HIDDEN, WINDOW = (CFG["sequence_length"], CFG["hidden_size"],
                       CFG["sliding_window_size"])
HELD = (2, 4)


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
    return _load("builders", "keras_smallthinker")


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max(), scale)


def _mm(ref):
    return lambda a, w: jnp.matmul(a, w, precision=ref.HI)


def _ident(t):
    return t


def _layer_params(ref, prefix, seed=0, cfg=CFG):
    params = ref.init_params(cfg, seed)
    return {k: v for k, v in params.items() if k.startswith(prefix)}


def _attn_layer(windowed, remat=False, window=WINDOW, rotary=None,
                name=None):
    from elephas_tpu.models import lm_mixers as zoo

    return zoo.BandedAttention(
        CFG["num_attention_heads"], CFG["num_key_value_heads"],
        CFG["head_dim"], window if windowed else None,
        windowed if rotary is None else rotary, CFG["rope_theta"],
        remat=remat, name=name or f"layer{int(windowed)}_attn")


def _moe_layer(held=HELD, remat=False, name="layer1_moe"):
    from elephas_tpu.models import lm_blocks as zoo

    return zoo.SparseMoeBlock(
        CFG["moe_num_primary_experts"],
        CFG["moe_num_active_primary_experts"], CFG["moe_ffn_hidden_size"],
        0, held, hidden_act="relu", remat=remat, name=name)


def _stateless(layer, params, *inputs):
    """``(result, non-trainable variables after the call)`` with every
    variable the reference names taken from ``params``."""
    tv = [params[v.path] for v in layer.trainable_variables]
    ntv = [params.get(v.path, v.value)
           for v in layer.non_trainable_variables]
    return layer.stateless_call(tv, ntv, *inputs)


def _inputs(seed=5):
    """The experts' input and, apart from it, the router's."""
    ks = jax.random.split(jax.random.key(seed), 2)
    return (jax.random.normal(ks[0], (2, SEQ, HIDDEN)),
            jax.random.normal(ks[1], (2, SEQ, HIDDEN)))


# -- each layer kind against the reference ----------------------------------


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("kind", ["window", "full", "moe"])
def test_layer_forward_and_gradients(ref, kind, remat):
    x, route_from = _inputs()
    if kind == "moe":
        layer, prefix = _moe_layer(remat=remat), "layer1_moe/"
        inputs = (x, route_from)
        want_fn = lambda p, x, r: ref._sparse_block(  # noqa: E731
            p, prefix, x, r, CFG, _ident, _mm(ref))
    else:
        windowed = kind == "window"
        layer = _attn_layer(windowed, remat)
        prefix, inputs = f"layer{int(windowed)}_attn/", (x,)
        want_fn = lambda p, x: ref._attention(  # noqa: E731
            p, prefix, x, CFG, windowed, windowed, _ident, _mm(ref))
    layer.build(x.shape)
    params = _layer_params(ref, prefix)
    assert {v.path for v in layer.variables
            if not v.path.endswith("/route_counts")} == set(params)
    got_fn = lambda p, *xs: _stateless(layer, p, *xs)[0]  # noqa: E731
    _close(jax.jit(got_fn)(params, *inputs),
           jax.jit(want_fn)(params, *inputs))
    loss = lambda f: lambda p, *xs: jnp.sum(  # noqa: E731
        jnp.sin(3.0 * f(p, *xs)))
    # the experts' input and every weight; the router's own input
    # reaches the result through the chosen logits' softmax
    wrt = tuple(range(1 + len(inputs)))
    got = jax.jit(jax.grad(loss(got_fn), wrt))(params, *inputs)
    want = jax.jit(jax.grad(loss(want_fn), wrt))(params, *inputs)
    for g, w in zip(got[1:], want[1:]):
        _close(g, w, 5e-4)
    for path in params:
        _close(got[0][path], want[0][path], 5e-4)


def test_a_window_changes_the_result_and_one_past_the_sequence_does_not(ref):
    """Rows whose window reaches back to position 0 are causal
    attention's; the rest differ; a window of at least the sequence is
    causal attention bit for bit."""
    x, _ = _inputs(6)
    params = _layer_params(ref, "layer1_attn/")
    outs = {}
    for window in (WINDOW, SEQ, 4 * SEQ, None):
        layer = _attn_layer(window is not None, window=window, rotary=True,
                            name="layer1_attn")
        layer.build(x.shape)
        outs[window] = np.asarray(_stateless(layer, params, x)[0])
    np.testing.assert_array_equal(outs[SEQ], outs[None])
    np.testing.assert_array_equal(outs[4 * SEQ], outs[None])
    np.testing.assert_array_equal(outs[WINDOW][:, :WINDOW],
                                  outs[None][:, :WINDOW])
    later = np.abs(outs[WINDOW][:, WINDOW:] - outs[None][:, WINDOW:])
    assert later.max(axis=-1).min() > 1e-4  # every later row moved


def test_the_full_layers_have_no_position_term(ref, monkeypatch):
    """Without rotation nothing tells a key's position: the last
    query's result does not change when the keys before it change
    places. With rotation it does. And the rotation-free layer hands
    the kernel its projections as they are, with no window."""
    import importlib

    x, _ = _inputs(7)
    shuffled = jnp.concatenate([x[:, :-1][:, ::-1], x[:, -1:]], axis=1)
    params = _layer_params(ref, "layer0_attn/")
    last = {}
    for rotary in (False, True):
        layer = _attn_layer(False, rotary=rotary)
        layer.build(x.shape)
        last[rotary] = [np.asarray(_stateless(layer, params, t)[0][:, -1])
                        for t in (x, shuffled)]
    _close(last[False][0], last[False][1], 1e-5)
    assert np.abs(last[True][0] - last[True][1]).max() > 1e-3
    # what the rotation-free layer hands the kernel is the projection
    fa = importlib.import_module("elephas_tpu.ops.flash_attention")
    seen, plain = {}, fa.flash_attention

    def watched(q, k, v, **kwargs):
        seen.update(q=q, window=kwargs.get("window"))
        return plain(q, k, v, **kwargs)

    monkeypatch.setattr(fa, "flash_attention", watched)
    layer = _attn_layer(False)
    layer.build(x.shape)
    _stateless(layer, params, x)
    want = jnp.matmul(x, params["layer0_attn/q_proj"]).reshape(
        2, SEQ, 14, 8).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(seen["q"], want)
    assert seen["window"] is None and seen["q"].shape == (2, 14, SEQ, 8)


def test_the_model_builds_layers_by_the_two_layouts():
    from elephas_tpu.models import smallthinker_lm

    model = smallthinker_lm(
        vocab_size=64, maxlen=SEQ, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=14, num_key_value_heads=2, head_dim=8,
        sliding_window_size=WINDOW, moe_num_primary_experts=16,
        moe_num_active_primary_experts=6, moe_ffn_hidden_size=12,
        experts_held=HELD, remat=True)
    kinds = [(model.get_layer(f"layer{i}_attn").window,
              model.get_layer(f"layer{i}_attn").rotary) for i in range(4)]
    assert kinds == [(None, False)] + [(WINDOW, True)] * 3
    moe = model.get_layer("layer2_moe")
    assert moe.shared_width == 0 and moe.hidden_act == "relu"
    assert not any("shared" in v.path for v in model.variables)
    config = moe.get_config()
    assert (config["hidden_act"], config["shared_width"],
            config["gated_shared_expert"]) == ("relu", 0, False)
    with pytest.raises(ValueError, match="entries"):
        smallthinker_lm(num_hidden_layers=5)
    with pytest.raises(ValueError, match="hidden_act"):
        type(moe)(16, 2, 16, 0, hidden_act="tanh")


# -- the router and the experts ---------------------------------------------


def test_the_router_reads_the_layers_input(ref):
    """The block routed from a tensor of its own is the reference's;
    routed from the experts' input it chooses other experts and gives
    another result; the rule is a softmax over the six chosen logits."""
    from elephas_tpu.ops.moe import route_top_k

    x, route_from = _inputs(8)
    layer = _moe_layer()
    layer.build(x.shape)
    params = _layer_params(ref, "layer1_moe/", seed=2)
    want = ref._sparse_block(
        params, "layer1_moe/", x, route_from, CFG, _ident, _mm(ref))
    got, _ = _stateless(layer, params, x, route_from)
    _close(got, want)
    alone, _ = _stateless(layer, params, x)
    _close(alone, ref._sparse_block(
        params, "layer1_moe/", x, x, CFG, _ident, _mm(ref)))
    assert np.abs(np.asarray(alone) - np.asarray(got)).max() > 1e-3
    router = params["layer1_moe/router"]
    flat = lambda t: t.reshape(-1, HIDDEN)  # noqa: E731
    weights, chosen = route_top_k(flat(route_from), router, 6)
    assert not np.array_equal(chosen, route_top_k(flat(x), router, 6)[1])
    want_w, want_c = ref.route(flat(route_from), router, CFG)
    np.testing.assert_array_equal(chosen, want_c)
    _close(weights, want_w, 1e-6)
    logits = jnp.take_along_axis(jnp.matmul(
        flat(route_from), router, precision=ref.HI), chosen, axis=-1)
    _close(weights, jax.nn.softmax(logits, axis=-1), 1e-6)


def test_reglu_experts_against_relu_written_out(ref):
    """One token slot a row, every row to held expert 0 at weight 1:
    ``down(max(gate, 0) * up)``; with ``silu`` in its place the result
    differs."""
    from elephas_tpu.ops.moe import held_experts_ffn

    ks = jax.random.split(jax.random.key(9), 4)
    x = jax.random.normal(ks[0], (40, HIDDEN))
    gate_up = jax.random.normal(ks[1], (2, HIDDEN, 24)) * 0.3
    down = jax.random.normal(ks[2], (2, 12, HIDDEN)) * 0.3
    router = jnp.zeros((HIDDEN, 4)).at[0, 0].set(50.0)
    x = x.at[:, 0].set(1.0)  # every token's logit for expert 0 is 50
    got, counts = held_experts_ffn(
        x, router, gate_up, down, (0, 2), 1, activation="relu")
    proj = jnp.matmul(x, gate_up[0], precision=ref.HI)
    want = jnp.matmul(
        jnp.maximum(proj[:, :12], 0.0) * proj[:, 12:], down[0],
        precision=ref.HI)
    _close(got, want)
    _close(got, ref._reglu(x, gate_up[0], down[0], _ident, _mm(ref)))
    assert counts.tolist() == [40, 40, 40, 1, 0]
    silu, _ = held_experts_ffn(x, router, gate_up, down, (0, 2), 1)
    assert np.abs(np.asarray(silu) - np.asarray(got)).max() > 1e-2
    with pytest.raises(KeyError):
        held_experts_ffn(x, router, gate_up, down, (0, 2), 1,
                         activation="tanh")


# -- the share of a deployment ------------------------------------------------


def test_shares_add_up_to_the_uncut_layer(ref):
    """The routed parts of all eight shares (2 of the 16 experts each;
    there is no shared expert to count once) add up to what the uncut
    reference (all 16 held) gives for the layer."""
    whole_cfg = dict(CFG, num_experts_held=16, experts_held_first=0)
    params = _layer_params(ref, "layer1_moe/", seed=3, cfg=whole_cfg)
    x, route_from = _inputs(10)
    want = ref._sparse_block(
        params, "layer1_moe/", x, route_from, whole_cfg, _ident, _mm(ref))
    total, routed_slots = 0.0, 0
    for share in range(8):
        first = 2 * share
        layer = _moe_layer((first, first + 2))
        layer.build(x.shape)
        mine = dict(params)
        for name in ("experts_gate_up", "experts_down"):
            mine["layer1_moe/" + name] = params[
                "layer1_moe/" + name][first:first + 2]
        out, ntv = _stateless(layer, mine, x, route_from)
        total = total + out
        counts = [v for v in ntv if v.dtype == jnp.int32]
        routed_slots += int(counts[0][0])
    _close(total, want)
    assert routed_slots == 2 * SEQ * 6  # every slot is some share's


def test_no_token_dropped_when_all_choose_one_held_expert(ref):
    """A router that sends every token to held expert 3 (and its other
    five choices anywhere): 48 rows on one expert, many times the mean
    of a uniform router over the two held, and still the reference's
    result."""
    layer = _moe_layer()
    x, route_from = _inputs(11)
    layer.build(x.shape)
    params = _layer_params(ref, "layer1_moe/", seed=4)
    # a constant feature of the router's input drives expert 3's logit
    route_from = route_from.at[..., 0].set(1.0)
    router = params["layer1_moe/router"].at[0, 3].set(60.0)
    params = dict(params, **{"layer1_moe/router": router})
    want = ref._sparse_block(
        params, "layer1_moe/", x, route_from, CFG, _ident, _mm(ref))
    got, ntv = _stateless(layer, params, x, route_from)
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


def test_model_forward_is_the_references_and_not_with_the_router_fed_m(
        ref, builder):
    """Logits of the whole model against the reference's forward pass;
    the reference under the other reading of "before attention" (the
    router fed the experts' input) and with the band dropped are both
    further off than that."""
    params = ref.init_params(CFG, 6)
    model = builder.build(dict(CFG), params)
    x, _ = _tokens(6, rows=2)
    got = np.asarray(model(x))
    _close(got, ref.forward(params, x, CFG))
    fed_m = dict(CFG, assumed=dict(CFG["assumed"], router_input="expert_input"))
    no_band = dict(CFG, sliding_window_layout=[0, 0, 0, 0])
    for other in (fed_m, no_band):
        want = np.asarray(ref.forward(params, x, other))
        assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()
    with pytest.raises(ValueError, match="router"):
        builder.build(fed_m, params)


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
    assert sorted(layers) == ["layer0_moe", "layer1_moe"]
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
        else:
            np.testing.assert_array_equal(var.value, params[var.path])
    with pytest.raises(ValueError, match="differ"):
        builder.assign(model, {k: v for k, v in params.items()
                               if "k_proj" not in k})
    wrong = dict(params)
    wrong["layer1_attn/q_proj"] = params["layer1_attn/q_proj"][:, :-1]
    with pytest.raises(ValueError, match="q_proj"):
        builder.assign(model, wrong)


def test_reference_param_count_and_flops(builder, ref):
    """The published widths by shape arithmetic alone: a layer
    68,326,400, the cell's 643,852,800 in all; about 3.1 GFLOP a token
    forward and backward, more than half of it attention at 16384
    positions; the band leaves 70 of the 136 causal pairs of
    1024-blocks and 952 of the 2080 of 256-blocks."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    shapes = ref.param_shapes(cfg)
    size = lambda keep: sum(  # noqa: E731
        int(np.prod(shape)) for path, (shape, _kind) in shapes.items()
        if keep(path))
    assert size(lambda p: p.startswith("layer1_attn/")) == 20_971_520
    assert size(lambda p: p.startswith("layer1_")) == 68_326_400
    assert size(lambda p: True) == cfg["parameters"] == 643_852_800
    assert ref.layer_kinds(cfg) == [
        (False, False), (True, True), (True, True), (True, True)] * 2
    # the published layouts, whole
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [
        int(l % 4 != 0) for l in range(52)]
    traffic = {"sequence_length": 16384, "batch_size": 1}
    per_token = builder.train_flops_per_example(cfg, traffic) / 16384
    macs = builder.forward_macs_per_token(cfg, 16384)
    assert builder.visible_keys(16384) == 8192.5
    assert builder.visible_keys(16384, 4096) == pytest.approx(3584.1, abs=0.1)
    attention = 28 * 256 * (6 * 3584.125 + 2 * 8192.5)
    assert per_token == 6 * macs and 0.5 < attention / macs < 0.6
    assert 3.0e9 < per_token < 3.3e9  # 51.6 TFLOP a step of 16384
    experts = builder.moe_experts_step_cost(cfg, traffic, 8 * 12288)
    assert experts["flops"] == 3 * 2 * 3 * 2560 * 768 * 8 * 12288
    assert experts["bytes"] > 0
    window = _load("metrics", "attn_window_roofline")
    assert window.band_pairs(16384, 4096) == 952
    assert window.band_pairs(16384, 16384) == 2080
    cost = window.step_cost(cfg, traffic)
    assert cost["flops"] / 6 == pytest.approx(3.13e12, rel=0.002)
    import importlib

    fa = importlib.import_module("elephas_tpu.ops.flash_attention")
    pairs = [bool(fa._pair_seen(i, j, 1024, 1024, w))
             for w in (4096, None) for i in range(16) for j in range(16)]
    assert (sum(pairs[:256]), sum(pairs[256:])) == (70, 136)


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
    time (so that 16384 positions fit): in blocks of 8 it gives what
    one block of all 24 gives, windowed or not."""
    x, _ = _inputs(12)
    params = _layer_params(ref, "layer1_attn/")
    for windowed in (True, False):
        fn = lambda: ref._attention(  # noqa: E731
            params, "layer1_attn/", x, CFG, windowed, True, _ident, _mm(ref))
        whole = fn()
        monkeypatch.setattr(ref, "ATTN_ROWS", 8)
        _close(fn(), whole, 1e-6)
        monkeypatch.undo()


def test_control_one_precision_down_moves_the_gaps(ref, fitted):
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
