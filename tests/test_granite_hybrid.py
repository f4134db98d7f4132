"""The dense state-space hybrid (``granite_hybrid_lm``) against the
benchmark's plain reference, at a small size on the CPU: widths cut,
ratios kept where they matter (4 Mamba-2 heads over ONE group of B and
C; 24 positions in chunks of 8; 4 query heads over 2 key/value heads,
their scores under a multiplier that is not ``head_dim ** -0.5``; the
first four of six layer types, ``mamba mamba attention mamba``, each
with its SwiGLU; one table for embedding and head; four multipliers,
none of them one)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "granite-4.0-h-micro-vp8"
TABLE = "embed_tokens/embeddings"

CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba",
                    "attention"],
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "shared_intermediate_size": 48, "num_local_experts": 0,
    "attention_bias": False, "position_embedding_type": "nope",
    "hidden_act": "silu", "normalization_function": "rmsnorm",
    "attention_multiplier": 0.2, "embedding_multiplier": 3.0,
    "residual_multiplier": 0.4, "logits_scaling": 2.5,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
    "sequence_length": 24, "remat": True, "dtype": "float32",
    "assumed": {"initializer_range": 0.2, "time_step_min": 0.001,
                "time_step_max": 0.1, "time_step_floor": 1e-4,
                "attention_scale": "multiplier", "logits": "divided",
                "mamba_norm": "gate_then_norm", "residual": "on_sublayer"},
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9},
}
SEQ, HIDDEN = CFG["sequence_length"], CFG["hidden_size"]
# program and reference are both float32 here and differ by the order
# of their sums (a chunked scan against a quadratic form, a flash
# kernel against a materialised softmax): a few float32 roundings of
# the largest entry
TOL, GRAD_TOL = 2e-4, 5e-4


def _load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + kind + "_" + name.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    mod = _load("reference", NAME)
    mod.SCAN_ROWS = 8  # three blocks of queries over the 24 positions
    mod.SCAN_HEADS = 2  # two passes over the one group's four heads
    return mod


@pytest.fixture(scope="module")
def builder():
    return _load("builders", "keras_granite_hybrid")


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max(), scale)


def _mm(ref):
    return lambda a, w: jnp.matmul(a, w, precision=ref.HI)


def _ident(t):
    return t


def _layer_params(ref, prefix, seed=0):
    params = ref.init_params(CFG, seed)
    return {k: v for k, v in params.items() if k.startswith(prefix)}


def _stateless(layer, params, *inputs):
    tv = [params[v.path] for v in layer.trainable_variables]
    return layer.stateless_call(tv, [], *inputs)[0]


def _hidden(seed=5):
    return jax.random.normal(jax.random.key(seed), (2, SEQ, HIDDEN))


def _tokens(seed, rows=4):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CFG["vocab_size"], size=(rows, SEQ + 1))
    tok = tok.astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def _readout(y):
    return jnp.sum(jnp.sin(y.astype(jnp.float32)))


# -- the scan at one group ----------------------------------------------------


@pytest.mark.parametrize("chunk", [128, 256])
def test_chunked_scan_at_one_group_is_the_recurrence(chunk):
    """One group of B and C for all heads, at the cell's published
    chunk of 256 and at the other state-space cell's 128: 300
    positions, so a padded tail at either; values, final state and
    every gradient."""
    from elephas_tpu.ops.ssd import ssd_chunked, ssd_recurrent

    ks = jax.random.split(jax.random.key(chunk), 6)
    b, s, h, p, n = 1, 300, 4, 8, 16
    args = (
        jax.random.normal(ks[0], (b, s, h, p)),
        jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
        * jnp.logspace(-2, 0, h),
        -jnp.exp(jax.random.normal(ks[2], (h,))),
        jax.random.normal(ks[3], (b, s, 1, n)),
        jax.random.normal(ks[4], (b, s, 1, n)),
        jax.random.normal(ks[5], (h,)))
    want_y, want_state = ssd_recurrent(*args)
    with jax.default_matmul_precision("highest"):
        got_y, got_state = ssd_chunked(*args, chunk_size=chunk)
        got = jax.grad(lambda *a: _readout(
            ssd_chunked(*a, chunk_size=chunk)[0]), range(6))(*args)
    _close(got_y, want_y, 2e-5)
    _close(got_state, want_state, 2e-5)
    want = jax.grad(lambda *a: _readout(ssd_recurrent(*a)[0]),
                    range(6))(*args)
    for g, w in zip(got, want):
        _close(g, w, 5e-5)


def test_the_references_scan_in_passes_is_the_whole_square(ref, monkeypatch):
    """The reference takes the one group's heads ``SCAN_HEADS`` a pass
    and a block of queries at a time: two heads a pass in blocks of 8
    give what all four in one block of 24 give."""
    x = _hidden(12)
    params = _layer_params(ref, "layer0_mamba/")
    fn = lambda: ref._mamba(  # noqa: E731
        params, "layer0_mamba/", x, CFG, _ident, _mm(ref))
    blocked = fn()
    monkeypatch.setattr(ref, "SCAN_ROWS", 24)
    monkeypatch.setattr(ref, "SCAN_HEADS", 8)
    _close(blocked, fn(), 1e-5)


# -- each layer kind against the reference ------------------------------------


def _layer(kind, remat=False):
    from elephas_tpu.models import lm_blocks, lm_mixers

    if kind == "mamba":
        return lm_mixers.Mamba2Mixer(
            CFG["mamba_n_heads"], CFG["mamba_d_head"], CFG["mamba_d_state"],
            CFG["mamba_n_groups"], CFG["mamba_d_conv"],
            CFG["mamba_chunk_size"], CFG["rms_norm_eps"], remat=remat,
            name="layer0_mamba")
    if kind == "attn":
        return lm_mixers.BandedAttention(
            CFG["num_attention_heads"], CFG["num_key_value_heads"],
            CFG["head_dim"], None, False,
            scale=CFG["attention_multiplier"], remat=remat,
            name="layer2_attn")
    return lm_blocks.DenseMLP(
        CFG["shared_intermediate_size"], remat=remat, name="layer0_mlp")


def _reference_layer(ref, kind):
    if kind == "mlp":
        return "layer0_mlp/", lambda p, x: ref._swiglu(
            p, "layer0_mlp/", x, _ident, _mm(ref))
    name, prefix = {"mamba": ("_mamba", "layer0_mamba/"),
                    "attn": ("_attention", "layer2_attn/")}[kind]
    return prefix, lambda p, x: getattr(ref, name)(
        p, prefix, x, CFG, _ident, _mm(ref))


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("kind", ["mamba", "attn", "mlp"])
def test_layer_forward_and_gradients(ref, kind, remat):
    x = _hidden()
    layer = _layer(kind, remat)
    layer.build(x.shape)
    prefix, want_fn = _reference_layer(ref, kind)
    params = _layer_params(ref, prefix)
    assert {v.path for v in layer.variables} == set(params)
    got_fn = lambda p, x: _stateless(layer, p, x)  # noqa: E731
    _close(jax.jit(got_fn)(params, x), jax.jit(want_fn)(params, x))
    loss = lambda f: lambda p, x: _readout(3.0 * f(p, x))  # noqa: E731
    got = jax.jit(jax.grad(loss(got_fn), (0, 1)))(params, x)
    want = jax.jit(jax.grad(loss(want_fn), (0, 1)))(params, x)
    _close(got[1], want[1], GRAD_TOL)
    for path in params:
        _close(got[0][path], want[0][path], GRAD_TOL)


def test_attention_scale_none_is_the_usual_one():
    """``scale=None`` is ``head_dim ** -0.5``, to the bit; another scale
    moves the result."""
    import keras

    from elephas_tpu.models import lm_mixers

    x = _hidden(3)
    results = []
    for scale in (None, 8 ** -0.5, 0.2):
        keras.utils.set_random_seed(11)  # the same weights each time
        layer = lm_mixers.BandedAttention(4, 2, 8, None, False, scale=scale)
        results.append(np.asarray(layer(x)))
        assert layer.get_config()["scale"] == scale
    np.testing.assert_array_equal(results[0], results[1])
    assert np.abs(results[0] - results[2]).max() > 1e-4


# -- the whole model ----------------------------------------------------------


def _faults():
    return {key: dict(CFG, assumed=dict(CFG["assumed"], **{key: fault}))
            for key, fault in (
                ("attention_scale", "multiplier_over_sqrt_head_dim"),
                ("logits", "multiplied"),
                ("mamba_norm", "norm_then_gate"),
                ("residual", "on_stream"))}


def _model_loss(model, x, y):
    ntv = [v.value for v in model.non_trainable_variables]

    def loss(tv):
        logits, _ = model.stateless_call(tv, ntv, x)
        return jnp.mean(model.loss(y, logits))

    return loss


def test_model_loss_and_gradients_are_the_references_and_not_a_faults(
        ref, builder):
    """Logits, loss and every leaf's gradient of the whole model against
    the reference's; each of the four faults (the multiplier on top of
    the usual scale; the logits multiplied; the norm before the gate;
    the multiplier on the stream) moves the loss's gradient by more than
    a hundred times the comparison's tolerance, and the builder refuses
    to build any."""
    assert set(_faults()) == set(ref.READINGS) == set(builder.READINGS)
    params = ref.init_params(CFG, 6)
    model = builder.build(dict(CFG), params)
    x, y = _tokens(6, rows=2)
    _close(model(x), ref.forward(params, x, CFG))
    tv = [v.value for v in model.trainable_variables]
    got_loss, got_grads = jax.jit(jax.value_and_grad(
        _model_loss(model, x, y)))(tv)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, x, y, CFG)))(params)
    assert abs(float(got_loss) - float(want_loss)) <= TOL * float(want_loss)
    worst = max(np.abs(np.asarray(g)).max() for g in want_grads.values())
    assert {v.path for v in model.trainable_variables} == set(params)
    for var, grad in zip(model.trainable_variables, got_grads):
        want = np.asarray(want_grads[var.path])
        assert np.abs(np.asarray(grad) - want).max() <= GRAD_TOL * max(
            np.abs(want).max(), 1e-3 * worst), var.path
    for name, other in _faults().items():
        far_grads = jax.jit(jax.grad(
            lambda p: ref.loss_fn(p, x, y, other)))(params)
        moved = max(
            np.abs(np.asarray(far_grads[k] - want_grads[k])).max()
            / max(np.abs(np.asarray(want_grads[k])).max(), 1e-3 * worst)
            for k in params)
        assert moved > 100 * GRAD_TOL, (name, moved)
        with pytest.raises(ValueError, match="assumed"):
            builder.build(other, params)
    with pytest.raises(ValueError, match="assumed.logits"):
        ref.forward(params, x, dict(CFG, assumed=dict(
            CFG["assumed"], logits="halved")))


def test_the_tied_leaf_takes_both_paths_gradients(ref, builder):
    """The model has one table and no ``lm_head``; the table's gradient
    is the embedding path's plus the head path's, each taken alone in
    the reference by stopping the other."""
    params = ref.init_params(CFG, 9)
    model = builder.build(dict(CFG), params)
    paths = [v.path for v in model.variables]
    assert paths.count(TABLE) == 1 and paths[0] == TABLE
    assert not any("lm_head" in p for p in paths)
    assert "lm_head" not in [layer.name for layer in model.layers]
    x, y = _tokens(9, rows=2)
    tv = [v.value for v in model.trainable_variables]
    got = jax.jit(jax.grad(_model_loss(model, x, y)))(tv)[0]

    def split(table_in, table_out):
        h = ref._embed({TABLE: table_in}, x, CFG, False)
        for i, kind in enumerate(ref.layer_kinds(CFG)):
            h = ref._layer(ref._of_layer(params, i), h, CFG, kind, False)
        head = {TABLE: table_out,
                "final_norm/weight": params["final_norm/weight"]}
        return ref._cross_entropy(ref._logits(head, h, CFG, False), y)

    by_embedding, by_head = jax.jit(jax.grad(split, (0, 1)))(
        params[TABLE], params[TABLE])
    for part in (by_embedding, by_head):
        assert np.abs(np.asarray(part)).max() > 1e-4
    _close(got, by_embedding + by_head, GRAD_TOL)


MULTIPLIERS = ("attention_multiplier", "embedding_multiplier",
               "residual_multiplier", "logits_scaling")


@pytest.mark.parametrize("multiplier", MULTIPLIERS)
def test_each_multiplier_set_to_one_changes_the_result(
        ref, builder, multiplier):
    """In the program and in the reference alike, and to the same
    logits."""
    params = ref.init_params(CFG, 4)
    x, _y = _tokens(4, rows=2)
    sound = np.asarray(builder.build(dict(CFG), params)(x))
    other = dict(CFG, **{multiplier: 1.0})
    got = np.asarray(builder.build(other, params)(x))
    assert np.abs(got - sound).max() > 100 * TOL * np.abs(sound).max()
    _close(got, ref.forward(params, x, other))


def test_multipliers_left_none_are_left_out_of_the_program():
    """``decoder_lm`` at its defaults multiplies by nothing: a model
    with no multiplier traces to fewer equations than one whose
    multipliers are all one, and to the same result."""
    from elephas_tpu.models import granite_hybrid_lm

    sizes = dict(
        vocab_size=64, maxlen=SEQ, hidden_size=32,
        layer_types=("mamba", "attention"), mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=16, mamba_chunk_size=8, shared_intermediate_size=48)
    ones = dict.fromkeys(
        ("embedding_multiplier", "residual_multiplier", "logits_scaling"),
        1.0)
    x, _y = _tokens(2, rows=2)
    counts, results = [], []
    for more in ({}, ones):
        model = granite_hybrid_lm(**sizes, **more, seed=3)
        tv = [v.value for v in model.trainable_variables]
        jaxpr = jax.make_jaxpr(
            lambda tv: model.stateless_call(tv, [], x)[0])(tv)
        counts.append(len(jaxpr.jaxpr.eqns))
        results.append(np.asarray(model(x)))
    assert counts[0] == counts[1] - (1 + 2 * 2 + 1)
    _close(results[0], results[1], 1e-6)


def test_logits_scaling_is_the_tied_heads_alone():
    """The untied ``LMHead`` divides nothing, so ``decoder_lm`` refuses
    the constant without the tied head rather than drop it."""
    from elephas_tpu.models import lm_blocks

    with pytest.raises(ValueError, match="tied head"):
        lm_blocks.decoder_lm(
            "untied", [], lm_blocks.RMSNorm, vocab_size=8, maxlen=4,
            hidden_size=8, init_std=0.02, lr=0.1, momentum=0.9, seed=0,
            dtype_policy=None, logits_scaling=2.0)


# -- through SparkModel.fit ---------------------------------------------------


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
    events = telemetry.default_tracer().events(since, name="ssd.chunks")
    want = ref.follow(CFG, 7, [(x[:2], y[:2]), (x[2:], y[2:])])
    return {"model": model, "history": history, "want": want,
            "start": {k: np.asarray(v) for k, v in params.items()},
            "events": events}


def test_fit_step_loss_matches_reference(fitted):
    got = fitted["history"]["loss"][0]
    assert abs(got - np.mean(fitted["want"]["losses"])) < TOL * got


def test_fit_step_momenta_and_change_match_reference(fitted):
    """By leaf, as the cell's ``correct`` compares them: against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger; 2e-3 is two steps of float32 sums in another order. The
    tied table is one leaf with one momentum on both sides."""
    model, want = fitted["model"], fitted["want"]
    norm = lambda a: float(np.sqrt(np.sum(np.square(  # noqa: E731
        np.asarray(a, np.float64)))))
    momenta = {v.path: np.asarray(v.value)
               for v in model.optimizer.variables}
    variables = {v.path: np.asarray(v.value) for v in model.variables}
    assert set(want["velocity_norm"]) == set(want["change_norm"]) == {
        v.path for v in model.trainable_variables}
    assert TABLE in want["velocity_norm"]
    assert sum("embed_tokens" in p or "lm_head" in p for p in momenta) == 1
    for kind, got_of in (
            ("velocity_norm", lambda p: momenta[
                "SGD/" + p.replace("/", "_") + "_momentum"]),
            ("change_norm", lambda p: variables[p] - fitted["start"][p])):
        floor = float(np.median(list(want[kind].values())))
        for path, ref_norm in want[kind].items():
            assert abs(norm(got_of(path)) - ref_norm) <= 2e-3 * max(
                ref_norm, floor), (kind, path)


def test_the_mixers_say_which_chunk_they_ran(fitted):
    """One ``ssd.chunks`` event a Mamba-2 layer and trace: the chunk,
    the chunks a sequence, heads and groups, and the bytes of one
    float32 ``[B, H, S / Q, Q, Q]`` factor; the benchmark's reader sums
    each distinct layer once."""
    events = fitted["events"]
    layers = {e["args"]["layer"] for e in events}
    assert layers == {"layer0_mamba", "layer1_mamba", "layer3_mamba"}
    for event in events:
        args = event["args"]
        assert (args["chunk"], args["chunks"], args["heads"],
                args["groups"]) == (8, 3, 4, 1)
        assert args["bytes"] == 4 * 2 * 4 * 3 * 8 * 8
    reader = _load("metrics", "ssm_scan_factors_gb")
    assert reader.read({}, events) == pytest.approx(3 * 6144 / 1e9)
    assert reader.read({}, []) is None


def test_the_references_layerwise_step_is_the_gradient_of_its_loss(ref):
    """``follow`` takes a sequence's gradient a piece at a time into the
    velocity (so that it fits the chip), the table's in two pieces:
    after one step from rest every leaf's velocity is ``-lr`` times
    ``jax.grad`` of the whole loss."""
    x, y = _tokens(10, rows=2)
    params = ref.init_params(CFG, 10)
    grads = jax.jit(jax.grad(lambda p: ref.loss_fn(p, x, y, CFG)))(params)
    got = ref.follow(CFG, 10, [(x, y)])
    assert set(got["velocity_norm"]) == set(params)
    lr = CFG["optimizer"]["learning_rate"]
    for path, norm in got["velocity_norm"].items():
        want = lr * float(jnp.sqrt(jnp.sum(jnp.square(grads[path]))))
        assert abs(norm - want) <= 1e-4 * max(want, 1e-6), path


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


def test_builder_assign_checks_paths_and_shapes(fitted, ref, builder):
    model = fitted["model"]
    params = ref.init_params(CFG, 8)
    builder.assign(model, params)
    for var in model.variables:
        np.testing.assert_array_equal(var.value, params[var.path])
    with pytest.raises(ValueError, match="differ"):
        builder.assign(model, {k: v for k, v in params.items()
                               if "conv_bias" not in k})
    with pytest.raises(ValueError, match="differ"):
        builder.assign(model, dict(params, **{
            "lm_head/kernel": params[TABLE].T}))
    wrong = dict(params)
    wrong["layer0_mamba/in_proj"] = params["layer0_mamba/in_proj"][:, :-1]
    with pytest.raises(ValueError, match="in_proj"):
        builder.assign(model, wrong)


# -- the configuration's own arithmetic ---------------------------------------


def test_reference_param_count_and_flops(builder, ref):
    """The published widths by shape arithmetic alone: a Mamba-2 layer
    76,182,976 with its SwiGLU and two pre-norms, the attention layer
    60,821,504, the tied table once, the cell's 772,160,448 in all; the
    scan's and the feed-forwards' counts a step."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    shapes = ref.param_shapes(cfg)
    size = lambda keep: sum(  # noqa: E731
        int(np.prod(shape)) for path, (shape, _kind) in shapes.items()
        if keep(path))
    assert ref.layer_kinds(cfg) == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    assert len(cfg["layer_types"]) == 40
    assert cfg["layer_types"].count("attention") == 4
    assert size(lambda p: p.startswith("layer0_")) == 76_182_976
    assert size(lambda p: p.startswith("layer5_")) == 60_821_504
    assert size(lambda p: p == TABLE) == 12544 * 2048
    assert size(lambda p: True) == cfg["parameters"] == 772_160_448
    assert shapes["layer0_mamba/in_proj"][0] == (2048, 8512)
    assert builder.layer_counts(cfg) == {"mamba": 9, "attention": 1}
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert cfg["attention_multiplier"] == 1 / 64
    traffic = {"sequence_length": 8192, "batch_size": 2}
    scan = builder.scan_macs_per_token_layer(cfg)
    assert scan == 32_768 + 1_048_576 + 2 * 524_288 == 2_129_920
    cost = builder.ssm_scan_step_cost(cfg, traffic)
    assert cost["flops"] == 6 * scan * 16384 * 9
    # a token a layer: x and y 4096, B and C 128 each in bfloat16, dt 64
    # in float32; forward reads four and writes y, backward reads those
    # and dy and writes four gradients
    inputs = 2 * (4096 + 256) + 4 * 64
    assert cost["bytes"] == (3 * inputs + 2 * 2 * 4096) * 16384 * 9
    mlp = builder.mlp_dense_step_cost(cfg, traffic)
    assert mlp["flops"] == 6 * 3 * 2048 * 8192 * 16384 * 10
    rows = 2 * (2 * 2048 + 3 * 8192)
    assert mlp["bytes"] == 10 * (
        2 * rows * 16384 + 3 * 2048 * 8192 * (2 + 2 + 4))
    macs = builder.forward_macs_per_token(cfg, 8192)
    assert builder.train_flops_per_example(cfg, traffic) == 6 * macs * 8192
    mamba = 2048 * 8512 + 4 * 4352 + scan + 4096 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 32 * 2 * 64 * 4096.5
    assert macs == 9 * mamba + attn + 10 * 3 * 2048 * 8192 + 2048 * 12544
    # 79.4 TFLOP a step of two sequences
    assert 2 * 6 * macs * 8192 == pytest.approx(79.4e12, rel=5e-3)
    assert cfg["mamba_chunk_size"] == 256
