"""The state-space / sparse / attention hybrid (``nemotron_h_lm``)
against the benchmark's plain reference, at a small size on the CPU:
widths cut, ratios kept where they matter (4 Mamba-2 heads over 2
groups of B and C, so 2 heads a group; 24 positions in chunks of 8; 4
query heads over 2 key/value heads; 6 of 16 experts a token with 2
held, an eighth; the first five characters of an eight-character
pattern, ``MEM*E``: every kind of layer)."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "nemotron-3-nano-30b-a3b-ep16"

CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "MEM*EMEM", "mamba_num_heads": 4,
    "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 8, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "rope_theta": 10000, "layer_norm_epsilon": 1e-5,
    "n_routed_experts": 16, "num_experts_per_tok": 6,
    "moe_intermediate_size": 12, "moe_shared_expert_intermediate_size": 24,
    "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2", "n_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True, "use_conv_bias": True,
    "use_bias": False, "rescale_prenorm_residual": True,
    "num_experts_held": 2, "experts_held_first": 2,
    "published": {"num_hidden_layers": 8},
    "sequence_length": 24, "remat": True, "dtype": "float32",
    "assumed": {"initializer_range": 0.2, "select_bias_std": 0.01,
                "rope": "none", "mamba_norm": "gate_then_norm"},
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9},
}
SEQ, HIDDEN = CFG["sequence_length"], CFG["hidden_size"]
HELD = (2, 4)
# program and reference are both float32 here and differ by the order
# of their sums (a chunked scan against a quadratic form, a flash
# kernel against a materialised softmax, a grouped product against a
# sum over experts): a few float32 roundings of the largest entry
TOL, GRAD_TOL = 2e-4, 5e-4


def _load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + kind + "_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    mod = _load("reference", NAME)
    mod.SCAN_ROWS = 8  # three blocks of queries over the 24 positions
    return mod


@pytest.fixture(scope="module")
def builder():
    return _load("builders", "keras_nemotron_h")


def _close(got, want, tol=TOL):
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


def _stateless(layer, params, *inputs):
    """``(result, non-trainable variables after the call)`` with every
    variable the reference names taken from ``params``."""
    tv = [params[v.path] for v in layer.trainable_variables]
    ntv = [params.get(v.path, v.value)
           for v in layer.non_trainable_variables]
    return layer.stateless_call(tv, ntv, *inputs)


def _hidden(seed=5):
    return jax.random.normal(jax.random.key(seed), (2, SEQ, HIDDEN))


# -- the scan ---------------------------------------------------------------


def _scan_args(seed=0, s=37, dtype=jnp.float32):
    """Several chunks and a ragged tail, 2 heads a group (3 groups), and
    steps that differ by two orders of magnitude between heads."""
    ks = jax.random.split(jax.random.key(seed), 6)
    b, h, p, g, n = 2, 6, 8, 3, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0) * (
        jnp.logspace(-2, 0.5, h))
    a_neg = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b_in = jax.random.normal(ks[3], (b, s, g, n))
    c_in = jax.random.normal(ks[4], (b, s, g, n))
    d_skip = jax.random.normal(ks[5], (h,))
    return tuple(t.astype(dtype) for t in (x, dt, a_neg, b_in, c_in, d_skip))


def _readout(y):
    return jnp.sum(jnp.sin(y.astype(jnp.float32)))


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_scan_is_the_recurrence(chunk):
    """Values, the final state and the gradient of every argument, at
    37 positions: 5 chunks of 8 with a padded tail, 3 of 16, and one
    chunk longer than the sequence."""
    from elephas_tpu.ops.ssd import ssd_chunked, ssd_recurrent

    args = _scan_args()
    want_y, want_state = ssd_recurrent(*args)
    with jax.default_matmul_precision("highest"):
        got_y, got_state = ssd_chunked(*args, chunk_size=chunk)
        _close(got_y, want_y, 1e-5)
        _close(got_state, want_state, 1e-5)
        loss = lambda fn: lambda *a: (  # noqa: E731
            _readout(fn(*a)[0]) + jnp.sum(jnp.square(fn(*a)[1])))
        got = jax.grad(loss(lambda *a: ssd_chunked(*a, chunk_size=chunk)),
                       range(6))(*args)
    want = jax.grad(loss(ssd_recurrent), range(6))(*args)
    for g, w in zip(got, want):
        _close(g, w, 1e-5)


def test_chunked_scan_is_the_references_quadratic_form(ref):
    """The reference computes the rule in another form: the masked sum
    over all earlier keys, the decays' running sums taken from each
    query block's first row. Values and every argument's gradient."""
    from elephas_tpu.ops.ssd import ssd_chunked, ssd_recurrent

    args = _scan_args(1, s=40)  # five blocks of the reference's 8 rows
    want = ref.ssd_quadratic(*args)
    _close(want, ssd_recurrent(*args)[0], 1e-5)
    with jax.default_matmul_precision("highest"):
        _close(ssd_chunked(*args, chunk_size=16)[0], want, 1e-5)
        got_g = jax.grad(lambda *a: _readout(
            ssd_chunked(*a, chunk_size=16)[0]), range(6))(*args)
    want_g = jax.grad(
        lambda *a: _readout(ref.ssd_quadratic(*a)), range(6))(*args)
    for g, w in zip(got_g, want_g):
        _close(g, w, 2e-5)


def test_scan_keeps_its_decays_and_state_in_float32():
    """bfloat16 inputs: the result is bfloat16 and within bfloat16's
    rounding of the float32 recurrence on the same rounded inputs; the
    state comes back float32; heads must be a whole number a group."""
    from elephas_tpu.ops.ssd import ssd_chunked, ssd_recurrent

    x, dt, a_neg, b_in, c_in, d_skip = _scan_args(2, s=32)
    lo = lambda t: t.astype(jnp.bfloat16)  # noqa: E731
    y, state = ssd_chunked(lo(x), dt, a_neg, lo(b_in), lo(c_in), d_skip,
                           chunk_size=8)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    want, want_state = ssd_recurrent(
        lo(x), dt, a_neg, lo(b_in), lo(c_in), d_skip)
    _close(y, want, 2e-2)  # a few roundings at 2^-8 of the largest entry
    _close(state, want_state, 2e-2)
    with pytest.raises(ValueError, match="groups"):
        ssd_chunked(x[:, :, :5], dt[:, :, :5], a_neg[:5], b_in, c_in,
                    d_skip[:5])


# -- each layer kind against the reference ----------------------------------


def _mixer(kind, remat=False):
    from elephas_tpu.models import lm_mixers

    if kind == "mamba":
        return lm_mixers.Mamba2Mixer(
            CFG["mamba_num_heads"], CFG["mamba_head_dim"],
            CFG["ssm_state_size"], CFG["n_groups"], CFG["conv_kernel"],
            CFG["chunk_size"], CFG["layer_norm_epsilon"], remat=remat,
            name="layer0_mamba")
    if kind == "moe":
        return _moe_layer(remat=remat)
    return lm_mixers.BandedAttention(
        CFG["num_attention_heads"], CFG["num_key_value_heads"],
        CFG["head_dim"], None, False, remat=remat, name="layer3_attn")


def _moe_layer(held=HELD, remat=False, name="layer1_moe"):
    from elephas_tpu.models import lm_blocks as zoo

    return zoo.SparseMoeBlock(
        CFG["n_routed_experts"], CFG["num_experts_per_tok"],
        CFG["moe_intermediate_size"],
        CFG["moe_shared_expert_intermediate_size"], held,
        scoring_func="sigmoid", selection_bias=True,
        routed_scaling_factor=CFG["routed_scaling_factor"],
        gated_shared_expert=False, hidden_act="relu2", gated_experts=False,
        remat=remat, name=name)


REFERENCE_MIXER = {"mamba": ("_mamba", "layer0_mamba/"),
                   "moe": ("_sparse_block", "layer1_moe/"),
                   "attn": ("_attention", "layer3_attn/")}


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("kind", ["mamba", "moe", "attn"])
def test_layer_forward_and_gradients(ref, kind, remat):
    x = _hidden()
    layer = _mixer(kind, remat)
    layer.build(x.shape)
    fn_name, prefix = REFERENCE_MIXER[kind]
    params = _layer_params(ref, prefix)
    assert {v.path for v in layer.variables
            if not v.path.endswith("/route_counts")} == set(params)
    want_fn = lambda p, x: getattr(ref, fn_name)(  # noqa: E731
        p, prefix, x, CFG, _ident, _mm(ref))
    got_fn = lambda p, x: _stateless(layer, p, x)[0]  # noqa: E731
    _close(jax.jit(got_fn)(params, x), jax.jit(want_fn)(params, x))
    loss = lambda f: lambda p, x: _readout(3.0 * f(p, x))  # noqa: E731
    got = jax.jit(jax.grad(loss(got_fn), (0, 1)))(params, x)
    want = jax.jit(jax.grad(loss(want_fn), (0, 1)))(params, x)
    _close(got[1], want[1], GRAD_TOL)
    for path in ref.trained(params):
        _close(got[0][path], want[0][path], GRAD_TOL)


def test_the_mixer_gates_before_it_norms_in_groups(ref):
    """The gated norm is over each group of ``inner / n_groups``
    channels and takes the gated tensor: scaling one group's gate
    leaves the other group's output channels (before the projection)
    alone, and the reference with the norm first is another result."""
    x = _hidden(6)
    params = _layer_params(ref, "layer0_mamba/", seed=1)
    want = ref._mamba(params, "layer0_mamba/", x, CFG, _ident, _mm(ref))
    swapped = dict(CFG, assumed=dict(CFG["assumed"],
                                     mamba_norm="norm_then_gate"))
    other = ref._mamba(params, "layer0_mamba/", x, swapped, _ident, _mm(ref))
    layer = _mixer("mamba")
    layer.build(x.shape)
    got = _stateless(layer, params, x)[0]
    _close(got, want)
    assert np.abs(np.asarray(got) - np.asarray(other)).max() > 1e-2 * (
        np.abs(np.asarray(want)).max())
    # an identity output projection shows the normed channels: each
    # group of 16 has unit mean square over its own channels (less what
    # epsilon 1e-5 takes where the gated tensor is small)
    eye = dict(params, **{
        "layer0_mamba/out_proj": jnp.eye(32),
        "layer0_mamba/norm": jnp.ones(32)})
    normed = np.asarray(_stateless(layer, eye, x)[0]).reshape(2, SEQ, 2, 16)
    np.testing.assert_allclose(
        np.mean(normed ** 2, axis=-1), 1.0, rtol=2e-2)
    with pytest.raises(ValueError, match="mamba_norm"):
        ref._mamba(params, "layer0_mamba/", x, dict(
            CFG, assumed={"mamba_norm": "x"}), _ident, _mm(ref))


def test_the_model_builds_one_mixer_a_layer_by_the_pattern():
    from elephas_tpu.models import nemotron_h_lm

    model = nemotron_h_lm(
        vocab_size=64, maxlen=SEQ, hidden_size=32,
        hybrid_override_pattern="MEM*EMEM", num_hidden_layers=5,
        mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
        chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, n_routed_experts=16, num_experts_per_tok=6,
        moe_intermediate_size=12, moe_shared_expert_intermediate_size=24,
        experts_held=HELD, remat=True)
    mixers = [layer.name for layer in model.layers
              if layer.name.startswith("layer") and "norm" not in layer.name]
    assert mixers == ["layer0_mamba", "layer1_moe", "layer2_mamba",
                      "layer3_attn", "layer4_moe"]
    norms = [layer.name for layer in model.layers if "norm" in layer.name]
    assert norms == [f"layer{i}_norm" for i in range(5)] + ["final_norm"]
    attn = model.get_layer("layer3_attn")
    assert attn.window is None and attn.rotary is False
    moe = model.get_layer("layer4_moe")
    config = moe.get_config()
    assert (config["gated_experts"], config["hidden_act"],
            config["gated_shared_expert"], config["scoring_func"]) == (
        False, "relu2", False, "sigmoid")
    assert {v.path.rsplit("/", 1)[-1] for v in moe.variables} == {
        "router", "experts_up", "experts_down", "up", "down",
        "e_score_correction_bias", "route_counts"}
    mamba = model.get_layer("layer2_mamba")
    assert type(mamba).from_config(mamba.get_config()).get_config() == (
        mamba.get_config())
    np.testing.assert_allclose(
        np.asarray(mamba.A_log.value), np.log(np.arange(1, 5)), rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(mamba.dt_bias.value)))
    assert np.all((step >= 1e-3 * 0.999) & (step <= 0.1 * 1.001))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nemotron_h_lm(hybrid_override_pattern="ME-E")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nemotron_h_lm(hybrid_override_pattern="ME", num_hidden_layers=3)


# -- the ungated experts ------------------------------------------------------


def test_ungated_experts_against_squared_relu_written_out(ref):
    """One token slot a row, every row to held expert 0 at weight 1:
    ``down(max(up x, 0)^2)`` over an ``[E, D, I]`` stack; the gated form
    over the same stack read as ``[E, D, 2 * I / 2]`` differs."""
    from elephas_tpu.ops.moe import held_experts_ffn

    ks = jax.random.split(jax.random.key(9), 3)
    x = jax.random.normal(ks[0], (40, HIDDEN)).at[:, 0].set(1.0)
    up = jax.random.normal(ks[1], (2, HIDDEN, 12)) * 0.3
    down = jax.random.normal(ks[2], (2, 12, HIDDEN)) * 0.3
    router = jnp.zeros((HIDDEN, 4)).at[0, 0].set(50.0)
    got, counts = held_experts_ffn(
        x, router, up, down, (0, 2), 1, activation="relu2", gated=False)
    hidden = jnp.square(jnp.maximum(
        jnp.matmul(x, up[0], precision=ref.HI), 0.0))
    _close(got, jnp.matmul(hidden, down[0], precision=ref.HI))
    _close(got, ref._relu2_mlp(x, up[0], down[0], _ident, _mm(ref)))
    assert counts.tolist() == [40, 40, 40, 1, 0]
    gated, _ = held_experts_ffn(
        x, router, up, down[:, :6], (0, 2), 1, activation="relu2")
    assert gated.shape == got.shape
    assert np.abs(np.asarray(gated) - np.asarray(got)).max() > 1e-2


# recorded under jax 0.9.0: sha256 of the text of the op's jaxpr and of
# its gradient's, under the arguments that each of the three LMs passes.
# PR 42 recorded them from its parent (a3ae677: 53937729b2b98166,
# ff98055e2f97b3bc, f4c9ef6e3a5a7dfd) to show that the ungated form left
# the three programs alone; PR 43 rebuilt the routing, which is every
# LM's program, and recorded its own: a later PR that means to leave the
# sparse block's program alone still reads that here
PARENT_JAXPRS = {
    "qwen3next": ("silu", {"score": "softmax", "scale": 1.0},
                  "d73faf395047c616"),
    "kanana2": ("silu", {"score": "sigmoid", "scale": 2.448, "bias": True},
                "907b67fe5177713e"),
    "smallthinker": ("relu", {"score": "softmax", "scale": 1.0,
                              "route_from": None}, "61eb4932e44ef08c"),
}


def _ffn_jaxprs(activation, extra, **more):
    from elephas_tpu.ops.moe import held_experts_ffn

    x = jnp.zeros((48, 32), jnp.bfloat16)
    # gate and up, 12 wide each, or an ungated expert's up alone
    router = jnp.zeros((32, 16))
    gate_up = jnp.zeros((2, 32, 24 if more.get("gated", True) else 12))
    down, bias = jnp.zeros((2, 12, 32)), jnp.zeros(16)

    def f(x, router, gate_up, down, bias):
        kw = dict(extra, **more)
        if kw.pop("bias", False):
            kw["select_bias"] = bias
        return held_experts_ffn(x, router, gate_up, down, (2, 4), 6,
                                activation=activation, **kw)

    grad = jax.grad(lambda *a: jnp.sum(f(*a)[0].astype(jnp.float32)),
                    (0, 1, 2, 3))
    args = (x, router, gate_up, down, bias)
    return str(jax.make_jaxpr(f)(*args)) + str(jax.make_jaxpr(grad)(*args))


@pytest.mark.parametrize("model", sorted(PARENT_JAXPRS))
def test_gated_experts_jaxpr_is_what_it_was(model):
    """With the arguments the three LMs pass today, ``held_experts_ffn``
    and its gradient trace to the program they traced to before the
    ungated form existed: naming ``gated=True`` changes nothing, and
    (under the jax the digest was recorded with) the text is the
    recorded one, character for character."""
    activation, extra, digest = PARENT_JAXPRS[model]
    text = _ffn_jaxprs(activation, extra)
    assert text == _ffn_jaxprs(activation, extra, gated=True)
    assert text != _ffn_jaxprs("relu2", extra, gated=False)
    if jax.__version__ == "0.9.0":
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# -- the share of a deployment ------------------------------------------------


def test_shares_add_up_to_the_uncut_layer(ref):
    """The routed parts of all eight shares (2 of the 16 experts each)
    plus the shared expert counted ONCE add up to what the uncut
    reference (all 16 held) gives for the sparse layer; every share
    computes the same shared expert."""
    whole_cfg = dict(CFG, num_experts_held=16, experts_held_first=0)
    params = _layer_params(ref, "layer1_moe/", seed=3, cfg=whole_cfg)
    x = _hidden(10)
    want = ref._sparse_block(
        params, "layer1_moe/", x, whole_cfg, _ident, _mm(ref))
    shared = ref._relu2_mlp(
        x.reshape(-1, HIDDEN), params["layer1_moe/shared_expert/up"],
        params["layer1_moe/shared_expert/down"], _ident, _mm(ref),
    ).reshape(x.shape)
    total, routed_slots = 0.0, 0
    for share in range(8):
        first = 2 * share
        layer = _moe_layer((first, first + 2))
        layer.build(x.shape)
        mine = dict(params)
        for name in ("experts_up", "experts_down"):
            mine["layer1_moe/" + name] = params[
                "layer1_moe/" + name][first:first + 2]
        out, ntv = _stateless(layer, mine, x)
        total = total + (out - shared)  # this share's routed part
        counts = [v for v in ntv if v.dtype == jnp.int32]
        routed_slots += int(counts[0][0])
    _close(total + shared, want)
    assert routed_slots == 2 * SEQ * 6  # every slot is some share's
    assert np.abs(np.asarray(shared)).max() > 1e-2


# -- the whole model through SparkModel.fit -----------------------------------


def _tokens(seed, rows=4):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CFG["vocab_size"], size=(rows, SEQ + 1))
    tok = tok.astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def _faults():
    return {
        "rotated": dict(CFG, assumed=dict(CFG["assumed"], rope="rotary")),
        "norm_before_gate": dict(CFG, assumed=dict(
            CFG["assumed"], mamba_norm="norm_then_gate")),
    }


def test_model_loss_and_gradients_are_the_references_and_not_a_faults(
        ref, builder):
    """Logits, loss and every trained leaf's gradient of the whole
    model against the reference's; each of the two faults (the
    attention layer rotated; the norm before the gate) moves the logits
    and the loss's gradient by more than a hundred times the
    comparison's tolerance, and the builder refuses to build either."""
    params = ref.init_params(CFG, 6)
    model = builder.build(dict(CFG), params)
    x, y = _tokens(6, rows=2)
    got = np.asarray(model(x))
    _close(got, ref.forward(params, x, CFG))
    tv = [v.value for v in model.trainable_variables]
    ntv = [v.value for v in model.non_trainable_variables]

    def loss(tv):
        logits, _ = model.stateless_call(tv, ntv, x)
        return jnp.mean(model.loss(y, logits))

    got_loss, got_grads = jax.jit(jax.value_and_grad(loss))(tv)
    sound = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, x, y, CFG, False)))
    want_loss, want_grads = sound(params)
    assert abs(float(got_loss) - float(want_loss)) <= TOL * float(want_loss)
    worst = max(np.abs(np.asarray(g)).max() for g in want_grads.values())
    for var, grad in zip(model.trainable_variables, got_grads):
        want = np.asarray(want_grads[var.path])
        assert np.abs(np.asarray(grad) - want).max() <= GRAD_TOL * max(
            np.abs(want).max(), 1e-3 * worst), var.path
    for name, other in _faults().items():
        far = np.asarray(ref.forward(params, x, other))
        assert np.abs(got - far).max() > 100 * TOL * np.abs(far).max(), name
        _l, far_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, x, y, other, False)))(params)
        moved = max(
            np.abs(np.asarray(far_grads[k] - want_grads[k])).max()
            / max(np.abs(np.asarray(want_grads[k])).max(), 1e-3 * worst)
            for k in ref.trained(params))
        assert moved > 100 * GRAD_TOL, (name, moved)
        with pytest.raises(ValueError, match="assumed"):
            builder.build(other, params)


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
    assert abs(got - np.mean(fitted["want"]["losses"])) < TOL * got


def test_fit_step_momenta_and_change_match_reference(fitted):
    """By leaf, as the cell's ``correct`` compares them: against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger; 2e-3 is two steps of float32 sums in another order."""
    model, want = fitted["model"], fitted["want"]
    norm = lambda a: float(np.sqrt(np.sum(np.square(  # noqa: E731
        np.asarray(a, np.float64)))))
    momenta = {v.path: np.asarray(v.value)
               for v in model.optimizer.variables}
    variables = {v.path: np.asarray(v.value) for v in model.variables}
    assert set(want["velocity_norm"]) == {
        v.path for v in model.trainable_variables}
    for kind, got_of in (
            ("velocity_norm", lambda p: momenta[
                "SGD/" + p.replace("/", "_") + "_momentum"]),
            ("change_norm", lambda p: variables[p] - fitted["start"][p])):
        floor = float(np.median(list(want[kind].values())))
        for path, ref_norm in want[kind].items():
            assert abs(norm(got_of(path)) - ref_norm) <= 2e-3 * max(
                ref_norm, floor), (kind, path)


def test_fit_leaves_the_selection_bias_alone_and_counts_what_it_routed(
        fitted):
    model, events = fitted["model"], fitted["events"]
    for i in (1, 4):
        path = f"layer{i}_moe/e_score_correction_bias"
        bias = {v.path: v for v in model.variables}[path]
        np.testing.assert_array_equal(bias.value, fitted["start"][path])
        assert np.abs(fitted["start"][path]).max() > 0
    assert not any("e_score" in v.path for v in model.optimizer.variables)
    assert len(events) == 1 and events[0]["mono_ns"] is not None
    layers = events[0]["args"]["layers"]
    assert sorted(layers) == ["layer1_moe", "layer4_moe"]
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
                               if "conv_bias" not in k})
    wrong = dict(params)
    wrong["layer0_mamba/in_proj"] = params["layer0_mamba/in_proj"][:, :-1]
    with pytest.raises(ValueError, match="in_proj"):
        builder.assign(model, wrong)


def test_reference_param_count_and_flops(builder, ref):
    """The published widths by shape arithmetic alone: a Mamba-2 layer
    38,744,896, the attention layer 23,399,040, a sparse layer
    100,125,440 with its 8 held experts, the cell's 666,963,456 in all;
    717.8 MFLOP a token forward at 8192 positions, 45% of it the four
    mixers; the scan's count a step."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    shapes = ref.param_shapes(cfg)
    size = lambda keep: sum(  # noqa: E731
        int(np.prod(shape)) for path, (shape, _kind) in shapes.items()
        if keep(path))
    assert ref.pattern(cfg) == "MEMEM*EME"
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert size(lambda p: p.startswith("layer0_")) == 38_744_896
    assert size(lambda p: p.startswith("layer5_")) == 23_399_040
    assert size(lambda p: p.startswith("layer1_")) == 100_125_440
    assert size(lambda p: True) == cfg["parameters"] == 666_963_456
    assert shapes["layer0_mamba/in_proj"][0] == (2688, 10304)
    assert builder.layer_counts(cfg) == {"M": 4, "E": 4, "*": 1}
    traffic = {"sequence_length": 8192, "batch_size": 2}
    macs = builder.forward_macs_per_token(cfg, 8192)
    assert builder.train_flops_per_example(cfg, traffic) == 6 * macs * 8192
    assert 2 * macs == pytest.approx(717.8e6, rel=1e-3)
    scan = builder.scan_macs_per_token_layer(cfg)
    assert scan == 8 * 128 * 128 + 64 * (128 * 64 + 2 * 64 * 128)
    mamba = 2688 * 10304 + 4 * 6144 + scan + 4096 * 2688
    assert 2 * mamba == pytest.approx(80.87e6, rel=1e-3)
    assert 0.44 < 4 * mamba / macs < 0.46
    cost = builder.ssm_scan_step_cost(cfg, traffic)
    assert cost["flops"] == 6 * scan * 16384 * 4
    # a token a layer: x and y 4096, B and C 1024 each in bfloat16, dt
    # 64 in float32; forward reads four and writes y, backward reads
    # those and dy and writes four gradients
    inputs = 2 * (4096 + 2048) + 4 * 64
    assert cost["bytes"] == (3 * inputs + 2 * 2 * 4096) * 16384 * 4
    experts = builder.moe_experts_step_cost(cfg, traffic, 4 * 6144)
    assert experts["flops"] == 3 * 2 * 2 * 2688 * 1856 * 4 * 6144
    assert experts["bytes"] > 8 * 2 * 2688 * 1856 * 4 * 8


def test_the_references_layerwise_step_is_the_gradient_of_its_loss(ref):
    """``follow`` takes a sequence's gradient a layer at a time into
    the velocity (so that it fits the chip): after one step from rest
    the velocity is ``-lr`` times ``jax.grad`` of the whole loss, and
    the selection bias has none."""
    x, y = _tokens(10, rows=2)
    params = ref.init_params(CFG, 10)
    grads = jax.jit(jax.grad(
        lambda p: ref.loss_fn(p, x, y, CFG, False)))(params)
    got = ref.follow(CFG, 10, [(x, y)])
    assert set(got["velocity_norm"]) == set(ref.trained(params))
    lr = CFG["optimizer"]["learning_rate"]
    for path, norm in got["velocity_norm"].items():
        want = lr * float(jnp.sqrt(jnp.sum(jnp.square(grads[path]))))
        assert abs(norm - want) <= 1e-4 * max(want, 1e-6), path


def test_the_references_scan_in_blocks_is_the_whole_square(ref, monkeypatch):
    """The reference takes the quadratic form a block of queries at a
    time, with the decays' running sums from the block's first query
    (so that float32 holds their differences at 8192 positions): in
    blocks of 8 it gives what one block of all 24 gives."""
    x = _hidden(12)
    params = _layer_params(ref, "layer0_mamba/")
    fn = lambda: ref._mamba(  # noqa: E731
        params, "layer0_mamba/", x, CFG, _ident, _mm(ref))
    blocked = fn()
    monkeypatch.setattr(ref, "SCAN_ROWS", 24)
    _close(blocked, fn(), 1e-5)


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
