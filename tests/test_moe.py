"""Expert-parallel MoE: EP result == single-device oracle, gradients
flow, and load-imbalance capacity semantics hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, PartitionSpec as P

from elephas_tpu.ops.moe import (
    expert_parallel_ffn,
    init_moe_params,
    moe_ffn_reference,
)

W = 4  # mesh width used throughout


def _setup(t_per_dev=32, d=16, h=32, e_local=2, seed=0):
    key = jax.random.PRNGKey(seed)
    e_total = W * e_local
    params = init_moe_params(key, d, h, e_total)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (W * t_per_dev, d))
    mesh = Mesh(np.array(jax.devices()[:W]), ("ep",))
    return x, params, mesh, e_local


def _run_ep(x, params, mesh, e_local, capacity_factor=1.25):
    gate_w, w1, b1, w2, b2 = params

    def fn(x, gate_w, w1, b1, w2, b2):
        return expert_parallel_ffn(
            x, gate_w, w1, b1, w2, b2, axis_name="ep",
            capacity_factor=capacity_factor,
        )

    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
        out_specs=P("ep"),
        check_vma=False,
    )
    return sharded(x, gate_w, w1, b1, w2, b2)


def test_ep_matches_reference():
    x, params, mesh, e_local = _setup()
    out_ep = _run_ep(x, params, mesh, e_local)
    out_ref = moe_ffn_reference(x, *params, num_shards=W)
    np.testing.assert_allclose(
        np.asarray(out_ep), np.asarray(out_ref), atol=1e-5, rtol=1e-5
    )


def test_ep_gradients_flow():
    x, params, mesh, e_local = _setup()

    def loss_ep(x, params):
        return jnp.sum(_run_ep(x, params, mesh, e_local) ** 2)

    def loss_ref(x, params):
        return jnp.sum(moe_ffn_reference(x, *params, num_shards=W) ** 2)

    g_ep = jax.grad(loss_ep, argnums=(0, 1))(x, params)
    g_ref = jax.grad(loss_ref, argnums=(0, 1))(x, params)
    flat_ep = jax.tree.leaves(g_ep)
    flat_ref = jax.tree.leaves(g_ref)
    for a, b in zip(flat_ep, flat_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )
    # expert weights actually receive gradient
    assert any(float(jnp.abs(l).max()) > 0 for l in jax.tree.leaves(g_ep[1]))


def test_capacity_drops_overflow():
    """With capacity_factor → 0 every expert keeps ≤1 slot; most tokens
    are dropped and the output collapses toward zero — the Switch
    overflow contract, not an error."""
    x, params, mesh, e_local = _setup()
    out_tight = _run_ep(x, params, mesh, e_local, capacity_factor=1e-6)
    out_roomy = _run_ep(x, params, mesh, e_local, capacity_factor=4.0)
    zero_rows_tight = float(
        jnp.mean(jnp.all(jnp.abs(out_tight) < 1e-12, axis=-1))
    )
    zero_rows_roomy = float(
        jnp.mean(jnp.all(jnp.abs(out_roomy) < 1e-12, axis=-1))
    )
    assert zero_rows_tight > zero_rows_roomy
    assert zero_rows_roomy < 0.05  # roomy capacity keeps ~all tokens


def test_ep_composes_with_jit():
    x, params, mesh, e_local = _setup()
    jit_out = jax.jit(lambda x, p: _run_ep(x, p, mesh, e_local))(x, params)
    np.testing.assert_allclose(
        np.asarray(jit_out),
        np.asarray(_run_ep(x, params, mesh, e_local)),
        atol=1e-6,
    )


def test_routing_exact_in_bfloat16():
    """Regression (ADVICE r1): routing math must run in int32 — a bf16
    cumsum goes inexact past 256 tokens, colliding queue slots."""
    from elephas_tpu.ops.moe import _top1_dispatch

    t, d, e = 512, 8, 4
    x = jnp.ones((t, d), jnp.bfloat16)
    gate_w = jnp.zeros((d, e), jnp.bfloat16).at[:, 0].set(1.0)
    dispatch, combine = _top1_dispatch(x, gate_w, e, capacity=t)
    disp = np.asarray(dispatch, dtype=np.float32)
    # every token kept, each in a distinct queue position of expert 0
    assert disp.sum() == t
    assert disp[:, 0, :].sum(axis=0).max() == 1.0


# -- r3: top-k routing + load-balance loss + L5 integration --------------


def _run_ep_topk(x, params, mesh, e_local, k, capacity_factor=1.5):
    gate_w, w1, b1, w2, b2 = params

    def fn(x, gate_w, w1, b1, w2, b2):
        out, aux = expert_parallel_ffn(
            x, gate_w, w1, b1, w2, b2, axis_name="ep",
            capacity_factor=capacity_factor, k=k, return_aux=True,
        )
        return out, jax.lax.pmean(aux, "ep")

    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
        out_specs=(P("ep"), P()),
        check_vma=False,
    )
    return sharded(x, gate_w, w1, b1, w2, b2)


def test_ep_top2_matches_reference():
    x, params, mesh, e_local = _setup()
    out_ep, aux_ep = _run_ep_topk(x, params, mesh, e_local, k=2)
    out_ref, aux_ref = moe_ffn_reference(
        x, *params, num_shards=W, k=2, capacity_factor=1.5, return_aux=True
    )
    np.testing.assert_allclose(
        np.asarray(out_ep), np.asarray(out_ref), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-5)


def test_top2_combine_weights_normalized():
    """GShard top-2: each kept token's combine weights sum to its two
    renormalized gates — for roomy capacity, exactly 1."""
    from elephas_tpu.ops.moe import _topk_dispatch

    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 8)), jnp.float32)
    gate_w = jnp.asarray(np.random.default_rng(1).normal(size=(8, 4)), jnp.float32)
    dispatch, combine, aux = _topk_dispatch(x, gate_w, 4, capacity=64, k=2)
    per_token = np.asarray(combine).sum(axis=(1, 2))
    np.testing.assert_allclose(per_token, np.ones(64), atol=1e-5)


def test_aux_loss_minimized_by_uniform_router():
    """Switch §2.2: aux = E·Σ f·p is 1 for a uniform router and >1 for a
    collapsed one — the gradient pushes toward balance."""
    from elephas_tpu.ops.moe import _topk_dispatch

    x = jnp.asarray(np.random.default_rng(0).normal(size=(256, 8)), jnp.float32)
    uniform = jnp.zeros((8, 4), jnp.float32)
    _, _, aux_u = _topk_dispatch(x, uniform, 4, capacity=256, k=1)
    collapsed = jnp.zeros((8, 4), jnp.float32).at[0, 0].set(50.0)
    x_pos = jnp.abs(x)  # all tokens push expert 0
    _, _, aux_c = _topk_dispatch(x_pos, collapsed, 4, capacity=256, k=1)
    assert abs(float(aux_u) - 1.0) < 0.05, float(aux_u)
    assert float(aux_c) > 2.0, float(aux_c)


def _token_blobs(n=256, maxlen=16, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, size=n).astype(np.int32)
    half = vocab // 2
    hi = rng.integers(half, vocab, size=(n, maxlen))
    lo = rng.integers(1, half, size=(n, maxlen))
    mask = rng.random((n, maxlen)) < np.where(y[:, None] == 1, 0.8, 0.2)
    x = np.where(mask, hi, lo).astype(np.int32)
    return x, y


def test_switch_transformer_trains_via_spark_model():
    """The L5 gate (VERDICT r2 missing #4): an MoE model trains through
    SparkModel with descending loss, reaches accuracy, and keeps every
    expert alive (the load-balance loss working end-to-end)."""
    import keras

    from elephas_tpu import SparkModel
    from elephas_tpu.models import switch_transformer_classifier

    x, y = _token_blobs(n=512)
    model = switch_transformer_classifier(
        vocab_size=64, maxlen=16, num_classes=2,
        d_model=32, num_heads=2, num_layers=1,
        num_experts=4, expert_hidden=64, k=2, dropout=0.0, seed=0,
        lr=3e-3, aux_weight=5e-2,
    )
    sm = SparkModel(model, num_workers=8)
    history = sm.fit((x, y), epochs=10, batch_size=16)
    assert history["loss"][-1] < history["loss"][0]
    preds = sm.predict(x[:128])
    acc = float((preds.argmax(1) == y[:128]).mean())
    assert acc > 0.8, acc

    # expert utilization: first-choice routing fractions over the REAL
    # router inputs (the block's post-LN activations)
    import keras as _keras

    moe = model.get_layer("blk0_moe")
    probe = _keras.Model(model.input, model.get_layer("blk0_ln2").output)
    h = np.asarray(probe(x[:128]))
    tokens = h.reshape(-1, h.shape[-1])
    logits = tokens @ np.asarray(moe.gate_kernel)
    first = logits.argmax(-1)
    fracs = np.bincount(first, minlength=4) / len(first)
    # no dead expert (uniform would be 0.25 each), and the Switch balance
    # metric E·Σf·p stays near its minimum of 1 (collapse → E)
    assert fracs.min() > 0.04, fracs
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    balance = 4 * float((fracs * probs.mean(0)).sum())
    assert balance < 2.0, (balance, fracs)


def test_moe_ffn_layer_save_load_roundtrip(tmp_path):
    import keras

    from elephas_tpu.models.switch import MoeFFN

    keras.utils.set_random_seed(0)
    model = keras.Sequential([
        keras.layers.Input((8, 16)),
        MoeFFN(4, 32, k=2, name="moe"),
        keras.layers.GlobalAveragePooling1D(),
        keras.layers.Dense(2, activation="softmax"),
    ])
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    x = np.random.default_rng(0).normal(size=(4, 8, 16)).astype(np.float32)
    before = np.asarray(model(x))
    path = str(tmp_path / "moe.keras")
    model.save(path)
    loaded = keras.models.load_model(path)  # registered: no custom_objects
    np.testing.assert_allclose(np.asarray(loaded(x)), before, atol=1e-6)


def test_moe_layer_shards_experts_under_tp():
    """Under SparkModel(model_parallel=2) the planner shards [E, ...]
    expert weights over the model axis (expert parallelism via GSPMD)."""
    from elephas_tpu import SparkModel
    from elephas_tpu.models import switch_transformer_classifier

    x, y = _token_blobs(n=128)
    model = switch_transformer_classifier(
        vocab_size=64, maxlen=16, num_classes=2,
        d_model=32, num_heads=2, num_layers=1,
        num_experts=4, expert_hidden=64, k=2, dropout=0.0, seed=1,
    )
    sm = SparkModel(model, model_parallel=2)
    runner = sm._get_runner()
    summary = runner.trainer.sharding_summary()
    expert_specs = {p: s for p, s in summary.items() if "expert_w" in p}
    assert expert_specs and all("model" in s for s in expert_specs.values()), (
        summary
    )
    history = sm.fit((x, y), epochs=2, batch_size=32)
    assert np.isfinite(history["loss"]).all()


def test_moe_stateless_grad_lowering_pinned():
    """Regression pin (ISSUE 11): the seed's MoE tier-1 failures all
    reduced to THIS lowering shape — ``jax.grad`` through
    ``MoeFFN.stateless_call`` (what every SparkModel training step
    runs). Raw keras Variables inside ``jnp`` ops are not valid JAX
    types (jax dropped the ``__jax_array__`` auto-convert), so the
    layer must read ``.value`` explicitly; under the stateless scope
    that resolves to the traced array and gradients flow. This test
    fails within seconds if the unwrap regresses — no SparkModel fit
    needed to see it."""
    import keras

    from elephas_tpu.models.switch import MoeFFN

    keras.utils.set_random_seed(0)
    layer = MoeFFN(4, 32, k=2, name="moe_pin")
    layer.build((None, 16))
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 16)), jnp.float32
    )
    tv = [v.value for v in layer.trainable_variables]
    ntv = [v.value for v in layer.non_trainable_variables]

    def loss(tv):
        out, _ntv2, losses = layer.stateless_call(
            tv, ntv, x, training=True, return_losses=True
        )
        return jnp.sum(out**2) + sum(losses)

    grads = jax.jit(jax.grad(loss))(tv)
    assert any(float(jnp.abs(g).max()) > 0 for g in grads)


def test_topk_rejects_k_above_num_experts():
    from elephas_tpu.ops.moe import _topk_dispatch
    from elephas_tpu.models.switch import MoeFFN

    x = jnp.ones((8, 4))
    gate_w = jnp.ones((4, 2))
    with pytest.raises(ValueError, match="exceed"):
        _topk_dispatch(x, gate_w, 2, capacity=8, k=3)
    with pytest.raises(ValueError, match="exceed"):
        MoeFFN(2, 16, k=4)


def test_switch_transformer_lm_trains_and_generates():
    """r5: the MoE decoder LM — sparse counterpart of transformer_lm —
    trains through SparkModel and decodes through generate(), with the
    KV-cache graph replay matching the full-recompute path exactly
    when expert capacity covers every token (k·cf ≥ E → no drops)."""
    from elephas_tpu import SparkModel
    from elephas_tpu.models import generate, switch_transformer_lm

    maxlen, vocab, n = 16, 8, 256
    rng = np.random.default_rng(0)
    starts = rng.integers(2, 6, size=n)
    seq = (starts[:, None] + np.arange(maxlen + 1)) % 4 + 2
    x, y = seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)

    m = switch_transformer_lm(
        vocab_size=vocab, maxlen=maxlen, d_model=32, num_heads=2,
        num_layers=1, num_experts=2, k=2, capacity_factor=2.0,
        dropout=0.0, lr=1e-2, seed=0,
    )
    sm = SparkModel(m, num_workers=4)
    h = sm.fit((x, y), epochs=8, batch_size=32)
    assert h["loss"][-1] < h["loss"][0], h["loss"]

    prompt = np.array([[2, 3, 4, 5], [4, 5, 2, 3]], np.int32)
    out = generate(m, prompt, steps=8)
    assert out.shape == (2, 12)
    assert out.min() >= 0 and out.max() < vocab
    np.testing.assert_array_equal(out[:, :4], prompt)
    # k=2 with cf=2.0 over E=2 experts: capacity >= tokens, nothing
    # drops, so the per-token cached replay is bit-identical routing
    cached = generate(m, prompt, steps=8, kv_cache=True)
    np.testing.assert_array_equal(cached, out)
    # the sparse LM also decodes on a mesh (DP route)
    mesh_out = sm.generate(prompt, steps=8)
    np.testing.assert_array_equal(mesh_out, out)


def test_switch_transformer_lm_shards_experts_under_tp():
    """The LM's expert weights shard over the model axis (the planner's
    expert_w rules) and TP training stays finite."""
    from elephas_tpu import SparkModel
    from elephas_tpu.models import switch_transformer_lm

    maxlen, vocab = 16, 8
    rng = np.random.default_rng(1)
    x = rng.integers(0, vocab, size=(64, maxlen)).astype(np.int32)
    y = rng.integers(0, vocab, size=(64, maxlen)).astype(np.int32)
    m = switch_transformer_lm(
        vocab_size=vocab, maxlen=maxlen, d_model=32, num_heads=2,
        num_layers=1, num_experts=2, dropout=0.0, seed=3,
    )
    sm = SparkModel(m, model_parallel=2)
    runner = sm._get_runner()
    summary = runner.trainer.sharding_summary()
    expert_specs = [v for p, v in summary.items() if "expert_w" in p]
    assert expert_specs and all("model" in s for s in expert_specs), summary
    h = sm.fit((x, y), epochs=1, batch_size=32)
    assert np.isfinite(h["loss"][0]), h
