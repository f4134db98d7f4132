"""Sequence parallelism behind the parity API, on the 8-device CPU mesh.

The reference has no long-context story (SURVEY.md §5); these tests
cover the TPU-native extension: ``SequenceShardedTrainer`` (DP×SP mesh,
ring attention inside ``FlashMHA``) and its ``SparkModel(model,
sequence_parallel=N)`` routing. Correctness is asserted the repo's
standard two ways — numeric parity with unsharded training, and
end-task quality on a task that *requires* cross-shard attention.
"""

import numpy as np
import pytest

import keras

from elephas_tpu.models import transformer_classifier
from elephas_tpu.parallel.sequence import (
    SequenceShardedTrainer,
    active_sequence_scope,
    dp_sp_mesh,
    ring_mha,
    sequence_parallel_scope,
)
from elephas_tpu.parallel.tensor import ShardedTrainer, dp_tp_mesh


def _tiny_transformer(seed=0, maxlen=32, vocab=64, heads=2):
    return transformer_classifier(
        vocab_size=vocab, maxlen=maxlen, num_classes=2,
        d_model=16, num_heads=heads, num_layers=1, dropout=0.0, seed=seed,
    )


def _marker_task(n, maxlen, vocab, seed=0):
    """Label = which half of the sequence carries marker token 1 — a
    shard-local model cannot solve it; attention must cross shards."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, size=n).astype(np.int32)
    x = rng.integers(4, vocab, size=(n, maxlen)).astype(np.int32)
    pos = rng.integers(0, maxlen // 2, size=n) + np.where(
        y == 1, maxlen // 2, 0
    )
    x[np.arange(n), pos] = 1
    return x, y


def test_dp_sp_mesh_construction():
    mesh = dp_sp_mesh(sequence_parallel=4)
    assert mesh.shape == {"data": 2, "seq": 4}
    with pytest.raises(ValueError, match="divide"):
        dp_sp_mesh(sequence_parallel=3)
    sub = dp_sp_mesh(sequence_parallel=3, data_parallel=2)
    assert sub.shape == {"data": 2, "seq": 3}


def test_scope_nesting_and_ring_guard():
    assert active_sequence_scope() is None
    mesh = dp_sp_mesh(sequence_parallel=2)
    with sequence_parallel_scope(mesh):
        assert active_sequence_scope().mesh is mesh
    assert active_sequence_scope() is None
    q = np.zeros((2, 2, 8, 4), np.float32)
    with pytest.raises(RuntimeError, match="outside"):
        ring_mha(q, q, q)
    with sequence_parallel_scope(dp_sp_mesh(sequence_parallel=4)):
        bad_s = np.zeros((2, 2, 6, 4), np.float32)  # 6 % 4 != 0
        with pytest.raises(ValueError, match="sequence length"):
            ring_mha(bad_s, bad_s, bad_s)


@pytest.mark.parametrize(
    "attention,sp,dp,mp,heads",
    [
        ("ring", 4, 2, 1, 2),
        ("ulysses", 2, 4, 1, 2),  # ulysses: heads(2) % sp == 0
        ("ring", 2, 2, 2, 2),  # TP×SP: Megatron shards + ring on one mesh
        # TP×SP ulysses with the head axis sharded over 'model'
        # (heads % mp == 0 and heads/mp % sp == 0 → head_axis engages)
        ("ulysses", 2, 2, 2, 4),
    ],
)
def test_sp_matches_unsharded_training(attention, sp, dp, mp, heads):
    """Same seeds, same data: sharded attention (ring KV rotation or
    Ulysses head<->sequence all-to-all), optionally composed with
    Megatron weight sharding, must reproduce the unsharded flash math
    to float tolerance."""
    maxlen, vocab = 32, 64
    x, y = _marker_task(128, maxlen, vocab, seed=3)

    m1 = _tiny_transformer(seed=7, maxlen=maxlen, vocab=vocab, heads=heads)
    t1 = ShardedTrainer(m1, mesh=dp_tp_mesh(model_parallel=1, data_parallel=1))
    h1 = t1.fit(x, y, epochs=2, batch_size=32)

    m2 = _tiny_transformer(seed=7, maxlen=maxlen, vocab=vocab, heads=heads)
    t2 = SequenceShardedTrainer(
        m2, sequence_parallel=sp, data_parallel=dp, attention=attention,
        model_parallel=mp,
    )
    expect_shape = {"data": dp, "seq": sp}
    if mp > 1:
        expect_shape["model"] = mp
        # the planner actually sharded weights over the model axis
        assert any(
            "model" in spec for spec in t2.sharding_summary().values()
        ), t2.sharding_summary()
    assert dict(t2.mesh.shape) == expect_shape
    h2 = t2.fit(x, y, epochs=2, batch_size=32)

    np.testing.assert_allclose(h1["loss"], h2["loss"], rtol=2e-3)
    for a, b in zip(m1.get_weights(), m2.get_weights()):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)

    # evaluate parity on the trained weights
    e1 = t1.evaluate(x, y, batch_size=32)
    e2 = t2.evaluate(x, y, batch_size=32)
    assert e1.keys() == e2.keys()
    for key in e1:
        np.testing.assert_allclose(e1[key], e2[key], rtol=5e-3, err_msg=key)


def test_noncausal_ring_jit_lowering_pinned():
    """Regression pin (ISSUE 11): the seed's 3 SP tier-1 failures all
    reduced to THIS lowering shape — a NON-causal ring inside jit.
    The ring's scan body computed ``axis_index`` unconditionally; on
    the non-causal path nothing consumed it, the dead instruction
    survived into the lowered module, and XLA's SPMD partitioner
    refused the orphaned ``PartitionId`` ("not supported for SPMD
    partitioning"). Causal rings (where the switch consumes it) never
    showed it — which is why every LM test stayed green while
    classifier evaluate/predict died. Pin BOTH directions: the jit
    must compile AND match unsharded flash attention."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops.flash_attention import attention_reference
    from jax.sharding import PartitionSpec as P

    mesh = dp_sp_mesh(sequence_parallel=4)
    bh, S, D = 4, 32, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(bh, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(bh, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(bh, S, D)), jnp.float32)
    from elephas_tpu.ops.ring_attention import ring_attention

    for causal in (False, True):  # False is the regression; True the control
        fn = lambda a, b, c: ring_attention(  # noqa: E731
            a, b, c, axis_name="seq", causal=causal
        )
        sharded = jax.shard_map(
            fn, mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
            out_specs=P(None, "seq", None), check_vma=False,
        )
        out = jax.jit(lambda a, b, c: sharded(a, b, c))(q, k, v)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"causal={causal}",
        )


def test_sp_weights_replicate_activations_shard():
    m = _tiny_transformer(seed=1)
    t = SequenceShardedTrainer(m, sequence_parallel=4)
    # rules=[]: every weight replicates — SP shards activations only
    assert all(
        spec == "PartitionSpec()" for spec in t.sharding_summary().values()
    ), t.sharding_summary()


def test_spark_model_sequence_parallel_learns(spark_context):
    """L5 route: SparkModel(sequence_parallel=4) trains a task that
    needs cross-shard attention, through the rdd fit path, and
    history/evaluate/predict all work."""
    from elephas_tpu import SparkModel
    from elephas_tpu.utils.rdd_utils import to_simple_rdd

    maxlen, vocab = 64, 32
    x, y = _marker_task(256, maxlen, vocab, seed=0)
    model = transformer_classifier(
        vocab_size=vocab, maxlen=maxlen, num_classes=2,
        d_model=32, num_heads=2, num_layers=1, dropout=0.0, seed=2,
        lr=1e-2,
    )
    sm = SparkModel(model, sequence_parallel=4)
    assert sm.num_workers == 2  # 8 devices / sp=4
    rdd = to_simple_rdd(spark_context, x, y)
    history = sm.fit(rdd, epochs=15, batch_size=32)
    assert history["loss"][-1] < history["loss"][0]
    preds = sm.predict(x)
    acc = float((preds.argmax(1) == y).mean())
    assert acc > 0.75, acc
    # evaluate on the trained weights: [loss, accuracy], both solved
    scores = sm.evaluate(rdd, batch_size=32)
    assert scores[0] < 0.2, scores
    assert scores[1] > 0.9, scores


def test_sequence_parallel_guards():
    from elephas_tpu import SparkModel

    model = _tiny_transformer(seed=0)
    # model_parallel composes with sequence_parallel (3-D mesh); the
    # pipeline stays exclusive
    sm = SparkModel(model, model_parallel=2, sequence_parallel=2)
    assert dict(sm.mesh.shape) == {"data": 2, "seq": 2, "model": 2}
    # r5: PP×TP composes now; pipeline × sequence is what stays out
    with pytest.raises(ValueError, match="cannot compose"):
        SparkModel(model, pipeline_parallel=2, sequence_parallel=2)
    with pytest.raises(ValueError, match="synchronously"):
        SparkModel(model, mode="asynchronous", sequence_parallel=2)
    with pytest.raises(ValueError, match="local-SGD"):
        SparkModel(model, frequency="fit", sequence_parallel=2)
    with pytest.raises(ValueError, match="exceeds"):
        SparkModel(model, sequence_parallel=16)
    # an explicit mesh without a 'seq' axis fails up front with a
    # descriptive error, not a bare KeyError (r3 advisor finding)
    with pytest.raises(ValueError, match="'seq' axis"):
        SequenceShardedTrainer(model, mesh=dp_tp_mesh(model_parallel=2))
    with pytest.raises(ValueError, match="positive"):
        dp_sp_mesh(sequence_parallel=2, data_parallel=0)


def test_sequence_parallel_config_roundtrip(tmp_path):
    from elephas_tpu import SparkModel
    from elephas_tpu.spark_model import load_spark_model

    model = _tiny_transformer(seed=4)
    sm = SparkModel(model, sequence_parallel=2)
    assert sm.get_config()["sequence_parallel"] == 2
    path = str(tmp_path / "sp_model.keras")
    sm.save(path)
    loaded = load_spark_model(path)
    assert loaded.sequence_parallel == 2
    assert loaded.num_workers == 4


def test_spark_model_ulysses_attention(spark_context):
    """L5: sequence_attention='ulysses' routes FlashMHA through the
    all-to-all mechanism and round-trips the config."""
    from elephas_tpu import SparkModel
    from elephas_tpu.utils.rdd_utils import to_simple_rdd

    maxlen, vocab = 32, 32
    x, y = _marker_task(128, maxlen, vocab, seed=1)
    model = transformer_classifier(
        vocab_size=vocab, maxlen=maxlen, num_classes=2,
        d_model=16, num_heads=2, num_layers=1, dropout=0.0, seed=6,
        lr=1e-2,
    )
    sm = SparkModel(model, sequence_parallel=2,
                    sequence_attention="ulysses")
    assert sm.get_config()["sequence_attention"] == "ulysses"
    rdd = to_simple_rdd(spark_context, x, y)
    history = sm.fit(rdd, epochs=4, batch_size=32)
    assert history["loss"][-1] < history["loss"][0]
    preds = sm.predict(x[:32])
    assert preds.shape == (32, 2)
    with pytest.raises(ValueError, match="sequence_attention"):
        SparkModel(model, sequence_parallel=2, sequence_attention="bogus")


def test_spark_model_tp_sp_composition(spark_context):
    """L5: SparkModel(model_parallel=2, sequence_parallel=2) routes to
    the SEQUENCE runner (not the TP runner, which would silently skip
    the ring), plans Megatron shardings over the 3-D mesh's model axis,
    and matches unsharded training to float tolerance."""
    from elephas_tpu import SparkModel
    from elephas_tpu.parallel.sequence import SequenceParallelRunner
    from elephas_tpu.parallel.tensor import ShardedTrainer, dp_tp_mesh

    maxlen, vocab = 32, 64
    x, y = _marker_task(128, maxlen, vocab, seed=3)

    m1 = _tiny_transformer(seed=7, maxlen=maxlen, vocab=vocab)
    t1 = ShardedTrainer(m1, mesh=dp_tp_mesh(model_parallel=1, data_parallel=1))
    h1 = t1.fit(x, y, epochs=2, batch_size=32)

    m2 = _tiny_transformer(seed=7, maxlen=maxlen, vocab=vocab)
    sm = SparkModel(m2, sequence_parallel=2, model_parallel=2)
    assert dict(sm.mesh.shape) == {"data": 2, "seq": 2, "model": 2}
    runner = sm._get_runner()
    assert isinstance(runner, SequenceParallelRunner), type(runner)
    summary = runner.trainer.sharding_summary()
    assert any("model" in spec for spec in summary.values()), summary
    h2 = sm.fit((x, y), epochs=2, batch_size=32)

    np.testing.assert_allclose(h1["loss"], h2["loss"], rtol=2e-3)
    for a, b in zip(m1.get_weights(), m2.get_weights()):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)


def test_rope_lm_sequence_parallel_matches_unsharded():
    """r4: rope rotation is positionwise over the GLOBAL sequence, so it
    composes with the ring — a rope causal LM under sequence_parallel
    trains identically to unsharded."""
    from elephas_tpu.models import transformer_lm

    maxlen, vocab = 32, 16
    rng = np.random.default_rng(4)
    starts = rng.integers(2, 6, size=128)
    seq = (starts[:, None] + np.arange(maxlen + 1)) % 4 + 2
    x, y = seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)

    def build():
        return transformer_lm(vocab_size=vocab, maxlen=maxlen, d_model=16,
                              num_heads=2, num_layers=1, dropout=0.0,
                              lr=1e-2, seed=6, rope=True)

    t1 = ShardedTrainer(build(), mesh=dp_tp_mesh(model_parallel=1,
                                                 data_parallel=1))
    h1 = t1.fit(x, y, epochs=2, batch_size=32)

    t2 = SequenceShardedTrainer(build(), sequence_parallel=4,
                                data_parallel=2)
    h2 = t2.fit(x, y, epochs=2, batch_size=32)

    np.testing.assert_allclose(h1["loss"], h2["loss"], rtol=2e-3)
    for a, b in zip(t1.model.get_weights(), t2.model.get_weights()):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)


def test_spark_model_sequence_parallel_lm_2d_targets(spark_context):
    """r4 regression (found by an end-to-end drive): a causal LM's 2-D
    [B, S] targets through the L5 SparkModel(sequence_parallel=N) route
    — per-ROW sample weights must broadcast against the per-token loss
    instead of failing jnp broadcasting."""
    from elephas_tpu import SparkModel
    from elephas_tpu.models import transformer_lm

    maxlen, vocab = 16, 8
    rng = np.random.default_rng(0)
    starts = rng.integers(2, 6, size=128)
    seq = (starts[:, None] + np.arange(maxlen + 1)) % 4 + 2
    x, y = seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)

    m = transformer_lm(vocab_size=vocab, maxlen=maxlen, d_model=32,
                       num_heads=2, num_layers=1, dropout=0.0, lr=1e-2,
                       seed=0, rope=True)
    sm = SparkModel(m, sequence_parallel=2)
    h = sm.fit((x, y), epochs=4, batch_size=32)
    assert np.isfinite(h["loss"]).all()
    assert h["loss"][-1] < h["loss"][0], h
    assert "accuracy" in h  # compiled metrics ride the 2-D-target path


def test_ring_mha_joint_batch_head_tiling():
    """r5 round sweep: when neither batch nor heads tile the data axis
    alone but their product does (b=2, h=2, dp=4), ring_mha keeps the
    merged batch×heads tiling (model-axis-free, so no remat cliff)
    instead of replicating — and stays exact."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops.flash_attention import attention_reference
    from elephas_tpu.parallel.sequence import (
        dp_sp_mesh, ring_mha, sequence_parallel_scope,
    )

    rng = np.random.default_rng(0)
    b, h, s, d = 2, 2, 64, 16
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, h, s, d)).astype(np.float32)
    )
    q, k, v = mk(), mk(), mk()
    mesh = dp_sp_mesh(2, data_parallel=4)  # data=4: b%4!=0, h%4!=0
    with sequence_parallel_scope(mesh):
        out = ring_mha(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
