"""Kernel correctness: flash attention vs the naive oracle, gradients,
and ring attention on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from elephas_tpu.ops import flash_attention, ring_attention
from elephas_tpu.ops.flash_attention import attention_reference
from elephas_tpu.ops.ring_attention import ring_attention_sharded


def _qkv(bh=4, s=256, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(bh, s, d)).astype(np.float32), dtype=dtype
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("dv", [64, 48])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal, dv):
    """``dv`` is the values' width: the scores' own (64), or narrower
    (latent attention scores 192 wide and sums 128 wide)."""
    q, k, v = _qkv()
    v = v[..., :dv]
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = attention_reference(q, k, v, causal=causal)
    assert out.shape == q.shape[:-1] + (dv,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_4d_and_scale():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 3, 128, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 3, 128, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 3, 128, 32)).astype(np.float32))
    out = flash_attention(q, k, v, scale=0.25)
    ref = attention_reference(q, k, v, scale=0.25)
    assert out.shape == (2, 3, 128, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match(causal):
    q, k, v = _qkv(bh=2, s=128, d=32, seed=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4, err_msg=name
        )


def _backward_cases():
    """The backward kernels' shapes: key/value heads shared by 1, 2 and
    8 query heads, causal or not, always ``block_q != block_k``; then
    unequal sequence lengths, bfloat16 inputs, and rows the mask
    leaves nothing of."""
    cases = {}
    for group in (1, 2, 8):
        cases[f"g{group}-causal"] = dict(group=group, causal=True)
        cases[f"g{group}-full"] = dict(group=group, causal=False)
    cases["g2-full-sq32-sk48"] = dict(
        group=2, causal=False, s_k=48, block_q=16, block_k=8)
    # key blocks that no query sees: their gradient is zero
    cases["g2-causal-sq16-sk48"] = dict(group=2, causal=True, s_q=16, s_k=48)
    cases["g2-causal-bf16"] = dict(group=2, causal=True, dtype=jnp.bfloat16)
    cases["g8-full-bf16"] = dict(group=8, causal=False, dtype=jnp.bfloat16)
    cases["g2-causal-masked-rows"] = dict(
        group=2, causal=True, masked_rows=True)
    # values narrower than the scores, in latent attention's ratio
    # (192 to 128): v, o, dO, dV at ``dv``; q, k, dQ, dK at ``d``
    for name, more in (("g1-causal", {}), ("g1-full", {}),
                       ("g2-causal-bf16", dict(dtype=jnp.bfloat16))):
        cases[name + "-d24-v16"] = dict(
            group=int(name[1]), causal="causal" in name, d=24, dv=16, **more)
    return cases


@pytest.mark.parametrize("case", list(_backward_cases()))
def test_flash_backward_kernels_match_the_blockwise_rule_and_autodiff(case):
    """The two backward kernels against ``_flash_backward`` on the
    repeated heads (the blockwise rule they replaced, still the packed
    layout's) and against ``jax.grad`` of the naive oracle."""
    from elephas_tpu.ops.flash_attention import (
        NEG_INF,
        _flash_attention_bhsd,
        _flash_backward,
        _flash_backward_kernels,
        _flash_forward,
    )

    c = {"s_q": 32, "s_k": 32, "block_q": 8, "block_k": 16,
         "dtype": jnp.float32, "masked_rows": False, "d": 16, "dv": 16,
         **_backward_cases()[case]}
    group, causal, d, kv_heads = c["group"], c["causal"], c["d"], 2
    scale = d ** -0.5
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (kv_heads * group, c["s_q"], d), c["dtype"])
    k = jax.random.normal(ks[1], (kv_heads, c["s_k"], d), c["dtype"])
    v = jax.random.normal(ks[2], (kv_heads, c["s_k"], c["dv"]), c["dtype"])
    g = jax.random.normal(ks[3], q.shape[:-1] + (c["dv"],), c["dtype"])
    blocks = (c["block_q"], c["block_k"])

    out, lse = _flash_forward(q, k, v, scale, causal, *blocks, True)
    if c["masked_rows"]:
        # a query block whose keys are all masked, as a ring-attention
        # chunk ahead of its queries leaves it: lse == NEG_INF, out == 0
        lse = lse.at[:, :c["block_q"]].set(NEG_INF)
        out = out.at[:, :c["block_q"]].set(0)
        got = _flash_backward_kernels(
            scale, causal, *blocks, True, (q, k, v, out, lse), g)
        assert not np.any(np.asarray(got[0][:, :c["block_q"]]))
    else:
        # through the public op's rule
        _, vjp = jax.vjp(lambda q, k, v: _flash_attention_bhsd(
            q, k, v, scale, causal, *blocks, True), q, k, v)
        got = vjp(g)

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    repeated = lambda t: jnp.repeat(f32(t), group, axis=0)  # noqa: E731
    shared = lambda t: t.reshape(  # noqa: E731
        (kv_heads, group) + t.shape[1:]).sum(axis=1)
    dq, dk, dv = _flash_backward(
        scale, causal, *blocks,
        (f32(q), repeated(k), repeated(v), f32(out), lse), f32(g))
    wants = [("blockwise", (dq, shared(dk), shared(dv)))]
    if not c["masked_rows"]:
        _, vjp = jax.vjp(lambda q, k, v: attention_reference(
            q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0),
            causal=causal, scale=scale), f32(q), f32(k), f32(v))
        wants.append(("autodiff", vjp(f32(g))))
    # float32 inputs round nowhere. bfloat16 keeps 8 significant bits:
    # the gradient's own rounding is up to 2^-9 of an element, and p and
    # ds, rounded the same way as operands, add as much each to a sum of
    # up to 32 terms: 2^-6 of the largest element holds all three
    for name, want in wants:
        for a, w, leaf in zip(got, want, "qkv"):
            assert a.dtype == c["dtype"] and a.shape == w.shape
            if c["dtype"] == jnp.float32:
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(w), atol=1e-5, rtol=1e-5,
                    err_msg=f"{name} d{leaf}")
            else:
                worst = float(jnp.max(jnp.abs(f32(a) - w)))
                assert worst <= 2.0 ** -6 * float(jnp.max(jnp.abs(w))), (
                    name, leaf, worst)


def test_flash_rejects_ragged_blocks():
    q, k, v = _qkv(bh=1, s=100, d=16)
    with pytest.raises(ValueError, match="multiples"):
        flash_attention(q, k, v, block_q=64, block_k=64)


def _forward_cases():
    """The forward kernel's grid: ``block_q != block_k`` both ways,
    causal or not, float32 and bfloat16, under two query heads a
    key/value head; then unequal sequence lengths (a clamped key map
    must stay inside the keys there are), eight heads a key/value head,
    values narrower than the scores, and no block named."""
    cases = {}
    for dtype in ("f32", "bf16"):
        for mask in ("causal", "full"):
            for bq, bk in ((8, 16), (16, 8)):
                cases[f"{mask}-{dtype}-q{bq}-k{bk}"] = dict(
                    causal=mask == "causal", block_q=bq, block_k=bk,
                    dtype=jnp.float32 if dtype == "f32" else jnp.bfloat16)
    cases["causal-sq16-sk48"] = dict(causal=True, s_q=16, s_k=48)
    cases["causal-sq48-sk16"] = dict(causal=True, s_q=48, s_k=16)
    cases["causal-sq48-sk16-q16-k8"] = dict(
        causal=True, s_q=48, s_k=16, block_q=16, block_k=8)
    cases["full-sq32-sk48"] = dict(causal=False, s_k=48)
    cases["causal-g8"] = dict(causal=True, group=8)
    cases["causal-bf16-d24-v16"] = dict(
        causal=True, d=24, dv=16, dtype=jnp.bfloat16)
    cases["full-d24-v16"] = dict(causal=False, d=24, dv=16)
    cases["causal-rule"] = dict(causal=True, block_q=None, block_k=None)
    cases["causal-bf16-rule"] = dict(
        causal=True, block_q=None, block_k=None, dtype=jnp.bfloat16)
    return cases


@pytest.mark.parametrize("case", list(_forward_cases()))
def test_flash_forward_grid_matches_reference(case):
    """Whatever the grid, the forward kernel gives the float32 answer:
    float32 inputs to the tolerance the op has always had, bfloat16
    inputs to bfloat16's rounding of it (the output's own 2^-9, and
    ``p``'s as it meets ``v``: at most 2^-9 of the largest value a row
    could sum)."""
    c = {"s_q": 32, "s_k": 32, "block_q": 8, "block_k": 16, "group": 2,
         "dtype": jnp.float32, "d": 16, "dv": 16, **_forward_cases()[case]}
    kv_heads, group = 2, c["group"]
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (1, kv_heads * group, c["s_q"], c["d"]),
                          c["dtype"])
    k = jax.random.normal(ks[1], (1, kv_heads, c["s_k"], c["d"]), c["dtype"])
    v = jax.random.normal(ks[2], (1, kv_heads, c["s_k"], c["dv"]), c["dtype"])
    out = flash_attention(q, k, v, causal=c["causal"],
                          block_q=c["block_q"], block_k=c["block_k"])
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    want = attention_reference(
        f32(q), jnp.repeat(f32(k), group, axis=1),
        jnp.repeat(f32(v), group, axis=1), causal=c["causal"])
    assert out.dtype == c["dtype"] and out.shape == want.shape
    if c["dtype"] == jnp.float32:
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    else:
        worst = float(jnp.max(jnp.abs(f32(out) - want)))
        assert worst <= 2.0 ** -8 * float(jnp.max(jnp.abs(f32(v)))), worst


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_forward_row_with_nothing_visible_is_zero(dtype):
    """A row whose every score is masked away leaves no weight: zeros,
    and ``lse == NEG_INF``, which ring attention's merge reads as "this
    chunk adds nothing". The mask alone never empties a row of this
    kernel (a query always sees key 0), so the scores are driven under
    ``NEG_INF`` through the keys: the first query block is all ones
    against keys of -2e30 / d; the other rows are zeros and see a plain
    mean of the values."""
    from elephas_tpu.ops.flash_attention import NEG_INF, _flash_forward

    s, d, bq = 32, 16, 8
    q = jnp.zeros((2, s, d), dtype).at[:, :bq].set(1)
    k = jnp.full((2, s, d), -2e30 / d, dtype)
    v = jax.random.normal(jax.random.key(3), (2, s, d), dtype)
    out, lse = _flash_forward(q, k, v, 1.0, True, bq, 16, True)
    assert not np.any(np.asarray(out[:, :bq].astype(jnp.float32)))
    assert np.all(np.asarray(lse[:, :bq]) == NEG_INF)
    mean = jnp.cumsum(v.astype(jnp.float32), axis=1) / jnp.arange(
        1, s + 1)[None, :, None]
    np.testing.assert_allclose(
        np.asarray(out[:, bq:].astype(jnp.float32)), np.asarray(mean[:, bq:]),
        atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)
    np.testing.assert_allclose(
        np.asarray(lse[:, bq:]),
        np.tile(np.log(np.arange(bq + 1, s + 1)), (2, 1)), rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_and_backward_grids_may_differ(causal, monkeypatch):
    """Where no block is named the forward kernel and the backward pair
    take their own from the table: ``out``, ``lse`` and the residuals
    depend on neither, so a forward grid of (16, 16) under backward
    kernels at (8, 16) gives what blocks of 16 named for all three
    give."""
    import importlib

    fa = importlib.import_module("elephas_tpu.ops.flash_attention")
    monkeypatch.setitem(fa._BLOCK_TABLE, "fwd", ((16, 16),))
    monkeypatch.setitem(fa._BLOCK_TABLE, "bwd", ((8, 16),))
    ks = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(ks[0], (4, 32, 24))
    k = jax.random.normal(ks[1], (2, 32, 24))
    v = jax.random.normal(ks[2], (2, 32, 16))
    g = jax.random.normal(ks[3], (4, 32, 16))

    def grads(block):
        out, vjp = jax.vjp(lambda q, k, v: fa._flash_attention_bhsd(
            q, k, v, 24 ** -0.5, causal, block, block, True), q, k, v)
        return (out,) + vjp(g)

    for got, want, leaf in zip(grads(None), grads(16), ("out", "q", "k", "v")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5,
            err_msg=leaf)


def _rule_cases():
    """``(s_q, s_k, d, dv, itemsize, named) -> blocks`` of the forward
    kernel and of the backward pair."""
    return {
        # the two LM cells' attention at 8192 positions, bfloat16
        "latent-192-128": ((8192, 8192, 192, 128, 2, (None, None)),
                           ((1024, 1024), (1024, 1024))),
        "gated-256": ((8192, 8192, 256, 256, 2, (None, None)),
                      ((1024, 1024), (1024, 1024))),
        # float32 operands are twice as wide: the working set of blocks
        # of 1024 passes half the VMEM the call asks for
        "gated-256-f32": ((8192, 8192, 256, 256, 4, (None, None)),
                          ((512, 1024), (512, 512))),
        # shorter than any block: the sequence is the block
        "short-100": ((100, 100, 64, 64, 4, (None, None)),
                      ((100, 100), (100, 100))),
        "short-q16-k48": ((16, 48, 16, 16, 4, (None, None)),
                          ((16, 48), (16, 48))),
        # 1536 = 3 x 512: the largest block that divides it
        "1536": ((1536, 1536, 128, 128, 2, (None, None)),
                 ((512, 512), (512, 512))),
        # queries of 1536 over keys of 2048
        "q1536-k2048": ((1536, 2048, 128, 128, 2, (None, None)),
                        ((512, 1024), (512, 512))),
        # nothing in the table divides 1100: one block, which fits
        "1100": ((1100, 1100, 128, 128, 2, (None, None)),
                 ((1100, 1100), (1100, 1100))),
        # a named block rules all three kernels, beside a chosen one
        "named-both": ((8192, 8192, 192, 128, 2, (64, 128)),
                       ((64, 128), (64, 128))),
        "named-q": ((8192, 8192, 192, 128, 2, (256, None)),
                    ((256, 1024), (256, 1024))),
        "named-k": ((8192, 8192, 192, 128, 2, (None, 128)),
                    ((1024, 128), (1024, 128))),
    }


@pytest.mark.parametrize("case", list(_rule_cases()))
def test_flash_block_rule(case):
    from elephas_tpu.ops.flash_attention import _resolve_blocks

    (s_q, s_k, d, dv, itemsize, named), want = _rule_cases()[case]
    got = tuple(
        _resolve_blocks(*named, s_q, s_k, d, dv, itemsize, kernel)
        for kernel in ("fwd", "bwd"))
    assert got == want


def test_flash_block_rule_refuses_what_nothing_divides_and_fits():
    """8200 positions: no block of the table divides them, and as one
    block the scores alone would take half a gigabyte of VMEM."""
    from elephas_tpu.ops.flash_attention import _resolve_blocks

    with pytest.raises(ValueError, match="multiples"):
        _resolve_blocks(None, None, 8200, 8200, 128, 128, 2, "fwd")


def _window_cases():
    """A sliding window over 48 positions: windows that are no multiple
    of a block, of one key, of a whole block, and past the sequence;
    ``block_q != block_k`` both ways, equal blocks and the rule's; one
    and seven query heads a key/value head; float32 and bfloat16."""
    cases = {}
    for window in (1, 5, 13, 16, 29, 48, 100):
        for bq, bk in ((8, 16), (16, 8)):
            cases[f"w{window}-g7-q{bq}-k{bk}"] = dict(
                window=window, block_q=bq, block_k=bk)
    cases["w13-g1-q16-k16"] = dict(window=13, group=1, block_q=16, block_k=16)
    cases["w13-g7-rule"] = dict(window=13, block_q=None, block_k=None)
    cases["w13-g7-bf16-q8-k16"] = dict(
        window=13, block_q=8, block_k=16, dtype=jnp.bfloat16)
    cases["w5-g2-d24-v16-q16-k8"] = dict(
        window=5, group=2, d=24, dv=16, block_q=16, block_k=8)
    # keys that no query reaches, and queries past every key's window
    cases["w13-g2-sq16-sk48"] = dict(window=13, group=2, s_q=16)
    cases["w13-g2-sq48-sk16"] = dict(window=13, group=2, s_k=16)
    return cases


@pytest.mark.parametrize("case", list(_window_cases()))
def test_flash_window_forward_and_backward_match_reference(case):
    """The forward kernel, dK/dV and dQ under a ``window`` against
    ``jax.vjp`` of the naive oracle with the same band, and the
    blockwise rule (the packed layout's) against the same."""
    from elephas_tpu.ops.flash_attention import (
        _flash_backward,
        _flash_forward,
    )

    c = {"s_q": 48, "s_k": 48, "group": 7, "dtype": jnp.float32, "d": 16,
         "dv": 16, "block_q": 8, "block_k": 16, **_window_cases()[case]}
    group, kv_heads, window = c["group"], 2, c["window"]
    ks = jax.random.split(jax.random.key(13), 4)
    shape = lambda heads, s, w: (1, heads, s, w)  # noqa: E731
    q = jax.random.normal(
        ks[0], shape(kv_heads * group, c["s_q"], c["d"]), c["dtype"])
    k = jax.random.normal(ks[1], shape(kv_heads, c["s_k"], c["d"]), c["dtype"])
    v = jax.random.normal(
        ks[2], shape(kv_heads, c["s_k"], c["dv"]), c["dtype"])
    g = jax.random.normal(ks[3], q.shape[:-1] + (c["dv"],), c["dtype"])
    # a query past every key's window sees nothing: the kernels give it
    # zeros (the oracle's softmax a mean of the values), so it is left
    # out of the comparison and carries no gradient
    rows, cols = np.arange(c["s_q"])[:, None], np.arange(c["s_k"])[None, :]
    live = jnp.asarray(
        ((cols <= rows) & (rows - cols < window)).any(axis=1))[:, None]
    g = jnp.where(live, g, 0)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    repeated = lambda t: jnp.repeat(f32(t), group, axis=1)  # noqa: E731

    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=c["block_q"],
        block_k=c["block_k"]), q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: jnp.where(
        live, attention_reference(
            q, repeated(k), repeated(v), causal=True, window=window), 0),
        f32(q), f32(k), f32(v))
    got = (out,) + vjp(g)
    wants = (want,) + want_vjp(f32(g))
    if window >= c["s_q"] and c["s_q"] == c["s_k"]:
        # a window that reaches every key is causal attention, bit for bit
        plain, plain_vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=c["block_q"],
            block_k=c["block_k"]), q, k, v)
        for a, b in zip(got, (plain,) + plain_vjp(g)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, w, leaf in zip(got, wants, ("out", "dq", "dk", "dv")):
        assert a.dtype == c["dtype"] and a.shape == w.shape, leaf
        if c["dtype"] == jnp.float32:
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(w), atol=2e-5, rtol=2e-5,
                err_msg=leaf)
        else:
            worst = float(jnp.max(jnp.abs(f32(a) - w)))
            assert worst <= 2.0 ** -6 * float(jnp.max(jnp.abs(w))), (
                leaf, worst)
    if c["dtype"] == jnp.float32 and c["block_q"]:
        blocks = (c["block_q"], c["block_k"])
        merged = lambda t: t.reshape((-1,) + t.shape[2:])  # noqa: E731
        args = (merged(q), merged(repeated(k)), merged(repeated(v)))
        o, lse = _flash_forward(
            *args, c["d"] ** -0.5, True, *blocks, True, window)
        dq, dk, dv = _flash_backward(
            c["d"] ** -0.5, True, *blocks, (*args, o, lse), merged(g),
            window=window)
        shared = lambda t: t.reshape(  # noqa: E731
            (1, kv_heads, group) + t.shape[1:]).sum(axis=2)
        for a, w, leaf in zip((dq[None], shared(dk), shared(dv)), wants[1:],
                              "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(w), atol=2e-5, rtol=2e-5,
                err_msg="blockwise d" + leaf)


def _band_walk(kernel, s_q, s_k, bq, bk, window):
    """The grid of ``kernel`` under a window, walked in Python as the
    ``pallas_call`` walks it: ``(outer, step, inner block the kernel
    computes on or None, block the index map fetches)`` a step."""
    from elephas_tpu.ops.flash_attention import (
        _band_ends, _band_maps, _grid_blocks, _pair_seen)

    n_outer, n_inner = _grid_blocks(kernel, s_q, s_k, bq, bk)
    span, fetched = _band_maps(kernel, s_q, s_k, bq, bk, window)
    for outer in range(n_outer):
        first, _ = _band_ends(kernel, outer, bq, bk, window, n_inner)
        for step in range(span):
            # what the kernel bodies do with their block index
            inner = first + step
            i, j = (inner, outer) if kernel == "dkv" else (outer, inner)
            computes = inner < n_inner and bool(
                _pair_seen(i, j, bq, bk, window))
            yield outer, step, inner if computes else None, fetched(
                outer, step)


@pytest.mark.parametrize("blocks", [(8, 16), (16, 8), (16, 16), (8, 8)])
@pytest.mark.parametrize("window", [None, 1, 5, 13, 16, 100])
def test_flash_window_maps_skip_what_the_band_empties(blocks, window):
    """With no window the index maps are the causal grid's as they
    were. With one the grid holds a band's blocks alone: every pair the
    band leaves something of is computed exactly once and on the block
    the map fetched, no empty pair computes, a step that computes
    nothing stays on the block before it (so that nothing is fetched
    for it), and ``band_grid`` counts the walk's steps."""
    from elephas_tpu.ops.flash_attention import (
        _pair_seen, _visible_maps, band_grid)

    bq, bk = blocks
    nq, nk = 48 // bq, 48 // bk
    rows, cols = np.arange(48)[:, None], np.arange(48)[None, :]
    mask = cols <= rows
    if window is not None:
        mask &= rows - cols < window
    seen = {(i, j) for i in range(nq) for j in range(nk)
            if mask[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()}
    assert seen == {(i, j) for i in range(nq) for j in range(nk)
                    if _pair_seen(i, j, bq, bk, window)}
    if window is None:  # the maps of the causal grid, as they were
        first_i, last_j = _visible_maps(True, bq, bk, nq)
        for i in range(nq):
            for j in range(nk):
                assert int(first_i(i, j)) == min(max(i, j * bk // bq), nq - 1)
                assert int(last_j(i, j)) == min(j, ((i + 1) * bq - 1) // bk)
        for kernel in ("fwd", "dkv", "dq"):
            assert band_grid(kernel, 48, 48, bq, bk, None) == (
                nq * nk, len(seen))
        return
    for kernel in ("fwd", "dkv", "dq"):
        computed, n_inner = [], nq if kernel == "dkv" else nk
        walk = list(_band_walk(kernel, 48, 48, bq, bk, window))
        for outer, step, inner, fetched in walk:
            assert 0 <= fetched < n_inner
            if inner is not None:
                assert fetched == inner
                computed.append(
                    (inner, outer) if kernel == "dkv" else (outer, inner))
            elif step:
                assert fetched == before
            before = fetched
        assert sorted(computed) == sorted(seen), kernel
        assert band_grid(kernel, 48, 48, bq, bk, window) == (
            len(walk), len(seen))
        if window <= 16:
            assert len(walk) < nq * nk


def _band_cases():
    """Sliding windows at a few thousand positions: under a block, a
    block's length, between two, SmallThinker's 4096 under a longer
    sequence, and past the sequence; equal blocks, each side longer,
    and the Laguna forward kernel's pair; one and nine query heads a
    key/value head."""
    cases = {}
    for window, s in ((100, 2048), (512, 2048), (640, 2048), (4096, 5120),
                      (3000, 2048)):
        for bq, bk in ((128, 128), (128, 256), (256, 128), (512, 1024)):
            for group in (1, 9):
                cases[f"w{window}-s{s}-q{bq}-k{bk}-g{group}"] = (
                    window, s, bq, bk, group)
    return cases


@pytest.mark.parametrize("case", list(_band_cases()))
def test_flash_band_grid_matches_reference(case):
    """The three kernels on the band's grid (interpret mode) against
    the plain attention under the same band: out, dQ, dK and dV."""
    window, s, bq, bk, group = _band_cases()[case]
    ks = jax.random.split(jax.random.key(window + group), 4)
    q = jax.random.normal(ks[0], (group, s, 16))
    k, v = (jax.random.normal(key, (1, s, 16)) for key in ks[1:3])
    g = jax.random.normal(ks[3], q.shape)
    got, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk),
        q, k, v)
    # a head at a time: nine heads' [S, S] scores at once are a gigabyte
    plain = lambda q, k, v: jax.lax.map(  # noqa: E731
        lambda head: attention_reference(
            head, k[0], v[0], causal=True, window=window), q)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(plain, q, k, v)
        wants = (want,) + want_vjp(g)
    for a, w, leaf in zip((got,) + vjp(g), wants, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), atol=2e-5, rtol=2e-5, err_msg=leaf)


@pytest.mark.parametrize("cell,shape,blocks,want", [
    # a head and sequence of the cell, at the blocks its rule resolves
    ("laguna-fwd", (8192, 512), (512, 1024), {"fwd": (32, 23)}),
    ("laguna-bwd", (8192, 512), (512, 512),
     {"dkv": (32, 31), "dq": (32, 31)}),
    ("smallthinker", (16384, 4096), (1024, 1024),
     {"fwd": (80, 70), "dkv": (80, 70), "dq": (80, 70)}),
    # the grid over every pair of blocks, which no window keeps
    ("laguna-no-window", (8192, None), (512, 512),
     {"fwd": (256, 136), "dkv": (256, 136), "dq": (256, 136)}),
    ("whole-sequence-window", (8192, 8192), (512, 512),
     {"fwd": (256, 136), "dkv": (256, 136), "dq": (256, 136)}),
])
def test_flash_band_grid_extents_of_the_cells(cell, shape, blocks, want):
    """``band_grid`` at the banded cells' shapes: 32 steps a kernel
    where the grid over every pair takes 128 and 256 (Laguna), 80 where
    it takes 256 (SmallThinker); every pair that holds something is
    computed exactly once on the walk; no window, or one of the whole
    sequence, keeps the grid it had."""
    from elephas_tpu.ops.flash_attention import _pair_seen, band_grid

    (s, window), (bq, bk) = shape, blocks
    for kernel, extents in want.items():
        assert band_grid(kernel, s, s, bq, bk, window) == extents
        if window is not None:
            computed = [
                (inner, outer) if kernel == "dkv" else (outer, inner)
                for outer, _, inner, _ in _band_walk(
                    kernel, s, s, bq, bk, window) if inner is not None]
            assert sorted(computed) == sorted(
                (i, j) for i in range(s // bq) for j in range(s // bk)
                if _pair_seen(i, j, bq, bk, window))


def test_flash_window_emits_its_grids_as_it_is_traced():
    """Tracing the op under a window emits one ``flash.grid`` event a
    windowed ``pallas_call``, with the blocks the rule resolved and
    ``band_grid``'s counts (Laguna's: 32 steps a kernel, 23, 31 and 31
    of them computing); without a window it emits none."""
    from elephas_tpu import telemetry

    q = jnp.zeros((1, 9, 8192, 128), jnp.bfloat16)
    k = jnp.zeros((1, 1, 8192, 128), jnp.bfloat16)
    tracer = telemetry.default_tracer()
    for window, want in (
            (None, {}),
            (512, {"fwd": ((512, 1024), 32, 23), "dkv": ((512, 512), 32, 31),
                   "dq": ((512, 512), 32, 31)})):
        since = tracer.seq
        jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window, interpret=False)
            .astype(jnp.float32)), (0, 1, 2)))(q, k, k)
        got = [e["args"] for e in tracer.events(since, name="flash.grid")]
        assert {a["kernel"]: ((a["block_q"], a["block_k"]), a["steps"],
                              a["computing"]) for a in got} == want
        assert all(a["window"] == window for a in got)
        assert len(got) == len(want)


def test_flash_window_is_a_positive_count_of_keys_under_the_causal_mask():
    q, k, v = _qkv(bh=2, s=32, d=16)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)
    # no window named: the jaxpr of the op is what it was without the
    # argument
    with_none = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=None))(q, k, v)
    without = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True))(q, k, v)
    assert str(with_none) == str(without)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "checkpoint"])
@pytest.mark.parametrize("case", ["causal", "window", "k192-v128"])
def test_flash_residual_names_are_nothing_without_a_policy(
        case, remat, monkeypatch):
    """The forward kernel's result and log-sum-exp carry names
    (``OUT_NAME``, ``LSE_NAME``) for a caller whose ``jax.checkpoint``
    policy keeps them. To every other caller they are nothing:
    ``jax.grad`` of the op, bare or under a ``jax.checkpoint`` with no
    policy, lowers to the program it lowers to with the names taken out
    of the rule, and gives the same arrays; full and banded causal
    attention over grouped heads, and latent attention's widths (keys
    of 192, values of 128)."""
    import importlib
    import re

    fa = importlib.import_module("elephas_tpu.ops.flash_attention")
    # the lowering numbers its private functions from a counter of the
    # process: ``@floor_divide_56`` is ``@floor_divide_57`` a trace later
    unnumbered = lambda text: re.sub(r"(@\w+?)_\d+\b", r"\1", text)  # noqa: E731
    d, dv, window = {"causal": (16, 16, None), "window": (16, 16, 13),
                     "k192-v128": (192, 128, None)}[case]
    ks = jax.random.split(jax.random.key(39), 3)
    q = jax.random.normal(ks[0], (1, 4, 48, d))
    k = jax.random.normal(ks[1], (1, 2, 48, d))
    v = jax.random.normal(ks[2], (1, 2, 48, dv))

    def gradient_program_and_names():
        def loss(q, k, v):  # a function of its own for each trace
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=True, window=window)))

        grad = jax.grad(jax.checkpoint(loss) if remat else loss, (0, 1, 2))
        jaxpr = str(jax.make_jaxpr(grad)(q, k, v))
        grad = jax.jit(grad)
        return (grad(q, k, v), unnumbered(grad.lower(q, k, v).as_text()),
                [f"name={n}" in jaxpr for n in (fa.OUT_NAME, fa.LSE_NAME)])

    named, program, names = gradient_program_and_names()
    assert names == [True, True]
    monkeypatch.setattr(fa, "checkpoint_name", lambda value, name: value)
    bare, bare_program, names = gradient_program_and_names()
    assert names == [False, False]
    assert program == bare_program
    for a, b in zip(named, bare):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    from jax.sharding import Mesh

    q, k, v = _qkv(bh=2, s=8 * 64, d=32, seed=3)
    mesh = Mesh(np.array(jax.devices()[:8]), ("workers",))
    out = ring_attention_sharded(
        q, k, v, mesh, axis_name="workers", causal=causal
    )
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_inside_user_shard_map():
    """ring_attention composes inside a user's own shard_map."""
    from jax.sharding import Mesh, PartitionSpec as P

    q, k, v = _qkv(bh=2, s=8 * 64, d=32, seed=4)
    mesh = Mesh(np.array(jax.devices()[:8]), ("workers",))
    spec = P(None, "workers", None)

    def fn(q, k, v):
        return ring_attention(q, k, v, axis_name="workers", causal=True)

    out = jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False
        )
    )(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients_match(causal):
    """The ring-pass VJP equals the dense oracle's gradients."""
    from jax.sharding import Mesh, PartitionSpec as P

    q, k, v = _qkv(bh=2, s=4 * 32, d=16, seed=5)
    mesh = Mesh(np.array(jax.devices()[:4]), ("workers",))
    spec = P(None, "workers", None)

    def loss_ring(q, k, v):
        fn = lambda q, k, v: ring_attention(  # noqa: E731
            q, k, v, axis_name="workers", causal=causal
        )
        out = jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False
        )(q, k, v)
        return jnp.sum(out**2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4, err_msg=name
        )


def test_ring_attention_long_context_training():
    """r3: sequence parallelism is TRAINABLE end-to-end — a classifier
    whose attention runs ring-sharded over 8 sequence shards has
    gradients matching the dense-attention oracle, and adam training
    through the ring drives the loss down. The task needs cross-shard
    attention (label = which half of the sequence carries the marker),
    so a shard-local model cannot solve it."""
    import optax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]), ("seq",))
    S, D, V, B = 128, 16, 32, 32  # 16 tokens per shard
    rng = np.random.default_rng(0)
    y = rng.integers(0, 2, size=B).astype(np.int32)
    x = rng.integers(4, V, size=(B, S)).astype(np.int32)
    # marker token 1 in the first half for class 0, second half for 1
    pos = rng.integers(0, S // 2, size=B) + np.where(y == 1, S // 2, 0)
    x[np.arange(B), pos] = 1

    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    params = {
        "emb": jax.random.normal(ks[0], (V, D)) * 0.5,
        "wq": jax.random.normal(ks[1], (D, D)) * D**-0.5,
        "wk": jax.random.normal(ks[2], (D, D)) * D**-0.5,
        "wv": jax.random.normal(ks[3], (D, D)) * D**-0.5,
        "head": jax.random.normal(ks[4], (D, 2)) * 0.2,
    }

    def forward(params, xb, ring: bool):
        h = params["emb"][xb]  # [B, S, D]
        q, k, v = h @ params["wq"], h @ params["wk"], h @ params["wv"]
        if ring:
            att = ring_attention_sharded(q, k, v, mesh, axis_name="seq")
        else:
            att = attention_reference(q, k, v)
        pooled = (att + h).mean(axis=1)
        return pooled @ params["head"]

    def loss_fn(params, xb, yb, ring):
        logits = forward(params, xb, ring)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

    g_ring = jax.grad(lambda p: loss_fn(p, x, y, True))(params)
    g_dense = jax.grad(lambda p: loss_fn(p, x, y, False))(params)
    for a, b in zip(jax.tree.leaves(g_ring), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    opt = optax.adam(3e-2)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, x, y, True))(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    losses = []
    for _ in range(40):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.3, (losses[0], losses[-1])
    preds = np.asarray(forward(params, x, True)).argmax(-1)
    assert (preds == y).mean() > 0.9


# -- Ulysses (all-to-all) sequence parallelism ---------------------------


def _qkv4(b=2, h=4, s=128, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, h, s, d)).astype(np.float32)
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(causal):
    """Head<->sequence all-to-all around full attention equals the
    dense oracle (the second SP family next to the ring)."""
    from jax.sharding import Mesh

    from elephas_tpu.ops.ulysses import ulysses_attention_sharded

    q, k, v = _qkv4(b=2, h=4, s=4 * 32, d=16, seed=3)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    out = ulysses_attention_sharded(
        q, k, v, mesh, axis_name="seq", causal=causal
    )
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ulysses_gradients_match():
    """all_to_all is linear and flash carries its VJP — gradients equal
    the dense oracle's with no custom VJP."""
    from jax.sharding import Mesh, PartitionSpec as P

    from elephas_tpu.ops.ulysses import ulysses_attention

    q, k, v = _qkv4(b=2, h=4, s=4 * 32, d=16, seed=5)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    spec = P(None, None, "seq", None)

    def loss_ulysses(q, k, v):
        fn = lambda q, k, v: ulysses_attention(  # noqa: E731
            q, k, v, axis_name="seq", causal=True
        )
        out = jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False,
        )(q, k, v)
        return jnp.sum(out**2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_u = jax.grad(loss_ulysses, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_u, g_r, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4, err_msg=name
        )


def test_ulysses_head_count_guard():
    from jax.sharding import Mesh

    from elephas_tpu.ops.ulysses import ulysses_attention_sharded

    q, k, v = _qkv4(b=1, h=3, s=4 * 8, d=8)  # 3 heads % 4 devices != 0
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(q, k, v, mesh, axis_name="seq")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,D", [(3, 16), (2, 128)])
def test_flash_attention_qkv_packed_matches_reference(causal, H, D):
    """r4 layout-native kernel: attention computed straight from the
    packed [B, S, 3, H, D] qkv tensor must equal the unpacked reference
    (values AND gradients), with the output in sequence-major layout.
    (H=2, D=128) drives the per-head packed BlockSpec index maps;
    (H=3, D=16) drives the transposed fallback the gate now routes
    small head dims to (code-review r5)."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops.flash_attention import (
        attention_reference,
        flash_attention_qkv,
    )

    B, S = 2, 64
    key = jax.random.PRNGKey(0)
    qkv = jax.random.normal(key, (B, S, 3, H, D), jnp.float32)

    out = flash_attention_qkv(qkv, causal=causal, block_q=16, block_k=16)
    # reference consumes [B, H, S, D]
    q, k, v = [jnp.transpose(qkv[:, :, i], (0, 2, 1, 3)) for i in range(3)]
    ref = jnp.transpose(attention_reference(q, k, v, causal=causal),
                        (0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_packed(qkv):
        return jnp.sum(
            flash_attention_qkv(qkv, causal=causal, block_q=16, block_k=16)
            ** 2
        )

    def loss_ref(qkv):
        q, k, v = [
            jnp.transpose(qkv[:, :, i], (0, 2, 1, 3)) for i in range(3)
        ]
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_packed)(qkv)
    g2 = jax.grad(loss_ref)(qkv)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_qkv_grouped_head64(causal):
    """r5 (VERDICT r4 #3c): head_dim-64 models take the lane-GROUPED
    packed kernel — two heads per 128-lane block, per-head masked dots
    (no transpose copies; measured +27% end-to-end on chip vs the
    transposed fallback). Values and gradients must equal the unpacked
    reference; odd head counts and tiny head dims gate to the
    fallback."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops.flash_attention import (
        attention_reference,
        flash_attention_qkv,
        packed_layout_supported,
    )

    assert packed_layout_supported(128, 3)
    assert packed_layout_supported(64, 4)
    assert not packed_layout_supported(64, 3)  # odd heads → fallback
    assert not packed_layout_supported(32, 4)  # MAC waste → fallback

    B, S, H, D = 2, 128, 4, 64
    key = jax.random.PRNGKey(1)
    qkv = jax.random.normal(key, (B, S, 3, H, D), jnp.float32) * 0.3

    out = flash_attention_qkv(qkv, causal=causal)
    q, k, v = [jnp.transpose(qkv[:, :, i], (0, 2, 1, 3)) for i in range(3)]
    ref = jnp.transpose(attention_reference(q, k, v, causal=causal),
                        (0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_packed(z):
        return jnp.sum(jnp.sin(flash_attention_qkv(z, causal=causal)))

    def loss_ref(z):
        qq, kk, vv = [
            jnp.transpose(z[:, :, i], (0, 2, 1, 3)) for i in range(3)
        ]
        o = attention_reference(qq, kk, vv, causal=causal)
        return jnp.sum(jnp.sin(jnp.transpose(o, (0, 2, 1, 3))))

    g1 = jax.grad(loss_packed)(qkv)
    g2 = jax.grad(loss_ref)(qkv)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=2e-4, rtol=2e-4)


def test_layer_norm_matches_keras():
    """r5: the fused Pallas LayerNorm matches keras LN forward exactly
    and its custom VJP matches autodiff of the plain-jnp math — for
    every rank/row-block shape class."""
    import keras

    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops.layer_norm import layer_norm

    rng = np.random.default_rng(0)
    for shape in [(8, 16, 64), (128, 256), (5, 7, 128)]:
        x = (rng.normal(size=shape) * 3 + 1.5).astype(np.float32)
        g = rng.normal(size=shape[-1]).astype(np.float32)
        b = rng.normal(size=shape[-1]).astype(np.float32)
        ref_ln = keras.layers.LayerNormalization(epsilon=1e-6)
        ref_ln.build(shape)
        ref_ln.gamma.assign(g)
        ref_ln.beta.assign(b)
        ref = np.asarray(ref_ln(x))
        out = np.asarray(
            layer_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
        )
        np.testing.assert_allclose(out, ref, atol=1e-5)

        def f_ref(x_, g_, b_):
            m = jnp.mean(x_, -1, keepdims=True)
            xc = x_ - m
            v = jnp.mean(xc * xc, -1, keepdims=True)
            y = xc * jax.lax.rsqrt(v + 1e-6) * g_ + b_
            return jnp.sum(jnp.sin(y))

        def f_ker(x_, g_, b_):
            return jnp.sum(jnp.sin(layer_norm(x_, g_, b_)))

        gr = jax.grad(f_ref, argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(g), jnp.asarray(b)
        )
        gk = jax.grad(f_ker, argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(g), jnp.asarray(b)
        )
        for a, c in zip(gr, gk):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(c), atol=1e-4
            )


def test_fused_layer_norm_layer_trains():
    """The FusedLayerNorm keras layer: serializes, trains inside a
    model, and matches a keras-LN twin to float tolerance."""
    import keras

    from elephas_tpu.models import FusedLayerNorm

    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)

    def build(ln_cls):
        keras.utils.set_random_seed(3)
        m = keras.Sequential([
            keras.layers.Input((16,)),
            keras.layers.Dense(32, activation="relu"),
            ln_cls(epsilon=1e-6),
            keras.layers.Dense(2, activation="softmax"),
        ])
        m.compile(optimizer=keras.optimizers.Adam(1e-2),
                  loss="sparse_categorical_crossentropy")
        return m

    m1 = build(FusedLayerNorm)
    m2 = build(keras.layers.LayerNormalization)
    h1 = m1.fit(x, y, epochs=3, batch_size=32, shuffle=False, verbose=0)
    h2 = m2.fit(x, y, epochs=3, batch_size=32, shuffle=False, verbose=0)
    np.testing.assert_allclose(
        h1.history["loss"], h2.history["loss"], rtol=1e-4
    )
    cfg = m1.get_layer(index=1).get_config()
    assert cfg["epsilon"] == 1e-6


def test_fused_layer_norm_sp_scope_fallback():
    """Under a sequence-parallel scope FusedLayerNorm takes the plain
    jnp math (GSPMD shards it with the seq-sharded activations instead
    of forcing the Pallas call replicated) — same numbers either way."""
    import keras

    from jax.sharding import Mesh

    from elephas_tpu.models import FusedLayerNorm
    from elephas_tpu.parallel.sequence import sequence_parallel_scope
    from elephas_tpu.parallel.sequence import dp_sp_mesh

    rng = np.random.default_rng(2)
    x = rng.normal(size=(4, 16, 32)).astype(np.float32)
    keras.utils.set_random_seed(7)
    ln = FusedLayerNorm(epsilon=1e-6)
    ln.build(x.shape)
    ln.gamma.assign(rng.normal(size=32).astype(np.float32))
    ln.beta.assign(rng.normal(size=32).astype(np.float32))

    out_plain = np.asarray(ln(x))
    mesh = dp_sp_mesh(2, data_parallel=2)
    with sequence_parallel_scope(mesh):
        out_scoped = np.asarray(ln(x))
    np.testing.assert_allclose(out_scoped, out_plain, atol=1e-5)
