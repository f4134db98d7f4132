"""Plain reference of one chip's share of
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (``model_type: nemotron_h``) and of
the training steps ``SparkModel.fit`` takes with it: float32
``jax.numpy`` at ``highest``, the state-space scan as a materialised
masked quadratic form, a materialised causal softmax a query block, the
routed part as a plain sum over the held experts, next-token
cross-entropy over the vocabulary slice, SGD with momentum as keras
applies it. Independent of ``elephas_tpu`` and keras: it makes its own
weights from the seed.

The layers, from the published ``config.json``. Layer ``i`` is ONE
mixer, the one that character ``i`` of ``hybrid_override_pattern``
names, under one pre-norm and one residual add: ``h = h +
mixer_i(norm(h))``, ``norm`` being ``w * x * rsqrt(mean(x^2) + eps)``
with ``eps = layer_norm_epsilon``; after the last, a norm and an untied
head. With ``u`` the normed input:

- ``M``, Mamba-2 (``H = mamba_num_heads``, ``P = mamba_head_dim``, so
  the inner width is ``H P``, not ``expand * hidden_size``; ``N =
  ssm_state_size``; ``G = n_groups``): ``[z | xBC | dt] = u W_in`` with
  widths ``H P``, ``H P + 2 G N`` and ``H``; ``xBC = silu(conv(xBC) +
  b_conv)``, a causal depthwise convolution of ``conv_kernel`` taps;
  split into ``x [S, H, P]``, ``B [S, G, N]``, ``C [S, G, N]``, head
  ``h`` reading group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``
  a head, with no upper clamp; ``A = -exp(A_log)`` a head. The state
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` starts at zero and ``y_t
  = S_t C_t + D x_t``, which is computed here as what it sums to::

      y_i = sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j + D x_i

  with ``a`` the running sum of ``dt A``: a group and a block of
  ``SCAN_ROWS`` queries at a time against all the keys, the running
  sums taken forwards and backwards from the block's first query so
  that float32 holds their differences. Then ``y = w_norm *
  GroupRMSNorm(y * silu(z))``, the gate first and the norm over each
  group of ``H P / G`` channels, and ``y W_out``.
- ``E``, sparse: ``s = sigmoid(u W_r)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` (``b`` is
  ``e_score_correction_bias``, no parameter; ``n_group`` and
  ``topk_group`` are 1, so the groups choose nothing); the weights are
  ``s`` at the chosen, over their sum plus 1e-20, times
  ``routed_scaling_factor``; a routed expert is ungated, ``relu(u
  W_up,e)^2 W_down,e``; the shared expert is the same form at
  ``moe_shared_expert_intermediate_size``, added unweighted.
- ``*``, attention: grouped-query causal attention, no bias, scale
  ``head_dim^-0.5``, query head ``n`` reading key/value head ``n //
  (heads / kv_heads)``, and no position term (``assumed.rope``).

Departures from the published model, each also in the configuration
file: (1) the vocabulary is the slice ``[0, vocab_size)`` that this
chip holds: ids, logits and loss are over the slice; (2) of the routed
experts only ``num_experts_held`` (from ``experts_held_first``) are
here: the router still scores all of them, and what the absent ones
would add to a token is left out, so the block's result is this chip's
partial sum; (3) ``b`` is made from the seed and no step updates it;
(4) no auxiliary loss; (5) the initialisers and the optimizer are
assumed. Two other readings of the model, which the program does not
compute, can be asked for as faults (``scripts/prove_reference_faults
.py``): ``assumed.rope`` ``"rotary"`` rotates the attention layers'
queries and keys (the pairs ``(i, i + head_dim / 2)`` at
``rope_theta``), and ``assumed.mamba_norm`` ``"norm_then_gate"`` norms
``y`` before the gate multiplies it.

So that it fits one chip beside nothing else: attention a head and
the scan a group are taken a block of queries at a time, each block
and each expert's part rematerialised in the backward pass
(``jax.checkpoint``), a step's sequences go one after the other, and a
sequence's gradient is taken a layer at a time (``jax.vjp`` of each
layer from its kept input), each layer's straight into the velocity.
That changes what is stored, not what is computed.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# the one float variable that no step trains
FIXED = "/e_score_correction_bias"
# queries a block of materialised scores: a head's 2048 x 8192 float32
# is 67 MB; a group's 8 heads' scan factors at 1024 x 8192 are 268 MB
ATTN_ROWS = 2048
SCAN_ROWS = 1024
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def pattern(cfg: dict) -> str:
    """The layers that are here: the first ``num_hidden_layers``
    characters of the published pattern."""
    got = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    if len(got) < cfg["num_hidden_layers"] or set(got) - set(KINDS):
        raise ValueError(f"hybrid_override_pattern {got!r}")
    return got


def param_shapes(cfg: dict) -> dict:
    """``{variable path: (shape, kind)}``; ``kind`` names the
    initialiser (:func:`init_params`)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = h * p
    conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    held, inter = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"]
    shapes = {"embed_tokens/embeddings": ((v, d), "normal")}
    for i, kind in enumerate(pattern(cfg)):
        shapes[f"layer{i}_norm/weight"] = ((d,), "ones")
        pre = f"layer{i}_{KINDS[kind]}/"
        if kind == "M":
            shapes[pre + "in_proj"] = ((d, inner + conv_dim + h), "normal")
            shapes[pre + "conv"] = ((cfg["conv_kernel"], conv_dim), "conv")
            shapes[pre + "conv_bias"] = ((conv_dim,), "conv")
            shapes[pre + "dt_bias"] = ((h,), "dt_bias")
            shapes[pre + "A_log"] = ((h,), "a_log")
            shapes[pre + "D"] = ((h,), "ones")
            shapes[pre + "norm"] = ((inner,), "ones")
            shapes[pre + "out_proj"] = ((inner, d), "out_proj")
        elif kind == "E":
            shapes[pre + "router"] = ((d, cfg["n_routed_experts"]), "normal")
            shapes[pre + "e_score_correction_bias"] = (
                (cfg["n_routed_experts"],), "select_bias")
            shapes[pre + "experts_up"] = ((held, d, inter), "normal")
            shapes[pre + "experts_down"] = ((held, inter, d), "normal")
            shapes[pre + "shared_expert/up"] = ((d, shared), "normal")
            shapes[pre + "shared_expert/down"] = ((shared, d), "normal")
        else:
            shapes[pre + "q_proj"] = ((d, heads * hd), "normal")
            shapes[pre + "k_proj"] = ((d, kv * hd), "normal")
            shapes[pre + "v_proj"] = ((d, kv * hd), "normal")
            shapes[pre + "o_proj"] = ((heads * hd, d), "normal")
    shapes["final_norm/weight"] = ((d,), "ones")
    shapes["lm_head/kernel"] = ((d, v), "normal")
    return shapes


def trained(tree: dict) -> dict:
    """The leaves a step updates: all but the selection bias."""
    return {k: v for k, v in tree.items() if not k.endswith(FIXED)}


def init_params(cfg: dict, seed: int) -> dict:
    """Every variable from the seed, on the device, in one jitted call,
    in float32 (mixed_bfloat16 keeps its variables in float32). By
    kind (``assumed`` in the configuration file says why): ``normal``
    at ``initializer_range``; ``out_proj`` that over the square root of
    the PUBLISHED depth (``rescale_prenorm_residual``); ``conv`` uniform
    within ``conv_kernel^-0.5``; ``a_log`` ``log(1..H)``; ``dt_bias``
    the inverse softplus of a log-uniform step in ``[time_step_min,
    time_step_max]`` floored at ``time_step_floor``; ``select_bias``
    normal at ``assumed.select_bias_std``."""
    shapes = param_shapes(cfg)
    std = cfg["assumed"]["initializer_range"]
    deep = cfg["published"]["num_hidden_layers"] if cfg[
        "rescale_prenorm_residual"] else 1
    lo, hi = np.log(cfg["time_step_min"]), np.log(cfg["time_step_max"])
    bound = cfg["conv_kernel"] ** -0.5

    def draw(kind, key, shape):
        f32 = jnp.float32
        if kind == "ones":
            return jnp.ones(shape, f32)
        if kind == "a_log":
            return jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))
        if kind == "conv":
            return jax.random.uniform(key, shape, f32, -bound, bound)
        if kind == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, f32, lo, hi)), cfg["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        scale = {"normal": std, "out_proj": std / np.sqrt(deep),
                 "select_bias": cfg["assumed"]["select_bias_std"]}[kind]
        return scale * jax.random.normal(key, shape, f32)

    @jax.jit
    def make(key):
        return {name: draw(kind, jax.random.fold_in(key, i), shape)
                for i, (name, (shape, kind)) in enumerate(shapes.items())}

    return make(jax.random.key(np.uint32(int(seed) % (1 << 32))))


# -- the control: one precision down --------------------------------------


def _through(t, dtype, largest):
    scale = largest / jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
    scaled = jnp.clip(t * scale, -largest, largest)  # never past the end
    return scaled.astype(dtype).astype(t.dtype) / scale


@jax.custom_vjp
def _fp8(t):
    """A tensor held in fp8: through e4m3 under one scale for the
    tensor on the way forward, and its gradient through e5m2 under one
    scale on the way back."""
    return _through(t, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(t):
    return _fp8(t), None


def _fp8_bwd(_res, g):
    return (_through(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


# -- the layers ------------------------------------------------------------


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def ssd_quadratic(x, dt, a_neg, b_in, c_in, d_skip, cast=lambda t: t):
    """The selective scan as the sum it amounts to. ``x [B, S, H, P]``,
    ``dt [B, S, H]`` (positive), ``a_neg [H]`` (negative), ``b_in`` and
    ``c_in [B, S, G, N]``, ``d_skip [H]``; returns ``y [B, S, H, P]``.
    A group (its ``C B^T`` is shared by its heads) and a block of
    ``SCAN_ROWS`` queries at a time."""
    b, s, h, p = x.shape
    g = b_in.shape[2]
    per = h // g
    rows = min(s, SCAN_ROWS)
    if s % rows:
        raise ValueError(f"{s} positions are no whole blocks of {rows}")
    at = jnp.arange(s)
    log_decay = dt * a_neg  # [B, S, H], <= 0

    @jax.checkpoint
    def one_block(c_blk, b_g, x_g, dt_g, da_g, first_row):
        # c_blk [B, rows, N]; b_g [B, S, N]; x_g [B, S, R, P];
        # dt_g, da_g [B, S, R]. sum_{j < t <= i} da_t for query i of
        # the block and key j <= i, as (from the block's first query
        # up to i) less (from it up to j), or plus (from j up to it)
        # for a key before the block: no difference of two long sums
        inside = (at >= first_row)[None, :, None]
        within = jnp.cumsum(jnp.where(inside, da_g, 0.0), axis=1)
        before = jnp.where(inside, 0.0, da_g)
        back = jnp.flip(jnp.cumsum(jnp.flip(before, 1), axis=1), 1) - before
        key_part = jnp.where(inside, -within, back)        # [B, S, R]
        query_part = jax.lax.dynamic_slice_in_dim(within, first_row, rows, 1)
        queries = first_row + jnp.arange(rows)
        seen = (at[None, :] <= queries[:, None])[None, None]  # [1, 1, rows, S]
        gap = (jnp.moveaxis(query_part, 2, 1)[..., :, None]
               + jnp.moveaxis(key_part, 2, 1)[..., None, :])  # [B, R, rows, S]
        factor = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)), 0.0)
        cb = jnp.einsum("bin,bjn->bij", c_blk, b_g, precision=HI)
        scores = cast(cb[:, None] * factor
                      * jnp.moveaxis(dt_g, 2, 1)[..., None, :])
        return jnp.einsum("brij,bjrp->birp", scores, x_g, precision=HI)

    def one_group(args):
        c_g, b_g, x_g, dt_g, da_g = args
        blocks = jnp.moveaxis(
            c_g.reshape(b, s // rows, rows, c_g.shape[-1]), 1, 0)
        out = jax.lax.map(
            lambda a: one_block(a[0], b_g, x_g, dt_g, da_g, a[1]),
            (blocks, jnp.arange(s // rows) * rows))
        return jnp.moveaxis(out, 0, 1).reshape(b, s, per, p)

    by_group = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape(t.shape[:2] + (g, per) + t.shape[3:]), 2, 0)
    y = jax.lax.map(one_group, (
        jnp.moveaxis(c_in, 2, 0), jnp.moveaxis(b_in, 2, 0), by_group(x),
        by_group(dt), by_group(log_decay)))
    y = jnp.moveaxis(y, 0, 2).reshape(b, s, h, p)
    return y + d_skip[:, None] * x


def _mamba(p, pre, u, cfg, cast, mm):
    b, s, _ = u.shape
    h, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, taps = h * hp, cfg["conv_kernel"]
    z, mixed, dt = jnp.split(
        mm(u, p[pre + "in_proj"]), (inner, 2 * inner + 2 * g * n), axis=-1)
    padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = sum(padded[:, j:j + s] * p[pre + "conv"][j]
                for j in range(taps)) + p[pre + "conv_bias"]
    mixed = cast(jax.nn.silu(mixed))
    x, b_in, c_in = jnp.split(mixed, (inner, inner + g * n), axis=-1)
    y = cast(ssd_quadratic(
        x.reshape(b, s, h, hp), jax.nn.softplus(dt + p[pre + "dt_bias"]),
        -jnp.exp(p[pre + "A_log"]), b_in.reshape(b, s, g, n),
        c_in.reshape(b, s, g, n), p[pre + "D"], cast)).reshape(b, s, inner)
    grouped = lambda t: _rms(  # noqa: E731
        t.reshape(b, s, g, inner // g), cfg["layer_norm_epsilon"]
    ).reshape(b, s, inner)
    order = cfg["assumed"].get("mamba_norm", "gate_then_norm")
    if order == "gate_then_norm":
        y = grouped(y * jax.nn.silu(z))
    elif order == "norm_then_gate":  # the fault, not the model
        y = grouped(y) * jax.nn.silu(z)
    else:
        raise ValueError(f"assumed.mamba_norm {order!r}")
    return mm(cast(y * p[pre + "norm"]), p[pre + "out_proj"])


def _rope_halves(x, theta):
    """Rotary embedding of ``x [B, S, heads, D]``: the pair ``(i, i +
    D / 2)`` turned by ``position * theta^(-2i / D)``, the angles made
    on the host in float64."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angle = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1)


def _attention(p, pre, x, cfg, cast, mm):
    b, s, _ = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    q = mm(x, p[pre + "q_proj"]).reshape(b, s, heads, hd)
    k = mm(x, p[pre + "k_proj"]).reshape(b, s, kv, hd)
    v = mm(x, p[pre + "v_proj"]).reshape(b, s, kv, hd)
    rope = cfg["assumed"].get("rope", "none")
    if rope == "rotary":  # the fault, not the model
        q = cast(_rope_halves(q, cfg["rope_theta"]))
        k = cast(_rope_halves(k, cfg["rope_theta"]))
    elif rope != "none":
        raise ValueError(f"assumed.rope {rope!r}")
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    rows = min(s, ATTN_ROWS)
    if s % rows:
        raise ValueError(f"{s} positions are no whole blocks of {rows}")
    keys = jnp.arange(s)[None, :]
    scale = hd ** -0.5

    @jax.checkpoint
    def one_block(q_blk, k_h, v_h, first_row):
        seen = keys <= first_row + jnp.arange(rows)[:, None]
        scores = jnp.einsum("bqd,bkd->bqk", q_blk, k_h, precision=HI)
        scores = jnp.where(seen, scores * scale, -jnp.inf)
        return jnp.einsum(
            "bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), v_h,
            precision=HI)

    def one_head(args):
        q_h, k_h, v_h = args  # [B, S, D] each
        blocks = jnp.moveaxis(q_h.reshape(b, s // rows, rows, hd), 1, 0)
        out = jax.lax.map(
            lambda a: one_block(a[0], k_h, v_h, a[1]),
            (blocks, jnp.arange(s // rows) * rows))
        return jnp.moveaxis(out, 0, 1).reshape(b, s, hd)

    by_head = lambda t: jnp.moveaxis(t, 2, 0)  # noqa: E731
    out = jax.lax.map(one_head, (by_head(q), by_head(k), by_head(v)))
    out = cast(jnp.moveaxis(out, 0, 2))  # [B, S, heads, D]
    return mm(out.reshape(b, s, heads * hd), p[pre + "o_proj"])


def _relu2_mlp(t, up, down, cast, mm):
    return mm(cast(jnp.square(jax.nn.relu(mm(t, up)))), down)


def route(flat, router, bias, cfg):
    """``(weights [T, k], chosen [T, k])`` of the published router."""
    scores = jax.nn.sigmoid(jnp.matmul(flat, router, precision=HI))
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * cfg["routed_scaling_factor"], chosen


def _sparse_block(p, pre, x, cfg, cast, mm):
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    first, held = cfg["experts_held_first"], cfg["num_experts_held"]
    weights, chosen = route(
        flat, p[pre + "router"], p[pre + "e_score_correction_bias"], cfg)

    @jax.checkpoint
    def one_expert(e, up, down):
        # this expert's weight a token: its renormalised, scaled score
        # where the token chose it, zero elsewhere
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return weight[:, None] * _relu2_mlp(flat, up, down, cast, mm)

    def add_expert(total, xs):
        return total + one_expert(*xs), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(flat),
        (jnp.arange(held), p[pre + "experts_up"], p[pre + "experts_down"]),
    )
    shared = _relu2_mlp(flat, p[pre + "shared_expert/up"],
                        p[pre + "shared_expert/down"], cast, mm)
    return cast(cast(routed) + shared).reshape(b, s, d)


def _casts(cfg: dict, lower: bool):
    """``(cast, mm, norm)``: what ``lower`` (the control) holds in fp8
    where the configuration's ``mixed_bfloat16`` holds bfloat16: the
    residual stream, the operands and result of every projection, the
    convolved channels, the scan's masked scores and its result, the
    gated and normed result, q, k, v, the attention's result and each
    block's, and their gradients on the way back, each tensor under its
    own scale. The variables, the steps ``dt``, the decays, both norms'
    statistics, the router, the attention's softmax, the logits and the
    loss stay float32 in both."""
    cast = _fp8 if lower else (lambda t: t)

    def mm(a, w):
        return cast(jnp.matmul(cast(a), cast(w), precision=HI))

    def norm(x, w):
        return cast(_rms(x, cfg["layer_norm_epsilon"]) * w)

    return cast, mm, norm


def _embed(p, tokens, cfg, lower):
    cast, _mm, _norm = _casts(cfg, lower)
    return cast(cast(p["embed_tokens/embeddings"])[tokens])


MIXERS = {"M": _mamba, "E": _sparse_block, "*": _attention}


def _layer(p, x, cfg, kind: str, lower: bool):
    """One decoder layer of the ``kind`` its pattern character names;
    ``p`` holds its variables without the ``layer<i>_`` prefix."""
    cast, mm, norm = _casts(cfg, lower)
    u = norm(x, p["norm/weight"])
    return cast(x + MIXERS[kind](p, KINDS[kind] + "/", u, cfg, cast, mm))


def _logits(p, x, cfg, lower):
    _cast, _mm, norm = _casts(cfg, lower)
    return jnp.matmul(norm(x, p["final_norm/weight"]), p["lm_head/kernel"],
                      precision=HI)


def _cross_entropy(logits, targets):
    picked = jnp.take_along_axis(
        logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def _of_layer(tree: dict, i: int) -> dict:
    """Layer ``i``'s leaves, their ``layer<i>_`` prefix taken off."""
    prefix = f"layer{i}_"
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


def forward(params: dict, tokens, cfg: dict, lower: bool = False):
    """Float32 logits ``[B, S, vocab_size]`` for ``tokens [B, S]``;
    ``lower`` is the control (:func:`_casts`)."""
    x = _embed(params, tokens, cfg, lower)
    for i, kind in enumerate(pattern(cfg)):
        x = _layer(_of_layer(params, i), x, cfg, kind, lower)
    return _logits(params, x, cfg, lower)


def loss_fn(params: dict, tokens, targets, cfg: dict, lower: bool):
    return _cross_entropy(forward(params, tokens, cfg, lower), targets)


# -- the steps, a layer at a time ---------------------------------------------
#
# One sequence's gradient is the chain of the pieces' own (``jax.vjp`` of
# the embedding, of each layer and of the head with the loss), taken one
# piece a compiled call, each piece's gradient going straight into its
# share of the velocity: the same numbers as ``jax.grad(loss_fn)``, with
# one layer's gradient and activations alive at a time and not the
# model's. 0.67 billion parameters, their velocity and one whole
# gradient beside a layer's float32 activations at 8192 positions do
# not fit the chip that the reference is compared on.

_CFGS: dict = {}
SIZES = (
    "hidden_size", "vocab_size", "num_hidden_layers",
    "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
    "ssm_state_size", "n_groups", "conv_kernel", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "layer_norm_epsilon",
    "n_routed_experts", "num_experts_per_tok", "num_experts_held",
    "experts_held_first", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
)
READINGS = {"rope": "none", "mamba_norm": "gate_then_norm"}
_STATIC = ("cfg_key", "kind", "lower")


def _cfg_key(cfg: dict) -> str:
    """The sizes and readings the compiled pieces depend on (the
    optimizer's rates are arguments, so that one compiled piece serves
    them all)."""
    key = json.dumps(
        {**{k: cfg[k] for k in SIZES},
         **{k: cfg["assumed"].get(k, v) for k, v in READINGS.items()}},
        sort_keys=True)
    _CFGS[key] = cfg
    return key


def _into(velocity, grads, keep, rate):
    """keras SGD's ``m = momentum * m - lr * g``, a sequence at a time:
    ``keep`` is the momentum for a step's first sequence and 1 after."""
    return {k: keep * velocity[k] - rate * grads[k] for k in velocity}


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer_forward(p, x, cfg_key, kind, lower):
    return _layer(p, x, _CFGS[cfg_key], kind, lower)


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnums=(1,))
def _layer_backward(p, velocity, fixed, x, d_out, keep, rate, cfg_key, kind,
                    lower):
    _out, vjp = jax.vjp(
        lambda t, x: _layer({**t, **fixed}, x, _CFGS[cfg_key], kind, lower),
        p, x)
    d_p, d_x = vjp(d_out)
    return _into(velocity, d_p, keep, rate), d_x


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"),
                   donate_argnums=(1,))
def _head_backward(p, velocity, x, targets, keep, rate, cfg_key, lower):
    loss, (d_p, d_x) = jax.value_and_grad(
        lambda p, x: _cross_entropy(
            _logits(p, x, _CFGS[cfg_key], lower), targets),
        argnums=(0, 1))(p, x)
    return _into(velocity, d_p, keep, rate), d_x, loss


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"))
def _embed_forward(p, tokens, cfg_key, lower):
    return _embed(p, tokens, _CFGS[cfg_key], lower)


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"),
                   donate_argnums=(1,))
def _embed_backward(p, velocity, tokens, d_out, keep, rate, cfg_key, lower):
    _out, vjp = jax.vjp(
        lambda p: _embed(p, tokens, _CFGS[cfg_key], lower), p)
    return _into(velocity, vjp(d_out)[0], keep, rate)


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply(params, velocity):
    return {k: params[k] + velocity[k] for k in params}


HEAD = ("final_norm/weight", "lm_head/kernel")
EMBED = ("embed_tokens/embeddings",)


def _sequence_into_velocity(params, velocity, fixed, tokens, targets, keep,
                            rate, key, lower):
    """``keep * velocity - rate * gradient`` of one sequence's loss,
    and that loss; ``velocity`` is updated in place, piece by piece."""
    only = lambda tree, names: {k: tree[k] for k in names}  # noqa: E731
    how = dict(cfg_key=key, lower=lower)
    kinds = pattern(_CFGS[key])
    inputs = [_embed_forward(only(params, EMBED), tokens, **how)]
    for i, kind in enumerate(kinds):
        inputs.append(_layer_forward(
            {**_of_layer(params, i), **_of_layer(fixed, i)}, inputs[-1],
            kind=kind, **how))
    mine, d_x, loss = _head_backward(
        only(params, HEAD), only(velocity, HEAD), inputs.pop(), targets,
        keep, rate, **how)
    velocity.update(mine)
    for i in reversed(range(len(kinds))):
        mine, d_x = _layer_backward(
            _of_layer(params, i), _of_layer(velocity, i), _of_layer(fixed, i),
            inputs.pop(), d_x, keep, rate, kind=kinds[i], **how)
        velocity.update({f"layer{i}_{k}": v for k, v in mine.items()})
    velocity.update(_embed_backward(
        only(params, EMBED), only(velocity, EMBED), tokens, d_x, keep, rate,
        **how))
    return loss


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def follow(cfg: dict, seed: int, batches, lower: bool = False) -> dict:
    """Takes the training steps ``batches`` yields (``(tokens,
    targets)`` a step) from the seeded weights: keras SGD, ``m =
    momentum * m - lr * g; w = w + m``, with ``g`` the gradient of the
    mean loss over the step's sequences (the mean over the batch's
    tokens, as one batched pass would give), the sequences one after
    the other. Returns each step's loss and, by the path of each
    variable that a step trains (the selection bias is none, and has
    no momentum), the norm of the optimizer's velocity and of the
    parameters' change after the last step."""
    key = _cfg_key(cfg)
    start = init_params(cfg, seed)
    fixed = {k: v for k, v in start.items() if k.endswith(FIXED)}
    params = trained(start)
    del start
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    lr = jnp.float32(cfg["optimizer"]["learning_rate"])
    momentum = jnp.float32(cfg["optimizer"]["momentum"])
    losses = []
    for tokens, targets in batches:
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        rows = tokens.shape[0]
        loss = 0.0
        for r in range(rows):
            loss += float(_sequence_into_velocity(
                params, velocity, fixed, tokens[r:r + 1], targets[r:r + 1],
                momentum if r == 0 else jnp.float32(1.0), lr / rows, key,
                lower)) / rows
        params = _apply(params, velocity)
        losses.append(loss)
    velocity_norm = leaf_norms(velocity)
    del velocity
    # the seeded weights again, not a copy kept through the steps
    start = trained(init_params(cfg, seed))
    change_norm = leaf_norms({k: params[k] - start[k] for k in start})
    return {"losses": losses, "velocity_norm": velocity_norm,
            "change_norm": change_norm}
