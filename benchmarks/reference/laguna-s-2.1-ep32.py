"""Plain reference of one chip's share of Laguna-S-2.1 (``model_type:
laguna``) and of the training steps ``SparkModel.fit`` takes with it:
float32 ``jax.numpy`` at ``highest``, a materialised masked softmax a
head and query block, the routed part as a plain sum over the held
experts, next-token cross-entropy over the vocabulary slice, SGD with
momentum as keras applies it. Independent of ``elephas_tpu`` and keras:
it makes its own weights from the seed.

The layers, from the published ``config.json`` (``h`` is a layer's
input, the residual stream; ``x = norm_1(h)``):

- norm: ``w * x * rsqrt(mean(x^2) + eps)``, ``w`` from ones.
- attention, layer ``l``: ``H_l = num_attention_heads_per_layer[l]``
  query heads (48 where ``layer_types[l]`` is ``full_attention``, 72
  where ``sliding_attention``) over ``num_key_value_heads`` key/value
  heads of ``head_dim``, no bias, no q/k norm: ``q = x W_q`` as ``[H_l,
  head_dim]``, ``k = x W_k`` and ``v = x W_v`` as ``[kv_heads,
  head_dim]``; the rotation of the layer's kind
  (``rope_parameters[layer_types[l]]``, :func:`rope_tables`); query
  ``i`` sees key ``j`` where ``j <= i`` and, in a sliding layer, ``i -
  j < sliding_window`` (that many keys with its own); scores ``q k^T /
  sqrt(head_dim)``, softmax over the visible keys, query head ``n``
  reads key/value head ``n // (H_l / kv_heads)``; the gate ``g =
  sigmoid(x W_g)``, ``W_g`` of ``[hidden, H_l]``, one scalar a head and
  token (``gating: "per-head"``); ``h1 = h + concat_n(g_n a_n) W_o``.
- rotation: a sliding layer turns the pairs ``(i, i + 64)`` of all 128
  dimensions by ``position * 10000^(-2i / 128)``. A full layer turns
  the pairs ``(i, i + 32)`` of the first 64 (``partial_rotary_factor``
  0.5; the other 64 pass unrotated and unscaled) by YaRN's
  frequencies: with ``f_i = base^(2i / 64)``, ``c(n) = 64 ln(original /
  (2 pi n)) / (2 ln base)``, ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))`` clamped to ``[0, 63]`` and ``ramp_i = clip((i -
  low) / (high - low), 0, 1)``, the frequency is ``ramp_i / (factor
  f_i) + (1 - ramp_i) / f_i``, and cos and sin are multiplied by
  ``attention_factor``.
- feed-forward, ``m = norm_2(h1)``: layer 0 (``mlp_layer_types``
  ``dense``) a SwiGLU of ``intermediate_size``; every other layer the
  sparse block: float32 router logits ``m W_r`` over all
  ``num_experts`` (``moe_router_logit_softcapping`` 0: none), a score
  each, the ``num_experts_per_tok`` largest chosen, their scores
  divided by their sum plus 1e-20 (``norm_topk_prob``) and multiplied
  by ``moe_routed_scaling_factor``, applied to the experts' results
  (``moe_apply_router_weight_on_input`` false); SwiGLU experts of
  ``moe_intermediate_size``; one ungated SwiGLU shared expert of
  ``shared_expert_intermediate_size`` added to every token. ``h' = h1 +
  ffn(m)``.

What the published config has no key for, each under ``assumed`` in
the configuration file, with the other reading as a fault that the
builder's runs put in the program's place: ``assumed.attention_gate``
(``sigmoid_of_layer_input_per_head``; ``none`` leaves the gate out, ``g
= 1``), ``assumed.scoring_func`` (``sigmoid``; ``softmax`` over all the
experts before the choice), and ``assumed.band`` (``sliding_window``;
``none`` runs every layer as full attention, each with its own
rotation and head count).

Departures from the published model, each also in the configuration
file: (1) the vocabulary is the slice ``[0, vocab_size)`` that this
chip holds: ids, logits and loss are over the slice; (2) of the routed
experts only ``num_experts_held`` (from ``experts_held_first``) are
here: the router still scores all of them, and what the absent ones
would add to a token is left out, so the block's result is this chip's
partial sum; (3) the initialiser and the optimizer are assumed, and
there is no auxiliary loss.

So that it fits one chip beside nothing else: attention is taken a
head and a block of ``ATTN_ROWS`` queries at a time, each block and
each expert's part rematerialised in the backward pass
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
# queries a block of materialised scores: 2048 x 8192 float32 is 67 MB
ATTN_ROWS = 2048
GATES = ("sigmoid_of_layer_input_per_head", "none")
SCORES = {"sigmoid": jax.nn.sigmoid,
          "softmax": lambda t: jax.nn.softmax(t, axis=-1)}
BANDS = ("sliding_window", "none")


def layer_kinds(cfg: dict) -> list:
    """``(sliding, dense, heads)`` of each layer that is here."""
    n = cfg["num_hidden_layers"]
    return [(kind == "sliding_attention", mlp == "dense", int(heads))
            for kind, mlp, heads in zip(
                cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
                cfg["num_attention_heads_per_layer"][:n])]


def param_shapes(cfg: dict) -> dict:
    """``{variable path: (shape, kind)}``; ``kind`` is ``normal``
    (``initializer_range``) or ``ones``."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    held, inter = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    shared = cfg["shared_expert_intermediate_size"]
    shapes = {"embed_tokens/embeddings": ((v, h), "normal")}
    for i, (_sliding, dense, heads) in enumerate(layer_kinds(cfg)):
        shapes[f"layer{i}_input_norm/weight"] = ((h,), "ones")
        p = f"layer{i}_attn/"
        shapes[p + "q_proj"] = ((h, heads * hd), "normal")
        shapes[p + "k_proj"] = ((h, kv * hd), "normal")
        shapes[p + "v_proj"] = ((h, kv * hd), "normal")
        shapes[p + "o_proj"] = ((heads * hd, h), "normal")
        shapes[p + "g_proj"] = ((h, heads), "normal")
        shapes[f"layer{i}_post_norm/weight"] = ((h,), "ones")
        if dense:
            p = f"layer{i}_mlp/"
            shapes[p + "gate_up"] = (
                (h, 2 * cfg["intermediate_size"]), "normal")
            shapes[p + "down"] = ((cfg["intermediate_size"], h), "normal")
            continue
        p = f"layer{i}_moe/"
        shapes[p + "router"] = ((h, cfg["num_experts"]), "normal")
        shapes[p + "experts_gate_up"] = ((held, h, 2 * inter), "normal")
        shapes[p + "experts_down"] = ((held, inter, h), "normal")
        shapes[p + "shared_expert/gate_up"] = ((h, 2 * shared), "normal")
        shapes[p + "shared_expert/down"] = ((shared, h), "normal")
    shapes["final_norm/weight"] = ((h,), "ones")
    shapes["lm_head/kernel"] = ((h, v), "normal")
    return shapes


def init_params(cfg: dict, seed: int) -> dict:
    """Every variable from the seed, on the device, in one jitted call,
    in float32 (mixed_bfloat16 keeps its variables in float32)."""
    shapes = param_shapes(cfg)
    std = cfg["assumed"]["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

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


def rope_tables(positions: int, group: dict, head_dim: int):
    """``(cos, sin)`` of ``[positions, rotated / 2]`` in float64 from
    one published ``rope_parameters`` group, one column a pair; the
    module's docstring has the closed form."""
    dim = int(head_dim * group.get("partial_rotary_factor", 1))
    base = float(group["rope_theta"])
    pair = np.arange(dim // 2, dtype=np.float64)
    freq = base ** (-2.0 * pair / dim)
    scale = 1.0
    if group.get("rope_type", "default") == "yarn":
        factor = float(group["factor"])
        original = float(group["original_max_position_embeddings"])

        def c(n):
            return dim * np.log(original / (2 * np.pi * n)) / (
                2 * np.log(base))

        low = min(max(np.floor(c(group["beta_fast"])), 0), dim - 1)
        high = min(max(np.ceil(c(group["beta_slow"])), 0), dim - 1)
        ramp = np.clip((pair - low) / max(high - low, 1e-3), 0, 1)
        freq = ramp * freq / factor + (1 - ramp) * freq
        scale = group.get("attention_factor")
        scale = 0.1 * np.log(factor) + 1.0 if scale is None else float(scale)
    angle = np.arange(positions, dtype=np.float64)[:, None] * freq[None, :]
    return scale * np.cos(angle), scale * np.sin(angle)


def _rope(x, group: dict):
    """``x [B, S, heads, D]`` with the first ``rotated`` dimensions of
    each head turned in the pairs ``(i, i + rotated / 2)``; the rest
    passes as it is. The angles are made on the host in float64."""
    cos, sin = rope_tables(x.shape[1], group, x.shape[-1])
    half = cos.shape[-1]
    cos = jnp.asarray(cos, jnp.float32)[None, :, None]
    sin = jnp.asarray(sin, jnp.float32)[None, :, None]
    first, second = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin,
         x[..., 2 * half:]], axis=-1)


def _attention(p, pre, x, cfg, sliding: bool, heads: int, cast, mm):
    b, s, _ = x.shape
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window"]
    band, gate = cfg["assumed"]["band"], cfg["assumed"]["attention_gate"]
    if band not in BANDS or gate not in GATES:
        raise ValueError(f"assumed.band {band!r}, attention_gate {gate!r}")
    group = cfg["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"]
    banded = sliding and band == "sliding_window"
    q = mm(x, p[pre + "q_proj"]).reshape(b, s, heads, hd)
    k = mm(x, p[pre + "k_proj"]).reshape(b, s, kv, hd)
    v = mm(x, p[pre + "v_proj"]).reshape(b, s, kv, hd)
    q, k = cast(_rope(q, group)), cast(_rope(k, group))
    # query head n reads key/value head n // (heads / kv)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    rows = min(s, ATTN_ROWS)
    if s % rows:
        raise ValueError(f"{s} positions are no whole blocks of {rows}")
    keys = jnp.arange(s)[None, :]
    scale = hd ** -0.5

    @jax.checkpoint
    def one_block(q_blk, k_h, v_h, first_row):
        # [B, rows, D] queries from position ``first_row`` on
        queries = first_row + jnp.arange(rows)[:, None]
        seen = keys <= queries
        if banded:
            seen &= queries - keys < window
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
    if gate != "none":
        # one scalar a head and token; the sigmoid is float32 in the
        # control too
        g = jax.nn.sigmoid(mm(x, p[pre + "g_proj"]))
        out = cast(out * g[..., None])
    return mm(out.reshape(b, s, heads * hd), p[pre + "o_proj"])


def _swiglu(t, gate_up, down, cast, mm):
    gate, up = jnp.split(mm(t, gate_up), 2, axis=-1)
    return mm(cast(jax.nn.silu(gate) * up), down)


def route(flat, router, cfg):
    """``(weights [T, k], chosen [T, k])``: every expert scored, the
    ``k`` largest chosen, their scores renormalised and scaled."""
    score = SCORES[cfg["assumed"]["scoring_func"]]
    scores = score(jnp.matmul(flat, router, precision=HI))
    weights, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * cfg["moe_routed_scaling_factor"], chosen


def _sparse_block(p, pre, x, cfg, cast, mm):
    """The held experts' part and the shared expert for the normed
    ``x`` (the router's own arithmetic is float32 in the control too)."""
    b, s, h = x.shape
    flat = x.reshape(b * s, h)
    first, held = cfg["experts_held_first"], cfg["num_experts_held"]
    weights, chosen = route(flat, p[pre + "router"], cfg)

    @jax.checkpoint
    def one_expert(e, gate_up, down):
        # this expert's weight a token: its renormalised, scaled score
        # where the token chose it, zero elsewhere
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return weight[:, None] * _swiglu(flat, gate_up, down, cast, mm)

    def add_expert(total, xs):
        return total + one_expert(*xs), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(flat),
        (jnp.arange(held), p[pre + "experts_gate_up"],
         p[pre + "experts_down"]),
    )
    shared = _swiglu(flat, p[pre + "shared_expert/gate_up"],
                     p[pre + "shared_expert/down"], cast, mm)
    return cast(cast(routed) + shared).reshape(b, s, h)


def _casts(cfg: dict, lower: bool):
    """``(cast, mm, norm)``: what ``lower`` (the control) holds in fp8
    where the configuration's ``mixed_bfloat16`` holds bfloat16: the
    residual stream, the operands and result of every projection, q,
    k, v, the attention's result before and after the gate and each
    block's, and their gradients on the way back, each tensor under
    its own scale. The variables, the norms' statistics, the rotation,
    the router, the attention's softmax, the gate's sigmoid, the logits
    and the loss stay float32 in both."""
    cast = _fp8 if lower else (lambda t: t)

    def mm(a, w):
        return cast(jnp.matmul(cast(a), cast(w), precision=HI))

    def norm(x, w):
        return cast(_rms(x, cfg["rms_norm_eps"]) * w)

    return cast, mm, norm


def _embed(p, tokens, cfg, lower):
    cast, _mm, _norm = _casts(cfg, lower)
    return cast(cast(p["embed_tokens/embeddings"])[tokens])


def _layer(p, x, cfg, sliding: bool, dense: bool, heads: int, lower: bool):
    """One decoder layer; ``p`` holds its variables without the
    ``layer<i>_`` prefix."""
    cast, mm, norm = _casts(cfg, lower)
    a = norm(x, p["input_norm/weight"])
    x = cast(x + _attention(p, "attn/", a, cfg, sliding, heads, cast, mm))
    m = norm(x, p["post_norm/weight"])
    if dense:
        m = _swiglu(m, p["mlp/gate_up"], p["mlp/down"], cast, mm)
    else:
        m = _sparse_block(p, "moe/", m, cfg, cast, mm)
    return cast(x + m)


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
    for i, kind in enumerate(layer_kinds(cfg)):
        x = _layer(_of_layer(params, i), x, cfg, *kind, lower)
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
# model's. 0.81 billion parameters, their velocity and one whole
# gradient beside a layer's float32 activations at 8192 positions do
# not fit the chip that the reference is compared on.

_CFGS: dict = {}
SIZES = (
    "hidden_size", "vocab_size", "num_hidden_layers", "intermediate_size",
    "num_attention_heads_per_layer", "num_key_value_heads", "head_dim",
    "layer_types", "mlp_layer_types", "sliding_window", "rope_parameters",
    "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "moe_routed_scaling_factor", "num_experts_held", "experts_held_first",
)
READINGS = ("attention_gate", "scoring_func", "band")
_STATIC = ("cfg_key", "sliding", "dense", "heads", "lower")


def _cfg_key(cfg: dict) -> str:
    """The sizes and readings the compiled pieces depend on (the
    optimizer's rates are arguments, so that one compiled piece serves
    them all)."""
    key = json.dumps(
        {**{k: cfg[k] for k in SIZES},
         **{k: cfg["assumed"][k] for k in READINGS}}, sort_keys=True)
    _CFGS[key] = cfg
    return key


def _into(velocity, grads, keep, rate):
    """keras SGD's ``m = momentum * m - lr * g``, a sequence at a time:
    ``keep`` is the momentum for a step's first sequence and 1 after."""
    return {k: keep * velocity[k] - rate * grads[k] for k in velocity}


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer_forward(p, x, cfg_key, sliding, dense, heads, lower):
    return _layer(p, x, _CFGS[cfg_key], sliding, dense, heads, lower)


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnums=(1,))
def _layer_backward(p, velocity, x, d_out, keep, rate, cfg_key, sliding,
                    dense, heads, lower):
    _out, vjp = jax.vjp(
        lambda t, x: _layer(
            t, x, _CFGS[cfg_key], sliding, dense, heads, lower), p, x)
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


def _sequence_into_velocity(params, velocity, tokens, targets, keep, rate,
                            key, lower):
    """``keep * velocity - rate * gradient`` of one sequence's loss,
    and that loss; ``velocity`` is updated in place, piece by piece."""
    only = lambda tree, names: {k: tree[k] for k in names}  # noqa: E731
    how = dict(cfg_key=key, lower=lower)
    kinds = [dict(zip(("sliding", "dense", "heads"), kind))
             for kind in layer_kinds(_CFGS[key])]
    inputs = [_embed_forward(only(params, EMBED), tokens, **how)]
    for i, kind in enumerate(kinds):
        inputs.append(_layer_forward(
            _of_layer(params, i), inputs[-1], **kind, **how))
    mine, d_x, loss = _head_backward(
        only(params, HEAD), only(velocity, HEAD), inputs.pop(), targets,
        keep, rate, **how)
    velocity.update(mine)
    for i in reversed(range(len(kinds))):
        mine, d_x = _layer_backward(
            _of_layer(params, i), _of_layer(velocity, i), inputs.pop(), d_x,
            keep, rate, **kinds[i], **how)
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
    the other. Returns each step's loss and, by variable path, the norm
    of the optimizer's velocity and of the parameters' change after the
    last step."""
    key = _cfg_key(cfg)
    params = init_params(cfg, seed)
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
                params, velocity, tokens[r:r + 1], targets[r:r + 1],
                momentum if r == 0 else jnp.float32(1.0), lr / rows, key,
                lower)) / rows
        params = _apply(params, velocity)
        losses.append(loss)
    velocity_norm = leaf_norms(velocity)
    del velocity
    # the seeded weights again, not a copy kept through the steps
    start = init_params(cfg, seed)
    change_norm = leaf_norms({k: params[k] - start[k] for k in start})
    return {"losses": losses, "velocity_norm": velocity_norm,
            "change_norm": change_norm}
