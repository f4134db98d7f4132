"""Plain reference of one chip's share of SmallThinker-21BA3B-Instruct
and of the training steps ``SparkModel.fit`` takes with it: float32
``jax.numpy`` at ``highest``, a materialised masked softmax a query
block, the routed part as a plain sum over the held experts,
next-token cross-entropy over the vocabulary slice, SGD with momentum
as keras applies it. Independent of ``elephas_tpu`` and keras: it makes
its own weights from the seed.

The layers, from the published ``config.json`` and the family's
description (``h`` is a layer's input, the residual stream; layer
``l`` of the published 52 has ``sliding_window_layout[l] =
rope_layout[l] = 0`` where ``l % 4 == 0``, else 1):

- norm: ``w * x * rsqrt(mean(x^2) + eps)``, ``w`` from ones.
- router, ahead of attention: ``r = h W_r`` over all
  ``moe_num_primary_experts``, from the un-normed ``h``.
- attention: ``a = norm_1(h)``; ``q = a W_q`` as ``[heads, head_dim]``,
  ``k = a W_k`` and ``v = a W_v`` as ``[kv_heads, head_dim]``; no bias,
  no q/k norm. Where ``rope_layout[l]`` is 1 the rotary embedding turns
  the pairs ``(i, i + head_dim / 2)`` of ``q`` and ``k`` by ``position
  * rope_theta^(-2i / head_dim)``; where it is 0 the layer has no
  position term. Query ``i`` sees key ``j`` where ``j <= i`` and, if
  ``sliding_window_layout[l]`` is 1, ``i - j < sliding_window_size``
  (that many keys with its own). Scores ``q k^T / sqrt(head_dim)``,
  softmax over the visible keys, query head ``n`` reads key/value head
  ``n // (heads / kv_heads)``; ``h1 = h + o W_o``.
- experts: ``m = norm_2(h1)``; the ``moe_num_active_primary_experts``
  largest of ``r`` are chosen and ``p = softmax`` over the chosen
  logits (``moe_primary_router_apply_softmax``, ``norm_topk_prob``);
  ``h' = h1 + sum_e p_e W_down,e(relu(W_gate,e m) * W_up,e m)``
  (ReGLU); no shared expert.

Departures from the published model, each also in the configuration
file: (1) the vocabulary is the slice ``[0, vocab_size)`` that this
chip holds: ids, logits and loss are over the slice; (2) of the
experts only ``num_experts_held`` (from ``experts_held_first``) are
here: the router still scores all of them, and what the absent ones
would add to a token is left out, so the block's result is this chip's
partial sum; (3) primary experts only, the initialiser, the optimizer,
the tensor the router reads and the window's edge are assumed
(``assumed.router_input`` may say ``expert_input`` to feed the router
``m`` instead: the other reading of "before attention", which the
builder's fault run puts in the program's place).

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
# queries a block of materialised scores: 2048 x 16384 float32 is 134 MB
ATTN_ROWS = 2048


def param_shapes(cfg: dict) -> dict:
    """``{variable path: (shape, kind)}``; ``kind`` is ``normal``
    (``initializer_range``) or ``ones``."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, inter = cfg["head_dim"], cfg["moe_ffn_hidden_size"]
    held = cfg["num_experts_held"]
    shapes = {"embed_tokens/embeddings": ((v, h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        shapes[f"layer{i}_input_norm/weight"] = ((h,), "ones")
        p = f"layer{i}_attn/"
        shapes[p + "q_proj"] = ((h, heads * hd), "normal")
        shapes[p + "k_proj"] = ((h, kv * hd), "normal")
        shapes[p + "v_proj"] = ((h, kv * hd), "normal")
        shapes[p + "o_proj"] = ((heads * hd, h), "normal")
        shapes[f"layer{i}_post_norm/weight"] = ((h,), "ones")
        p = f"layer{i}_moe/"
        shapes[p + "router"] = ((h, cfg["moe_num_primary_experts"]), "normal")
        shapes[p + "experts_gate_up"] = ((held, h, 2 * inter), "normal")
        shapes[p + "experts_down"] = ((held, inter, h), "normal")
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


def _rope_halves(x, theta):
    """Rotary embedding of ``x [B, S, heads, D]``: the pair ``(i, i +
    D / 2)`` turned by ``position * theta^(-2i / D)``. The angles are
    made on the host in float64: at 16384 positions a float32 product
    is already a thousandth of a radian off."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angle = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1)


def _attention(p, pre, x, cfg, windowed: bool, rotary: bool, cast, mm):
    b, s, _ = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, window = cfg["head_dim"], cfg["sliding_window_size"]
    q = mm(x, p[pre + "q_proj"]).reshape(b, s, heads, hd)
    k = mm(x, p[pre + "k_proj"]).reshape(b, s, kv, hd)
    v = mm(x, p[pre + "v_proj"]).reshape(b, s, kv, hd)
    if rotary:
        q = cast(_rope_halves(q, cfg["rope_theta"]))
        k = cast(_rope_halves(k, cfg["rope_theta"]))
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
        if windowed:
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
    return mm(out.reshape(b, s, heads * hd), p[pre + "o_proj"])


def _reglu(t, gate_up, down, cast, mm):
    gate, up = jnp.split(mm(t, gate_up), 2, axis=-1)
    return mm(cast(jax.nn.relu(gate) * up), down)


def route(flat, router, cfg):
    """``(weights [T, k], chosen [T, k])``: the ``k`` largest logits,
    and the softmax over those alone."""
    logits = jnp.matmul(flat, router, precision=HI)
    top, chosen = jax.lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    return jax.nn.softmax(top, axis=-1), chosen


def _sparse_block(p, pre, x, route_from, cfg, cast, mm):
    """The held experts' part for the normed ``x``, routed by what the
    router reads of ``route_from`` (its own arithmetic is float32 in
    the control too)."""
    b, s, h = x.shape
    flat = x.reshape(b * s, h)
    first, held = cfg["experts_held_first"], cfg["num_experts_held"]
    weights, chosen = route(
        route_from.reshape(b * s, h), p[pre + "router"], cfg)

    @jax.checkpoint
    def one_expert(e, gate_up, down):
        # this expert's weight a token: its share of the softmax over
        # the token's chosen where it is one of them, zero elsewhere
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return weight[:, None] * _reglu(flat, gate_up, down, cast, mm)

    def add_expert(total, xs):
        return total + one_expert(*xs), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(flat),
        (jnp.arange(held), p[pre + "experts_gate_up"],
         p[pre + "experts_down"]),
    )
    return cast(routed).reshape(b, s, h)


def _casts(cfg: dict, lower: bool):
    """``(cast, mm, norm)``: what ``lower`` (the control) holds in fp8
    where the configuration's ``mixed_bfloat16`` holds bfloat16: the
    residual stream, the operands and result of every projection, q,
    k, v, the attention's result and each block's, and their gradients
    on the way back, each tensor under its own scale. The variables,
    the norms' statistics, the rotation, the router, the attention's
    softmax, the logits and the loss stay float32 in both."""
    cast = _fp8 if lower else (lambda t: t)

    def mm(a, w):
        return cast(jnp.matmul(cast(a), cast(w), precision=HI))

    def norm(x, w):
        return cast(_rms(x, cfg["rms_norm_eps"]) * w)

    return cast, mm, norm


def _embed(p, tokens, cfg, lower):
    cast, _mm, _norm = _casts(cfg, lower)
    return cast(cast(p["embed_tokens/embeddings"])[tokens])


def _layer(p, x, cfg, windowed: bool, rotary: bool, lower: bool):
    """One decoder layer; ``p`` holds its variables without the
    ``layer<i>_`` prefix."""
    cast, mm, norm = _casts(cfg, lower)
    a = norm(x, p["input_norm/weight"])
    attended = cast(
        x + _attention(p, "attn/", a, cfg, windowed, rotary, cast, mm))
    m = norm(attended, p["post_norm/weight"])
    reads = cfg["assumed"].get("router_input", "layer_input")
    if reads not in ("layer_input", "expert_input"):
        raise ValueError(f"assumed.router_input {reads!r}")
    route_from = x if reads == "layer_input" else m
    return cast(attended + _sparse_block(
        p, "moe/", m, route_from, cfg, cast, mm))


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


def layer_kinds(cfg: dict) -> list:
    """``(windowed, rotary)`` of each layer that is here."""
    n = cfg["num_hidden_layers"]
    return [(bool(w), bool(r)) for w, r in zip(
        cfg["sliding_window_layout"][:n], cfg["rope_layout"][:n])]


def forward(params: dict, tokens, cfg: dict, lower: bool = False):
    """Float32 logits ``[B, S, vocab_size]`` for ``tokens [B, S]``;
    ``lower`` is the control (:func:`_casts`)."""
    x = _embed(params, tokens, cfg, lower)
    for i, (windowed, rotary) in enumerate(layer_kinds(cfg)):
        x = _layer(_of_layer(params, i), x, cfg, windowed, rotary, lower)
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
# model's. 0.64 billion parameters, their velocity and one whole
# gradient beside a layer's float32 activations at 16384 positions do
# not fit the chip that the reference is compared on.

_CFGS: dict = {}
SIZES = (
    "hidden_size", "vocab_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "sliding_window_size", "sliding_window_layout", "rope_layout",
    "moe_num_primary_experts", "moe_num_active_primary_experts",
    "moe_ffn_hidden_size", "num_experts_held", "experts_held_first",
)
_STATIC = ("cfg_key", "windowed", "rotary", "lower")


def _cfg_key(cfg: dict) -> str:
    """The sizes and readings the compiled pieces depend on (the
    optimizer's rates are arguments, so that one compiled piece serves
    them all)."""
    key = json.dumps(
        {**{k: cfg[k] for k in SIZES},
         "router_input": cfg["assumed"].get("router_input", "layer_input")},
        sort_keys=True)
    _CFGS[key] = cfg
    return key


def _into(velocity, grads, keep, rate):
    """keras SGD's ``m = momentum * m - lr * g``, a sequence at a time:
    ``keep`` is the momentum for a step's first sequence and 1 after."""
    return {k: keep * velocity[k] - rate * grads[k] for k in velocity}


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer_forward(p, x, cfg_key, windowed, rotary, lower):
    return _layer(p, x, _CFGS[cfg_key], windowed, rotary, lower)


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnums=(1,))
def _layer_backward(p, velocity, x, d_out, keep, rate, cfg_key, windowed,
                    rotary, lower):
    _out, vjp = jax.vjp(
        lambda t, x: _layer(t, x, _CFGS[cfg_key], windowed, rotary, lower),
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


def _sequence_into_velocity(params, velocity, tokens, targets, keep, rate,
                            key, lower):
    """``keep * velocity - rate * gradient`` of one sequence's loss,
    and that loss; ``velocity`` is updated in place, piece by piece."""
    only = lambda tree, names: {k: tree[k] for k in names}  # noqa: E731
    how = dict(cfg_key=key, lower=lower)
    kinds = layer_kinds(_CFGS[key])
    inputs = [_embed_forward(only(params, EMBED), tokens, **how)]
    for i, (windowed, rotary) in enumerate(kinds):
        inputs.append(_layer_forward(
            _of_layer(params, i), inputs[-1], windowed=windowed,
            rotary=rotary, **how))
    mine, d_x, loss = _head_backward(
        only(params, HEAD), only(velocity, HEAD), inputs.pop(), targets,
        keep, rate, **how)
    velocity.update(mine)
    for i in reversed(range(len(kinds))):
        mine, d_x = _layer_backward(
            _of_layer(params, i), _of_layer(velocity, i), inputs.pop(), d_x,
            keep, rate, windowed=kinds[i][0], rotary=kinds[i][1], **how)
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
