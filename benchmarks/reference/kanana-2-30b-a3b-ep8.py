"""Plain reference of one chip's share of kanana-2-30b-a3b-instruct-2601
(``model_type: deepseek_v3``) and of the training steps
``SparkModel.fit`` takes with it: float32 ``jax.numpy`` at ``highest``,
a materialised causal softmax a head, the routed part as a plain sum
over the held experts, next-token cross-entropy over the vocabulary
slice, SGD with momentum as keras applies it. Independent of
``elephas_tpu`` and keras: it makes its own weights from the seed.

The layers, from the published ``config.json`` (``t`` is a layer's
normed input):

- norm: ``w * x * rsqrt(mean(x^2) + eps)``, ``w`` from ones.
- decoder layer ``i``: ``x += attn(norm(x)); x += ffn(norm(x))``; the
  feed-forward is a dense SwiGLU of ``intermediate_size`` below
  ``first_k_dense_replace`` and the sparse block from there on; a
  final norm and an untied head.
- latent attention (``q_lora_rank`` null): ``q = q_proj(t)`` as
  ``[heads, qk_nope_head_dim + qk_rope_head_dim]``;
  ``kv_a_proj_with_mqa(t)`` is ``[kv_lora_rank + qk_rope_head_dim]``,
  split into the latent ``c`` and ``k_rope`` (one head, shared by
  all); ``kv_b_proj(norm(c))`` as ``[heads, qk_nope_head_dim +
  v_head_dim]``, split into ``k_nope`` and ``v``. The rotary embedding
  turns the pairs ``(2i, 2i + 1)`` of ``q_rope`` and ``k_rope`` by the
  angle ``position * rope_theta^(-2i / qk_rope_head_dim)``
  (``rope_interleave``; no rope scaling). ``k = [k_nope, k_rope]``;
  scores ``q k^T / sqrt(192)``, causal softmax; ``o = p v`` is
  ``v_head_dim`` wide; ``o_proj``.
- sparse block: ``s = sigmoid(t w_r)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` (``b`` is
  ``e_score_correction_bias``, no parameter; ``n_group`` and
  ``topk_group`` are 1, so the groups choose nothing); the weights are
  ``s`` at the chosen, divided by their sum plus 1e-20 and multiplied
  by ``routed_scaling_factor``; the chosen experts' SwiGLU outputs
  weighted and summed; plus, ungated, one SwiGLU of width
  ``n_shared_experts * moe_intermediate_size`` (the shared experts).

Departures from the published model, each also in the configuration
file: (1) ``b`` is made from the seed, non-zero and small against the
scores' spread, and no step updates it (``config.json`` gives no
update rate); (2) no auxiliary loss and no multi-token-prediction
module; (3) the vocabulary is the slice ``[0, vocab_size)`` that this
chip holds: ids, logits and loss are over the slice; (4) of the
routed experts only ``num_experts_held`` (from ``experts_held_first``)
are here: the router still scores all of them, and what the absent
ones would add to a token is left out, so the block's result is this
chip's partial sum; (5) the initialiser and the optimizer are assumed.

So that it fits one chip beside nothing else: each attention head and
each expert's part is rematerialised in the backward pass
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


def _is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def param_shapes(cfg: dict) -> dict:
    """``{variable path: (shape, kind)}``; ``kind`` is ``normal``
    (``initializer_range``), ``ones`` or ``select_bias`` (normal at
    ``assumed.select_bias_std``; not trained)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    held, inter = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * inter
    shapes = {"embed_tokens/embeddings": ((v, h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        shapes[f"layer{i}_input_norm/weight"] = ((h,), "ones")
        p = f"layer{i}_attn/"
        shapes[p + "q_proj"] = ((h, heads * (nope + rope)), "normal")
        shapes[p + "kv_a_proj_with_mqa"] = ((h, rank + rope), "normal")
        shapes[p + "kv_a_layernorm"] = ((rank,), "ones")
        shapes[p + "kv_b_proj"] = ((rank, heads * (nope + dv)), "normal")
        shapes[p + "o_proj"] = ((heads * dv, h), "normal")
        shapes[f"layer{i}_post_norm/weight"] = ((h,), "ones")
        if _is_dense(cfg, i):
            p = f"layer{i}_mlp/"
            shapes[p + "gate_up"] = (
                (h, 2 * cfg["intermediate_size"]), "normal")
            shapes[p + "down"] = ((cfg["intermediate_size"], h), "normal")
            continue
        p = f"layer{i}_moe/"
        shapes[p + "router"] = ((h, cfg["n_routed_experts"]), "normal")
        shapes[p + "e_score_correction_bias"] = (
            (cfg["n_routed_experts"],), "select_bias")
        shapes[p + "experts_gate_up"] = ((held, h, 2 * inter), "normal")
        shapes[p + "experts_down"] = ((held, inter, h), "normal")
        shapes[p + "shared_expert/gate_up"] = ((h, 2 * shared), "normal")
        shapes[p + "shared_expert/down"] = ((shared, h), "normal")
    shapes["final_norm/weight"] = ((h,), "ones")
    shapes["lm_head/kernel"] = ((h, v), "normal")
    return shapes


def trained(tree: dict) -> dict:
    """The leaves a step updates: all but the selection bias."""
    return {k: v for k, v in tree.items() if not k.endswith(FIXED)}


def init_params(cfg: dict, seed: int) -> dict:
    """Every variable from the seed, on the device, in one jitted call,
    in float32 (mixed_bfloat16 keeps its variables in float32)."""
    shapes = param_shapes(cfg)
    std = {"normal": cfg["assumed"]["initializer_range"],
           "select_bias": cfg["assumed"]["select_bias_std"]}

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = std[kind] * jax.random.normal(
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


def _rope_pairs(x, theta):
    """Rotary embedding of ``x [B, S, heads, R]``: the pair ``(2i, 2i +
    1)`` turned by ``position * theta^(-2i / R)``."""
    s, r = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape)


def _latent_attention(p, pre, x, cfg, cast, mm):
    b, s, _ = x.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, theta = cfg["v_head_dim"], cfg["rope_theta"]
    q = mm(x, p[pre + "q_proj"]).reshape(b, s, heads, nope + rope)
    latent = mm(x, p[pre + "kv_a_proj_with_mqa"])
    c, k_rope = latent[..., :rank], latent[..., None, rank:]
    c = cast(_rms(c, cfg["rms_norm_eps"]) * p[pre + "kv_a_layernorm"])
    kv = mm(c, p[pre + "kv_b_proj"]).reshape(b, s, heads, nope + dv)
    q = jnp.concatenate(
        [q[..., :nope], cast(_rope_pairs(q[..., nope:], theta))], axis=-1)
    k_rope = cast(_rope_pairs(k_rope, theta))
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))],
        axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = (nope + rope) ** -0.5

    @jax.checkpoint
    def one_head(args):
        q_h, k_h, v_h = args  # [B, S, 192], [B, S, 192], [B, S, 128]
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h, precision=HI)
        scores = jnp.where(causal, scores * scale, -jnp.inf)
        return jnp.einsum(
            "bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), v_h,
            precision=HI)

    by_head = lambda t: jnp.moveaxis(t, 2, 0)  # noqa: E731
    out = jax.lax.map(one_head, (by_head(q), by_head(k), by_head(v)))
    out = cast(jnp.moveaxis(out, 0, 2))  # [B, S, heads, dv]
    return mm(out.reshape(b, s, heads * dv), p[pre + "o_proj"])


def _swiglu(t, gate_up, down, cast, mm):
    gate, up = jnp.split(mm(t, gate_up), 2, axis=-1)
    return mm(cast(jax.nn.silu(gate) * up), down)


def route(flat, router, bias, cfg):
    """``(weights [T, k], chosen [T, k])`` of the published router."""
    scores = jax.nn.sigmoid(jnp.matmul(flat, router, precision=HI))
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * cfg["routed_scaling_factor"], chosen


def _sparse_block(p, pre, x, cfg, cast, mm):
    b, s, h = x.shape
    flat = x.reshape(b * s, h)
    first, held = cfg["experts_held_first"], cfg["num_experts_held"]
    weights, chosen = route(
        flat, p[pre + "router"], p[pre + "e_score_correction_bias"], cfg)

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
    k, v, the attention's result and each block's, and their gradients
    on the way back, each tensor under its own scale. The variables,
    the norms' statistics, the router, the attention's softmax, the
    logits and the loss stay float32 in both."""
    cast = _fp8 if lower else (lambda t: t)

    def mm(a, w):
        return cast(jnp.matmul(cast(a), cast(w), precision=HI))

    def norm(x, w):
        return cast(_rms(x, cfg["rms_norm_eps"]) * w)

    return cast, mm, norm


def _embed(p, tokens, cfg, lower):
    cast, _mm, _norm = _casts(cfg, lower)
    return cast(cast(p["embed_tokens/embeddings"])[tokens])


def _layer(p, x, cfg, dense: bool, lower: bool):
    """One decoder layer; ``p`` holds its variables without the
    ``layer<i>_`` prefix."""
    cast, mm, norm = _casts(cfg, lower)
    h = norm(x, p["input_norm/weight"])
    x = cast(x + _latent_attention(p, "attn/", h, cfg, cast, mm))
    h = norm(x, p["post_norm/weight"])
    if dense:
        h = _swiglu(h, p["mlp/gate_up"], p["mlp/down"], cast, mm)
    else:
        h = _sparse_block(p, "moe/", h, cfg, cast, mm)
    return cast(x + h)


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
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(_of_layer(params, i), x, cfg, _is_dense(cfg, i), lower)
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
# model's. 0.7 to 0.9 billion parameters, their velocity and one whole
# gradient beside a layer's float32 activations at 8192 positions do
# not fit the chip that the reference is compared on.

_CFGS: dict = {}
SIZES = (
    "hidden_size", "vocab_size", "num_hidden_layers", "first_k_dense_replace",
    "intermediate_size", "num_attention_heads", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
    "rms_norm_eps", "n_routed_experts", "num_experts_per_tok",
    "num_experts_held", "experts_held_first", "moe_intermediate_size",
    "n_shared_experts", "routed_scaling_factor",
)
_STATIC = ("cfg_key", "dense", "lower")


def _cfg_key(cfg: dict) -> str:
    """The sizes the compiled pieces depend on (the optimizer's rates
    are arguments, so that one compiled piece serves them all)."""
    key = json.dumps({k: cfg[k] for k in SIZES}, sort_keys=True)
    _CFGS[key] = cfg
    return key


def _into(velocity, grads, keep, rate):
    """keras SGD's ``m = momentum * m - lr * g``, a sequence at a time:
    ``keep`` is the momentum for a step's first sequence and 1 after."""
    return {k: keep * velocity[k] - rate * grads[k] for k in velocity}


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer_forward(p, x, cfg_key, dense, lower):
    return _layer(p, x, _CFGS[cfg_key], dense, lower)


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnums=(1,))
def _layer_backward(p, velocity, fixed, x, d_out, keep, rate, cfg_key, dense,
                    lower):
    _out, vjp = jax.vjp(
        lambda t, x: _layer({**t, **fixed}, x, _CFGS[cfg_key], dense, lower),
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
    cfg = _CFGS[key]
    only = lambda tree, names: {k: tree[k] for k in names}  # noqa: E731
    how = dict(cfg_key=key, lower=lower)
    dense = [_is_dense(cfg, i) for i in range(cfg["num_hidden_layers"])]
    inputs = [_embed_forward(only(params, EMBED), tokens, **how)]
    for i, d in enumerate(dense):
        inputs.append(_layer_forward(
            {**_of_layer(params, i), **_of_layer(fixed, i)}, inputs[-1],
            dense=d, **how))
    mine, d_x, loss = _head_backward(
        only(params, HEAD), only(velocity, HEAD), inputs.pop(), targets,
        keep, rate, **how)
    velocity.update(mine)
    for i in reversed(range(len(dense))):
        mine, d_x = _layer_backward(
            _of_layer(params, i), _of_layer(velocity, i), _of_layer(fixed, i),
            inputs.pop(), d_x, keep, rate, dense=dense[i], **how)
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
