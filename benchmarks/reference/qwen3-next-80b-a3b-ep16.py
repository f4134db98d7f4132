"""Plain reference of one chip's share of Qwen3-Next-80B-A3B-Instruct
and of the training steps ``SparkModel.fit`` takes with it: float32
``jax.numpy`` at ``highest``, the token-by-token gated delta rule (not
the chunked form), a materialised causal softmax, the routed part as a
plain sum over the held experts, next-token cross-entropy over the
vocabulary slice, SGD with momentum as keras applies it. Independent of
``elephas_tpu`` and keras: it makes its own weights from the seed.

The layers, from the published ``config.json`` (``H`` = hidden_size):

- norm: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``, ``w`` from zeros.
- decoder layer ``i``: ``x += mixer_i(norm(x)); x += moe(norm(x))``; the
  mixer is gated attention where ``(i + 1) % full_attention_interval ==
  0``, Gated DeltaNet elsewhere; a final norm and an untied head.
- gated attention: ``q_proj`` gives a head its query and a gate;
  per-head q/k norms; rotary embedding on the first
  ``partial_rotary_factor`` of each head; causal softmax attention,
  ``num_attention_heads / num_key_value_heads`` query heads a key/value
  head; ``o_proj(attn * sigmoid(gate))``.
- Gated DeltaNet: ``in_proj_qkvz`` and ``in_proj_ba`` grouped by key
  head; a causal depthwise convolution and SiLU over q, k, v; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; q and k
  L2-normalised, q scaled by ``Dk^-0.5``; per value head, from a zero
  state, ``S = exp(g_t) S; d = beta_t (v_t - S^T k_t); S += k_t d^T;
  o_t = S^T q_t``; a gated per-head norm ``w * norm(o) * silu(z)``;
  ``out_proj``.
- sparse block: softmax over all ``num_experts`` router outputs, the
  ``num_experts_per_tok`` largest renormalised, the chosen experts'
  SwiGLU outputs weighted and summed, plus ``sigmoid(x w_s) *
  shared(x)``.

Departures from the published model, each also in the configuration
file: (1) no multi-token-prediction module (the config has no key for
it); (2) no router auxiliary loss (no coefficient published); (3) the
vocabulary is the slice ``[0, vocab_size)`` that this chip holds: ids,
logits and loss are over the slice; (4) of the experts only
``num_experts_held`` (from ``experts_held_first``) are here: the router
still scores all of them, and what the absent ones would add to a
token is left out, so the block's result is this chip's partial sum.

So that it fits one chip beside nothing else: each layer, each
attention head, each expert's part and each block of
``scan_block`` tokens of the recurrence is rematerialised in the
backward pass (``jax.checkpoint``). That changes what is stored, not
what is computed.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64


def _is_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def param_shapes(cfg: dict) -> dict:
    """``{variable path: (shape, kind)}``; ``kind`` is ``normal``
    (``initializer_range``), ``zeros``, ``ones``, ``conv`` (uniform in
    ``+-kernel^-0.5``) or ``a_log`` (log of uniform in [1, 16))."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    held, inter = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    shared = cfg["shared_expert_intermediate_size"]
    shapes = {"embed_tokens/embeddings": ((v, h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        shapes[f"layer{i}_input_norm/weight"] = ((h,), "zeros")
        if _is_attention(cfg, i):
            p = f"layer{i}_attn/"
            shapes[p + "q_proj"] = ((h, heads * 2 * hd), "normal")
            shapes[p + "k_proj"] = ((h, kv * hd), "normal")
            shapes[p + "v_proj"] = ((h, kv * hd), "normal")
            shapes[p + "o_proj"] = ((heads * hd, h), "normal")
            shapes[p + "q_norm"] = ((hd,), "zeros")
            shapes[p + "k_norm"] = ((hd,), "zeros")
        else:
            p = f"layer{i}_gdn/"
            shapes[p + "in_proj_qkvz"] = (
                (h, 2 * key_dim + 2 * value_dim), "normal")
            shapes[p + "in_proj_ba"] = ((h, 2 * hv), "normal")
            shapes[p + "conv"] = (
                (cfg["linear_conv_kernel_dim"], 2 * key_dim + value_dim),
                "conv")
            shapes[p + "dt_bias"] = ((hv,), "ones")
            shapes[p + "A_log"] = ((hv,), "a_log")
            shapes[p + "norm"] = ((dv,), "ones")
            shapes[p + "out_proj"] = ((value_dim, h), "normal")
        shapes[f"layer{i}_post_norm/weight"] = ((h,), "zeros")
        p = f"layer{i}_moe/"
        shapes[p + "router"] = ((h, cfg["num_experts"]), "normal")
        shapes[p + "experts_gate_up"] = ((held, h, 2 * inter), "normal")
        shapes[p + "experts_down"] = ((held, inter, h), "normal")
        shapes[p + "shared_gate"] = ((h, 1), "normal")
        shapes[p + "shared_expert/gate_up"] = ((h, 2 * shared), "normal")
        shapes[p + "shared_expert/down"] = ((shared, h), "normal")
    shapes["final_norm/weight"] = ((h,), "zeros")
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
            k = jax.random.fold_in(key, i)
            if kind == "normal":
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif kind == "conv":
                bound = shape[0] ** -0.5
                out[name] = jax.random.uniform(
                    k, shape, jnp.float32, -bound, bound)
            elif kind == "a_log":
                out[name] = jnp.log(
                    jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
            else:
                out[name] = jnp.full(shape, float(kind == "ones"), jnp.float32)
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


def _rope(x, theta):
    """Rotary embedding over the whole last axis of ``x [B, S, heads,
    R]`` (half-split convention)."""
    s, r = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(p, pre, x, cfg, cast, mm):
    b, s, _ = x.shape
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    rot = int(hd * cfg["partial_rotary_factor"])
    eps = cfg["rms_norm_eps"]
    q, gate = jnp.split(
        mm(x, p[pre + "q_proj"]).reshape(b, s, heads, 2 * hd), 2, axis=-1)
    k = mm(x, p[pre + "k_proj"]).reshape(b, s, kv, hd)
    v = mm(x, p[pre + "v_proj"]).reshape(b, s, kv, hd)
    q = _rms(q, eps) * (1.0 + p[pre + "q_norm"])
    k = _rms(k, eps) * (1.0 + p[pre + "k_norm"])

    def rotate(t):
        return cast(jnp.concatenate(
            [_rope(t[..., :rot], cfg["rope_theta"]), t[..., rot:]], axis=-1))

    q, k = rotate(q), rotate(k)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = heads // kv

    @jax.checkpoint
    def one_head(args):
        q_h, k_h, v_h = args  # [B, S, hd]
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h, precision=HI)
        scores = jnp.where(causal, scores * hd ** -0.5, -jnp.inf)
        return jnp.einsum(
            "bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), v_h,
            precision=HI)

    by_head = lambda t: jnp.moveaxis(t, 2, 0)  # noqa: E731
    out = jax.lax.map(one_head, (
        by_head(q), by_head(jnp.repeat(k, group, axis=2)),
        by_head(jnp.repeat(v, group, axis=2)),
    ))  # [heads, B, S, hd]
    out = cast(jnp.moveaxis(out, 0, 2) * jax.nn.sigmoid(gate))
    return mm(out.reshape(b, s, heads * hd), p[pre + "o_proj"])


def _delta_rule(q, k, v, g, beta):
    """The recurrence, one token a step: ``q, k [B, S, Hv, Dk]``, ``v
    [B, S, Hv, Dv]``, ``g, beta [B, S, Hv]``; the time scan runs in
    blocks of ``SCAN_BLOCK`` tokens, each rematerialised."""
    b, s, hv, dk = q.shape
    dv = v.shape[-1]
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI)
        d = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * d[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    def blocks(t):  # [B, S, ...] -> [S / block, block, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((s // block, block) + t.shape[1:])

    state = jnp.zeros((b, hv, dk, dv), jnp.float32)
    _state, out = jax.lax.scan(
        tokens, state, tuple(blocks(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out.reshape((s,) + out.shape[2:]), 0, 1)


def _gated_delta_net(p, pre, x, cfg, cast, mm):
    b, s, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per = hv // hk
    qkvz = mm(x, p[pre + "in_proj_qkvz"]).reshape(
        b, s, hk, 2 * dk + 2 * per * dv)
    q, k, v, z = jnp.split(qkvz, (dk, 2 * dk, 2 * dk + per * dv), axis=-1)
    ba = mm(x, p[pre + "in_proj_ba"]).reshape(b, s, hk, 2 * per)
    b_in, a = (t.reshape(b, s, hv) for t in jnp.split(ba, 2, axis=-1))
    mixed = jnp.concatenate([
        q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
        v.reshape(b, s, hv * dv)], axis=-1)
    width = cfg["linear_conv_kernel_dim"]
    padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
    mixed = sum(padded[:, j:j + s] * p[pre + "conv"][j] for j in range(width))
    mixed = cast(jax.nn.silu(mixed))
    q, k, v = jnp.split(mixed, (hk * dk, 2 * hk * dk), axis=-1)

    def unit(t):
        t = t.reshape(b, s, hk, dk)
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q, k = cast(unit(q) * dk ** -0.5), cast(unit(k))
    beta = jax.nn.sigmoid(b_in)
    g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(a + p[pre + "dt_bias"])
    out = _delta_rule(
        jnp.repeat(q, per, axis=2), jnp.repeat(k, per, axis=2),
        v.reshape(b, s, hv, dv), g, beta,
    )
    out = cast(_rms(out, cfg["rms_norm_eps"]) * p[pre + "norm"]
               * jax.nn.silu(z.reshape(b, s, hv, dv)))
    return mm(out.reshape(b, s, hv * dv), p[pre + "out_proj"])


def _sparse_block(p, pre, x, cfg, cast, mm):
    b, s, h = x.shape
    flat = x.reshape(b * s, h)
    first, held = cfg["experts_held_first"], cfg["num_experts_held"]
    probs = jax.nn.softmax(
        jnp.matmul(flat, p[pre + "router"], precision=HI), axis=-1)
    weights, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def swiglu(t, gate_up, down):
        gate, up = jnp.split(mm(t, gate_up), 2, axis=-1)
        return mm(cast(jax.nn.silu(gate) * up), down)

    @jax.checkpoint
    def one_expert(e, gate_up, down):
        # this expert's weight a token: its renormalised probability
        # where the token chose it, zero elsewhere
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return weight[:, None] * swiglu(flat, gate_up, down)

    def add_expert(total, xs):
        return total + one_expert(*xs), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(flat),
        (jnp.arange(held), p[pre + "experts_gate_up"],
         p[pre + "experts_down"]),
    )
    shared = swiglu(flat, p[pre + "shared_expert/gate_up"],
                    p[pre + "shared_expert/down"])
    shared = shared * jax.nn.sigmoid(mm(flat, p[pre + "shared_gate"]))
    return cast(cast(routed) + shared).reshape(b, s, h)


def forward(params: dict, tokens, cfg: dict, lower: bool = False):
    """Float32 logits ``[B, S, vocab_size]`` for ``tokens [B, S]``.
    ``lower`` is the control: wherever the configuration's
    ``mixed_bfloat16`` holds a tensor in bfloat16 (the residual stream,
    the operands and result of every projection, q, k, v and each
    mixer's and block's result, and their gradients on the way back)
    the control holds it in fp8, each tensor under its own scale. The
    variables, the norms' statistics, the router, the decays and the
    recurrent state, the attention's softmax, the logits and the loss
    stay float32 in both."""
    cast = _fp8 if lower else (lambda t: t)
    eps = cfg["rms_norm_eps"]

    def mm(a, w):
        return cast(jnp.matmul(cast(a), cast(w), precision=HI))

    def norm(x, w):
        return cast(_rms(x, eps) * (1.0 + w))

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def layer(p, x, i):
        h = norm(x, p[f"layer{i}_input_norm/weight"])
        if _is_attention(cfg, i):
            h = _attention(p, f"layer{i}_attn/", h, cfg, cast, mm)
        else:
            h = _gated_delta_net(p, f"layer{i}_gdn/", h, cfg, cast, mm)
        x = cast(x + h)
        h = norm(x, p[f"layer{i}_post_norm/weight"])
        return cast(x + _sparse_block(p, f"layer{i}_moe/", h, cfg, cast, mm))

    x = cast(cast(params["embed_tokens/embeddings"])[tokens])
    for i in range(cfg["num_hidden_layers"]):
        mine = {k: v for k, v in params.items() if k.startswith(f"layer{i}_")}
        x = layer(mine, x, i)
    x = norm(x, params["final_norm/weight"])
    return jnp.matmul(x, params["lm_head/kernel"], precision=HI)


def loss_fn(params: dict, tokens, targets, cfg: dict, lower: bool):
    logits = forward(params, tokens, cfg, lower)
    picked = jnp.take_along_axis(
        logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


_CFGS: dict = {}
SIZES = (
    "hidden_size", "vocab_size", "num_hidden_layers",
    "full_attention_interval", "num_attention_heads", "num_key_value_heads",
    "head_dim", "partial_rotary_factor", "rope_theta", "rms_norm_eps",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts",
    "num_experts_per_tok", "num_experts_held", "experts_held_first",
    "moe_intermediate_size", "shared_expert_intermediate_size",
)


def _cfg_key(cfg: dict) -> str:
    """The sizes the compiled step depends on (the optimizer's rates
    are arguments, so that one compiled step serves them all)."""
    key = json.dumps({k: cfg[k] for k in SIZES}, sort_keys=True)
    _CFGS[key] = cfg
    return key


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"),
                   donate_argnums=(0, 1))
def _step(params, velocity, tokens, targets, lr, mom, cfg_key, lower):
    cfg = _CFGS[cfg_key]
    # the step's sequences one after the other, their gradients added:
    # the mean over the batch's tokens, as one batched pass would give
    rows = tokens.shape[0]
    loss, grads = 0.0, None
    for r in range(rows):
        one, g = jax.value_and_grad(loss_fn)(
            params, tokens[r:r + 1], targets[r:r + 1], cfg, lower)
        loss = loss + one / rows
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    # keras SGD: m = momentum * m - lr * g ; w = w + m
    velocity = {k: mom * velocity[k] - (lr / rows) * grads[k] for k in params}
    return {k: params[k] + velocity[k] for k in params}, velocity, loss


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def follow(cfg: dict, seed: int, batches, lower: bool = False) -> dict:
    """Takes the training steps ``batches`` yields (``(tokens,
    targets)`` a step) from the seeded weights. Returns each step's
    loss and, by variable path, the norm of the optimizer's velocity
    and of the parameters' change after the last step."""
    key = _cfg_key(cfg)
    params = init_params(cfg, seed)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses = []
    for tokens, targets in batches:
        params, velocity, loss = _step(
            params, velocity, jnp.asarray(tokens), jnp.asarray(targets),
            jnp.float32(cfg["optimizer"]["learning_rate"]),
            jnp.float32(cfg["optimizer"]["momentum"]), key, lower,
        )
        losses.append(float(loss))
    velocity_norm = leaf_norms(velocity)
    del velocity
    # the seeded weights again, not a copy kept through the steps: 2.5
    # GB that the step program needs
    start = init_params(cfg, seed)
    change_norm = leaf_norms({k: params[k] - start[k] for k in start})
    return {"losses": losses, "velocity_norm": velocity_norm,
            "change_norm": change_norm}
