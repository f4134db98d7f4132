"""Plain reference of one chip's share of ``granite-4.0-h-micro``
(``model_type: granitemoehybrid``, no experts) and of the training
steps ``SparkModel.fit`` takes with it: float32 ``jax.numpy`` at
``highest``, the state-space scan as a materialised masked quadratic
form, a materialised causal softmax a query block, next-token
cross-entropy over the vocabulary slice, SGD with momentum as keras
applies it. Independent of ``elephas_tpu`` and keras: it makes its own
weights from the seed.

The layers, from the published ``config.json``. With ``e`` =
``embedding_multiplier``, ``m`` = ``residual_multiplier``, ``a`` =
``attention_multiplier``, ``c`` = ``logits_scaling``, ``E`` the ``[V,
hidden]`` embedding, which is the head too (``tie_word_embeddings``),
and ``norm`` = ``w * x * rsqrt(mean(x^2) + rms_norm_eps)``::

    h = e * E[ids]
    for layer i:  h = h + m * mixer_i(norm(h));  h = h + m * swiglu(norm(h))
    logits = (norm(h) @ E^T) / c

``swiglu(u) = (silu(u W_gate) * (u W_up)) W_down`` at
``shared_intermediate_size``. ``mixer_i`` by ``layer_types[i]``, with
``u`` the normed input:

- ``mamba`` (``H = mamba_n_heads``, ``P = mamba_d_head``, ``N =
  mamba_d_state``, ``G = mamba_n_groups``): ``[z | xBC | dt] = u W_in``
  with widths ``H P``, ``H P + 2 G N`` and ``H``; ``xBC = silu(conv(xBC)
  + b_conv)``, a causal depthwise convolution of ``mamba_d_conv`` taps;
  split into ``x [S, H, P]``, ``B [S, G, N]``, ``C [S, G, N]``, head
  ``h`` reading group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)`` a
  head, with no upper clamp; ``A = -exp(A_log)`` a head. The state ``S_t
  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` starts at zero and ``y_t = S_t
  C_t + D x_t``, which is computed here as what it sums to::

      y_i = sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j + D x_i

  with ``a`` the running sum of ``dt A``: ``SCAN_HEADS`` heads and a
  block of ``SCAN_ROWS`` queries at a time against all the keys, the
  running sums taken forwards and backwards from the block's first query
  so that float32 holds their differences. Then ``y = w_norm *
  GroupRMSNorm(y * silu(z))``, the gate first and the norm over each
  group of ``H P / G`` channels, and ``y W_out``. No projection bias.
- ``attention``: grouped-query causal attention, no bias, no position
  term (``position_embedding_type: nope``), query head ``n`` reading
  key/value head ``n // (heads / kv_heads)``: ``softmax(a q k^T +
  causal) v``, then ``o_proj``. The scale is ``a`` itself, not ``a``
  on top of ``head_dim^-0.5``.

Departures from the published model, each also in the configuration
file: (1) the vocabulary is the slice ``[0, vocab_size)`` that this
chip holds: ids, logits and loss are over the slice; (2) the layers are
the first ``num_hidden_layers`` of ``layer_types``; (3) the initialisers
and the optimizer are assumed. Four other readings of the model, which
the program does not compute, can be asked for as faults
(``scripts/prove_reference_faults.py``), by a key of ``assumed`` each:
``attention_scale`` ``"multiplier_over_sqrt_head_dim"`` (``a /
sqrt(head_dim)``), ``logits`` ``"multiplied"`` (logits times ``c``),
``mamba_norm`` ``"norm_then_gate"`` (norms ``y`` before the gate
multiplies it) and ``residual`` ``"on_stream"`` (``h = m * h +
f(norm(h))``).

So that it fits one chip beside nothing else: attention a head and the
scan ``SCAN_HEADS`` heads are taken a block of queries at a time, each
block rematerialised in the backward pass (``jax.checkpoint``), a
step's sequences go one after the other, and a sequence's gradient is
taken a layer at a time (``jax.vjp`` of each layer from its kept
input), each layer's straight into the velocity. The one leaf of
embedding and head takes the head's gradient first and the embedding's
on top: the sum of both paths. That changes what is stored, not what is
computed.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# queries a block of materialised scores: a head's 2048 x 8192 float32
# is 67 MB; 8 heads' scan factors at 1024 x 8192 are 268 MB
ATTN_ROWS = 2048
SCAN_ROWS = 1024
SCAN_HEADS = 8
KINDS = {"mamba": "mamba", "attention": "attn"}
TABLE = "embed_tokens/embeddings"
# the readings of the row that the model is (``assumed``), and the
# fault that stands against each
READINGS = {
    "attention_scale": ("multiplier", "multiplier_over_sqrt_head_dim"),
    "logits": ("divided", "multiplied"),
    "mamba_norm": ("gate_then_norm", "norm_then_gate"),
    "residual": ("on_sublayer", "on_stream"),
}


def reading(cfg: dict, key: str) -> bool:
    """True where ``assumed[key]`` is the model's reading (or absent),
    False where it is the fault's."""
    sound, fault = READINGS[key]
    got = cfg["assumed"].get(key, sound)
    if got not in (sound, fault):
        raise ValueError(f"assumed.{key} {got!r}")
    return got == sound


def layer_kinds(cfg: dict) -> tuple:
    """The layers that are here: the first ``num_hidden_layers``
    entries of the published ``layer_types``."""
    got = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
    if len(got) < cfg["num_hidden_layers"] or set(got) - set(KINDS):
        raise ValueError(f"layer_types {got!r}")
    return got


def param_shapes(cfg: dict) -> dict:
    """``{variable path: (shape, kind)}``; ``kind`` names the
    initialiser (:func:`init_params`). Embedding and head are ONE
    leaf."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    inner = h * p
    conv_dim = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    width = cfg["shared_intermediate_size"]
    shapes = {TABLE: ((v, d), "normal")}
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes[f"layer{i}_input_norm/weight"] = ((d,), "ones")
        pre = f"layer{i}_{KINDS[kind]}/"
        if kind == "mamba":
            shapes[pre + "in_proj"] = ((d, inner + conv_dim + h), "normal")
            shapes[pre + "conv"] = ((cfg["mamba_d_conv"], conv_dim), "conv")
            shapes[pre + "conv_bias"] = ((conv_dim,), "conv")
            shapes[pre + "dt_bias"] = ((h,), "dt_bias")
            shapes[pre + "A_log"] = ((h,), "a_log")
            shapes[pre + "D"] = ((h,), "ones")
            shapes[pre + "norm"] = ((inner,), "ones")
            shapes[pre + "out_proj"] = ((inner, d), "normal")
        else:
            shapes[pre + "q_proj"] = ((d, heads * hd), "normal")
            shapes[pre + "k_proj"] = ((d, kv * hd), "normal")
            shapes[pre + "v_proj"] = ((d, kv * hd), "normal")
            shapes[pre + "o_proj"] = ((heads * hd, d), "normal")
        shapes[f"layer{i}_post_norm/weight"] = ((d,), "ones")
        shapes[f"layer{i}_mlp/gate_up"] = ((d, 2 * width), "normal")
        shapes[f"layer{i}_mlp/down"] = ((width, d), "normal")
    shapes["final_norm/weight"] = ((d,), "ones")
    return shapes


def init_params(cfg: dict, seed: int) -> dict:
    """Every variable from the seed, on the device, in one jitted call,
    in float32 (mixed_bfloat16 keeps its variables in float32). By
    kind (``assumed`` in the configuration file says why): ``normal``
    at ``initializer_range``; ``conv`` uniform within
    ``mamba_d_conv^-0.5``; ``a_log`` ``log(1..H)``; ``dt_bias`` the
    inverse softplus of a log-uniform step in ``[time_step_min,
    time_step_max]`` floored at ``time_step_floor``."""
    shapes = param_shapes(cfg)
    assumed = cfg["assumed"]
    std = assumed["initializer_range"]
    lo, hi = np.log(assumed["time_step_min"]), np.log(assumed["time_step_max"])
    bound = cfg["mamba_d_conv"] ** -0.5

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
                key, shape, f32, lo, hi)), assumed["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        return std * jax.random.normal(key, shape, f32)

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
    ``SCAN_HEADS`` heads of a group (its ``C B^T`` is the same for all
    its heads) and a block of ``SCAN_ROWS`` queries at a time."""
    b, s, h, p = x.shape
    g = b_in.shape[2]
    per = min(h // g, SCAN_HEADS)  # heads a pass
    passes = h // per
    if h % g or (h // g) % per:
        raise ValueError(f"{h} heads over {g} groups, {per} heads a pass")
    rows = min(s, SCAN_ROWS)
    if s % rows:
        raise ValueError(f"{s} positions are no whole blocks of {rows}")
    at = jnp.arange(s)
    log_decay = dt * a_neg  # [B, S, H], <= 0
    # a pass's B and C: those of the group its heads lie in
    of_pass = jnp.arange(passes) // (passes // g)

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

    def one_pass(args):
        group, x_g, dt_g, da_g = args
        c_g = jnp.take(c_in, group, axis=2)  # [B, S, N]
        b_g = jnp.take(b_in, group, axis=2)
        blocks = jnp.moveaxis(
            c_g.reshape(b, s // rows, rows, c_g.shape[-1]), 1, 0)
        out = jax.lax.map(
            lambda a: one_block(a[0], b_g, x_g, dt_g, da_g, a[1]),
            (blocks, jnp.arange(s // rows) * rows))
        return jnp.moveaxis(out, 0, 1).reshape(b, s, per, p)

    by_pass = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape(t.shape[:2] + (passes, per) + t.shape[3:]), 2, 0)
    y = jax.lax.map(one_pass, (
        of_pass, by_pass(x), by_pass(dt), by_pass(log_decay)))
    y = jnp.moveaxis(y, 0, 2).reshape(b, s, h, p)
    return y + d_skip[:, None] * x


def _mamba(p, pre, u, cfg, cast, mm):
    b, s, _ = u.shape
    h, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner, taps = h * hp, cfg["mamba_d_conv"]
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
        t.reshape(b, s, g, inner // g), cfg["rms_norm_eps"]
    ).reshape(b, s, inner)
    if reading(cfg, "mamba_norm"):
        y = grouped(y * jax.nn.silu(z))
    else:  # the fault, not the model
        y = grouped(y) * jax.nn.silu(z)
    return mm(cast(y * p[pre + "norm"]), p[pre + "out_proj"])


def _attention(p, pre, x, cfg, cast, mm):
    b, s, _ = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    q = mm(x, p[pre + "q_proj"]).reshape(b, s, heads, hd)
    k = mm(x, p[pre + "k_proj"]).reshape(b, s, kv, hd)
    v = mm(x, p[pre + "v_proj"]).reshape(b, s, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    rows = min(s, ATTN_ROWS)
    if s % rows:
        raise ValueError(f"{s} positions are no whole blocks of {rows}")
    keys = jnp.arange(s)[None, :]
    scale = cfg["attention_multiplier"]
    if not reading(cfg, "attention_scale"):  # the fault, not the model
        scale = scale * hd ** -0.5

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


def _swiglu(p, pre, u, cast, mm):
    gate, up = jnp.split(mm(u, p[pre + "gate_up"]), 2, axis=-1)
    return mm(cast(jax.nn.silu(gate) * up), p[pre + "down"])


def _casts(cfg: dict, lower: bool):
    """``(cast, mm, norm)``: what ``lower`` (the control) holds in fp8
    where the configuration's ``mixed_bfloat16`` holds bfloat16: the
    residual stream, the operands and result of every projection, the
    convolved channels, the scan's masked scores and its result, the
    gated and normed result, q, k, v, the attention's result, the
    feed-forward's hidden and each sub-layer's result, and their
    gradients on the way back, each tensor under its own scale. The
    variables, the steps ``dt``, the decays, the norms' statistics, the
    attention's softmax, the logits and the loss stay float32 in both."""
    cast = _fp8 if lower else (lambda t: t)

    def mm(a, w):
        return cast(jnp.matmul(cast(a), cast(w), precision=HI))

    def norm(x, w):
        return cast(_rms(x, cfg["rms_norm_eps"]) * w)

    return cast, mm, norm


def _embed(p, tokens, cfg, lower):
    cast, _mm, _norm = _casts(cfg, lower)
    return cast(cast(cast(p[TABLE])[tokens]) * cfg["embedding_multiplier"])


def _layer(p, x, cfg, kind: str, lower: bool):
    """One decoder layer: the mixer its ``kind`` names, then the dense
    feed-forward, each from its own pre-norm and added times the
    residual multiplier; ``p`` holds the layer's variables without the
    ``layer<i>_`` prefix."""
    cast, mm, norm = _casts(cfg, lower)
    m = cfg["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention

    def add(x, y):
        if reading(cfg, "residual"):
            return cast(x + cast(y * m))
        return cast(cast(x * m) + y)  # the fault, not the model

    x = add(x, mixer(p, KINDS[kind] + "/", norm(x, p["input_norm/weight"]),
                     cfg, cast, mm))
    return add(x, _swiglu(p, "mlp/", norm(x, p["post_norm/weight"]), cast, mm))


def _logits(p, x, cfg, lower):
    _cast, _mm, norm = _casts(cfg, lower)
    logits = jnp.matmul(
        norm(x, p["final_norm/weight"]), p[TABLE].T, precision=HI)
    c = cfg["logits_scaling"]
    return logits / c if reading(cfg, "logits") else logits * c


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
        x = _layer(_of_layer(params, i), x, cfg, kind, lower)
    return _logits(params, x, cfg, lower)


def loss_fn(params: dict, tokens, targets, cfg: dict, lower: bool = False):
    return _cross_entropy(forward(params, tokens, cfg, lower), targets)


# -- the steps, a layer at a time ---------------------------------------------
#
# One sequence's gradient is the chain of the pieces' own (``jax.vjp`` of
# the embedding, of each layer and of the head with the loss), taken one
# piece a compiled call, each piece's gradient going straight into its
# share of the velocity: the same numbers as ``jax.grad(loss_fn)``, with
# one layer's gradient and activations alive at a time and not the
# model's. The table is in two pieces, the head and the embedding: its
# velocity takes the head's gradient first (under the step's momentum)
# and the embedding's on top (kept whole).

_CFGS: dict = {}
SIZES = (
    "hidden_size", "vocab_size", "num_hidden_layers", "layer_types",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "num_attention_heads", "num_key_value_heads", "head_dim",
    "shared_intermediate_size", "rms_norm_eps", "attention_multiplier",
    "embedding_multiplier", "residual_multiplier", "logits_scaling",
)
_STATIC = ("cfg_key", "kind", "lower")


def _cfg_key(cfg: dict) -> str:
    """The sizes and readings the compiled pieces depend on (the
    optimizer's rates are arguments, so that one compiled piece serves
    them all)."""
    key = json.dumps(
        {**{k: cfg[k] for k in SIZES},
         **{k: cfg["assumed"].get(k, v[0]) for k, v in READINGS.items()}},
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
def _layer_backward(p, velocity, x, d_out, keep, rate, cfg_key, kind, lower):
    _out, vjp = jax.vjp(
        lambda t, x: _layer(t, x, _CFGS[cfg_key], kind, lower), p, x)
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
def _embed_backward(p, velocity, tokens, d_out, rate, cfg_key, lower):
    _out, vjp = jax.vjp(
        lambda p: _embed(p, tokens, _CFGS[cfg_key], lower), p)
    # on top of what the head's path has left there
    return _into(velocity, vjp(d_out)[0], 1.0, rate)


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply(params, velocity):
    return {k: params[k] + velocity[k] for k in params}


HEAD = ("final_norm/weight", TABLE)
EMBED = (TABLE,)


def _sequence_into_velocity(params, velocity, tokens, targets, keep, rate,
                            key, lower):
    """``keep * velocity - rate * gradient`` of one sequence's loss,
    and that loss; ``velocity`` is updated in place, piece by piece."""
    only = lambda tree, names: {k: tree[k] for k in names}  # noqa: E731
    how = dict(cfg_key=key, lower=lower)
    kinds = layer_kinds(_CFGS[key])
    inputs = [_embed_forward(only(params, EMBED), tokens, **how)]
    for i, kind in enumerate(kinds):
        inputs.append(_layer_forward(
            _of_layer(params, i), inputs[-1], kind=kind, **how))
    mine, d_x, loss = _head_backward(
        only(params, HEAD), only(velocity, HEAD), inputs.pop(), targets,
        keep, rate, **how)
    velocity.update(mine)
    for i in reversed(range(len(kinds))):
        mine, d_x = _layer_backward(
            _of_layer(params, i), _of_layer(velocity, i), inputs.pop(), d_x,
            keep, rate, kind=kinds[i], **how)
        velocity.update({f"layer{i}_{k}": v for k, v in mine.items()})
    velocity.update(_embed_backward(
        only(params, EMBED), only(velocity, EMBED), tokens, d_x, rate,
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
    variable, the norm of the optimizer's velocity and of the
    parameters' change after the last step."""
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
