"""Plain reference of the bottleneck ResNet that ``elephas_tpu.models.
resnet`` builds, and of the training steps ``SparkModel.fit`` takes
with it: float32 ``jax.numpy`` and ``lax`` convolutions at ``highest``,
BatchNorm on the batch's own statistics, softmax cross-entropy, SGD with
momentum as keras applies it. Independent of ``elephas_tpu`` and keras:
it makes its own weights from the seed.

Each block is rematerialised in the backward pass (``jax.checkpoint``)
so that a batch of 256 at 224x224 in float32 fits one chip beside
nothing else; that changes what is stored, not what is computed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DN = ("NHWC", "HWIO", "NHWC")


def _blocks(cfg: dict):
    """``(name, filters, stride, has_shortcut_conv)`` of every block."""
    width, channels = cfg["width"], cfg["width"]
    for stage, count in enumerate(cfg["depths"]):
        filters = width * 2 ** stage
        for b in range(count):
            stride = 2 if (stage > 0 and b == 0) else 1
            yield (f"s{stage}_b{b}", filters, stride,
                   stride != 1 or channels != filters * 4, channels)
            channels = filters * 4


def param_shapes(cfg: dict) -> dict:
    """``{variable path: (shape, kind)}``; ``kind`` is ``glorot``,
    ``ones`` or ``zeros``. Moving statistics included."""
    shapes = {}

    def conv(name, k, cin, cout):
        shapes[name + "/kernel"] = ((k, k, cin, cout), "glorot")

    def bn(name, c):
        shapes[name + "/gamma"] = ((c,), "ones")
        shapes[name + "/beta"] = ((c,), "zeros")
        shapes[name + "/moving_mean"] = ((c,), "zeros")
        shapes[name + "/moving_variance"] = ((c,), "ones")

    conv("stem_conv", 7, cfg["channels"], cfg["width"])
    bn("stem_bn", cfg["width"])
    channels = cfg["width"]
    for name, filters, _stride, shortcut, cin in _blocks(cfg):
        if shortcut:
            conv(name + "_sc_conv", 1, cin, filters * 4)
            bn(name + "_sc_bn", filters * 4)
        conv(name + "_c1", 1, cin, filters)
        bn(name + "_bn1", filters)
        conv(name + "_c2", 3, filters, filters)
        bn(name + "_bn2", filters)
        conv(name + "_c3", 1, filters, filters * 4)
        bn(name + "_bn3", filters * 4)
        channels = filters * 4
    shapes["head/kernel"] = ((channels, cfg["num_classes"]), "glorot")
    shapes["head/bias"] = ((cfg["num_classes"],), "zeros")
    return shapes


def is_trainable(path: str) -> bool:
    return not path.endswith(("/moving_mean", "/moving_variance"))


def init_params(cfg: dict, seed: int) -> dict:
    """Every variable from the seed, on the device, in one jitted call,
    in float32 (mixed_bfloat16 keeps its variables in float32)."""
    shapes = param_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            if kind == "glorot":
                receptive = int(np.prod(shape[:-2]))
                limit = np.sqrt(
                    6.0 / (receptive * shape[-2] + receptive * shape[-1])
                )
                out[name] = jax.random.uniform(
                    jax.random.fold_in(key, i), shape, jnp.float32,
                    -limit, limit,
                )
            else:
                out[name] = jnp.full(shape, float(kind == "ones"), jnp.float32)
        return out

    return make(jax.random.key(np.uint32(int(seed) % (1 << 32))))


def _through(t, dtype, largest):
    scale = largest / jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
    scaled = jnp.clip(t * scale, -largest, largest)  # never past the end
    return scaled.astype(dtype).astype(t.dtype) / scale


@jax.custom_vjp
def _fp8(t):
    """A tensor held in fp8: through e4m3 under one scale for the
    tensor on the way forward, and its gradient through e5m2 under one
    scale on the way back (the usual fp8 training recipe, not a bare
    cast, which flushes small gradients to zero)."""
    return _through(t, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(t):
    return _fp8(t), None


def _fp8_bwd(_res, g):
    return (_through(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _conv(x, kernel, stride, cast):
    return cast(jax.lax.conv_general_dilated(
        cast(x), cast(kernel), (stride, stride), "SAME",
        dimension_numbers=DN, precision=jax.lax.Precision.HIGHEST,
    ))


def _bn_train(x, p, name, eps, new_stats, momentum):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    new_stats[name + "/moving_mean"] = (
        p[name + "/moving_mean"] * momentum + mean * (1 - momentum)
    )
    new_stats[name + "/moving_variance"] = (
        p[name + "/moving_variance"] * momentum + var * (1 - momentum)
    )
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return y * p[name + "/gamma"] + p[name + "/beta"]


def forward_train(params: dict, images, cfg: dict, lower: bool = False):
    """Logits and the BatchNorm layers' new moving statistics for one
    batch in training mode. ``lower`` is the control: wherever the
    configuration's ``mixed_bfloat16`` holds a tensor in bfloat16 (the
    operands and the result of every convolution, every BatchNorm's
    result, every residual sum, the pooled features and the logits, and
    their gradients on the way back) the control holds it in fp8, each
    tensor under its own scale. Variables, BatchNorm's statistics and
    the softmax stay float32 in both."""
    eps = cfg["batch_norm"]["epsilon"]
    momentum = cfg["batch_norm"]["momentum"]
    cast = _fp8 if lower else (lambda t: t)
    stats: dict = {}

    def bn(x, name, local):
        return cast(_bn_train(x, params, name, eps, local, momentum))

    def stem(x):
        local: dict = {}
        x = _conv(x, params["stem_conv/kernel"], 2, cast)
        x = jax.nn.relu(bn(x, "stem_bn", local))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
        )
        return x, local

    def block(x, name, stride, shortcut):
        local: dict = {}
        sc = x
        if shortcut:
            sc = _conv(x, params[name + "_sc_conv/kernel"], stride, cast)
            sc = bn(sc, name + "_sc_bn", local)
        y = _conv(x, params[name + "_c1/kernel"], 1, cast)
        y = jax.nn.relu(bn(y, name + "_bn1", local))
        y = _conv(y, params[name + "_c2/kernel"], stride, cast)
        y = jax.nn.relu(bn(y, name + "_bn2", local))
        y = _conv(y, params[name + "_c3/kernel"], 1, cast)
        y = bn(y, name + "_bn3", local)
        return jax.nn.relu(cast(sc + y)), local

    x, local = jax.checkpoint(stem)(images.astype(jnp.float32))
    stats.update(local)
    for name, _filters, stride, shortcut, _cin in _blocks(cfg):
        x, local = jax.checkpoint(
            functools.partial(block, name=name, stride=stride,
                              shortcut=shortcut)
        )(x)
        stats.update(local)
    x = cast(jnp.mean(x, axis=(1, 2)))
    logits = jnp.dot(x, cast(params["head/kernel"]),
                     precision=jax.lax.Precision.HIGHEST)
    return cast(logits + params["head/bias"]), stats


def loss_fn(trainable: dict, frozen: dict, images, labels, cfg, lower):
    logits, stats = forward_train({**trainable, **frozen}, images, cfg, lower)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1)
    return -jnp.mean(picked), stats


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"),
                   donate_argnums=(0, 1))
def _step(params, velocity, images, labels, lr, mom, cfg_key, lower):
    cfg = _cfg_from_key(cfg_key)
    trainable = {k: v for k, v in params.items() if is_trainable(k)}
    frozen = {k: v for k, v in params.items() if not is_trainable(k)}
    (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        trainable, frozen, images, labels, cfg, lower
    )
    # keras SGD: m = momentum * m - lr * g ; w = w + m
    velocity = {k: mom * velocity[k] - lr * grads[k] for k in trainable}
    new = {k: trainable[k] + velocity[k] for k in trainable}
    new.update(stats)
    return new, velocity, loss


_CFGS: dict = {}


def _cfg_key(cfg: dict) -> str:
    """The sizes the compiled step depends on (the optimizer's rates
    are arguments, so that one compiled step serves them all)."""
    import json

    key = json.dumps(
        {k: cfg[k] for k in ("image_size", "channels", "num_classes",
                             "depths", "width", "batch_norm")},
        sort_keys=True,
    )
    _CFGS[key] = cfg
    return key


def _cfg_from_key(key: str) -> dict:
    return _CFGS[key]


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def follow(cfg: dict, seed: int, batches, lower: bool = False) -> dict:
    """Takes the training steps ``batches`` yields (``(images, labels)``
    a step) from the seeded weights. Returns each step's loss and, by
    variable path, the norm of the optimizer's velocity and of the
    parameters' change after the last step."""
    key = _cfg_key(cfg)
    params = init_params(cfg, seed)
    start = {k: jnp.copy(v) for k, v in params.items() if is_trainable(k)}
    velocity = {k: jnp.zeros_like(v) for k, v in start.items()}
    losses = []
    for images, labels in batches:
        params, velocity, loss = _step(
            params, velocity, jnp.asarray(images), jnp.asarray(labels),
            jnp.float32(cfg["optimizer"]["learning_rate"]),
            jnp.float32(cfg["optimizer"]["momentum"]), key, lower,
        )
        losses.append(float(loss))
    change = {k: params[k] - start[k] for k in start}
    return {
        "losses": losses,
        "velocity_norm": leaf_norms(velocity),
        "change_norm": leaf_norms(change),
        "moving_norm": leaf_norms(
            {k: v for k, v in params.items() if not is_trainable(k)}
        ),
    }
