"""Turns a SmallThinker-style configuration file (window and full
attention mixed by ``sliding_window_layout``, rotation by
``rope_layout``, ReGLU experts with no shared expert behind a softmax
router that reads the layer's input) into the compiled Keras model that
``SparkModel`` takes (``elephas_tpu.models.smallthinker_lm``), with the
benchmark's seeded weights in it, and counts from the file's shapes
what the model and its grouped expert products must compute and move."""

from __future__ import annotations

import numpy as np

# a program without this model cannot run the configuration: the run
# then ends here, as the builder is loaded, before any weight is made
from elephas_tpu.models import smallthinker  # noqa: F401

# what the model counts for itself (a sparse block's routed token
# slots): no weight of the reference's, zeroed with every new seed
COUNTERS = "/route_counts"


def build(cfg: dict, params: dict):
    import jax

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"this builder compiles SGD, not {opt['name']!r}")
    if cfg["assumed"]["router_input"] != "layer_input":
        raise ValueError(
            "smallthinker_lm's router reads the layer's input, not "
            f"{cfg['assumed']['router_input']!r}"
        )
    if not (cfg["moe_primary_router_apply_softmax"]
            and cfg["norm_topk_prob"]) or cfg["rope_scaling"] is not None:
        raise ValueError(
            "smallthinker_lm builds the softmax-over-the-chosen router "
            "and plain rotary frequencies"
        )
    # built on the host: keras would otherwise draw 2.6 GB of initial
    # weights and as many zero momenta on the chip, only for assign()
    # and fit's stage-in to replace them; the chip's peak would count
    # them (the runner moves the state to the chip itself)
    with jax.default_device(jax.devices("cpu")[0]):
        model = _build(cfg, opt)
    assign(model, params)
    return model


def _build(cfg, opt):
    from elephas_tpu.models import smallthinker_lm

    first, n = cfg["experts_held_first"], cfg["num_hidden_layers"]
    return smallthinker_lm(
        vocab_size=cfg["vocab_size"], maxlen=cfg["sequence_length"],
        hidden_size=cfg["hidden_size"], num_hidden_layers=n,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        sliding_window_size=cfg["sliding_window_size"],
        # the published layouts, of which the layers here are the first
        sliding_window_layout=tuple(cfg["sliding_window_layout"][:n]),
        rope_layout=tuple(cfg["rope_layout"][:n]),
        rope_theta=cfg["rope_theta"],
        moe_num_primary_experts=cfg["moe_num_primary_experts"],
        moe_num_active_primary_experts=cfg[
            "moe_num_active_primary_experts"],
        moe_ffn_hidden_size=cfg["moe_ffn_hidden_size"],
        experts_held=(first, first + cfg["num_experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        init_std=cfg["assumed"]["initializer_range"],
        lr=opt["learning_rate"], momentum=opt["momentum"],
        dtype_policy=None if cfg["dtype"] == "float32" else cfg["dtype"],
        remat=bool(cfg["remat"]), seed=0,
    )


def assign(model, params: dict) -> None:
    """The reference's weights into the model by variable path, after
    checking that the two agree on what the weights are; the model's
    own counters start from zero."""
    weights = {v.path: v for v in model.variables
               if not v.path.endswith(COUNTERS)}
    if set(weights) != set(params):
        raise ValueError(
            f"the model's variables and the reference's differ: "
            f"{sorted(set(weights) ^ set(params))[:8]}"
        )
    for path, var in weights.items():
        if tuple(var.shape) != tuple(params[path].shape):
            raise ValueError(
                f"{path}: model {var.shape}, reference {params[path].shape}"
            )
        var.assign(params[path])
    for var in model.variables:
        if var.path.endswith(COUNTERS):
            var.assign(np.zeros(var.shape, var.dtype))


# -- what the shapes call for ---------------------------------------------


def window_layers(cfg: dict) -> int:
    return sum(cfg["sliding_window_layout"][:cfg["num_hidden_layers"]])


def visible_keys(sequence_length: int, window: int | None = None) -> float:
    """Keys a query sees on average: ``(S + 1) / 2`` under the causal
    mask, and under a band the first ``window`` queries' growing share
    and ``window`` for the rest."""
    s = sequence_length
    w = s if window is None else min(window, s)
    return (w * (w + 1) / 2 + (s - w) * w) / s


def forward_macs_per_token(cfg: dict, sequence_length: int) -> float:
    """Multiply-adds of one token's forward pass, from the shapes:
    every projection is ``in x out``; attention reads the keys its mask
    leaves (:func:`visible_keys`), scores and sum both ``head_dim``
    wide; the routed part at its expectation under uniform routing
    (``moe_num_active_primary_experts * num_experts_held /
    moe_num_primary_experts`` expert visits a token)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, inter = cfg["head_dim"], cfg["moe_ffn_hidden_size"]
    layers, windowed = cfg["num_hidden_layers"], window_layers(cfg)
    proj = 2 * h * heads * hd + 2 * h * kv * hd
    keys = (windowed * visible_keys(
        sequence_length, cfg["sliding_window_size"])
        + (layers - windowed) * visible_keys(sequence_length))
    visits = (cfg["moe_num_active_primary_experts"] * cfg["num_experts_held"]
              / cfg["moe_num_primary_experts"])
    moe = h * cfg["moe_num_primary_experts"] + visits * 3 * h * inter
    return layers * (proj + moe) + heads * 2 * hd * keys + h * v


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-add, the backward pass twice the forward's products;
    recomputation is not counted."""
    s = int(traffic["sequence_length"])
    return 3.0 * 2.0 * forward_macs_per_token(cfg, s) * s


def moe_experts_step_cost(cfg: dict, traffic: dict,
                          routed_slots_per_step: float) -> dict:
    """Operations and bytes of the grouped products over the held
    experts of all layers for one step, forward and backward, for the
    token slots that were really routed here (the layers' counters, not
    the expectation). Bytes: the held experts' weights in bfloat16 read
    forward and again backward, their gradients written in float32, and
    each routed row read and written at the hidden width on both sides
    of the two products, forward and backward."""
    h, inter = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    weights = cfg["num_experts_held"] * 3 * h * inter * cfg[
        "num_hidden_layers"]
    flops = 3.0 * 2.0 * 3 * h * inter * routed_slots_per_step
    rows = routed_slots_per_step * (2 * h + 3 * inter) * 2  # bf16, in and out
    return {"flops": flops,
            "bytes": float(weights * (2 + 2 + 4) + 3 * rows)}
