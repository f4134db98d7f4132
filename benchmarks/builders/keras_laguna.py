"""Turns a Laguna-style configuration file (window and full attention
mixed by ``layer_types``, a head count a layer, a per-head output gate,
a rotation a layer kind, a leading dense SwiGLU and then a sparse block
with a shared expert) into the compiled Keras model that ``SparkModel``
takes (``elephas_tpu.models.laguna_lm``), with the benchmark's seeded
weights in it, and counts from the file's shapes what the model and its
grouped expert products must compute and move."""

from __future__ import annotations

import numpy as np

# a program without this model cannot run the configuration: the run
# then ends here, as the builder is loaded, before any weight is made
from elephas_tpu.models import laguna  # noqa: F401

# what the model counts for itself (a sparse block's routed token
# slots): no weight of the reference's, zeroed with every new seed
COUNTERS = "/route_counts"
# the readings of ``assumed`` that ``laguna_lm`` builds; the reference
# takes the others as faults
BUILT = {"attention_gate": "sigmoid_of_layer_input_per_head",
         "scoring_func": laguna.SCORING_FUNC, "band": "sliding_window"}


def build(cfg: dict, params: dict):
    import jax

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"this builder compiles SGD, not {opt['name']!r}")
    for key, built in BUILT.items():
        if cfg["assumed"][key] != built:
            raise ValueError(
                f"laguna_lm builds assumed.{key} {built!r}, not "
                f"{cfg['assumed'][key]!r}")
    if not cfg["norm_topk_prob"] or cfg["moe_router_logit_softcapping"] or (
            cfg["moe_apply_router_weight_on_input"] or cfg["attention_bias"]):
        raise ValueError(
            "laguna_lm builds the router that normalises the chosen scores "
            "and weights the experts' results, without softcapping, and "
            "attention without bias")
    # built on the host: keras would otherwise draw 3.2 GB of initial
    # weights and as many zero momenta on the chip, only for assign()
    # and fit's stage-in to replace them; the chip's peak would count
    # them (the runner moves the state to the chip itself)
    with jax.default_device(jax.devices("cpu")[0]):
        model = _build(cfg, opt)
    assign(model, params)
    return model


def _build(cfg, opt):
    from elephas_tpu.models import laguna_lm

    first, n = cfg["experts_held_first"], cfg["num_hidden_layers"]
    return laguna_lm(
        vocab_size=cfg["vocab_size"], maxlen=cfg["sequence_length"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=n,
        # the published per-layer lists, of which the layers here are
        # the first
        num_attention_heads_per_layer=tuple(
            cfg["num_attention_heads_per_layer"][:n]),
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"][:n]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"][:n]),
        sliding_window=cfg["sliding_window"],
        rope_parameters=cfg["rope_parameters"], gating=cfg["gating"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        moe_routed_scaling_factor=cfg["moe_routed_scaling_factor"],
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


def visible_keys(sequence_length: int, window: int | None = None) -> float:
    """Keys a query sees on average: ``(S + 1) / 2`` under the causal
    mask, and under a band the first ``window`` queries' growing share
    and ``window`` for the rest."""
    s = sequence_length
    w = s if window is None else min(window, s)
    return (w * (w + 1) / 2 + (s - w) * w) / s


def forward_macs_per_token(cfg: dict, sequence_length: int) -> float:
    """Multiply-adds of one token's forward pass, from the shapes, a
    layer at a time: every projection is ``in x out`` (the gate's
    among them); attention reads the keys its layer's mask leaves
    (:func:`visible_keys`, the exact count), scores and sum both
    ``head_dim`` wide, over the layer's own heads; the routed part at
    its expectation under uniform routing (``num_experts_per_tok *
    num_experts_held / num_experts`` expert visits a token)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    n = cfg["num_hidden_layers"]
    visits = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
              / cfg["num_experts"])
    sparse = (h * cfg["num_experts"]
              + 3 * h * cfg["shared_expert_intermediate_size"]
              + visits * 3 * h * cfg["moe_intermediate_size"])
    total = h * v
    for kind, mlp, heads in zip(
            cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
            cfg["num_attention_heads_per_layer"][:n]):
        window = (cfg["sliding_window"] if kind == "sliding_attention"
                  else None)
        total += 2 * h * heads * hd + 2 * h * kv * hd + h * heads
        total += heads * 2 * hd * visible_keys(sequence_length, window)
        total += (3 * h * cfg["intermediate_size"] if mlp == "dense"
                  else sparse)
    return total


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-add, the backward pass twice the forward's products;
    recomputation is not counted."""
    s = int(traffic["sequence_length"])
    return 3.0 * 2.0 * forward_macs_per_token(cfg, s) * s


def moe_experts_step_cost(cfg: dict, traffic: dict,
                          routed_slots_per_step: float) -> dict:
    """Operations and bytes of the grouped products over the held
    experts of the sparse layers for one step, forward and backward,
    for the token slots that were really routed here (the layers'
    counters, not the expectation). Bytes: the held experts' weights in
    bfloat16 read forward and again backward, their gradients written
    in float32, and each routed row read and written at the hidden
    width on both sides of the two products, forward and backward."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sparse_layers = cfg["mlp_layer_types"][:cfg["num_hidden_layers"]].count(
        "sparse")
    weights = cfg["num_experts_held"] * 3 * h * inter * sparse_layers
    flops = 3.0 * 2.0 * 3 * h * inter * routed_slots_per_step
    rows = routed_slots_per_step * (2 * h + 3 * inter) * 2  # bf16, in and out
    return {"flops": flops,
            "bytes": float(weights * (2 + 2 + 4) + 3 * rows)}
