"""Turns a Nemotron-H configuration file (``model_type: nemotron_h``:
one mixer a layer by ``hybrid_override_pattern``: Mamba-2, a sparse
block of ungated squared-ReLU experts with a shared expert, attention
with no position term) into the compiled Keras model that
``SparkModel`` takes (``elephas_tpu.models.nemotron_h_lm``), with the
benchmark's seeded weights in it, and counts from the file's shapes
what the model, its selective scan and its grouped expert products must
compute and move."""

from __future__ import annotations

import numpy as np

# a program without this model cannot run the configuration: the run
# then ends here, as the builder is loaded, before any weight is made
from elephas_tpu.models import nemotron_h  # noqa: F401

# what the model counts for itself (a sparse block's routed token
# slots): no weight of the reference's, zeroed with every new seed
COUNTERS = "/route_counts"
# the readings of the row that the program computes (``assumed``); the
# reference takes others too, as faults
READINGS = {"rope": "none", "mamba_norm": "gate_then_norm"}


def build(cfg: dict, params: dict):
    import jax

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"this builder compiles SGD, not {opt['name']!r}")
    for key, computed in READINGS.items():
        if cfg["assumed"].get(key, computed) != computed:
            raise ValueError(
                f"nemotron_h_lm computes assumed.{key} {computed!r}, not "
                f"{cfg['assumed'][key]!r}"
            )
    if cfg["n_group"] != 1 or cfg["n_shared_experts"] != 1 or not cfg[
            "norm_topk_prob"] or not cfg["use_conv_bias"] or cfg["use_bias"]:
        raise ValueError(
            "nemotron_h_lm builds one expert group, one shared expert, "
            "renormalised scores, a convolution with bias and projections "
            "without"
        )
    # built on the host: keras would otherwise draw 2.7 GB of initial
    # weights and as many zero momenta on the chip, only for assign()
    # and fit's stage-in to replace them; the chip's peak would count
    # them (the runner moves the state to the chip itself)
    with jax.default_device(jax.devices("cpu")[0]):
        model = _build(cfg, opt)
    assign(model, params)
    return model


def _build(cfg, opt):
    from elephas_tpu.models import nemotron_h_lm

    first = cfg["experts_held_first"]
    return nemotron_h_lm(
        vocab_size=cfg["vocab_size"], maxlen=cfg["sequence_length"],
        hidden_size=cfg["hidden_size"],
        # the published pattern, of which the layers here are the first
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        num_hidden_layers=cfg["num_hidden_layers"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        mlp_hidden_act=cfg["mlp_hidden_act"],
        experts_held=(first, first + cfg["num_experts_held"]),
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        rescale_prenorm_residual=cfg["rescale_prenorm_residual"],
        init_std=cfg["assumed"]["initializer_range"],
        lr=opt["learning_rate"], momentum=opt["momentum"],
        dtype_policy=None if cfg["dtype"] == "float32" else cfg["dtype"],
        remat=bool(cfg["remat"]), seed=0,
    )


def assign(model, params: dict) -> None:
    """The reference's weights into the model by variable path (the
    routers' selection bias among them: a variable that no step
    trains), after checking that the two agree on what the weights
    are; the model's own counters start from zero."""
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


def layer_counts(cfg: dict) -> dict:
    """How many layers of each pattern character are here."""
    here = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return {kind: here.count(kind) for kind in "ME*"}


def scan_macs_per_token_layer(cfg: dict) -> int:
    """The selective scan of one Mamba-2 layer for one token, by the
    chunked form at the published ``chunk_size`` ``Q``: a group's row
    of ``C B^T`` against the chunk's keys (``Q N``), a head's row of the
    masked product (``Q P``), its share of the chunk's state ``B^T
    (decay x)`` (``P N``) and what the carried state adds, ``C S`` (``P
    N``). Whatever implements the rule is held to this count."""
    q, n = cfg["chunk_size"], cfg["ssm_state_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return cfg["n_groups"] * q * n + h * (q * p + 2 * p * n)


def visible_keys(sequence_length: int) -> float:
    """Keys a query sees on average under the causal mask."""
    return (sequence_length + 1) / 2


def forward_macs_per_token(cfg: dict, sequence_length: int) -> float:
    """Multiply-adds of one token's forward pass, from the shapes:
    every projection is ``in x out``; the convolution its taps a
    channel; the scan by :func:`scan_macs_per_token_layer`; causal
    attention reads :func:`visible_keys` keys a query, scores and sum
    both ``head_dim`` wide; an ungated expert is two products; the
    routed part at its expectation under uniform routing
    (``num_experts_per_tok * num_experts_held / n_routed_experts``
    expert visits a token)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = h * p
    conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = (d * (inner + conv_dim + h) + cfg["conv_kernel"] * conv_dim
             + scan_macs_per_token_layer(cfg) + inner * d)
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    attn = (2 * d * heads * hd + 2 * d * kv * hd
            + heads * 2 * hd * visible_keys(sequence_length))
    visits = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
              / cfg["n_routed_experts"])
    moe = (d * cfg["n_routed_experts"]
           + 2 * d * cfg["moe_shared_expert_intermediate_size"]
           + visits * 2 * d * cfg["moe_intermediate_size"])
    n = layer_counts(cfg)
    return n["M"] * mamba + n["E"] * moe + n["*"] * attn + d * v


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-add, the backward pass twice the forward's products;
    recomputation is not counted."""
    s = int(traffic["sequence_length"])
    return 3.0 * 2.0 * forward_macs_per_token(cfg, s) * s


def ssm_scan_step_cost(cfg: dict, traffic: dict) -> dict:
    """Operations and bytes that the selective scan of all the Mamba-2
    layers needs for one training step, forward once and twice that
    backward; recomputation is not counted. Bytes are the least a
    kernel could move: x and y a head, B and C a group in bfloat16 and
    dt in float32, each once forward and, on the way back, read again
    with the output's gradient and written as four gradients."""
    tokens = int(traffic["batch_size"]) * int(traffic["sequence_length"])
    layers = layer_counts(cfg)["M"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    flops = 3.0 * 2.0 * scan_macs_per_token_layer(cfg) * tokens * layers
    inputs = (2 * (inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"])
              + 4 * cfg["mamba_num_heads"])  # x, B, C; dt
    output = 2 * inner
    forward = inputs + output
    backward = inputs + output + inputs  # read all and dy, write gradients
    return {"flops": flops,
            "bytes": float((forward + backward) * tokens * layers)}


def moe_experts_step_cost(cfg: dict, traffic: dict,
                          routed_slots_per_step: float) -> dict:
    """Operations and bytes of the grouped products over the held
    experts of all sparse layers for one step, forward and backward,
    for the token slots that were really routed here (the layers'
    counters, not the expectation). An ungated expert is TWO products
    (``up``, ``down``). Bytes: the held experts' weights in bfloat16
    read forward and again backward, their gradients written in
    float32, and each routed row read and written at the hidden width
    on both sides of the two products, forward and backward."""
    d, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts_held"] * 2 * d * inter * layer_counts(cfg)["E"]
    flops = 3.0 * 2.0 * 2 * d * inter * routed_slots_per_step
    rows = routed_slots_per_step * (2 * d + 2 * inter) * 2  # bf16, in and out
    return {"flops": flops,
            "bytes": float(weights * (2 + 2 + 4) + 3 * rows)}
