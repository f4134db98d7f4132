"""Turns a DeepSeek-V3-style configuration file (``model_type:
deepseek_v3``: latent attention, a sigmoid router with a selection
bias, ungated shared experts, leading dense layers) into the compiled
Keras model that ``SparkModel`` takes
(``elephas_tpu.models.deepseek_v3_lm``), with the benchmark's seeded
weights in it, and counts from the file's shapes what the model and its
grouped expert products must compute and move."""

from __future__ import annotations

import numpy as np

# a program without this model cannot run the configuration: the run
# then ends here, as the builder is loaded, before any weight is made
from elephas_tpu.models import deepseek_v3  # noqa: F401

# what the model counts for itself (a sparse block's routed token
# slots): no weight of the reference's, zeroed with every new seed
COUNTERS = "/route_counts"


def build(cfg: dict, params: dict):
    import jax

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"this builder compiles SGD, not {opt['name']!r}")
    if cfg["q_lora_rank"] is not None or cfg["n_group"] != 1:
        raise ValueError(
            "deepseek_v3_lm builds the q_lora_rank-null form with one "
            "expert group"
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
    from elephas_tpu.models import deepseek_v3_lm

    first = cfg["experts_held_first"]
    return deepseek_v3_lm(
        vocab_size=cfg["vocab_size"], maxlen=cfg["sequence_length"],
        hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        num_attention_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=cfg["rope_theta"],
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        scoring_func=cfg["scoring_func"],
        experts_held=(first, first + cfg["num_experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"],
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


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def forward_macs_per_token(cfg: dict, sequence_length: int) -> float:
    """Multiply-adds of one token's forward pass, from the shapes:
    every projection is ``in x out``; causal attention reads half the
    square (``S / 2`` keys a query on average: the scores over the
    query and key width, the sum over the value width); the routed
    part at its expectation under uniform routing
    (``num_experts_per_tok * num_experts_held / n_routed_experts``
    expert visits a token)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, inter = cfg["v_head_dim"], cfg["moe_intermediate_size"]
    attn = (h * heads * (nope + rope) + h * (rank + rope)
            + rank * heads * (nope + dv) + heads * dv * h
            + heads * (nope + rope + dv) * sequence_length / 2)
    dense = 3 * h * cfg["intermediate_size"]
    visits = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
              / cfg["n_routed_experts"])
    moe = (h * cfg["n_routed_experts"]
           + 3 * h * cfg["n_shared_experts"] * inter
           + visits * 3 * h * inter)
    return (cfg["num_hidden_layers"] * attn
            + cfg["first_k_dense_replace"] * dense
            + sparse_layers(cfg) * moe + h * v)


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-add, the backward pass twice the forward's products;
    recomputation is not counted."""
    s = int(traffic["sequence_length"])
    return 3.0 * 2.0 * forward_macs_per_token(cfg, s) * s


def moe_experts_step_cost(cfg: dict, traffic: dict,
                          routed_slots_per_step: float) -> dict:
    """Operations and bytes of the grouped products over the held
    experts of all sparse layers for one step, forward and backward,
    for the token slots that were really routed here (the layers'
    counters, not the expectation). Bytes: the held experts' weights in
    bfloat16 read forward and again backward, their gradients written
    in float32, and each routed row read and written at the hidden
    width on both sides of the two products, forward and backward."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts_held"] * 3 * h * inter * sparse_layers(cfg)
    flops = 3.0 * 2.0 * 3 * h * inter * routed_slots_per_step
    rows = routed_slots_per_step * (2 * h + 3 * inter) * 2  # bf16, in and out
    return {"flops": flops,
            "bytes": float(weights * (2 + 2 + 4) + 3 * rows)}
