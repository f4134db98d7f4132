"""Turns a Qwen3-Next configuration file into the compiled Keras model
that ``SparkModel`` takes (``elephas_tpu.models.qwen3_next_lm``), with
the benchmark's seeded weights in it, and counts from the file's shapes
what the model and each of its new kernels must compute and move."""

from __future__ import annotations

import numpy as np

# what the model counts for itself (a sparse block's routed token
# slots): no weight of the reference's, zeroed with every new seed
COUNTERS = "/route_counts"


def build(cfg: dict, params: dict):
    import jax

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"this builder compiles SGD, not {opt['name']!r}")
    policy = None if cfg["dtype"] == "float32" else cfg["dtype"]
    first = cfg["experts_held_first"]
    # built on the host: keras would otherwise draw 2.5 GB of initial
    # weights and as many zero momenta on the chip, only for assign()
    # and fit's stage-in to replace them; the chip's peak would count
    # them (the runner moves the state to the chip itself)
    with jax.default_device(jax.devices("cpu")[0]):
        model = _build(cfg, opt, policy, first)
    assign(model, params)
    return model


def _build(cfg, opt, policy, first):
    from elephas_tpu.models import qwen3_next_lm

    return qwen3_next_lm(
        vocab_size=cfg["vocab_size"], maxlen=cfg["sequence_length"],
        hidden_size=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_theta"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        experts_held=(first, first + cfg["num_experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"], chunk_size=cfg["chunk_size"],
        init_std=cfg["assumed"]["initializer_range"],
        lr=opt["learning_rate"], momentum=opt["momentum"],
        dtype_policy=policy, remat=bool(cfg["remat"]), seed=0,
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


def _layers(cfg: dict) -> tuple:
    """``(Gated DeltaNet layers, gated-attention layers)``."""
    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    full = sum(1 for i in range(n) if (i + 1) % every == 0)
    return n - full, full


def scan_macs_per_token_layer(cfg: dict) -> int:
    """The recurrence of one Gated DeltaNet layer for one token, as the
    token-by-token rule states it: a value head reads its state twice
    (``S^T k``, ``S^T q``) and writes it once (``k d^T``): three
    multiply-adds an entry of ``Dk x Dv``. The decay is one multiply an
    entry (half a multiply-add)."""
    entries = cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return int(cfg["linear_num_value_heads"] * 3.5 * entries)


def forward_macs_per_token(cfg: dict, sequence_length: int) -> float:
    """Multiply-adds of one token's forward pass, from the shapes:
    every projection is ``in x out``; causal attention reads half the
    square (``S / 2`` keys a query on average, twice: scores and
    values); the routed part at its expectation under uniform routing
    (``num_experts_per_tok * num_experts_held / num_experts`` expert
    visits a token)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    gdn = (h * (2 * key_dim + 2 * value_dim) + h * 2 * hv
           + cfg["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
           + scan_macs_per_token_layer(cfg) + value_dim * h)
    attn = (h * heads * 2 * hd + 2 * h * kv * hd + heads * hd * h
            + 2 * heads * hd * sequence_length / 2)
    visits = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
              / cfg["num_experts"])
    moe = (h * cfg["num_experts"] + h
           + 3 * h * cfg["shared_expert_intermediate_size"]
           + visits * 3 * h * cfg["moe_intermediate_size"])
    n_gdn, n_attn = _layers(cfg)
    return n_gdn * gdn + n_attn * attn + cfg["num_hidden_layers"] * moe + h * v


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward and backward of one sequence: two operations a
    multiply-add, the backward pass twice the forward's products;
    recomputation is not counted."""
    s = int(traffic["sequence_length"])
    return 3.0 * 2.0 * forward_macs_per_token(cfg, s) * s


def gdn_scan_step_cost(cfg: dict, traffic: dict) -> dict:
    """Operations and bytes that the gated delta rule of all the Gated
    DeltaNet layers needs for one training step, forward and backward
    (three times the forward's products). Bytes are the least a kernel
    could move: q, k (a key head each), v, the output and z-free
    gradients of each in bfloat16, the decay and beta in float32, each
    once forward and, on the way back, read again with the output's
    gradient and written as five gradients."""
    tokens = int(traffic["batch_size"]) * int(traffic["sequence_length"])
    n_gdn, _ = _layers(cfg)
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    flops = 3.0 * 2.0 * scan_macs_per_token_layer(cfg) * tokens * n_gdn
    inputs = 2 * (2 * hk * dk + hv * dv) + 4 * 2 * hv  # q, k, v; g, beta
    output = 2 * hv * dv
    forward = inputs + output
    backward = inputs + output + inputs  # read all and dO, write gradients
    return {"flops": flops,
            "bytes": float((forward + backward) * tokens * n_gdn)}


def moe_experts_step_cost(cfg: dict, traffic: dict,
                          routed_slots_per_step: float) -> dict:
    """Operations and bytes of the grouped products over the held
    experts of all layers for one step, forward and backward, for the
    token slots that were really routed here (the layers' counters, not
    the expectation). Bytes: the held experts' weights in bfloat16 read
    forward and again backward, their gradients written in float32, and
    each routed row read and written at the hidden width on both sides
    of the two products, forward and backward."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = cfg["num_hidden_layers"]
    weights = cfg["num_experts_held"] * 3 * h * inter * layers
    flops = 3.0 * 2.0 * 3 * h * inter * routed_slots_per_step
    rows = routed_slots_per_step * (2 * h + 3 * inter) * 2  # bf16, in and out
    return {"flops": flops,
            "bytes": float(weights * (2 + 2 + 4) + 3 * rows)}
