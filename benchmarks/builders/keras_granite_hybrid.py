"""Turns a Granite 4.0-H configuration file (``model_type:
granitemoehybrid`` with no experts: by ``layer_types`` a Mamba-2 mixer
or attention with no position term, a dense SwiGLU behind each, a tied
head and the family's four multipliers) into the compiled Keras model
that ``SparkModel`` takes (``elephas_tpu.models.granite_hybrid_lm``),
with the benchmark's seeded weights in it, and counts from the file's
shapes what the model, its selective scan and its dense feed-forwards
must compute and move."""

from __future__ import annotations

# a program without this model cannot run the configuration: the run
# then ends here, as the builder is loaded, before any weight is made
from elephas_tpu.models import granite_hybrid  # noqa: F401

# the readings of the row that the program computes (``assumed``); the
# reference takes others too, as faults
READINGS = {"attention_scale": "multiplier", "logits": "divided",
            "mamba_norm": "gate_then_norm", "residual": "on_sublayer"}


def build(cfg: dict, params: dict):
    import jax

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"this builder compiles SGD, not {opt['name']!r}")
    for key, computed in READINGS.items():
        if cfg["assumed"].get(key, computed) != computed:
            raise ValueError(
                f"granite_hybrid_lm computes assumed.{key} {computed!r}, "
                f"not {cfg['assumed'][key]!r}"
            )
    if (cfg["num_local_experts"] or cfg["attention_bias"]
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]
            or not cfg["tie_word_embeddings"]
            or cfg["position_embedding_type"] != "nope"
            or cfg["hidden_act"] != "silu"
            or cfg["normalization_function"] != "rmsnorm"):
        raise ValueError(
            "granite_hybrid_lm builds no experts, projections without "
            "bias, a convolution with bias, a tied head, attention with no "
            "position term, silu and RMSNorm"
        )
    # built on the host: keras would otherwise draw 3.1 GB of initial
    # weights and as many zero momenta on the chip, only for assign()
    # and fit's stage-in to replace them; the chip's peak would count
    # them (the runner moves the state to the chip itself)
    with jax.default_device(jax.devices("cpu")[0]):
        model = _build(cfg, opt)
    assign(model, params)
    return model


def _build(cfg, opt):
    from elephas_tpu.models import granite_hybrid_lm

    assumed = cfg["assumed"]
    return granite_hybrid_lm(
        vocab_size=cfg["vocab_size"], maxlen=cfg["sequence_length"],
        hidden_size=cfg["hidden_size"],
        # the published list, of which the layers here are the first
        layer_types=cfg["layer_types"],
        num_hidden_layers=cfg["num_hidden_layers"],
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        time_step_min=assumed["time_step_min"],
        time_step_max=assumed["time_step_max"],
        time_step_floor=assumed["time_step_floor"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        shared_intermediate_size=cfg["shared_intermediate_size"],
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        rms_norm_eps=cfg["rms_norm_eps"],
        init_std=assumed["initializer_range"],
        lr=opt["learning_rate"], momentum=opt["momentum"],
        dtype_policy=None if cfg["dtype"] == "float32" else cfg["dtype"],
        remat=bool(cfg["remat"]), seed=0,
    )


def assign(model, params: dict) -> None:
    """The reference's weights into the model by variable path, after
    checking that the two agree on what the weights are (the one table
    of embedding and head is one variable on both sides)."""
    weights = {v.path: v for v in model.variables}
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


# -- what the shapes call for ---------------------------------------------


def layer_counts(cfg: dict) -> dict:
    """How many layers of each type are here."""
    here = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {kind: here.count(kind) for kind in ("mamba", "attention")}


def scan_macs_per_token_layer(cfg: dict) -> int:
    """The selective scan of one Mamba-2 layer for one token, by the
    chunked form at the published ``mamba_chunk_size`` ``Q``: a group's
    row of ``C B^T`` against the chunk's keys (``Q N``), a head's row of
    the masked product (``Q P``), its share of the chunk's state ``B^T
    (decay x)`` (``P N``) and what the carried state adds, ``C S`` (``P
    N``). Whatever implements the rule, at whatever chunk, is held to
    this count."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return cfg["mamba_n_groups"] * q * n + h * (q * p + 2 * p * n)


def visible_keys(sequence_length: int) -> float:
    """Keys a query sees on average under the causal mask."""
    return (sequence_length + 1) / 2


def mlp_macs_per_token_layer(cfg: dict) -> int:
    """A dense SwiGLU for one token: gate, up and down, each ``hidden x
    shared_intermediate_size``."""
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def forward_macs_per_token(cfg: dict, sequence_length: int) -> float:
    """Multiply-adds of one token's forward pass, from the shapes:
    every projection is ``in x out``; the convolution its taps a
    channel; the scan by :func:`scan_macs_per_token_layer`; causal
    attention reads :func:`visible_keys` keys a query, scores and sum
    both ``head_dim`` wide; a SwiGLU behind every mixer; the tied head
    ``hidden x vocab_size`` (the embedding is a look-up)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    inner = h * p
    conv_dim = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    mamba = (d * (inner + conv_dim + h) + cfg["mamba_d_conv"] * conv_dim
             + scan_macs_per_token_layer(cfg) + inner * d)
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    attn = (2 * d * heads * hd + 2 * d * kv * hd
            + heads * 2 * hd * visible_keys(sequence_length))
    n = layer_counts(cfg)
    return (n["mamba"] * mamba + n["attention"] * attn
            + cfg["num_hidden_layers"] * mlp_macs_per_token_layer(cfg)
            + d * v)


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
    layers = layer_counts(cfg)["mamba"]
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    flops = 3.0 * 2.0 * scan_macs_per_token_layer(cfg) * tokens * layers
    inputs = (2 * (inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])
              + 4 * cfg["mamba_n_heads"])  # x, B, C; dt
    output = 2 * inner
    forward = inputs + output
    backward = inputs + output + inputs  # read all and dy, write gradients
    return {"flops": flops,
            "bytes": float((forward + backward) * tokens * layers)}


def mlp_dense_step_cost(cfg: dict, traffic: dict) -> dict:
    """Operations and bytes of all the dense feed-forwards for one
    training step: the three products forward and twice that backward
    (recomputation not counted). Bytes: a token's input and result at
    the hidden width and its gate, up and hidden at the feed-forward's,
    in bfloat16, written forward and read backward, and their gradients
    moved once too; the weights in bfloat16 read forward and again
    backward and their gradients written in float32."""
    tokens = int(traffic["batch_size"]) * int(traffic["sequence_length"])
    layers = cfg["num_hidden_layers"]
    d, width = cfg["hidden_size"], cfg["shared_intermediate_size"]
    flops = 3.0 * 2.0 * mlp_macs_per_token_layer(cfg) * tokens * layers
    rows = 2 * (2 * d + 3 * width)  # x, y; gate, up, hidden
    weights = 3 * d * width
    return {"flops": flops,
            "bytes": float(layers * (2 * rows * tokens
                                     + weights * (2 + 2 + 4)))}
