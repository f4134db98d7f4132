"""Latent attention's causal attention a step over all layers, forward once and the backward pass's five products, each at its own width (scores and their gradients over the query and key width, the sums over the value width), counted over the block pairs of 256 the mask leaves something of (diagonal pairs whole; recomputation not counted), with q, k_nope, v, o a head and k_rope once moved each way, at the chip's binding peak over the device time under attn.mla, in percent."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"

# the count's own granularity, not the program's: a kernel with other
# blocks, or one that scores in two parts, is held to the same operations
BLOCK = 256


def step_cost(cfg: dict, traffic: dict) -> dict:
    batch, s = int(traffic["batch_size"]), int(traffic["sequence_length"])
    layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    qk, dv = nope + rope, cfg["v_head_dim"]
    blocks = -(-s // BLOCK)
    pairs = blocks * (blocks + 1) // 2
    # forward q k^T and p v; then q k^T again, dO v^T, p^T dO, ds k, ds^T q
    widths = (qk + dv) + (qk + dv + dv + qk + qk)
    flops = 2.0 * BLOCK * BLOCK * widths * pairs * batch * heads * layers
    # bfloat16, a token a layer: q, k_nope, v and o a head, k_rope once
    # (all heads share it). Forward reads q, k, v and writes o; backward
    # reads those four and dO and writes the gradients of q, k and v
    k = heads * nope + rope
    forward = heads * qk + k + 2 * heads * dv
    backward = forward + heads * dv + heads * qk + k + heads * dv
    return {"flops": flops,
            "bytes": float(2 * (forward + backward) * s * batch * layers)}


def read(run):
    cfg = run["config"]
    if "kv_lora_rank" not in cfg:
        return None
    return xplane_ops.roofline_share(
        run, "attn.mla", step_cost(cfg, run["traffic"]))
