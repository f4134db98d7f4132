"""The gated-attention layers' causal attention a step, forward once and the backward pass's five products, counted over the block pairs of 256 the mask leaves something of (diagonal pairs whole; recomputation not counted), with q, k, v, o and their gradients moved once each way, at the chip's binding peak over the device time under attn.full, in percent."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"

# the count's own granularity, not the program's: a kernel with other
# blocks is held to the same operations
BLOCK = 256
PRODUCTS = 2 + 5  # q k^T, p v; then q k^T again, dO v^T, p^T dO, ds k, ds^T q


def step_cost(cfg: dict, traffic: dict) -> dict:
    batch, s = int(traffic["batch_size"]), int(traffic["sequence_length"])
    layers = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    blocks = -(-s // BLOCK)
    pairs = blocks * (blocks + 1) // 2
    flops = 2.0 * BLOCK * BLOCK * d * pairs * PRODUCTS * batch * heads * layers
    # bfloat16: q and o a query head, k and v a key/value head; forward
    # reads three and writes o, backward reads those four and dO and
    # writes three gradients
    moved = 2 * (2 * heads + 2 * kv_heads) + heads
    return {"flops": flops, "bytes": float(2 * moved * d * s * batch * layers)}


def read(run):
    return xplane_ops.roofline_share(
        run, "attn.full", step_cost(run["config"], run["traffic"]))
