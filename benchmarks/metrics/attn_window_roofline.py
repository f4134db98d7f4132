"""The sliding-window layers' attention a step, forward once and the backward pass's five products, counted over the block pairs of 256 that the causal band leaves something of (pairs on the diagonal and on the band's edge whole; recomputation not counted), with q, k, v, o and their gradients moved once each way, at the chip's binding peak over the device time under attn.window, in percent."""

from benchmarks.harness import xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"

# the count's own granularity, not the program's: a kernel with other
# blocks, or one that masks where it could skip, is held to the same
# operations
BLOCK = 256
PRODUCTS = 2 + 5  # q k^T, p v; then q k^T again, dO v^T, p^T dO, ds k, ds^T q


def band_pairs(sequence_length: int, window: int) -> int:
    """Pairs ``(i, j)`` of blocks of ``BLOCK`` positions in which some
    query sees some key: ``j <= i`` and the block's first query, ``i *
    BLOCK``, within ``window - 1`` of the key block's last."""
    blocks = -(-sequence_length // BLOCK)
    return sum(
        1 for i in range(blocks) for j in range(i + 1)
        if (j + 1) * BLOCK - 1 + window > i * BLOCK
    )


def step_cost(cfg: dict, traffic: dict) -> dict:
    batch, s = int(traffic["batch_size"]), int(traffic["sequence_length"])
    layers = sum(cfg["sliding_window_layout"][:cfg["num_hidden_layers"]])
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    pairs = band_pairs(s, cfg["sliding_window_size"])
    flops = 2.0 * BLOCK * BLOCK * d * pairs * PRODUCTS * batch * heads * layers
    # bfloat16: q and o a query head, k and v a key/value head; forward
    # reads three and writes o, backward reads those four and dO and
    # writes three gradients
    moved = 2 * (2 * heads + 2 * kv_heads) + heads
    return {"flops": flops, "bytes": float(2 * moved * d * s * batch * layers)}


def read(run):
    cfg = run["config"]
    if "sliding_window_layout" not in cfg:
        return None
    return xplane_ops.roofline_share(
        run, "attn.window", step_cost(cfg, run["traffic"]))
