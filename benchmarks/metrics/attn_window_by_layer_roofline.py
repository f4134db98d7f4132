"""The sliding-window layers' attention a step where the layers differ in their heads: forward once and the backward pass's five products, counted a layer (layers from layer_types, heads from num_attention_heads_per_layer) over the block pairs of 256 that the causal band leaves something of (pairs on the diagonal and on the band's edge whole; recomputation not counted), with q, k, v, o and their gradients moved once each way, at the chip's binding peak over the device time under attn.window, in percent."""

from benchmarks.harness import manifest, xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"

# the accepted reader's count (its blocks of 256, its seven products,
# its pairs under the band), taken a layer at a time; a window of the
# whole sequence leaves the causal pairs
ONE_HEAD_COUNT = manifest.load_module("metrics", "attn_window_roofline")
BLOCK, PRODUCTS = ONE_HEAD_COUNT.BLOCK, ONE_HEAD_COUNT.PRODUCTS
band_pairs = ONE_HEAD_COUNT.band_pairs


def step_cost(cfg: dict, traffic: dict, kind: str = "sliding_attention"):
    """Operations and bytes a step of the layers of ``kind``, each with
    its own heads; None for a configuration whose layers all have one
    head count (the accepted readers count those) or none of the kind."""
    if "num_attention_heads_per_layer" not in cfg:
        return None
    batch, s = int(traffic["batch_size"]), int(traffic["sequence_length"])
    n = cfg["num_hidden_layers"]
    heads_of = [h for h, k in zip(cfg["num_attention_heads_per_layer"][:n],
                                  cfg["layer_types"][:n]) if k == kind]
    if not heads_of:
        return None
    kv_heads, d = cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else s
    pairs = band_pairs(s, window)
    flops = 2.0 * BLOCK * BLOCK * d * pairs * PRODUCTS * batch * sum(heads_of)
    # bfloat16: q and o a query head, k and v a key/value head; forward
    # reads three and writes o, backward reads those four and dO and
    # writes three gradients
    moved = sum(2 * (2 * heads + 2 * kv_heads) + heads for heads in heads_of)
    return {"flops": flops, "bytes": float(2 * moved * d * s * batch)}


def read(run):
    return xplane_ops.roofline_share(
        run, "attn.window", step_cost(run["config"], run["traffic"]))
