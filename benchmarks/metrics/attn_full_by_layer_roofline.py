"""The full-attention layers' causal attention a step where the layers differ in their heads: forward once and the backward pass's five products, counted a layer (layers from layer_types, heads from num_attention_heads_per_layer; attn_window_by_layer_roofline's count under a window of the whole sequence) over the causal block pairs of 256 (diagonal pairs whole; recomputation not counted), with q, k, v, o and their gradients moved once each way, at the chip's binding peak over the device time under attn.full, in percent."""

from benchmarks.harness import manifest, xplane_ops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_examples_per_s_per_chip"


BY_LAYER = manifest.load_module("metrics", "attn_window_by_layer_roofline")


def step_cost(cfg: dict, traffic: dict):
    return BY_LAYER.step_cost(cfg, traffic, "full_attention")


def read(run):
    return xplane_ops.roofline_share(
        run, "attn.full", step_cost(run["config"], run["traffic"]))
