"""Turns a ResNet configuration file into the compiled Keras model that
``SparkModel`` takes, with the benchmark's seeded weights in it, and
counts the operations its shapes call for."""

from __future__ import annotations

from benchmarks.harness.weights import assign  # noqa: F401  (drivers call builder.assign)


def build(cfg: dict, params: dict):
    from elephas_tpu.models import resnet

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"this builder compiles SGD, not {opt['name']!r}")
    policy = None if cfg["dtype"] == "float32" else cfg["dtype"]
    model = resnet(
        input_shape=(cfg["image_size"], cfg["image_size"], cfg["channels"]),
        num_classes=cfg["num_classes"], depths=tuple(cfg["depths"]),
        width=cfg["width"], lr=opt["learning_rate"],
        momentum=opt["momentum"], dtype_policy=policy, seed=0,
    )
    assign(model, params)
    return model


def forward_macs_per_example(cfg: dict) -> int:
    """Multiply-adds of one image's forward pass, from the layers'
    shapes: every convolution is ``out_h * out_w * k * k * cin * cout``,
    the head ``channels * classes``. 'SAME' padding: a stride of 2
    halves the side, rounding up."""
    def half(n):
        return -(-n // 2)

    side = half(cfg["image_size"])
    macs = side * side * 49 * cfg["channels"] * cfg["width"]
    side = half(side)  # the max-pool
    channels = cfg["width"]
    for stage, count in enumerate(cfg["depths"]):
        filters = cfg["width"] * 2 ** stage
        for b in range(count):
            stride = 2 if (stage > 0 and b == 0) else 1
            out = half(side) if stride == 2 else side
            if stride != 1 or channels != filters * 4:
                macs += out * out * channels * filters * 4
            macs += side * side * channels * filters          # 1x1
            macs += out * out * 9 * filters * filters         # 3x3, strided
            macs += out * out * filters * filters * 4         # 1x1
            side, channels = out, filters * 4
    return macs + channels * cfg["num_classes"]


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward and backward of one image: two operations a multiply-add,
    and the backward pass twice the forward's products."""
    return 3.0 * 2.0 * forward_macs_per_example(cfg)
