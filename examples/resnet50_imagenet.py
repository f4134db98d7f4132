"""BASELINE config #5 — ResNet-50 data-parallel training (north star).

ImageNet-shaped synthetic data (zero-egress environment) through the full
``SparkModel.fit`` path: per-step in-XLA ``pmean`` gradient allreduce over
the worker mesh, mixed-bfloat16 compute on the MXU. On a pod slice, run
one process per host after ``jax.distributed.initialize`` and the same
script scales over all chips. ``python3 benchmarks/run.py --workload
resnet50-fit-staged`` measures this configuration on the chip.
"""

import argparse
import time

from elephas_tpu import SparkModel
from elephas_tpu.data import SparkContext
from elephas_tpu.models import resnet50, resnet
from elephas_tpu.utils.rdd_utils import to_simple_rdd

from _datasets import synthetic_imagenet


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--img", type=int, default=224)
    p.add_argument("--samples", type=int, default=1024)
    p.add_argument("--tiny", action="store_true", help="CPU-sized model/data")
    args = p.parse_args()

    if args.tiny:
        args.img, args.samples, args.batch_size = 32, 128, 8
        model = resnet(input_shape=(32, 32, 3), num_classes=10, depths=(1, 1), width=16)
        x, y = synthetic_imagenet(args.samples, args.img, num_classes=10)
    else:
        model = resnet50(
            input_shape=(args.img, args.img, 3), dtype_policy="mixed_bfloat16"
        )
        x, y = synthetic_imagenet(args.samples, args.img)

    sc = SparkContext("local[*]")
    rdd = to_simple_rdd(sc, x, y)
    spark_model = SparkModel(model, mode="synchronous", batch_size=args.batch_size)

    t0 = time.perf_counter()
    spark_model.fit(rdd, epochs=1, batch_size=args.batch_size)  # compile+warmup
    print(f"first epoch (incl. compile): {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    history = spark_model.fit(rdd, epochs=args.epochs, batch_size=args.batch_size)
    dt = time.perf_counter() - t0
    images = len(x) * args.epochs
    n_chips = spark_model.num_workers
    print(
        f"loss={history['loss'][-1]:.4f}  "
        f"{images / dt:.1f} img/s total, {images / dt / n_chips:.1f} img/s/chip"
    )


if __name__ == "__main__":
    main()
