"""Toy-size contexts for the drivers: the committed files with their
sizes cut, on ``force_cpu_devices``. ``run.py`` itself keeps no CPU path."""

import copy
import time

from benchmarks.harness import manifest as mf
from benchmarks.harness import runner


def context(cell_name: str, seconds: float, seed: int, work_dir: str):
    from elephas_tpu.utils.backend_guard import force_cpu_devices

    force_cpu_devices(1)
    from benchmarks.harness.compile_meter import CompileMeter, ProgramSizes

    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, cell_name)
    config = copy.deepcopy(mf.config_of(manifest, cell))
    traffic = copy.deepcopy(mf.load_json("traffic", cell["traffic"]))
    config.update(image_size=32, num_classes=10, depths=[1, 1], width=8,
                  dtype="float32")
    traffic.update(examples=64, batch_size=8, steps_per_epoch=8,
                   base_block=8, epoch_seconds=0.25)
    return runner.Context(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=seed, seconds=seconds, trace=0, t_process=time.monotonic(),
        meter=CompileMeter(), sizes=ProgramSizes(), work_dir=work_dir,
    )


DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
