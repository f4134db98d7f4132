#!/usr/bin/env python3
"""``benchmarks/prove.py`` for the LM cells, whose reference does not fit
the chip beside the master model.

    chiprun --timeout 2400 -- python3 scripts/prove_off_chip.py \
        --workload kanana2-fit-seq8k --seeds 12 --controls 0 --out <file>

``prove.py`` keeps one model for all its seeds, and a ``fit`` call's last
write-back leaves the master's variables and momenta on the default
device (5.0 GB for the hybrid LM, 7.3 GB for the latent-attention LM).
The float32 reference that follows then has no room (``PERF.md`` section
7, edit 8). This wrapper gives every variable its host copy once the
first epoch's state has been read, as ``MeshRunner._device_state(
park_master=True)`` does at the start of a ``fit``; the next seed's
``assign`` puts them on the chip again. Nothing of what is compared
changes: the state was read before it moved. Arguments are ``prove.py``'s.
"""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as mf  # noqa: E402


def park(model) -> None:
    import jax

    host = jax.local_devices(backend="cpu")[0]
    for var in list(model.variables) + list(model.optimizer.variables):
        var.assign(jax.device_put(var.value, host))


def load_module(kind: str, name: str, _load=mf.load_module):
    module = _load(kind, name)
    if kind == "drivers":
        first_epoch = module.first_epoch

        def first_epoch_then_park(ctx, job):
            first = first_epoch(ctx, job)
            park(job["model"])
            return first

        module.first_epoch = first_epoch_then_park
    return module


if __name__ == "__main__":
    mf.load_module = load_module
    runpy.run_path(os.path.join(ROOT, "benchmarks", "prove.py"),
                   run_name="__main__")
