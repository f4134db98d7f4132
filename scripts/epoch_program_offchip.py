#!/usr/bin/env python3
"""A cell's whole epoch program compiled for a described v5e chip on a
machine that has none: what the TPU compiler refuses and what the
program's temporaries take, before any chip time is spent.

    JAX_PLATFORMS=cpu python3 scripts/epoch_program_offchip.py \
        --workload laguna-fit-seq8k [--set KEY JSON] [--out <file.json>]

Builds the cell's model as its builder does (weights are the Keras
initialiser's: nothing runs), hands ``MeshRunner`` a mesh of one
described device, makes the flash and grouped kernels compile instead
of interpreting (``backend_guard.pallas_interpret``: a process on the
CPU would interpret them), and lowers ``MeshRunner._build_epoch_fn`` on
``ShapeDtypeStruct``s sharded as ``fit`` shards them. Prints XLA's
``memory_analysis()`` of the compiled program, how many of each
Pallas kernel it holds and a sha256 of the lowered text (two trees that
trace a cell to the same program print the same hash). A compile that
passes is not a chip run: the chip's ``hbm_peak_gb.fit`` is the
allocator's peak plus these temporaries. The model's real weights live on the host while this runs
(a few GB for an LM cell).
"""

import argparse
import collections
import hashlib
import inspect
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("KERAS_BACKEND", "jax")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _more_arguments(build, cfg):
    """What a builder's ``_build`` takes beside the configuration and
    its optimizer (the first builder's takes the dtype policy and the
    first held expert, which the later ones read from the file)."""
    given = {"policy": None if cfg["dtype"] == "float32" else cfg["dtype"],
             "first": cfg.get("experts_held_first")}
    names = list(inspect.signature(build).parameters)[2:]
    return {name: given[name] for name in names}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--out")
    parser.add_argument("--set", nargs=2, action="append", default=[],
                        metavar=("KEY", "JSON"),
                        help="a (dotted) key of the configuration, set "
                             "for this compile alone")
    args = parser.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.harness import manifest as mf
    from elephas_tpu.utils import backend_guard
    from elephas_tpu.worker import MeshRunner

    backend_guard.pallas_interpret = lambda: False
    # the lowered text then holds no Python call stack (JAX writes the
    # callers' files, functions and lines into each kernel's locations):
    # its hash names the program, wherever the code that traced it stands
    jax.config.update("jax_traceback_in_locations_limit", 0)
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, args.workload)
    # scripts/ is this process's sys.path[0]
    from prove_reference_faults import with_keys

    cfg = with_keys(mf.config_of(manifest, cell), [
        (key, json.loads(value)) for key, value in args.set])
    traffic = mf.load_json("traffic", cell["traffic"])
    builder = mf.load_module("builders", cfg["builder"])
    t0 = time.monotonic()
    model = builder._build(cfg, cfg["optimizer"], **_more_arguments(
        builder._build, cfg))
    built = time.monotonic()

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), ("workers",))
    runner = MeshRunner(model, cfg["spark_model"]["mode"], "epoch", mesh)
    sharded = NamedSharding(mesh, P("workers"))

    def like(variables):
        return [jax.ShapeDtypeStruct((1,) + tuple(v.shape), v.dtype,
                                     sharding=sharded) for v in variables]

    steps, batch = int(traffic["steps_per_epoch"]), int(traffic["batch_size"])
    rows = jax.ShapeDtypeStruct(
        (1, steps, batch, int(traffic["sequence_length"])), np.int32,
        sharding=sharded)
    state = (like(model.trainable_variables),
             like(model.non_trainable_variables),
             like(model.optimizer.variables))
    lowered = runner._build_epoch_fn([]).lower(*state, [], rows, rows)
    traced = time.monotonic()
    compiled = lowered.compile()
    done = time.monotonic()
    memory = compiled.memory_analysis()
    text = lowered.as_text()
    kernels = collections.Counter(re.findall(r'kernel_name = "(\w+)"', text))
    result = {
        "cell": args.workload,
        "set": args.set,
        "parameters": int(sum(np.prod(v.shape) for v in model.variables)),
        "temp_size_in_bytes": int(memory.temp_size_in_bytes),
        "argument_size_in_bytes": int(memory.argument_size_in_bytes),
        "output_size_in_bytes": int(memory.output_size_in_bytes),
        "alias_size_in_bytes": int(memory.alias_size_in_bytes),
        "generated_code_size_in_bytes": int(
            memory.generated_code_size_in_bytes),
        "kernels_in_the_lowered_program": dict(kernels),
        "lowered_text_sha256": hashlib.sha256(text.encode()).hexdigest(),
        "build_s": round(built - t0, 1),
        "trace_and_lower_s": round(traced - built, 1),
        "compile_s": round(done - traced, 1),
    }
    print(json.dumps(result, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
