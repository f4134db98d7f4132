"""Worker mesh construction — the SparkContext/executor-pool analogue.

The reference asks Spark for ``num_workers`` executors and repartitions
RDDs to match (``[U] elephas/spark_model.py::SparkModel.fit``). Here the
executor pool is the set of addressable JAX devices; a 1-D
``Mesh(devices[:W], ('workers',))`` fixes the data-parallel axis. Requests
for more workers than devices clamp (with a warning) — TPU topology is
physical, unlike Spark's oversubscribable task slots.

Multi-host: ``jax.devices()`` spans all processes after
``jax.distributed.initialize``; the same mesh construction then yields a
cross-host DP axis whose collectives ride ICI within a slice and DCN
across slices — XLA picks the transport, this module never needs to know.
"""

from __future__ import annotations

import functools
import logging

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


def put_global(arr, sharding: NamedSharding):
    """Host→device under an arbitrary sharding, multi-process safe.

    Every gang process holds the identical full host value (the SPMD
    contract); each materializes only its addressable shards of the
    global array — ``device_put`` alone rejects shardings that span
    devices this process cannot address."""
    arr = np.asarray(arr)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


# one cached identity-jit replicator per mesh (the jit compilation
# cache then hits per input shape/sharding; a fresh wrapper per call
# would retrace and recompile the all-gather every time). The cache is
# BOUNDED, not weak: the jitted fn's out_shardings holds the mesh
# strongly, so weak keys could never evict — lru eviction releases old
# meshes' wrappers once newer ones (hyperparam trials lease many)
# displace them.
@functools.lru_cache(maxsize=8)
def _gather_fn_for(mesh: Mesh):
    return jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, P()))


def host_read(leaf, mesh: Mesh) -> np.ndarray:
    """Device→host full value of a (possibly sharded) leaf. When the
    leaf spans devices this process cannot address, replicate via an
    identity jit (an XLA all-gather) first."""
    if not isinstance(leaf, jax.Array) or getattr(
        leaf, "is_fully_addressable", True
    ):
        return np.asarray(leaf)
    return np.asarray(_gather_fn_for(mesh)(leaf))


def num_available_workers() -> int:
    return len(jax.devices())


def worker_mesh(num_workers: int | None = None) -> Mesh:
    """Build a 1-D ``('workers',)`` mesh over up to ``num_workers`` devices."""
    devices = jax.devices()
    if num_workers is None or num_workers <= 0:
        num_workers = len(devices)
    if num_workers > len(devices):
        logger.warning(
            "requested %d workers but only %d devices are addressable; "
            "clamping (mesh workers are physical devices, not task slots)",
            num_workers,
            len(devices),
        )
        num_workers = len(devices)
    return Mesh(devices[:num_workers], ("workers",))
