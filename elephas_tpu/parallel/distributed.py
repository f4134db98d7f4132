"""Multi-host process bring-up — the cluster-side of the runtime.

Reference equivalents (SURVEY.md §2b): Spark's driver↔executor dispatch
(JVM scheduler + Netty RPC, py4j bridge) and
``utils/sockets.py::determine_master`` host discovery. On TPU pods the
platform analogue is one Python process per host, gang-connected through
JAX's built-in coordination service; afterwards ``jax.devices()`` spans
every chip in the slice and the SAME single-host code (SparkModel,
ShardedTrainer, ring attention) runs pod-wide — collectives ride ICI
within a slice and DCN across slices, placed by XLA.

Environment-driven like Spark's launcher: set ``ELEPHAS_COORDINATOR``
(host:port of process 0), ``ELEPHAS_NUM_PROCESSES`` and
``ELEPHAS_PROCESS_ID`` — or rely on the TPU metadata auto-detection baked
into ``jax.distributed.initialize`` on Cloud TPU VMs.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

_initialized = False


def determine_coordinator(port: int = 8476) -> str | None:
    """Coordinator address from the environment (the ``determine_master``
    analogue): ``ELEPHAS_COORDINATOR`` or ``SPARK_LOCAL_IP`` + port."""
    addr = os.environ.get("ELEPHAS_COORDINATOR")
    if addr:
        return addr if ":" in addr else f"{addr}:{port}"
    host = os.environ.get("SPARK_LOCAL_IP")
    if host:
        return f"{host}:{port}"
    return None


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Join the multi-host gang. Idempotent; no-op for single-host runs.

    Returns True when running multi-host. Call once per process, before
    any JAX computation, on every host of the pod slice.
    """
    global _initialized
    if _initialized:
        return True
    import jax

    coordinator_address = coordinator_address or determine_coordinator()
    if num_processes is None:
        env = os.environ.get("ELEPHAS_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("ELEPHAS_PROCESS_ID")
        process_id = int(env) if env else None

    on_tpu_pod = os.environ.get("TPU_WORKER_HOSTNAMES", "").count(",") >= 1
    if coordinator_address is None and not on_tpu_pod:
        logger.info("no coordinator configured; staying single-host")
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    logger.info(
        "joined gang: process %d/%d, %d global devices",
        jax.process_index(),
        jax.process_count(),
        len(jax.devices()),
    )
    return True


def process_count() -> int:
    import jax

    return jax.process_count()


def is_coordinator() -> bool:
    import jax

    return jax.process_index() == 0


def sync_global_devices(tag: str = "barrier") -> None:
    """Cross-host barrier (host-level gang sync)."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(tag)


def broadcast_from_coordinator(pytree):
    """Replicate host-side values from process 0 to every process —
    the broadcast-variable analogue for configs/initial weights."""
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(pytree)
