"""Chaos-injection harness + fault-model primitives (ISSUE 3).

``plan`` is the declarative side — deterministic, seedable
:class:`FaultPlan` objects describing PS crashes, wire faults,
duplicated update frames, and worker-partition loss. ``harness`` is
the executable side — :class:`RestartablePS`, :class:`PSKiller`, and
:func:`run_chaos_training` drive real servers/workers under a plan
for the chaos test suite.

The production fault-tolerance machinery itself lives where the
failures happen: journaled restartable servers in
:mod:`elephas_tpu.parameter.server`, sequence-ID idempotent clients in
:mod:`elephas_tpu.parameter.client`, the supervised worker retry in
:mod:`elephas_tpu.worker`, and the driver's failure budget in
:mod:`elephas_tpu.spark_model`. This package only *injects* faults.
"""

from elephas_tpu.fault.plan import (  # noqa: F401
    FaultBudgetExceeded,
    FaultPlan,
    SocketFaults,
    WorkerFault,
    active_plan,
    check_partition,
    use_plan,
)
from elephas_tpu.fault.harness import (  # noqa: F401
    DeployChaosStore,
    PSKiller,
    ReplicaKiller,
    RestartablePS,
    ShardKiller,
    ShardedRestartablePS,
    run_chaos_training,
    run_elastic_membership,
    run_sharded_chaos_training,
)
