#!/usr/bin/env python3
"""What the args that PR 40 put on ``fit.epoch_dispatch`` cost, in ns a
span, on the host this runs on: ``benchmarks/results/span-cost-pr24.json``'s
micro again (five repeats of 200000 enter/exit pairs) for

- ``bare``: ``with trace_span("fit.epoch_dispatch", epoch=3): pass``, the
  span as PR 24 left it;
- ``with_args``: the same span as ``MeshRunner._dispatch_epoch`` opens it
  now: a ``JaxWork`` block inside it, one ``_cache_size()`` of a jitted
  function, ``signatures`` and ``new_signature`` set;
- ``memory_event``: one ``MeshRunner._emit_memory`` (a ``memory_stats()``
  a local device and one ring event), 20000 a repeat; None on a backend
  without the statistics.

    chiprun -- python3 scripts/dispatch_span_cost.py chiprun_out/PR40/span-cost-pr40.json
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPEATS, CALLS = 5, 200_000


def ns_a_call(body, calls=CALLS) -> list:
    out = []
    for _ in range(REPEATS):
        t0 = time.perf_counter_ns()
        for _ in range(calls):
            body()
        out.append(round((time.perf_counter_ns() - t0) / calls, 1))
    return out


def main(path: str) -> int:
    import jax
    import jax.numpy as jnp

    from elephas_tpu import telemetry
    from elephas_tpu.worker import JaxWork, MeshRunner

    span = telemetry.trace_span
    fn = jax.jit(lambda a: a + 1)
    fn(jnp.zeros(4)).block_until_ready()

    def bare():
        with span("fit.epoch_dispatch", epoch=3):
            pass

    seen = [0]

    def with_args():
        with span("fit.epoch_dispatch", epoch=3) as sp:
            with JaxWork(sp):
                pass
            signatures = fn._cache_size()
            sp.set(signatures=signatures, new_signature=signatures > seen[0])
        seen[0] = signatures

    class Holder:
        _memory_peak = 0

    holder = Holder()
    stats = jax.local_devices()[0].memory_stats()
    record = {
        "about": "PR 40: ns a span on this host, five repeats of "
                 f"{CALLS}; see scripts/dispatch_span_cost.py",
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": jax.device_count()},
        "bare_ns": ns_a_call(bare),
        "with_args_ns": ns_a_call(with_args),
        "memory_event_ns": ns_a_call(
            lambda: MeshRunner._emit_memory(holder, 3), CALLS // 10)
        if stats and "bytes_in_use" in stats else None,
    }
    print(json.dumps(record), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
