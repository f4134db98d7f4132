"""Process launcher — the ``spark-submit`` analogue for multi-host runs.

Reference equivalent (SURVEY.md §2b): Spark's driver↔executor dispatch.
There, a cluster manager starts executors and ships closures; here, one
Python process per host joins a JAX coordination-service gang
(:mod:`elephas_tpu.parallel.distributed`) and then runs the SAME user
script everywhere — SPMD at the process level, matching how TPU pods are
actually operated.

Two ways to use it:

1. Real cluster: start the same script on every host yourself (or via
   your scheduler) with ``ELEPHAS_COORDINATOR=host0:port``,
   ``ELEPHAS_NUM_PROCESSES=N``, ``ELEPHAS_PROCESS_ID=i`` exported, and
   call ``elephas_tpu.parallel.distributed.initialize()`` first thing.
   On Cloud TPU pods the env is auto-detected and none of this is needed.

2. Single machine (testing / CI): ``python -m elephas_tpu.launch
   --num-processes 2 --cpu-devices-per-process 4 script.py`` spawns the
   gang locally with a virtual CPU mesh per process — the multi-host
   analogue of the reference's Spark ``local[N]`` trick (SURVEY.md §4).

The launcher streams each child's output (prefixed) and exits non-zero
if any child fails — same contract as ``spark-submit``.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import threading


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(
    process_id: int,
    num_processes: int,
    coordinator: str,
    cpu_devices_per_process: int = 0,
    restart_from: str | None = None,
    attempt: int = 0,
) -> dict:
    """Environment for one gang member (exported keys are the public
    launcher contract; see module docstring)."""
    env = dict(os.environ)
    env["ELEPHAS_COORDINATOR"] = coordinator
    env["ELEPHAS_NUM_PROCESSES"] = str(num_processes)
    env["ELEPHAS_PROCESS_ID"] = str(process_id)
    if restart_from:
        env["ELEPHAS_CHECKPOINT_DIR"] = restart_from
    # scripts pass resume=ELEPHAS_RESUME=="1" straight through to fit();
    # restore of an empty checkpoint dir is a fresh start, so exporting
    # "1" from the first attempt would also be safe — "only on restart"
    # just keeps attempt 0's logs free of resume-probe noise
    env["ELEPHAS_RESTART_COUNT"] = str(attempt)
    env["ELEPHAS_RESUME"] = "1" if attempt else "0"
    if cpu_devices_per_process:
        # the CPU-simulated gang: each member gets its own virtual host
        # devices and never loads an accelerator runtime
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_NUM_CPU_DEVICES"] = str(cpu_devices_per_process)
    return env


def _run_gang_once(
    script: str,
    script_args: list[str] | None,
    num_processes: int,
    coordinator: str,
    cpu_devices_per_process: int,
    timeout: float | None,
    restart_from: str | None = None,
    attempt: int = 0,
) -> int:
    """One gang generation: spawn, stream prefixed output, fail fast.

    Gang semantics on failure: the FIRST child to exit non-zero kills
    the whole generation immediately (the collective is wedged without
    it — surviving members would block in a collective until the gang
    timeout), so the launcher can relaunch everyone promptly.
    """
    procs = []
    for i in range(num_processes):
        procs.append(
            subprocess.Popen(
                [sys.executable, script, *(script_args or [])],
                env=child_env(
                    i, num_processes, coordinator, cpu_devices_per_process,
                    restart_from=restart_from, attempt=attempt,
                ),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )

    def stream(i: int, p: subprocess.Popen) -> None:
        for line in p.stdout:
            if not line.endswith("\n"):
                # a child's unterminated final line would otherwise merge
                # with the other process's next line in the combined
                # stream, corrupting machine-read output (RESULT lines)
                line += "\n"
            sys.stdout.write(f"[proc {i}] {line}")
            sys.stdout.flush()

    threads = [
        threading.Thread(target=stream, args=(i, p), daemon=True)
        for i, p in enumerate(procs)
    ]
    for t in threads:
        t.start()
    import time

    deadline = time.monotonic() + timeout if timeout else None
    rcs = []
    try:
        while True:
            polled = [p.poll() for p in procs]
            if all(rc is not None for rc in polled):
                rcs = polled
                break
            failed = [
                i for i, rc in enumerate(polled) if rc not in (None, 0)
            ]
            if failed:
                sys.stdout.write(
                    f"[launch] proc {failed[0]} exited rc="
                    f"{polled[failed[0]]}; killing the gang\n"
                )
                # the FIRST failing child's real code is the gang's exit
                # code — siblings are about to be killed (-9) and their
                # placeholder must not mask it (code-review r4)
                rcs = [polled[failed[0]]]
                break
            if deadline and time.monotonic() > deadline:
                sys.stdout.write("[launch] gang timed out; killing children\n")
                rcs = [124]  # timeout exit code, not an escaping exception
                break
            time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for t in threads:
        t.join(timeout=5)
    return max(abs(rc) for rc in rcs) if rcs else 1


def launch(
    script: str,
    script_args: list[str] | None = None,
    num_processes: int = 2,
    coordinator: str | None = None,
    cpu_devices_per_process: int = 0,
    timeout: float | None = None,
    max_restarts: int = 0,
    restart_from: str | None = None,
) -> int:
    """Spawn the gang; stream prefixed output; return max child exit code.

    With ``max_restarts > 0`` the launcher is the failure-recovery loop
    the reference delegates to Spark (``spark.task.maxFailures``,
    SURVEY.md §5): any child death kills the whole gang generation and
    a fresh gang is relaunched — up to ``max_restarts`` times — with
    ``ELEPHAS_RESUME=1`` exported so the script's
    ``fit(checkpoint_dir=os.environ["ELEPHAS_CHECKPOINT_DIR"],
    resume=...)`` continues from the newest snapshot under
    ``restart_from``. A fresh coordinator port is chosen per generation
    (unless pinned explicitly), so a half-dead coordination service
    can't wedge the relaunch.
    """
    for attempt in range(max_restarts + 1):
        rc = _run_gang_once(
            script, script_args, num_processes,
            coordinator or f"127.0.0.1:{free_port()}",
            cpu_devices_per_process, timeout,
            restart_from=restart_from, attempt=attempt,
        )
        if rc == 0 or attempt == max_restarts:
            return rc
        sys.stdout.write(
            f"[launch] gang generation {attempt} failed (rc={rc}); "
            f"restarting ({attempt + 1}/{max_restarts})"
            + (f" from {restart_from}\n" if restart_from else "\n")
        )
        sys.stdout.flush()
    return rc


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m elephas_tpu.launch", description=__doc__
    )
    p.add_argument("--num-processes", type=int, default=2)
    p.add_argument("--coordinator", default=None, help="host:port of process 0")
    p.add_argument(
        "--cpu-devices-per-process",
        type=int,
        default=0,
        help="simulate with N virtual CPU devices per process (testing)",
    )
    p.add_argument(
        "--max-restarts",
        type=int,
        default=0,
        help="relaunch the whole gang up to N times after a child failure "
             "(elastic checkpoint-restart; pair with --restart-from)",
    )
    p.add_argument(
        "--restart-from",
        default=None,
        metavar="CKPT_DIR",
        help="checkpoint dir exported to children as "
             "ELEPHAS_CHECKPOINT_DIR; restarted generations also get "
             "ELEPHAS_RESUME=1 so fit() resumes from the newest snapshot",
    )
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    return launch(
        args.script,
        args.script_args,
        num_processes=args.num_processes,
        coordinator=args.coordinator,
        cpu_devices_per_process=args.cpu_devices_per_process,
        max_restarts=args.max_restarts,
        restart_from=args.restart_from,
    )


if __name__ == "__main__":
    sys.exit(main())
