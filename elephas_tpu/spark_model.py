"""SparkModel — the master-side façade for data-parallel Keras training.

Reference surface: ``[U] elephas/spark_model.py`` — ``SparkModel``,
``SparkMLlibModel``, ``load_spark_model`` (SURVEY.md §2, §3.1–3.4). The
constructor/kwarg surface is the parity contract: ``SparkModel(model,
mode=, frequency=, parameter_server_mode=, num_workers=, custom_objects=,
batch_size=, port=)`` with ``.fit/.predict/.evaluate/.save`` and a
``master_network`` property.

TPU-first redesign: ``fit`` does not ship pickled closures to executors.
It maps RDD partitions onto a ``('workers',)`` device mesh and runs the
whole training loop as compiled XLA programs (see
:mod:`elephas_tpu.worker`). ``parameter_server_mode`` is accepted for
parity: when set, an actual HTTP/TCP weight store is started on the driver
(``elephas_tpu.parameter``) and kept in sync at epoch boundaries so
external observers (dashboards, cross-host pollers) see live weights —
but the hot-path synchronization is always in-XLA collectives, never
pickle round-trips.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import queue
import threading

import numpy as np

from elephas_tpu import telemetry
from elephas_tpu.data.rdd import Rdd
from elephas_tpu.parallel.mesh import worker_mesh
from elephas_tpu.utils import rdd_utils
from elephas_tpu.worker import (
    FREQUENCIES, MODES, JaxWork, MeshRunner, reads_model,
)

logger = logging.getLogger(__name__)

# trace-id run counter for fit() (ISSUE 13): process-monotonic like
# telemetry.instance_label(), so gang processes running identical
# schedules mint identical ids (no pids, no wall time)
_fit_trace_ids = itertools.count()


class _WeightPublisher:
    """Latest-wins background publication to the in-process weight
    store (ISSUE 2): the epoch loop hands off a snapshot and keeps
    training while ``set_weights`` runs on a daemon thread. The queue
    holds ONE snapshot — a slow store drops intermediate epochs rather
    than stalling training (external pollers see a bounded-stale view;
    the end-of-fit publish is always synchronous and final)."""

    _STOP = object()

    def __init__(self, server):
        self._server = server
        self._q: queue.Queue = queue.Queue(maxsize=1)
        self._thread = threading.Thread(
            target=self._run, name="elephas-ps-publish", daemon=True
        )
        self._thread.start()

    def _run(self):
        while True:
            item = self._q.get()
            if item is self._STOP:
                return
            try:
                self._server.set_weights(item)
            except Exception:  # publication is best-effort mid-fit
                logger.exception("background weight publication failed")

    def publish(self, weights) -> None:
        try:
            self._q.put_nowait(weights)
        except queue.Full:  # replace the stale queued snapshot
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            try:
                self._q.put_nowait(weights)
            except queue.Full:
                pass  # a concurrent publish won the slot; equally fresh

    def stop(self) -> None:
        self._q.put(self._STOP)  # behind any queued snapshot: drains first
        self._thread.join(timeout=30)


class SparkModel:
    def __init__(
        self,
        model,
        mode: str = "synchronous",
        frequency: str = "epoch",
        parameter_server_mode: str | None = None,
        num_workers: int | None = None,
        custom_objects: dict | None = None,
        batch_size: int = 32,
        port: int = 4000,
        ps_overlap: bool | None = None,
        ps_journal_dir: str | None = None,
        ps_shards: int = 1,
        failure_budget: int = 0,
        reassign_orphans: bool = True,
        model_parallel: int = 1,
        pipeline_parallel: int = 1,
        pipeline_microbatches: int = 4,
        sequence_parallel: int = 1,
        sequence_attention: str = "ring",
        *args,
        **kwargs,
    ):
        import keras

        if not isinstance(model, keras.Model):
            raise ValueError(f"model must be a keras.Model, got {type(model)}")
        if getattr(model, "optimizer", None) is None:
            raise ValueError(
                "model must be compiled (optimizer/loss/metrics) before "
                "wrapping in SparkModel — same contract as the reference"
            )
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        if frequency not in FREQUENCIES:
            raise ValueError(
                f"frequency must be one of {FREQUENCIES}, got {frequency!r}"
            )
        if parameter_server_mode not in (None, "http", "socket", "native"):
            # validated here (not in start_server) so every gang process
            # fails fast and identically — non-coordinators skip
            # start_server entirely
            raise ValueError(
                f"parameter_server_mode must be 'http', 'socket', 'native' "
                f"or None, got {parameter_server_mode!r}"
            )

        self._master_network = model
        self.mode = mode
        self.frequency = frequency
        self.parameter_server_mode = parameter_server_mode
        self.custom_objects = custom_objects
        self.batch_size = batch_size
        self.port = port
        # overlapped publication (ISSUE 2): epoch-boundary set_weights on
        # the external store rides a background thread instead of
        # blocking the epoch loop. Default: on for async/hogwild, OFF
        # for synchronous (which stays bit-exact and blocking).
        self.ps_overlap = (
            mode != "synchronous" if ps_overlap is None else bool(ps_overlap)
        )
        # fault tolerance (ISSUE 3): journal the external weight store
        # (crash-restartable PS; also the sub-epoch resume source for
        # fit(resume=True)), and tolerate up to `failure_budget` lost
        # worker partitions before aborting a fit
        self.ps_journal_dir = ps_journal_dir
        self.failure_budget = max(0, int(failure_budget))
        # sharded PS topology (ISSUE 6): ps_shards > 1 hosts the
        # external weight store as N per-shard servers (each journaling
        # under journal_dir/shard-<i>/) reachable via `ps_endpoints`
        self.ps_shards = int(ps_shards)
        if self.ps_shards < 1:
            raise ValueError(f"ps_shards must be >= 1, got {ps_shards}")
        if self.ps_shards > 1 and parameter_server_mode == "native":
            raise ValueError(
                "ps_shards > 1 needs parameter_server_mode='http' or "
                "'socket' — the native raw-f32 wire has no shard "
                "identity or sequence IDs"
            )
        # elastic membership (ISSUE 6): within failure_budget, a lost
        # worker partition's rows are REASSIGNED to the survivors
        # instead of dropped (False restores the ISSUE 3 drop behavior)
        self.reassign_orphans = bool(reassign_orphans)
        self._publisher = None
        self.model_parallel = int(model_parallel)
        self.pipeline_parallel = int(pipeline_parallel)
        self.pipeline_microbatches = int(pipeline_microbatches)
        self.sequence_parallel = int(sequence_parallel)
        self.sequence_attention = str(sequence_attention)
        self.kwargs = kwargs
        if self.sequence_attention not in ("ring", "ulysses"):
            raise ValueError(
                f"sequence_attention must be 'ring' or 'ulysses', got "
                f"{sequence_attention!r}"
            )

        active = [
            name
            for name, n in (
                ("model_parallel", self.model_parallel),
                ("pipeline_parallel", self.pipeline_parallel),
                ("sequence_parallel", self.sequence_parallel),
            )
            if n > 1
        ]
        # model_parallel composes with sequence_parallel (3-D
        # ('data','seq','model') mesh) AND with pipeline_parallel (r5:
        # ('data','stages','model') — stage weights width-shard inside
        # each ring position); pipeline × sequence stays exclusive
        if (
            "pipeline_parallel" in active
            and "sequence_parallel" in active
        ):
            raise ValueError(
                "pipeline_parallel and sequence_parallel cannot compose "
                "— shard depth (stages) with model_parallel instead, or "
                "drop one of the two"
            )
        if self.pipeline_parallel > 1:
            import jax

            need = self.pipeline_parallel * self.model_parallel
            if need > len(jax.devices()):
                raise ValueError(
                    f"pipeline_parallel={pipeline_parallel}"
                    + (
                        f" × model_parallel={model_parallel}"
                        if self.model_parallel > 1
                        else ""
                    )
                    + f" exceeds the {len(jax.devices())} available "
                    f"devices"
                )
            if self.mode != "synchronous":
                raise ValueError(
                    "pipeline_parallel trains synchronously (one model, "
                    "depth-sharded); asynchronous/hogwild modes apply to "
                    "data-parallel replicas"
                )
            from elephas_tpu.ops.pipeline import pipeline_mesh

            # DP×PP(×TP): num_workers asks for data replicas AROUND the
            # pipeline — each data row runs its own activation ring
            # (capped to the device budget, like the TP/SP branches)
            max_dp = max(1, len(jax.devices()) // need)
            dp = min(num_workers, max_dp) if num_workers else 1
            self.mesh = pipeline_mesh(
                self.pipeline_parallel, dp,
                model_parallel=self.model_parallel,
            )
            self.num_workers = dp
            self._runner = None
            self._parameter_server = None
            self.training_histories = []
            return

        if self.model_parallel > 1 and self.sequence_parallel <= 1:
            # models bigger than one chip: 2-D ('data', 'model') mesh —
            # workers are the data-axis replicas (the reference's
            # fit-one-worker ceiling removed; SURVEY.md §2a TP row)
            from elephas_tpu.parallel.tensor import dp_tp_mesh

            import jax

            self.mesh = self._dp_submesh(
                self.model_parallel, "model_parallel", dp_tp_mesh,
                num_workers, jax,
            )
            self.num_workers = self.mesh.shape["data"]
        elif self.sequence_parallel > 1:
            # sequences longer than one chip's memory: 2-D ('data',
            # 'seq') mesh — attention rings KV shards over the seq axis
            # (SURVEY.md §5 long-context row; TPU-native extension)
            from elephas_tpu.parallel.sequence import dp_sp_mesh

            import jax

            if self.mode != "synchronous":
                raise ValueError(
                    "sequence_parallel trains synchronously (the seq "
                    "shards jointly compute one model's step); "
                    "asynchronous/hogwild modes apply to data-parallel "
                    "replicas"
                )
            if self.frequency == "fit":
                raise ValueError(
                    "frequency='fit' selects per-replica local-SGD "
                    "semantics, which don't apply under "
                    "sequence_parallel (synchronous per-step training; "
                    "use frequency='epoch')"
                )
            if self.model_parallel > 1:
                # TP×SP: 3-D ('data','seq','model') mesh — Megatron
                # weight shards and ring/ulysses sequence shards compose
                from elephas_tpu.parallel.sequence import dp_sp_tp_mesh

                self.mesh = self._dp_submesh(
                    self.sequence_parallel * self.model_parallel,
                    "sequence_parallel×model_parallel",
                    lambda n, data_parallel: dp_sp_tp_mesh(
                        self.sequence_parallel, self.model_parallel,
                        data_parallel,
                    ),
                    num_workers, jax,
                )
            else:
                self.mesh = self._dp_submesh(
                    self.sequence_parallel, "sequence_parallel",
                    dp_sp_mesh, num_workers, jax,
                )
            self.num_workers = self.mesh.shape["data"]
        else:
            self.mesh = worker_mesh(num_workers)
            self.num_workers = self.mesh.devices.size
        self._runner = None
        self._parameter_server = None
        self.training_histories: list[dict] = []

    @staticmethod
    def _dp_submesh(parallel_n, label, build_mesh, num_workers, jax):
        """2-D ``('data', <axis>)`` mesh for a model/sequence-parallel
        strategy: the second axis gets ``parallel_n`` devices, data
        replicas fill the rest (capped by ``num_workers`` if given)."""
        max_dp = len(jax.devices()) // parallel_n
        if max_dp < 1:
            raise ValueError(
                f"{label}={parallel_n} exceeds the "
                f"{len(jax.devices())} available devices"
            )
        dp = min(num_workers, max_dp) if num_workers else max_dp
        return build_mesh(parallel_n, data_parallel=dp)

    # -- properties ----------------------------------------------------

    @property
    def master_network(self):
        return self._master_network

    @master_network.setter
    def master_network(self, network):
        self._master_network = network
        self._runner = None

    def get_config(self) -> dict:
        return {
            "mode": self.mode,
            "frequency": self.frequency,
            "parameter_server_mode": self.parameter_server_mode,
            "num_workers": self.num_workers,
            "batch_size": self.batch_size,
            "port": self.port,
            "ps_overlap": self.ps_overlap,
            "ps_journal_dir": self.ps_journal_dir,
            "ps_shards": self.ps_shards,
            "failure_budget": self.failure_budget,
            "reassign_orphans": self.reassign_orphans,
            "model_parallel": self.model_parallel,
            "pipeline_parallel": self.pipeline_parallel,
            "pipeline_microbatches": self.pipeline_microbatches,
            "sequence_parallel": self.sequence_parallel,
            "sequence_attention": self.sequence_attention,
        }

    # -- parameter server (API parity; see module docstring) -----------

    def start_server(self, restore_journal: bool = True) -> None:
        if self.parameter_server_mode is None:
            return
        from elephas_tpu.parallel.distributed import is_coordinator

        if not is_coordinator():
            # one weight store per gang, hosted by process 0 (the
            # reference's PS lives on the driver; N stores on one shared
            # port would race) — non-coordinators publish nothing
            return
        from elephas_tpu.parameter.server import HttpServer, SocketServer

        cls = {"http": HttpServer, "socket": SocketServer}.get(
            self.parameter_server_mode
        )
        if cls is None:
            # mode already validated in __init__; only 'native' remains
            from elephas_tpu.parameter.native import NativeParameterServer

            cls = NativeParameterServer
        kwargs = {}
        if self.ps_journal_dir:
            # journaled store (ISSUE 3): restartable, and the sub-epoch
            # state source for fit(resume=True) — the constructor
            # replays an existing journal before serving. A fresh
            # (non-resume) fit passes restore_journal=False: starting
            # over must not silently continue from a previous run's
            # journal (it gets overwritten as this run snapshots).
            kwargs["restore_journal"] = restore_journal
            if self.ps_shards <= 1:
                kwargs["journal_dir"] = self.ps_journal_dir
        if self.ps_shards > 1:
            # sharded topology (ISSUE 6): N per-shard servers, each
            # holding only its slice and journaling independently
            # under journal_dir/shard-<i>/; workers reach them through
            # `ps_endpoints` (port=0 auto-assigns, a fixed port takes
            # consecutive ports from there)
            from elephas_tpu.parameter.sharding import ShardedServerGroup

            ports = (
                [0] * self.ps_shards
                if not self.port
                else [self.port + i for i in range(self.ps_shards)]
            )
            self._parameter_server = ShardedServerGroup(
                cls,
                self._master_network.get_weights(),
                self.ps_shards,
                mode=self.mode,
                ports=ports,
                journal_dir=self.ps_journal_dir,
                **kwargs,
            )
        else:
            self._parameter_server = cls(
                self._master_network.get_weights(), mode=self.mode,
                port=self.port, **kwargs,
            )
        self._parameter_server.start()
        if self.ps_overlap and self.mode != "synchronous":
            self._publisher = _WeightPublisher(self._parameter_server)

    @property
    def ps_endpoints(self) -> str | None:
        """The running external weight store's endpoint list — one
        ``host:port`` (single PS) or a comma-separated shard list in
        shard order (``ps_shards > 1``), the exact string an
        :class:`~elephas_tpu.worker.AsynchronousSparkWorker` takes as
        ``master=``. None until :meth:`start_server` ran."""
        server = self._parameter_server
        if server is None:
            return None
        if hasattr(server, "endpoints"):
            return server.endpoints
        return f"127.0.0.1:{server.port}"

    def stop_server(self) -> None:
        self._stop_publisher()
        if self._parameter_server is not None:
            self._parameter_server.stop()
            self._parameter_server = None

    def _stop_publisher(self) -> None:
        if self._publisher is not None:
            self._publisher.stop()
            self._publisher = None

    def scrape(self) -> str:
        """The process telemetry registry (training, PS, serving, and
        chaos counters alike) rendered as Prometheus exposition text —
        the in-process twin of the HTTP parameter server's
        ``GET /metrics`` (ISSUE 5)."""
        return telemetry.scrape_text()

    def _publish_weights(self, final: bool = False) -> None:
        if self._parameter_server is None:
            return
        telemetry.registry().counter(
            "elephas_ps_weight_publications_total",
            "Master-weight snapshots published to the external store",
        ).inc()
        weights = self._get_runner().host_weights()
        if self._publisher is not None and not final:
            self._publisher.publish(weights)
            return
        if final:
            # drain the background publisher so the synchronous final
            # publish can't be overwritten by a stale queued snapshot
            self._stop_publisher()
        self._parameter_server.set_weights(weights)

    # -- training ------------------------------------------------------

    # datasets larger than this stage blockwise instead of whole-epoch
    STREAM_THRESHOLD_BYTES = 1 << 30

    def fit(
        self,
        rdd: Rdd,
        epochs: int = 10,
        batch_size: int | None = None,
        verbose: int = 0,
        validation_split: float = 0.0,
        profile_dir: str | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        resume: bool = False,
        steps_per_epoch: int | None = None,
        stream_block_steps: int | None = None,
        history_log: str | None = None,
        **kwargs,
    ) -> dict:
        """Train on a simple RDD of ``(x_row, y_row)`` pairs — or on an
        ``(x, y)`` pair of array-likes (``np.ndarray``, ``np.memmap``,
        ``h5py.Dataset``) for datasets that should not be materialized.
        Returns the Keras-style history dict (also appended to
        ``training_histories``).

        The master model holds the trained weights after the call,
        always. While the call runs it is synced at an epoch boundary
        only ahead of something that reads it there (parameter-server
        publication, a checkpoint that is due, per-epoch validation):
        a plain ``fit`` moves the state to the host once, at its end.

        Beyond the reference's surface (SURVEY.md §5):

        - ``profile_dir``: capture a ``jax.profiler`` trace of the compiled
          epochs (view in TensorBoard/Perfetto).
        - ``checkpoint_dir``/``checkpoint_every``: snapshot model+optimizer
          every N epochs; ``resume=True`` restarts from the latest
          snapshot, training only the remaining epochs. With
          ``parameter_server_mode`` and ``ps_journal_dir`` set, resume
          also replays the PS journal — sub-epoch state newer than the
          checkpoint — and seeds both the server and the master model
          from it (ISSUE 3).
        - out-of-core streaming: array-like inputs bigger than
          ``STREAM_THRESHOLD_BYTES`` (or lazily backed, or with
          ``stream_block_steps`` set) stream block-by-block through the
          compiled epoch program instead of staging whole epochs —
          datasets beyond HBM (and beyond host RAM, for memmap/h5py
          sources) train with the same math (see
          :mod:`elephas_tpu.data.streaming`).
        """
        batch_size = batch_size or self.batch_size
        with telemetry.trace_span(
            "fit.call", epochs=int(epochs), batch_size=int(batch_size),
            workers=self.num_workers,
        ) as call, JaxWork(call):  # the call's totals, inner spans' included
            start_epoch = self._resume(checkpoint_dir, resume)
            # cross-process trace context minted at the training edge
            # (ISSUE 13): every event this fit records — the fit.* spans
            # of stage-in and of each epoch, fit.epoch boundaries, weight
            # publications, and any PS round-trips on this thread —
            # carries one deterministic run id, and the PS clients
            # forward it over the wire so server-side applies/journal
            # writes join the same trace. The id is a process-monotonic
            # run count + start epoch: no pids, no wall time (gang
            # processes mint identical ids).
            trace_id = f"fit-r{next(_fit_trace_ids)}e{start_epoch}"
            call.set(trace=trace_id)  # the root closes after the scope
            with telemetry.trace_scope(trace_id):
                return self._fit_scoped(
                    rdd,
                    epochs,
                    batch_size,
                    verbose,
                    validation_split,
                    profile_dir=profile_dir,
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every,
                    resume=resume,
                    start_epoch=start_epoch,
                    steps_per_epoch=steps_per_epoch,
                    stream_block_steps=stream_block_steps,
                    history_log=history_log,
                )

    def _fit_scoped(
        self, rdd, epochs, batch_size, verbose, validation_split,
        steps_per_epoch=None, stream_block_steps=None, **fit_kwargs,
    ) -> dict:
        """``fit`` inside its ``fit.call`` span and trace scope: route
        the input to the staged or the streamed path."""
        if not isinstance(rdd, Rdd):
            x, y = rdd
            return self._fit_arrays(
                x,
                y,
                epochs,
                batch_size,
                verbose,
                validation_split,
                steps_per_epoch=steps_per_epoch,
                stream_block_steps=stream_block_steps,
                **fit_kwargs,
            )
        if rdd.is_lazy() and self.frequency != "fit":
            # partitions are row-range views of backing stores — stream
            # them instead of materializing (the cluster-resident-RDD
            # property on the parity entry point; VERDICT r2 missing #6).
            # frequency='fit' (train whole fit locally, average once)
            # contradicts streaming, so lazy RDDs fall through to the
            # eager path there — partition_arrays gathers each partition
            # in one ranged read.
            from elephas_tpu.data.streaming import lazy_rdd_sources

            x, y = lazy_rdd_sources(rdd)
            return self._fit_arrays(
                x,
                y,
                epochs,
                batch_size,
                verbose,
                validation_split,
                steps_per_epoch=steps_per_epoch,
                stream_block_steps=stream_block_steps,
                **fit_kwargs,
            )
        with telemetry.trace_span("fit.partition_arrays") as sp:
            if (
                not rdd.is_lazy()
                and self.pipeline_parallel <= 1
                and rdd.getNumPartitions() != self.num_workers
            ):
                # lazy RDDs skip the element-wise repartition (it would
                # materialize row-by-row); the runner's partition shaping
                # re-splits the ranged reads to the mesh instead. Pipeline
                # stages are depth shards, not data shards — repartitioning
                # for them would just shuffle rows to re-concatenate.
                rdd = rdd.repartition(self.num_workers)
            partitions = rdd_utils.partition_arrays(rdd)
            sp.set(
                rows=sum(len(x) for x, _ in partitions),
                bytes=sum(x.nbytes + y.nbytes for x, y in partitions),
            )
        return self._fit_partitions(
            partitions,
            epochs,
            batch_size,
            verbose,
            validation_split,
            **fit_kwargs,
        )

    def _resume(self, checkpoint_dir, resume) -> int:
        """Restore what ``fit(resume=True)`` resumes from, before any
        data is staged; returns the epoch to start at (0 for a fresh
        fit)."""
        start_epoch = 0
        if checkpoint_dir and resume:
            meta = self._get_runner().restore_checkpoint(
                checkpoint_dir, self.custom_objects
            )
            if meta is not None:
                start_epoch = int(meta["epoch"])
                logger.info(
                    "resuming from %s at epoch %d", checkpoint_dir, start_epoch
                )
        if resume and self.ps_journal_dir:
            # fit(resume=True) end-to-end (ISSUE 3): the PS journal may
            # carry sub-epoch updates newer than the epoch-granular
            # checkpoint restored above — adopt the journaled weights as
            # the master state, and start_server in _fit_partitions
            # re-seeds the PS from the same journal, so neither the
            # workers nor external pollers regress past the last snapshot
            journaled = self._load_ps_journal_weights()
            if journaled is not None:
                self._master_network.set_weights(journaled)
                logger.info(
                    "resume: adopted journaled parameter-server state "
                    "from %s", self.ps_journal_dir,
                )
        return start_epoch

    def _fit_arrays(
        self,
        x,
        y,
        epochs,
        batch_size,
        verbose,
        validation_split,
        steps_per_epoch=None,
        stream_block_steps=None,
        **fit_kwargs,
    ) -> dict:
        from elephas_tpu.data.streaming import (
            ShardedStream,
            estimate_nbytes,
            is_lazy_source,
        )

        # each member coerces independently: a memmap x paired with a
        # plain-list y must still stream x while y becomes indexable
        # (streaming gathers by numpy index arrays)
        if not is_lazy_source(x) and type(x) is not np.ndarray:
            x = np.asarray(x)
        if not is_lazy_source(y) and type(y) is not np.ndarray:
            y = np.asarray(y)
        lazily_backed = is_lazy_source(x) or is_lazy_source(y)
        should_stream = (
            stream_block_steps is not None
            or steps_per_epoch is not None
            or lazily_backed
            or estimate_nbytes(x, y) > self.STREAM_THRESHOLD_BYTES
        )
        if not should_stream:
            if self.pipeline_parallel > 1:
                # the pipeline consumes whole batches — splitting into
                # per-worker partitions only to re-concatenate would copy
                # the dataset
                partitions = [(x, y)]
            else:
                xs = np.array_split(x, self.num_workers)
                ys = np.array_split(y, self.num_workers)
                # fewer rows than workers → empty splits; drop them and
                # let the runner's partition shaping fill the mesh (same
                # contract as partition_arrays on the RDD path)
                partitions = [(a, b) for a, b in zip(xs, ys) if len(a)]
            return self._fit_partitions(
                partitions, epochs, batch_size, verbose, validation_split,
                **fit_kwargs,
            )
        n = len(x)
        val_spec = None
        num_rows = None
        if validation_split and validation_split > 0.0:
            # the train split stays a lazy view via the stream's
            # num_rows limit, and the validation tail is evaluated in
            # BLOCKS per epoch (r5, VERDICT r4 #7) — neither span is
            # ever materialized whole, so memmap/h5py datasets beyond
            # host RAM can hold out validation too
            n_val = min(max(1, int(n * validation_split)), n - 1)
            num_rows = n - n_val
            val_spec = (x, y, n, n_val)
        # The DP runner interprets batch_size per worker (reference
        # semantics), and the stream's batch is per worker — they agree.
        # The TP/SP/PP trainers interpret batch_size as the GLOBAL
        # batch, so their streams must divide it across the data
        # replicas (with the staged path's own rounding) or the same
        # fit() call would train a dp×-larger batch when it streams.
        stream_batch = batch_size
        if self.pipeline_parallel > 1:
            m = self.pipeline_microbatches
            stream_batch = max(
                m, (batch_size // (m * self.num_workers)) * m
            )
        elif self.model_parallel > 1 or self.sequence_parallel > 1:
            stream_batch = max(1, batch_size // self.num_workers)
        stream = ShardedStream(
            x,
            y,
            stream_batch,
            self.num_workers,
            block_steps=stream_block_steps or 16,
            steps_per_epoch=steps_per_epoch,
            num_rows=num_rows,
        )
        val_block = max(
            stream_batch, (stream_block_steps or 16) * stream_batch
        ) * max(1, self.num_workers)
        return self._fit_partitions(
            None, epochs, batch_size, verbose, 0.0,
            stream=stream, val_spec=val_spec, val_block=val_block,
            **fit_kwargs,
        )

    def _fit_partitions(
        self,
        partitions,
        epochs,
        batch_size,
        verbose=0,
        validation_split=0.0,
        profile_dir=None,
        checkpoint_dir=None,
        checkpoint_every=1,
        resume=False,
        start_epoch=0,
        stream=None,
        val_partitions=None,
        val_spec=None,
        val_block=None,
        history_log=None,
    ) -> dict:
        runner = self._get_runner()
        if start_epoch >= epochs:
            history = {"loss": []}
            self.training_histories.append(history)
            return history
        epochs = epochs - start_epoch

        if partitions is not None:
            # ISSUE 3: drop worker partitions whose executors died (the
            # chaos harness injects these) and continue on the
            # survivors, up to the configured failure budget
            partitions = self._survive_partitions(partitions)

        if validation_split and validation_split > 0.0:
            # hold out the global tail fraction (keras semantics) by
            # SLICING the ordered partitions at the global cut — pure
            # views, no concatenation (the old concat staged a second
            # full host copy of the dataset; VERDICT r4 weak #5)
            lens = [len(p[0]) for p in partitions]
            n_total = sum(lens)
            n_val = min(max(1, int(n_total * validation_split)), n_total - 1)
            cut = n_total - n_val
            train_parts, val_parts, acc = [], [], 0
            for (px, py), ln in zip(partitions, lens):
                lo, hi = acc, acc + ln
                acc = hi
                if hi <= cut:
                    train_parts.append((px, py))
                elif lo >= cut:
                    val_parts.append((px, py))
                else:
                    k = cut - lo
                    train_parts.append((px[:k], py[:k]))
                    val_parts.append((px[k:], py[k:]))
            partitions = train_parts
            val_partitions = val_parts
        if partitions is not None:
            with telemetry.trace_span("fit.to_mesh"):
                partitions = runner._fit_partitions_to_mesh(partitions)

        self.start_server(restore_journal=bool(resume))
        try:
            # Each callback declares whether it reads the master model:
            # the runner syncs it at an epoch boundary only ahead of one
            # that does, so telemetry alone moves no state to the host.
            # First, epoch boundaries land on the shared trace timeline
            # (ISSUE 5) so training cadence can be correlated with PS
            # round-trips and chaos events in one Chrome trace
            @reads_model(False)
            def emit_epoch(epoch, loss):
                telemetry.emit("fit.epoch", epoch=int(epoch), loss=float(loss))

            callbacks = [emit_epoch]
            if self._parameter_server is not None:
                # keep the external weight store live at epoch boundaries
                # (the runner syncs the master model ahead of a callback
                # that reads it, as this one does at every epoch)
                @reads_model(True)
                def publish(_epoch, _loss):
                    self._publish_weights()

                callbacks.append(publish)
            if checkpoint_dir:

                def ckpt_due(epoch):
                    return (start_epoch + epoch + 1) % checkpoint_every == 0

                @reads_model(ckpt_due)
                def save_ckpt(epoch, _loss):
                    if ckpt_due(epoch):
                        runner.save_checkpoint(
                            checkpoint_dir, start_epoch + epoch + 1
                        )

                callbacks.append(save_ckpt)
            if history_log:
                # epoch-level JSONL metrics export (SURVEY.md §5: the
                # reference has none) — live lines per epoch from the
                # coordinator, one final line with the full history
                import time as _time

                from elephas_tpu.parallel.distributed import is_coordinator

                t_start = _time.time()
                if is_coordinator():

                    @reads_model(False)
                    def log_epoch(epoch, loss):
                        with open(history_log, "a") as f:
                            f.write(
                                json.dumps(
                                    {
                                        "epoch": start_epoch + epoch + 1,
                                        "loss": float(loss),
                                        "elapsed_s": round(
                                            _time.time() - t_start, 3
                                        ),
                                    }
                                )
                                + "\n"
                            )

                    callbacks.append(log_epoch)
            val_history: dict[str, list[float]] = {}
            val_evaluate = self._make_val_evaluate(
                runner, val_partitions, val_spec, val_block, batch_size
            )
            if val_evaluate is not None and self.frequency != "fit":
                # per-epoch validation, like keras.fit's val_* history
                # (evaluate stages its weights from the master model)
                @reads_model(True)
                def eval_cb(_epoch, _loss):
                    for k, v in val_evaluate().items():
                        val_history.setdefault(f"val_{k}", []).append(v)

                callbacks.append(eval_cb)

            if profile_dir:
                import jax

                trace_ctx = jax.profiler.trace(profile_dir)
            else:
                import contextlib

                trace_ctx = contextlib.nullcontext()
            with trace_ctx:
                if stream is not None:
                    history = runner.run_epochs_stream(
                        stream, epochs, verbose, callbacks=callbacks
                    )
                else:
                    history = runner.run_epochs(
                        partitions, epochs, batch_size, verbose, callbacks=callbacks
                    )
            if val_evaluate is not None and self.frequency == "fit":
                # 'fit' averages worker weights only once, after the epoch
                # loop — per-epoch callbacks would evaluate worker-0's
                # un-averaged replica, so validate once against the final
                # averaged model instead
                for k, v in val_evaluate().items():
                    val_history[f"val_{k}"] = [v]
            if checkpoint_dir:
                # terminal snapshot regardless of checkpoint_every cadence
                runner.save_checkpoint(checkpoint_dir, start_epoch + epochs, history)
            history.update(val_history)
            if history_log:
                from elephas_tpu.parallel.distributed import is_coordinator

                if is_coordinator():
                    with open(history_log, "a") as f:
                        f.write(
                            json.dumps({"final": True, "history": history})
                            + "\n"
                        )
            self._publish_weights(final=True)
        finally:
            self.stop_server()
        self.training_histories.append(history)
        return history

    def _load_ps_journal_weights(self):
        """Journaled PS weights for fit(resume=True), or None. With
        ``ps_shards > 1`` each shard journaled only its slice — gather
        them through the SAME deterministic shard map the servers used;
        a partially-journaled topology (some shards never snapshotted)
        is refused as a resume source rather than mixing journal slices
        with the (older) checkpoint weights."""
        from elephas_tpu.parameter import journal as ps_journal

        if self.ps_shards <= 1:
            state = ps_journal.load_journal(self.ps_journal_dir)
            return None if state is None else state[0]
        from elephas_tpu.parameter.sharding import (
            ShardMap,
            shard_journal_dir,
        )

        smap = ShardMap.from_weights(
            self._master_network.get_weights(), self.ps_shards
        )
        slices: list = [None] * self.ps_shards
        missing = []
        for i in range(self.ps_shards):
            state = ps_journal.load_journal(
                shard_journal_dir(self.ps_journal_dir, i)
            )
            if state is None:
                missing.append(i)
            else:
                slices[i] = state[0]
        if missing:
            # warn whenever the topology is PARTIALLY journaled — which
            # shard is missing must not decide whether the operator
            # hears that newer journaled slices were discarded
            if len(missing) < self.ps_shards:
                logger.warning(
                    "resume: shard journal(s) %s missing under %s (%d "
                    "of %d exist) — refusing a mixed journal/checkpoint "
                    "weight state; resuming from the checkpoint alone",
                    missing, self.ps_journal_dir,
                    self.ps_shards - len(missing), self.ps_shards,
                )
            return None
        return smap.gather(slices)

    def _survive_partitions(self, partitions):
        """Worker-loss supervision (ISSUE 3): a partition whose executor
        died (``fault.check_partition`` raises under an active chaos
        plan) is dropped and training continues on the survivors — the
        elastic-training degrade — until more than ``failure_budget``
        workers are gone, which aborts with a clear error instead of
        silently training on a sliver of the data."""
        from elephas_tpu.fault.plan import (
            FaultBudgetExceeded,
            WorkerFault,
            active_plan,
            check_partition,
        )

        if active_plan() is None:
            return partitions
        survivors, orphans, lost = [], [], []
        for i, part in enumerate(partitions):
            try:
                check_partition(i)
            except WorkerFault as e:
                logger.warning("worker partition %d lost: %s", i, e)
                lost.append(i)
                orphans.append(part)
                continue
            survivors.append(part)
        if not lost:
            return partitions
        if len(lost) > self.failure_budget or not survivors:
            raise FaultBudgetExceeded(
                f"lost {len(lost)} worker partition(s) {lost} of "
                f"{len(partitions)}, exceeding failure_budget="
                f"{self.failure_budget} (survivors: {len(survivors)}) — "
                f"raise the budget to continue degraded, or repair the "
                f"failing workers"
            )
        if self.reassign_orphans:
            # elastic membership (ISSUE 6): the orphaned partitions'
            # rows are still driver-side — re-stage them onto the
            # survivors (round-robin, whole partitions) so the epoch
            # trains on ALL the data with fewer workers, instead of
            # silently shrinking the dataset by the dead workers' share
            survivors = self._reassign_orphans(survivors, orphans)
            logger.warning(
                "reassigned %d orphaned partition(s) %s across %d "
                "survivors (failure_budget=%d) — full dataset, fewer "
                "workers", len(lost), lost, len(survivors),
                self.failure_budget,
            )
            return survivors
        logger.warning(
            "continuing with %d/%d worker partitions (failure_budget=%d)",
            len(survivors), len(partitions), self.failure_budget,
        )
        return survivors

    @staticmethod
    def _reassign_orphans(survivors, orphans):
        """Concatenate each orphaned partition onto a survivor
        (round-robin). ``y`` may be a pytree of row-aligned arrays
        (multi-output models) — concatenate leaf-wise."""
        import jax

        merged = list(survivors)
        for j, (ox, oy) in enumerate(orphans):
            t = j % len(merged)
            sx, sy = merged[t]
            merged[t] = (
                np.concatenate([np.asarray(sx), np.asarray(ox)]),
                jax.tree.map(
                    lambda a, b: np.concatenate(
                        [np.asarray(a), np.asarray(b)]
                    ),
                    sy, oy,
                ),
            )
        return merged

    def _make_val_evaluate(self, runner, val_partitions, val_spec,
                           val_block, batch_size):
        """The per-epoch validation evaluator, or None.

        Staged validation evaluates its (view-sliced) partitions in one
        call. Streamed validation (r5, VERDICT r4 #7) walks the held-out
        tail of the lazy source in blocks, aggregating a row-weighted
        mean — exact for loss and every mean-reduction keras metric
        (accuracy, mae, ...); distribution-stateful metrics (e.g. AUC)
        would be approximate across blocks."""
        if val_partitions is not None:
            return lambda: runner.evaluate(val_partitions, batch_size)
        if val_spec is None:
            return None
        x, y, n, n_val = val_spec
        block = max(1, int(val_block or n_val))
        if block < n_val:
            # surface the blockwise approximation for metrics that are
            # NOT row-weighted means (code-review r5): AUC-class state
            # does not average across blocks
            import keras

            mean_like = (keras.metrics.Mean, keras.metrics.MeanMetricWrapper)
            flat = []
            for m in getattr(self._master_network, "metrics", []):
                flat.extend(getattr(m, "metrics", None) or [m])
            stateful = [
                m.name
                for m in flat
                if isinstance(m, keras.metrics.Metric)
                and not isinstance(m, mean_like)
            ]
            if stateful:
                logger.warning(
                    "streamed validation evaluates the held-out tail in "
                    "blocks and aggregates a row-weighted mean — exact "
                    "for loss and mean-reduction metrics, approximate "
                    "for %s (distribution-stateful); evaluate() on the "
                    "full tail gives the exact value",
                    stateful,
                )

        def evaluate_blocks():
            totals: dict[str, float] = {}
            wsum = 0
            for lo in range(n - n_val, n, block):
                hi = min(n, lo + block)
                res = runner.evaluate(
                    [(np.asarray(x[lo:hi]), np.asarray(y[lo:hi]))],
                    batch_size,
                )
                w = hi - lo
                for k, v in res.items():
                    totals[k] = totals.get(k, 0.0) + float(v) * w
                wsum += w
            return {k: v / wsum for k, v in totals.items()}

        return evaluate_blocks

    # -- inference -----------------------------------------------------

    def predict(self, data, batch_size: int | None = None) -> np.ndarray:
        """Distributed forward pass. Accepts an Rdd of feature rows or a
        numpy array; returns stacked predictions in input order."""
        batch_size = batch_size or self.batch_size
        runner = self._get_runner()
        if isinstance(data, Rdd):
            parts = [
                np.stack([np.asarray(el) for el in p])
                for p in data.partitions()
                if p
            ]
        else:
            arr = np.asarray(data)
            parts = [a for a in np.array_split(arr, self.num_workers) if len(a)]
        return runner.predict(parts, batch_size)

    def evaluate(self, x_test, y_test=None, batch_size: int | None = None, **kwargs):
        """Distributed evaluate. Accepts (x, y) arrays or a simple RDD.
        Returns ``[loss, *metrics]`` like ``keras.Model.evaluate``."""
        batch_size = batch_size or self.batch_size
        runner = self._get_runner()
        if isinstance(x_test, Rdd):
            partitions = rdd_utils.partition_arrays(x_test)
        else:
            import jax

            x = np.asarray(x_test)
            xs = np.array_split(x, self.num_workers)
            offsets = np.cumsum([0] + [len(a) for a in xs])
            # y may be a list/tuple of per-output targets (multi-output
            # models); split each component with the same row boundaries
            partitions = [
                (
                    a,
                    jax.tree.map(
                        lambda t, lo=int(offsets[i]), hi=int(offsets[i + 1]): (
                            np.asarray(t)[lo:hi]
                        ),
                        y_test,
                    ),
                )
                for i, a in enumerate(xs)
                if len(a)
            ]
        results = runner.evaluate(partitions, batch_size)
        # pin the reporting order to keras's own metrics_names when the
        # model exposes it (one keras version bump away from silently
        # permuting insertion order); fall back to insertion order
        # (loss, per-output losses, metrics in compile order)
        names = list(getattr(self._master_network, "metrics_names", []) or [])
        if names and set(names) == set(results):
            ordered = [results[k] for k in names]
        else:
            if names and "compile_metrics" not in names:
                # one keras version bump from silently mislabeled
                # metrics — make the fallback visible (VERDICT r4 #8).
                # keras 3's lumped ['loss', 'compile_metrics'] view is
                # the NORMAL case, not a mismatch worth warning about.
                logger.warning(
                    "evaluate(): model.metrics_names %s does not match "
                    "the computed result keys %s — falling back to "
                    "insertion order (loss, per-output losses, metrics "
                    "in compile order)",
                    names, list(results),
                )
            ordered = [results.pop("loss")] + list(results.values())
        return ordered if len(ordered) > 1 else ordered[0]

    def generate(
        self,
        prompt,
        steps: int,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int = 0,
        kv_cache: bool = False,
    ):
        """Distributed autoregressive generation on the wrapper's mesh —
        the LM analogue of :meth:`predict` (the reference's inference is
        distributed too: ``[U] elephas/spark_model.py::predict``,
        SURVEY.md §3.4).

        The decode loop runs as ONE GSPMD program over the SAME mesh
        this wrapper trains on, so a model that only fits sharded can
        also decode:

        - data / seq / workers axes fan the batch out (prompts pad up to
          the axis product and the padding is sliced off);
        - ``model_parallel``: weights stay sharded through the decode
          loop under the TP planner's layouts, and with
          ``kv_cache=True`` the per-layer K/V caches shard with the
          head axis;
        - ``pipeline_parallel``: decode runs THROUGH the stage ring
          (r5) — weights stay depth-sharded (and width-sharded under
          PP×TP) for the whole generation, full-recompute per token.
          ``kv_cache=True`` instead takes the depth-REPLICATED cached
          decode (O(S·L), but the model must fit one device).

        Every gang process must make the identical call (SPMD
        contract); all return the full ``[B, P+steps]`` tokens.
        """
        from elephas_tpu.models.transformer import generate as _generate

        if self.pipeline_parallel > 1 and not kv_cache:
            return self._get_runner().generate(
                prompt, steps, temperature=temperature, top_k=top_k,
                top_p=top_p, seed=seed,
            )
        if self.pipeline_parallel > 1:
            # kv_cache decode is depth-replicated: the per-layer caches
            # live in one program — the stage axis joins the batch axes
            # (dp=1 builds a mesh without a 'data' axis; only fan over
            # the axes that exist — code-review r5). Under PP×TP the
            # model axis decodes TP-sharded like the pure-TP route.
            batch_axes = tuple(
                a for a in ("data", "stages") if a in self.mesh.shape
            )
            model_axis = "model" if self.model_parallel > 1 else None
        else:
            batch_axes, model_axis = self._decode_axes()
        return _generate(
            self._master_network,
            prompt,
            steps,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            seed=seed,
            kv_cache=kv_cache,
            mesh=self.mesh,
            batch_axes=batch_axes,
            model_axis=model_axis,
        )

    def _decode_axes(self):
        """Shared mesh-axis ladder for decode-time fan-out
        (:meth:`generate` and :meth:`serve` must agree): the batch
        rides every non-model axis of this wrapper's (non-pipeline)
        mesh, the weights shard over the model axis when one exists."""
        if self.sequence_parallel > 1:
            return (
                ("data", "seq"),
                "model" if self.model_parallel > 1 else None,
            )
        if self.model_parallel > 1:
            return ("data",), "model"
        return ("workers",), None

    def serve(
        self,
        num_slots: int = 8,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int = 0,
        buckets=None,
        steps_per_sync: int = 1,
        prefix_cache: bool = False,
        prefix_min_reuse: int = 1,
        prefill_chunk: int | None = None,
        prefill_budget: int | None = None,
        paged: bool = False,
        block_size: int | None = None,
        num_blocks: int | None = None,
        preemption: bool = False,
        kv_dtype: str = "fp",
        speculative: bool = False,
        spec_k: int | None = None,
        spec_drafter=None,
        policy=None,
        tenants=None,
        gateway_port: int | None = None,
        gateway_host: str = "127.0.0.1",
        attention: str = "flash",
        flight_recorder: int | None = 256,
    ):
        """A continuous-batching :class:`~elephas_tpu.serving.engine.\
InferenceEngine` over this wrapper's mesh — the serving analogue of
        :meth:`generate` (ISSUE 1 tentpole).

        Where :meth:`generate` is one-shot (all prompts start together,
        the batch stalls until its slowest sequence finishes, every new
        shape risks a compile), the engine admits requests into a
        slot-based KV cache at every decode step, reclaims slots on
        EOS/max-tokens, and runs ONE fixed-shape compiled decode step
        for its whole life. Submit with ``engine.submit(prompt,
        max_new_tokens, temperature=, eos_id=)``, drive with
        ``engine.step()`` / ``engine.stream()`` / ``engine.run()``.

        Works on the DP and TP meshes (the slot arena shards slots over
        the batch axes and heads over the model axis). Every gang
        process must submit the identical request sequence (SPMD
        contract, as for :meth:`generate`).

        ``paged=True`` (ISSUE 7) switches the KV storage to the paged
        block-pool arena: per-request reservations of
        ``ceil((prompt + max_new_tokens) / block_size)`` blocks out of
        ``num_blocks`` (default: capacity parity with the fixed
        arena), copy-free prefix sharing when ``prefix_cache=True``,
        and — with ``preemption=True`` — priority-based preempt/
        host-offload/resume under pool pressure.

        ``kv_dtype=`` (ISSUE 19) selects the paged arena's KV storage:
        ``"fp"`` (default) keeps float32 blocks and IS the parity
        oracle; ``"int8"`` / ``"int4"`` store quantized codes with
        per-(position, head) scales — ~3.5x / ~6x fewer KV bytes per
        position, so proportionally more admitted concurrency on the
        same per-device KV budget, at the price of temp-0 exactness
        vs the fp oracle (quality is gated by token agreement via
        ``engine.score()`` / ``POST /v1/score``; see docs/API.md
        "Quantized KV"). Requires ``paged=True``.

        ``speculative=True`` (ISSUE 8) turns on draft-and-verify
        decoding: ``spec_drafter`` (``"ngram"`` prompt-lookup by
        default, or a small causal-LM keras model, or a custom
        :class:`~elephas_tpu.serving.speculative.Drafter`) guesses up
        to ``spec_k`` tokens per slot per round and one batched verify
        forward accepts the longest greedy-matching prefix — multiple
        tokens per target forward, temperature-0 output bit-exact.

        ``attention=`` (ISSUE 11) selects the serving attention kernel:
        ``"flash"`` (default) runs the tiled online-softmax programs —
        O(span) score memory, causal tile-skipping in prefill,
        span-bucketed block-span reads in decode; ``"naive"`` keeps
        the full-materialized seed path as the parity oracle. Flash
        matches naive to float tolerance and temperature-0 token
        streams exactly (docs/API.md "Attention kernels").

        ``policy=`` / ``tenants=`` (ISSUE 10) install an SLO admission
        policy: ``"fair"`` (or just ``tenants={"name": weight}``) gets
        VTC-style per-tenant fair share + deadline-EDF + overload
        admission control, ``"fifo"`` the legacy order with tenant
        accounting, or pass a :class:`~elephas_tpu.serving.policy.\
Policy` instance. ``gateway_port=`` (0 = ephemeral) additionally
        starts the async HTTP/SSE front door on the engine
        (``POST /v1/generate``, ``GET /metrics``, ``GET /stats``,
        ``GET /healthz``, ``GET /v1/requests/{rid}/trace``,
        ``GET /debug/engine``; see
        :mod:`elephas_tpu.serving.gateway`). The returned engine is a
        context manager: leaving the ``with`` block stops the gateway,
        severs live SSE connections, and releases the port.

        ``flight_recorder=`` (ISSUE 12) sizes the per-request flight
        recorder behind ``engine.explain(rid)`` and the gateway trace
        route — the last N finished request lifecycles (0/None off).
        """
        from elephas_tpu.serving import InferenceEngine
        from elephas_tpu.serving.policy import resolve_policy

        if self.pipeline_parallel > 1:
            raise NotImplementedError(
                "serve() does not integrate the pipeline ring decode "
                "yet — the slot arena would need depth-sharding across "
                "stages; serve from a DP/TP wrapper (or use "
                "generate() for one-shot ring decode)"
            )
        batch_axes, model_axis = self._decode_axes()
        engine = InferenceEngine(
            self._master_network,
            num_slots=num_slots,
            mesh=self.mesh,
            batch_axes=batch_axes,
            model_axis=model_axis,
            top_k=top_k,
            top_p=top_p,
            seed=seed,
            buckets=buckets,
            steps_per_sync=steps_per_sync,
            prefix_cache=prefix_cache,
            prefix_min_reuse=prefix_min_reuse,
            prefill_chunk=prefill_chunk,
            prefill_budget=prefill_budget,
            paged=paged,
            block_size=block_size,
            num_blocks=num_blocks,
            preemption=preemption,
            kv_dtype=kv_dtype,
            speculative=speculative,
            spec_k=spec_k,
            spec_drafter=spec_drafter,
            policy=resolve_policy(policy, tenants),
            attention=attention,
            flight_recorder=flight_recorder,
        )
        if gateway_port is not None:
            from elephas_tpu.serving.gateway import Gateway

            gw = Gateway(
                engine, host=gateway_host, port=int(gateway_port)
            )
            try:
                engine.gateway = gw.start()
            except Exception:
                # a start() failure (port in use) means the caller
                # never receives the engine — retire BOTH the
                # engine's and the half-built gateway's telemetry
                # series before re-raising, or every retry strands
                # labeled families in the process registry
                gw.release_telemetry()
                engine.release_telemetry()
                raise
        return engine

    # -- persistence ---------------------------------------------------

    def save(self, file_name: str) -> None:
        """Save the trained master network plus elephas config.

        ``.keras``/``.h5`` hold the model; a sidecar ``<file>.elephas.json``
        carries the distribution config so ``load_spark_model`` restores an
        equivalent wrapper (reference stores config inside HDF5 attrs;
        Keras-3's saver owns the archive format here, hence the sidecar).
        """
        self._master_network.save(file_name)
        with open(file_name + ".elephas.json", "w") as f:
            json.dump(self.get_config(), f)

    def _get_runner(self):
        if self._runner is None:
            if self.pipeline_parallel > 1:
                from elephas_tpu.parallel.pipeline_runner import PipelineRunner

                self._runner = PipelineRunner(
                    self._master_network,
                    self.pipeline_parallel,
                    num_microbatches=self.pipeline_microbatches,
                    mesh=self.mesh,
                    data_parallel=self.num_workers,
                    model_parallel=self.model_parallel,
                )
            elif self.sequence_parallel > 1:
                # before the TP check: TP×SP routes here (the sequence
                # runner plans model-axis shardings from the 3-D mesh —
                # TensorParallelRunner would silently skip the ring)
                from elephas_tpu.parallel.sequence import (
                    SequenceParallelRunner,
                )

                self._runner = SequenceParallelRunner(
                    self._master_network, self.mesh,
                    attention=self.sequence_attention,
                )
            elif self.model_parallel > 1:
                from elephas_tpu.parallel.tensor import TensorParallelRunner

                self._runner = TensorParallelRunner(
                    self._master_network, self.mode, self.frequency, self.mesh
                )
            else:
                self._runner = MeshRunner(
                    self._master_network, self.mode, self.frequency, self.mesh
                )
        return self._runner


class SparkMLlibModel(SparkModel):
    """SparkModel over MLlib-style ``LabeledPoint`` RDDs
    (``[U] elephas/spark_model.py::SparkMLlibModel``)."""

    def train(
        self,
        labeled_points: Rdd,
        epochs: int = 10,
        batch_size: int = 32,
        categorical: bool = False,
        nb_classes: int | None = None,
        **kwargs,
    ) -> dict:
        rdd = rdd_utils.lp_to_simple_rdd(labeled_points, categorical, nb_classes)
        return self.fit(rdd, epochs=epochs, batch_size=batch_size, **kwargs)

    def predict(self, data, batch_size: int | None = None) -> np.ndarray:
        from elephas_tpu.data.linalg import DenseVector

        if isinstance(data, Rdd):
            data = data.map(
                lambda el: el.toArray() if isinstance(el, DenseVector) else el
            )
        elif isinstance(data, DenseVector):
            data = data.toArray()[None]
        return super().predict(data, batch_size)


def load_spark_model(file_name: str) -> SparkModel:
    """Reload a model saved by :meth:`SparkModel.save`."""
    import keras

    model = keras.models.load_model(file_name)
    config = {}
    sidecar = file_name + ".elephas.json"
    if os.path.exists(sidecar):
        with open(sidecar) as f:
            config = json.load(f)
    return SparkModel(
        model,
        mode=config.get("mode", "synchronous"),
        frequency=config.get("frequency", "epoch"),
        parameter_server_mode=config.get("parameter_server_mode"),
        num_workers=config.get("num_workers"),
        batch_size=config.get("batch_size", 32),
        port=config.get("port", 4000),
        ps_overlap=config.get("ps_overlap"),
        ps_journal_dir=config.get("ps_journal_dir"),
        ps_shards=config.get("ps_shards", 1),
        failure_budget=config.get("failure_budget", 0),
        reassign_orphans=config.get("reassign_orphans", True),
        model_parallel=config.get("model_parallel", 1),
        pipeline_parallel=config.get("pipeline_parallel", 1),
        pipeline_microbatches=config.get("pipeline_microbatches", 4),
        sequence_parallel=config.get("sequence_parallel", 1),
        sequence_attention=config.get("sequence_attention", "ring"),
    )
