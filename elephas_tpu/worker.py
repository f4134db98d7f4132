"""Compiled distributed training programs — the SparkWorker equivalent.

Reference surface: ``[U] elephas/worker.py`` — ``SparkWorker`` (synchronous)
and ``AsynchronousSparkWorker`` rebuild the Keras model inside each Spark
executor, run local ``model.fit`` over their RDD partition, and exchange
weights either by driver-side averaging or through a pickle-over-HTTP/TCP
parameter server (SURVEY.md §3.1/3.2).

TPU-first redesign: there are no worker processes. A whole training epoch
for *all* workers is one XLA program — ``jax.jit(shard_map(...))`` over a
1-D ``('workers',)`` mesh:

- each worker's parameters/optimizer state live as one shard of a stacked
  ``[W, ...]`` array (its leading-axis slice), so "per-worker model
  replicas" are just a sharded pytree;
- the per-batch loop is ``lax.scan`` — no Python, no dispatch, no pickle;
- weight synchronization is ``lax.pmean`` compiled into the program,
  riding ICI/DCN instead of the reference's Flask/socket round-trips.

Mode semantics (see SURVEY.md §2a):

- ``synchronous``: gradients are ``pmean``-ed across workers every step
  (replicas stay bit-identical — classic SPMD data parallelism; the
  north-star path). The reference's coarser "train whole fit locally,
  average once" behavior is available as ``frequency='fit'``.
- ``asynchronous``: workers take independent local steps; weights (and
  float non-trainable state) are ``pmean``-averaged at each ``frequency``
  boundary (``'batch'`` or ``'epoch'``) — local-SGD with a staleness bound
  of one period, the honest SPMD mapping of the reference's
  parameter-server staleness.
- ``hogwild``: same schedule as ``asynchronous``. The reference's only
  difference is eliding a server-side lock (a *race*, not an algorithm);
  on gang-scheduled TPUs there is no lock to elide, so the two modes are
  computationally identical here. The semantic difference is documented
  rather than simulated.
"""

from __future__ import annotations

import collections
import itertools
import logging
import queue
import threading
import time
from concurrent.futures import Future
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu import telemetry
from elephas_tpu.utils import sockets

logger = logging.getLogger(__name__)

MODES = ("synchronous", "asynchronous", "hogwild")
FREQUENCIES = ("epoch", "batch", "fit")


def reads_model(when):
    """Decorator: declare whether an epoch callback ``cb(epoch, loss)``
    reads the master model. ``when`` is a bool, or a predicate of the
    epoch number for a callback that reads it at some epochs only (a
    checkpoint every tenth). :meth:`MeshRunner._end_epoch` syncs the
    master model at an epoch boundary only ahead of a callback that
    reads it there. A callable that declares nothing is taken to read
    the model at every epoch."""

    def declare(cb):
        cb.reads_model = when
        return cb

    return declare


def _reads_model_at(cb, epoch: int) -> bool:
    when = getattr(cb, "reads_model", True)
    return bool(when(epoch) if callable(when) else when)


# -- what JAX did under a span -------------------------------------------
#
# JAX reports each trace, lowering and compile to its monitoring
# listeners as it ends, on the thread that made it. One pair of listeners
# a process adds them into the frames that this thread has open
# (`JaxWork`), so a span can say what JAX did under it; a thread with no
# frame open (a serving thread's compile, everything under null mode)
# returns at once.

_JAX_STAGE_ARGS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower_s",
    # cache retrieval included: a hit is a compile that took that long
    "/jax/core/compile/backend_compile_duration": "jax_compile_s",
}
_JAX_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_COUNT_ARGS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# an event that began this close before another is taken to lie in it
_JAX_NESTING_SLACK_S = 1e-4
_jax_frames = threading.local()
_jax_listening = threading.Lock()
_jax_listeners = []  # the registered pair, once a process


def _on_jax_duration(event: str, seconds: float, fun_name="", **_kw) -> None:
    frames = getattr(_jax_frames, "open", None)
    if not frames:
        return
    arg = _JAX_STAGE_ARGS.get(event)
    if arg is None:
        if event == _JAX_CACHE_LOAD:
            for frame in frames:
                frame["jax_cache_load_s"] += seconds
        return
    # events nest (a function traced inside another's trace, an eager
    # operation compiled inside a trace) and the inner one ends first:
    # each stage gets the seconds of its events less those of the
    # events inside them, so the stages add up to time that passed once
    began = time.monotonic() - seconds
    for frame in frames:
        inside, ended = 0.0, frame["ended"]
        while ended and ended[-1][0] >= began - _JAX_NESTING_SLACK_S:
            inside += ended.pop()[1]
        ended.append((began, seconds))
        frame[arg] += max(seconds - inside, 0.0)
        frame["jax_events"] += 1
        if seconds > frame["longest_s"]:
            frame["longest_s"], frame["jax_longest"] = seconds, str(fun_name)


def _on_jax_event(event: str, **_kw) -> None:
    frames = getattr(_jax_frames, "open", None)
    if not frames:
        return
    arg = _JAX_COUNT_ARGS.get(event)
    if arg is not None:
        for frame in frames:
            frame[arg] += 1


def _listen_to_jax() -> None:
    """Register the two listeners, once however often this is called."""
    with _jax_listening:
        if not _jax_listeners:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            jax.monitoring.register_event_listener(_on_jax_event)
            _jax_listeners.extend((_on_jax_duration, _on_jax_event))


class JaxWork:
    """``with trace_span(...) as sp, JaxWork(sp):`` puts on the open
    span what JAX did on this thread inside the block: seconds of
    tracing, lowering and compiling (``jax_trace_s``, ``jax_lower_s``,
    ``jax_compile_s``, the last with ``jax_cache_load_s`` inside it),
    how many such events (``jax_events``), the compile cache's
    ``cache_hits`` and ``cache_misses``, and ``jax_longest``, the
    function of the longest single event. Blocks nest: the root span of
    a call holds the call's totals. Under null mode nothing is opened."""

    __slots__ = ("_span", "_frame")

    def __init__(self, span):
        self._span = span
        self._frame = None

    def __enter__(self):
        if telemetry.null_mode():
            return self
        self._frame = {
            "jax_trace_s": 0.0, "jax_lower_s": 0.0, "jax_compile_s": 0.0,
            "jax_cache_load_s": 0.0, "jax_events": 0, "cache_hits": 0,
            "cache_misses": 0, "jax_longest": "", "longest_s": 0.0,
            "ended": [],  # (began, seconds) of events no later one holds
        }
        frames = getattr(_jax_frames, "open", None)
        if frames is None:
            frames = _jax_frames.open = []
        frames.append(self._frame)
        return self

    def __exit__(self, *exc):
        frame = self._frame
        if frame is not None:
            _jax_frames.open.pop()  # blocks nest, so the last is this one
            del frame["longest_s"], frame["ended"]
            self._span.set(**frame)
        return False


def _fullest_device_stats():
    """``memory_stats()`` of the local device whose allocator peak is
    the highest; None where the backend keeps none (the CPU)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    stats = [s for s in stats if s and "bytes_in_use" in s]
    if not stats:
        return None
    return max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))


def _pmean_floats(tree, axis_name: str):
    """pmean float leaves; pass integer leaves (counters, seeds) through."""
    return jax.tree.map(
        lambda a: jax.lax.pmean(a, axis_name)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else a,
        tree,
    )


def _unstack0(tree):
    return jax.tree.map(lambda a: a[0], tree)


def _stack0(tree):
    return jax.tree.map(lambda a: a[None], tree)


def pad_to_batches(x: np.ndarray, num_batches: int, batch_size: int) -> np.ndarray:
    """Wrap-pad rows so ``x`` reshapes to ``[num_batches, batch_size, ...]``.

    Wrap-around duplication (rather than zero-pad + masking) keeps the
    training program mask-free; duplicated samples slightly overweight a
    few rows in the last partial batch, matching the spirit of the
    reference's per-worker ``model.fit`` which also sees a ragged final
    batch.
    """
    n = len(x)
    total = num_batches * batch_size
    if n == 0:
        raise ValueError("cannot pad an empty partition")
    idx = np.arange(total) % n
    return x[idx].reshape((num_batches, batch_size) + x.shape[1:])


def stack_worker_batches(
    partitions: list[tuple[np.ndarray, np.ndarray]],
    batch_size: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Partition arrays → ``x[W, nb, B, ...]``, ``y[W, nb, B, ...]``.

    Also returns per-worker true sample counts and the common batch count
    (the max over workers — shorter partitions wrap).
    """
    counts = np.array([len(x) for x, _ in partitions])
    nb = max(1, int(np.ceil(counts.max() / batch_size)))
    xs = np.stack([pad_to_batches(x, nb, batch_size) for x, _ in partitions])
    ys = np.stack([pad_to_batches(y, nb, batch_size) for _, y in partitions])
    return xs, ys, counts, nb


class KerasIntrospection:
    """Loss/metric introspection over a compiled Keras model — shared by
    :class:`MeshRunner` (DP over a ``('workers',)`` mesh) and
    :class:`~elephas_tpu.parallel.tensor.ShardedTrainer` (DP×TP over a
    ``('data', 'model')`` mesh). Subclasses provide ``self.model``."""

    model = None  # set by subclass __init__

    def _host_read(self, leaf) -> np.ndarray:
        """Full host value of a (possibly sharded) device leaf —
        :func:`elephas_tpu.parallel.mesh.host_read` over ``self.mesh``
        (cross-process shards all-gather in XLA first)."""
        from elephas_tpu.parallel.mesh import host_read

        return host_read(leaf, self.mesh)

    def _output_names(self) -> list[str]:
        names = list(getattr(self.model, "output_names", []) or [])
        if not names:
            n_out = len(getattr(self.model, "outputs", None) or [1])
            names = [f"output_{i}" for i in range(n_out)]
        return names

    def _single_loss_fn(self, loss):
        """One loss spec → per-sample (unreduced) callable."""
        import keras

        if isinstance(loss, str):
            fn = keras.losses.get(loss)  # plain function: per-sample values
        elif isinstance(loss, keras.losses.Loss):
            fn = loss.call  # unreduced
        elif callable(loss):
            fn = loss
        else:
            raise ValueError(f"unsupported loss spec {loss!r}")

        def aligned(y, y_pred):
            # keras Loss.__call__ squeezes/expands rank-mismatched targets
            # (e.g. binary y [B] vs y_pred [B,1]); raw loss fns don't
            y = jnp.asarray(y)
            if y.ndim == y_pred.ndim - 1 and y_pred.shape[-1] == 1:
                y = y[..., None]
            return fn(y, y_pred)

        return aligned

    def _per_sample_loss_fn(self):
        """Per-sample loss over possibly multi-output models.

        Returns ``fn(y, y_pred) -> dict`` with key ``'loss'`` ([B] total,
        loss-weighted like ``keras.Model.compute_loss``) plus
        ``'<output>_loss'`` per output when the model has several
        (matching ``keras.Model.evaluate``'s reporting).
        """
        loss = self.model.loss
        names = self._output_names()
        weights = getattr(
            getattr(self.model, "_compile_loss", None), "_user_loss_weights", None
        )
        # weight-by-output-name first, then select: keeps list weights
        # aligned to outputs even when a dict loss omits some of them
        if isinstance(weights, dict):
            weight_of = {n: float(weights.get(n, 1.0)) for n in names}
        elif weights is not None:
            weight_of = {n: float(w) for n, w in zip(names, weights)}
        else:
            weight_of = {n: 1.0 for n in names}

        if isinstance(loss, (list, tuple)):
            specs = list(loss)
        elif isinstance(loss, dict):
            missing = [n for n in loss if n not in names]
            if missing:
                raise ValueError(
                    f"loss dict keys {missing} do not match outputs {names}"
                )
            specs = [loss[n] for n in names if n in loss]
            names = [n for n in names if n in loss]
        else:
            fn = self._single_loss_fn(loss)
            return lambda y, y_pred: {"loss": fn(y, y_pred)}

        fns = [self._single_loss_fn(s) for s in specs]
        ws = [weight_of[n] for n in names]

        def multi(y, y_pred):
            ys = list(y) if isinstance(y, (list, tuple)) else [y]
            yps = list(y_pred) if isinstance(y_pred, (list, tuple)) else [y_pred]
            out = {}
            total = 0.0
            for name, f, w, yi, ypi in zip(names, fns, ws, ys, yps):
                values = f(yi, ypi)
                out[f"{name}_loss"] = values
                total = total + w * values
            out["loss"] = total
            return out

        return multi

    def _unwrapped_metrics(self, x_sample, y_sample):
        """Compiled metric entries: ``(metric, output_index, reported_name)``.

        CompileMetrics mishandles ``sample_weight`` in its count update
        (observed keras 3.13), so the underlying metrics are used directly
        for exact padded-batch aggregation. For multi-output models the
        per-output nesting (``CompileMetrics._flat_metrics``) supplies the
        output index and the ``<output>_<metric>`` reported name keras
        uses. CompileMetrics (and its inner metrics) build lazily — force
        variable creation with one tiny host-side update, then reset.
        """
        # loss trackers ('loss' plus per-output '<name>_loss' Means) are
        # computed by the evaluator's own per-sample loss path, not as
        # y/y_pred metrics
        loss_tracker_names = set(self._loss_keys())
        if all(m.name in loss_tracker_names for m in self.model.metrics):
            return []  # compiled with no metric
        # a prediction's shape and dtype are all a metric needs to build
        # its variables: zeros of the model's output spec stand in for
        # one, so the master model is never run op by op
        import keras

        x_head = np.asarray(x_sample)[:1]
        yp = jax.tree.map(
            lambda spec: jnp.zeros(spec.shape, spec.dtype),
            self.model.compute_output_spec(
                keras.KerasTensor(x_head.shape, dtype=str(x_head.dtype))),
        )
        multi = isinstance(yp, (list, tuple))
        names = self._output_names()

        def y_head(y):
            return jax.tree.map(lambda a: np.asarray(a)[:1], y)

        out = []
        for m in self.model.metrics:
            if m.name in loss_tracker_names:
                continue
            is_compile = type(m).__name__ == "CompileMetrics"
            if is_compile and not getattr(m, "metrics", None):
                m.update_state(y_head(y_sample), yp)
                m.reset_state()
            per_output = getattr(m, "_flat_metrics", None)
            if is_compile and multi and per_output is not None:
                for i, bucket in enumerate(per_output):
                    for mm in getattr(bucket, "metrics", None) or []:
                        out.append((mm, i, f"{names[i]}_{mm.name}"))
            elif is_compile and getattr(m, "metrics", None):
                out.extend((mm, 0, mm.name) for mm in m.metrics)
            else:
                out.append((m, 0, m.name))
        for mm, i, _name in out:
            if not mm.variables:
                yi = y_sample[i] if multi else y_sample
                ypi = yp[i] if multi else yp
                mm.update_state(np.asarray(yi)[:1], ypi)
                mm.reset_state()
        return out

    def _loss_keys(self) -> list[str]:
        """Reported loss keys, in keras order: total first, then per-output."""
        loss = self.model.loss
        names = self._output_names()
        if isinstance(loss, dict):
            return ["loss"] + [f"{n}_loss" for n in names if n in loss]
        if isinstance(loss, (list, tuple)):
            return ["loss"] + [f"{n}_loss" for n in names]
        return ["loss"]

    def _zero_metric_state(self, metric_objects):
        """Fresh metric variables as host zeros."""
        return [
            [np.zeros(v.shape, v.dtype) for v in m.variables]
            for m, _i, _n in metric_objects
        ]

    def _history_from_metrics(self, history, metric_objects, mvs):
        """Append one epoch's metric results to a history dict."""
        for (m, _i, name), mv in zip(metric_objects, mvs):
            res = m.stateless_result(mv)
            if isinstance(res, dict):
                for k, v in res.items():
                    history.setdefault(k, []).append(float(np.asarray(v)))
            else:
                history.setdefault(name, []).append(float(np.asarray(res)))

    @staticmethod
    def _broadcast_sw(sw, y):
        """Per-ROW sample weights ``[B]`` gain trailing singleton axes
        so they broadcast against rank>1 targets — a sequence model's
        per-token loss/metric is ``[B, S]`` and a flat ``[B]`` weight
        fails jnp broadcasting (found driving an LM through the L5
        sequence-parallel route, r4)."""
        y_rank = getattr(y, "ndim", 1)
        if sw is not None and getattr(sw, "ndim", 1) == 1 and y_rank > 1:
            return sw.reshape(sw.shape + (1,) * (y_rank - 1))
        return sw

    def _stateless_loss(self, tv, ntv, x, y, sample_weight=None):
        """Forward pass + total training loss with differentiable
        add_loss/regularizer contributions.

        ``stateless_call(return_losses=True)`` collects add_loss values
        AND regularization losses computed from the TRACED variables;
        ``compute_loss`` must read those via ``_losses_override`` —
        keras's own jax train_step pattern. Calling ``compute_loss``
        bare would fold in regularizers recomputed from concrete
        variable state: right value, zero gradient.

        Returns ``(y_pred, ntv2, total_loss, extras_sum)`` where
        ``extras_sum`` is the (differentiable) sum of the add_loss /
        regularizer terms inside ``total_loss``.
        """
        model = self.model
        y_pred, ntv2, losses = model.stateless_call(
            tv, ntv, x, training=True, return_losses=True
        )
        extras = sum(losses) if losses else 0.0
        if losses:
            model._losses_override.clear()
            model._losses_override = list(losses)
        try:
            kwargs = {}
            if sample_weight is not None:
                kwargs["sample_weight"] = self._broadcast_sw(
                    sample_weight, y
                )
            total = model.compute_loss(x=x, y=y, y_pred=y_pred, **kwargs)
        finally:
            if losses:
                model._losses_override.clear()
        return y_pred, ntv2, total, extras


class MeshRunner(KerasIntrospection):
    """Owns the compiled train/eval/predict programs for one Keras model.

    The model must be compiled (optimizer/loss/metrics) and built. All
    programs are cached per (static-shape) signature, so repeated ``fit``
    epochs reuse one executable.
    """

    def __init__(self, model, mode: str, frequency: str, mesh: Mesh):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        if frequency not in FREQUENCIES:
            raise ValueError(
                f"frequency must be one of {FREQUENCIES}, got {frequency!r}"
            )
        self.model = model
        self.mode = mode
        self.frequency = frequency
        self.mesh = mesh
        self.num_workers = mesh.devices.size
        self._epoch_fn = None
        self._signatures = 0  # of _epoch_fn's dispatch cache, last seen
        self._memory_peak = 0  # the allocator's, at the last fit.memory
        self._counters = None  # found on first use (_counter_layers)
        self._eval_fn = None
        self._predict_fn = None
        model.optimizer.build(model.trainable_variables)
        _listen_to_jax()

    # -- state plumbing ------------------------------------------------

    def _host_state(self):
        tv = [np.asarray(v.value) for v in self.model.trainable_variables]
        ntv = [np.asarray(v.value) for v in self.model.non_trainable_variables]
        ov = [np.asarray(v.value) for v in self.model.optimizer.variables]
        return tv, ntv, ov

    def _local_worker_indices(self) -> list[int]:
        """Mesh positions whose device belongs to this process (multi-host:
        the workers whose data/state this process stages)."""
        pid = jax.process_index()
        return [
            i
            for i, d in enumerate(self.mesh.devices.flat)
            if d.process_index == pid
        ]

    def _device_state(self, park_master: bool = False):
        """Current model state, replicated to ``[W, ...]`` worker shards.

        Multi-host: each process materializes only its addressable
        workers' slices (``jax.make_array_from_process_local_data``); the
        global array spans the pod without any host holding all of it.

        ``park_master`` (a ``fit``, which ends in a write-back): the
        master model's variables are given the host's copy, just read,
        before the runner's goes up. Keras puts a variable where JAX
        puts any new array, on the first accelerator, so the state
        would otherwise be there twice for the length of the call (the
        hybrid MoE LM's 5.0 GB does not fit twice beside its epoch
        program). A write-back assigns them as it always did, so after
        the call, and for a callback that reads the master model, they
        are on the default device again.
        """
        W = self.num_workers
        sharding = NamedSharding(self.mesh, P("workers"))
        tv, ntv, ov = self._host_state()
        if park_master:
            model = self.model
            host = jax.local_devices(backend="cpu")[0]
            for variables, leaves in (
                (model.trainable_variables, tv),
                (model.non_trainable_variables, ntv),
                (model.optimizer.variables, ov),
            ):
                for var, leaf in zip(variables, leaves):
                    var.assign(jax.device_put(leaf, host))
        multiproc = jax.process_count() > 1
        n_local = len(self._local_worker_indices()) if multiproc else W

        def rep(leaf):
            local = np.broadcast_to(leaf[None], (n_local,) + leaf.shape)
            if multiproc:
                return jax.make_array_from_process_local_data(
                    sharding, local, (W,) + leaf.shape
                )
            return jax.device_put(local, sharding)

        return (
            [rep(l) for l in tv],
            [rep(l) for l in ntv],
            [rep(l) for l in ov],
        )

    def _shard_data(self, arr: np.ndarray):
        """Worker-shard a GLOBAL ``[W, ...]`` host array (multi-host:
        slice out this process's workers first). This dispatches the
        ``device_put`` and does not wait for the copy: the
        ``fit.shard_data`` span around it times the dispatch alone, and
        the copy's tail lands in whatever first waits for the device."""
        if jax.process_count() > 1:
            arr = arr[np.asarray(self._local_worker_indices())]
        return self._shard_local_data(arr)

    def _shard_local_data(self, local: np.ndarray):
        """Worker-shard an array of which this process holds ONLY its
        local workers' slices (``[W_local, ...]``) — the streaming path
        gathers local rows only, so there is no global array to slice."""
        sharding = NamedSharding(self.mesh, P("workers"))
        if jax.process_count() > 1:
            global_shape = (self.num_workers,) + local.shape[1:]
            return jax.make_array_from_process_local_data(
                sharding, local, global_shape
            )
        return jax.device_put(local, sharding)

    @staticmethod
    def _worker_slice(leaf, index: int = 0):
        """One worker's slice of a ``[W, ...]``-sharded leaf. Multi-host,
        leaves span non-addressable devices — read the first local shard
        instead (all replicas agree post-sync)."""
        if getattr(leaf, "is_fully_addressable", True):
            return np.asarray(leaf[index])
        return np.asarray(leaf.addressable_shards[0].data)[0]

    def _write_back(self, tv, ntv, ov=None, release: bool = False):
        """Worker-0 slice → model variables (all replicas agree post-sync).
        ``release`` (the call's last write-back): each device leaf is
        freed once it is read, so that the master's copy takes its place
        on the devices and is never there beside it."""
        model = self.model
        for variables, leaves in (
            (model.trainable_variables, tv),
            (model.non_trainable_variables, ntv),
            (model.optimizer.variables, ov or ()),
        ):
            for var, leaf in zip(variables, leaves):
                var.assign(self._worker_slice(leaf))
                if release and isinstance(leaf, jax.Array):
                    leaf.delete()

    def _traced_device_state(self):
        """:meth:`_device_state` under its ``fit.device_state`` span.
        Returns the state and its size (``variables``, and one
        replica's ``bytes``, from shapes alone): the args that carry
        the per-variable detail, which must never become ring events."""
        with telemetry.trace_span("fit.device_state") as sp, JaxWork(sp):
            state = self._device_state(park_master=True)
            leaves = [leaf for part in state for leaf in part]
            size = {
                "variables": len(leaves),
                "bytes": sum(l.nbytes for l in leaves) // self.num_workers,
            }
            sp.set(**size)
        return state, size

    def _traced_write_back(self, state, size, epoch, final=False, sync=True):
        """:meth:`_write_back` under its ``fit.write_back`` span, whose
        ``variables`` and ``bytes`` say what crossed to the host:
        nothing where ``sync`` is false, and the span stays to say so."""
        if not sync:
            size = dict.fromkeys(size, 0)
        with telemetry.trace_span(
            "fit.write_back", epoch=epoch, final=final, **size
        ):
            if sync:
                self._write_back(*state, release=final)

    def _end_epoch(self, epoch, epoch_loss, state, size, callbacks):
        """The tail that the staged and the streamed epoch loops share:
        sync the master model if a callback reads it at this epoch
        (:func:`reads_model`; e.g. parameter-server publication, which
        must observe live weights), then invoke the callbacks. With no
        such callback the master model stays as the call found it until
        the call's ``final`` write-back. The decision rests on the list
        and the epoch number alone, so every process of a gang decides
        alike."""
        if not callbacks:
            return
        sync = any(_reads_model_at(cb, epoch) for cb in callbacks)
        self._traced_write_back(state, size, epoch, sync=sync)
        with telemetry.trace_span(
            "fit.callbacks", epoch=epoch, count=len(callbacks)
        ):
            for cb in callbacks:
                cb(epoch, epoch_loss)

    # -- layer counters ------------------------------------------------

    def _counter_layers(self) -> list:
        """``[(layer name, counter names, index among the non-trainable
        variables)]`` of the layers that count what they do inside the
        compiled program. Such a layer says so itself: its
        ``epoch_counters`` maps the attribute that holds an integer
        non-trainable variable, which the layer adds to call by call,
        to the names of that variable's entries (a sparse block's
        routed token slots). Found once a runner."""
        if self._counters is None:
            index = {
                id(v): i
                for i, v in enumerate(self.model.non_trainable_variables)
            }
            self._counters = [
                (layer.name, tuple(names), index[id(getattr(layer, attr))])
                for layer in self.model._flatten_layers()
                for attr, names in getattr(layer, "epoch_counters", {}).items()
            ]
        return self._counters

    def _counter_totals(self, ntv=None) -> list:
        """Every counter layer's running totals, summed over the
        workers: from the device state ``ntv`` (a few integers a layer
        cross to the host), or from the master model before a call's
        first epoch (each worker starts from its copy)."""
        totals = []
        for _name, _names, i in self._counter_layers():
            if ntv is None:
                var = self.model.non_trainable_variables[i]
                value = np.asarray(var.value).astype(np.int64)
                totals.append(value * self.num_workers)
            else:
                totals.append(
                    np.asarray(self._gather(ntv[i])).astype(np.int64).sum(0))
        return totals

    def _emit_counters(self, epoch: int, ntv, before: list) -> list:
        """One ``fit.counters`` event an epoch: what each counter layer
        added up in the epoch that just ended (its totals less those
        before it; the variables are int32 and may wrap). Called where
        the epoch's loss has just been read, so the program is done and
        nothing waits. Returns the totals for the next epoch's call."""
        layers = self._counter_layers()
        if not layers:
            return before
        now = self._counter_totals(ntv)
        by_layer: dict = {}
        for (name, names, _i), after, prior in zip(layers, now, before):
            by_layer.setdefault(name, {}).update(
                (key, int(v))
                for key, v in zip(names, (after - prior) % (1 << 32))
            )
        telemetry.emit("fit.counters", epoch=int(epoch), layers=by_layer)
        return now

    def _emit_memory(self, epoch: int) -> None:
        """One ``fit.memory`` event an epoch, where :meth:`_emit_counters`
        is called: the allocator's ``bytes_in_use`` and
        ``peak_bytes_in_use`` on the fullest local device, and whether
        the peak rose since the last such event (``peak_rose``): in
        which epoch the peak is set, and what the state between epochs
        holds. Nothing where the backend keeps no such statistics."""
        stats = _fullest_device_stats()
        if stats is None:
            return
        peak = int(stats.get("peak_bytes_in_use", 0))
        telemetry.emit(
            "fit.memory", epoch=int(epoch),
            bytes_in_use=int(stats["bytes_in_use"]), peak_bytes_in_use=peak,
            peak_rose=peak > self._memory_peak,
        )
        self._memory_peak = peak

    # -- loss helpers --------------------------------------------------

    def _loss_and_updates(self, tv, ntv, x, y):
        y_pred, ntv2, loss, _extras = self._stateless_loss(tv, ntv, x, y)
        return loss, (ntv2, y_pred)

    # -- training ------------------------------------------------------

    def _build_epoch_fn(self, metric_objects=None):
        """One whole training epoch as a single XLA program.

        With ``metric_objects`` (from :meth:`_unwrapped_metrics`), metric
        states thread through the batch scan exactly as keras accumulates
        training metrics over an epoch, then ``psum`` across workers
        (Mean-type states are additive) — history gains the compiled
        metrics with zero extra forward passes.
        """
        mode, frequency = self.mode, self.frequency
        grad_fn = jax.value_and_grad(self._loss_and_updates, has_aux=True)
        optimizer = self.model.optimizer
        metric_objects = metric_objects or []

        def per_worker(tv, ntv, ov, mvs, xb, yb):
            # tv/ntv/ov arrive as the worker's [1, ...] shard; mvs arrive
            # whole (replicated zeros) and leave whole (psum'd)
            tv, ntv, ov = _unstack0(tv), _unstack0(ntv), _unstack0(ov)
            xb, yb = xb[0], yb[0]

            def step(carry, batch):
                tv, ntv, ov, mvs = carry
                x, y = batch
                (loss, (ntv2, y_pred)), grads = grad_fn(tv, ntv, x, y)
                if mode == "synchronous" and frequency != "fit":
                    grads = jax.lax.pmean(grads, "workers")
                    ntv2 = _pmean_floats(ntv2, "workers")
                tv2, ov2 = optimizer.stateless_apply(ov, grads, tv)
                if mode != "synchronous" and frequency == "batch":
                    tv2 = _pmean_floats(tv2, "workers")
                    ntv2 = _pmean_floats(ntv2, "workers")
                mvs2 = [
                    m.stateless_update_state(mv, y, y_pred)
                    for (m, _i, _n), mv in zip(metric_objects, mvs)
                ]
                return (tv2, ntv2, ov2, mvs2), loss

            (tv, ntv, ov, mvs), losses = jax.lax.scan(
                step, (tv, ntv, ov, mvs), (xb, yb)
            )
            if mode != "synchronous" and frequency == "epoch":
                tv = _pmean_floats(tv, "workers")
                ntv = _pmean_floats(ntv, "workers")
            # merge metric states across workers (additive for Mean-types);
            # loss pmean'd so every process can read it without a gather
            mvs = jax.tree.map(lambda a: jax.lax.psum(a, "workers"), mvs)
            loss = jax.lax.pmean(jnp.mean(losses), "workers")
            return (
                _stack0(tv),
                _stack0(ntv),
                _stack0(ov),
                mvs,
                loss,
            )

        # tv, ntv, ov; the metric states and the loss. One tuple for the
        # shard_map and for jit, so the two cannot drift: named, the
        # state leaves under the sharding object _device_state gave it
        # and a call fed its own outputs meets the dispatch cache's one
        # entry. Left to JAX, a one-device mesh hands leaves back as
        # P(): the same placement, another cache key, and a dispatch of
        # 0.4-1.4 s on the host with the chip idle (PERF.md, PR 41)
        out_specs = (P("workers"), P("workers"), P("workers"), P(), P())
        sharded = jax.shard_map(
            per_worker,
            mesh=self.mesh,
            in_specs=(P("workers"), P("workers"), P("workers"), P(),
                      P("workers"), P("workers")),
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(
            sharded,
            donate_argnums=(0, 1, 2),
            out_shardings=tuple(
                NamedSharding(self.mesh, spec) for spec in out_specs),
        )

    def _dispatch_epoch(self, state, mvs, xb, yb, **where):
        """The epoch function's call under its ``fit.epoch_dispatch``
        span, which says what JAX did under it (:class:`JaxWork`), how
        many ``signatures`` the function's dispatch cache then holds and
        whether this call added one (``new_signature``): the call met
        arguments that no earlier call had shown JAX, and was traced
        again."""
        with telemetry.trace_span("fit.epoch_dispatch", **where) as sp, \
                JaxWork(sp):
            out = self._epoch_fn(*state, mvs, xb, yb)
            # JAX's own counter, not a public one: an epoch function
            # without it (a stand-in that a test wraps) counts as 0
            count = getattr(self._epoch_fn, "_cache_size", None)
            signatures = count() if count is not None else 0
            sp.set(signatures=signatures,
                   new_signature=signatures > self._signatures)
        self._signatures = signatures
        return out

    def run_epochs(
        self,
        partitions: list[tuple[np.ndarray, np.ndarray]],
        epochs: int,
        batch_size: int,
        verbose: int = 0,
        callbacks=None,
    ) -> dict:
        """Run ``epochs`` compiled epochs; returns a Keras-style history dict
        (loss + every compiled metric, like ``keras.Model.fit``) and leaves
        trained weights on the master model.

        ``callbacks`` are ``cb(epoch, loss)``, invoked at each epoch
        boundary. The master model is live after the call, always; at
        an epoch boundary only ahead of a callback that reads it there
        (:func:`reads_model`; a callable that declares nothing is taken
        to read it at every epoch), because the sync costs a transfer
        of the whole state to the host.

        Metric values count wrap-padded rows of ragged final batches
        (duplicated samples weigh in twice) — the same rows the loss
        already trains on; exact de-duplication would put masks in the
        train program for a sub-1% reporting delta on real shards.
        """
        if len(partitions) != self.num_workers:
            raise ValueError(
                f"got {len(partitions)} partitions for {self.num_workers} workers"
            )
        span = telemetry.trace_span
        with span("fit.stack_batches") as sp:
            xs, ys, counts, nb = stack_worker_batches(partitions, batch_size)
            sp.set(bytes=xs.nbytes + ys.nbytes)
        with span("fit.shard_data", bytes=xs.nbytes + ys.nbytes):
            xb = self._shard_data(xs)
            yb = self._shard_data(ys)
        counted = self._counter_totals()  # as the device state starts
        (tv, ntv, ov), size = self._traced_device_state()
        metric_objects = self._unwrapped_metrics(partitions[0][0], partitions[0][1])
        if self._epoch_fn is None:
            self._epoch_fn = self._build_epoch_fn(metric_objects)

        history: dict[str, list[float]] = {"loss": []}
        for epoch in range(epochs):
            mvs = self._zero_metric_state(metric_objects)
            tv, ntv, ov, mvs, loss = self._dispatch_epoch(
                (tv, ntv, ov), mvs, xb, yb, epoch=epoch)
            with span("fit.loss_wait", epoch=epoch):
                epoch_loss = float(np.asarray(loss))  # replicated: direct read
            counted = self._emit_counters(epoch, ntv, counted)
            self._emit_memory(epoch)
            history["loss"].append(epoch_loss)
            self._history_from_metrics(history, metric_objects, mvs)
            if verbose:
                logger.info("epoch %d/%d - loss: %.4f", epoch + 1, epochs, epoch_loss)
            self._end_epoch(epoch, epoch_loss, (tv, ntv, ov), size, callbacks)

        # 'fit' frequency (reference-parity synchronous): average once at end.
        if self.frequency == "fit":
            tv = [
                np.mean(self._gather(l), axis=0, keepdims=True).repeat(
                    self.num_workers, 0
                )
                for l in tv
            ]
            ntv = [
                np.mean(self._gather(l), axis=0, keepdims=True).repeat(
                    self.num_workers, 0
                )
                if np.issubdtype(l.dtype, np.floating)
                else self._gather(l)
                for l in ntv
            ]
        self._traced_write_back((tv, ntv, ov), size, epochs - 1, final=True)
        return history

    def run_epochs_stream(
        self,
        stream,
        epochs: int,
        verbose: int = 0,
        callbacks=None,
    ) -> dict:
        """Streamed training: like :meth:`run_epochs` but the epoch arrives
        as :class:`~elephas_tpu.data.streaming.ShardedStream` blocks that
        never all live in device memory at once.

        The same compiled epoch program runs per block (same math, same
        history), with the next block's host gather/`device_put` hidden
        under the current block's compute by async dispatch. Each block
        enters with zero metric state and leaves its psum'd (cross-worker
        additive) contribution, which accumulates across blocks — exact
        for integer and float states alike (a divide-by-W re-entry would
        silently truncate integer counters at every block boundary).
        """
        if self.frequency == "fit":
            raise ValueError(
                "frequency='fit' (train whole fit locally, average once) "
                "contradicts streaming; use 'epoch' or 'batch'"
            )
        metric_objects = self._unwrapped_metrics(
            *next(self._first_rows(stream))
        )
        if self._epoch_fn is None:
            self._epoch_fn = self._build_epoch_fn(metric_objects)
        span = telemetry.trace_span
        counted = self._counter_totals()  # as the device state starts
        (tv, ntv, ov), size = self._traced_device_state()

        # multi-host: gather only this process's workers' rows from the
        # backing store (VERDICT r2 weak #3 — full-block gathers multiply
        # storage bandwidth by the process count)
        from elephas_tpu.data.streaming import prefetch_blocks

        local_idx = (
            self._local_worker_indices() if jax.process_count() > 1 else None
        )
        history: dict[str, list[float]] = {"loss": []}
        for epoch in range(epochs):
            mvs = None  # accumulated block contributions (additive states)
            losses: list[tuple] = []
            # background reader keeps blocks ahead of the device (gathers
            # overlap compute beyond async-dispatch depth)
            blocks = prefetch_blocks(stream.blocks(worker_indices=local_idx))
            for block in itertools.count():
                # the host blocked on the reader; the last wait of an
                # epoch is the one that finds the stream exhausted
                with span("fit.input_wait", epoch=epoch, block=block):
                    got = next(blocks, None)
                if got is None:
                    break
                xs, ys, steps = got
                xb, yb = self._shard_local_data(xs), self._shard_local_data(ys)
                zero_mvs = self._zero_metric_state(metric_objects)
                tv, ntv, ov, block_mvs, loss = self._dispatch_epoch(
                    (tv, ntv, ov), zero_mvs, xb, yb, epoch=epoch, block=block)
                mvs = (
                    block_mvs
                    if mvs is None
                    else jax.tree.map(jnp.add, mvs, block_mvs)
                )
                losses.append((loss, steps))
            total_steps = sum(s for _, s in losses)
            with span("fit.loss_wait", epoch=epoch):
                epoch_loss = (
                    sum(float(np.asarray(l)) * s for l, s in losses)
                    / total_steps
                )
            counted = self._emit_counters(epoch, ntv, counted)
            self._emit_memory(epoch)
            history["loss"].append(epoch_loss)
            self._history_from_metrics(history, metric_objects, mvs)
            if verbose:
                logger.info(
                    "epoch %d/%d - loss: %.4f (%d blocks streamed)",
                    epoch + 1, epochs, epoch_loss, len(losses),
                )
            self._end_epoch(epoch, epoch_loss, (tv, ntv, ov), size, callbacks)
        self._traced_write_back((tv, ntv, ov), size, epochs - 1, final=True)
        return history

    @staticmethod
    def _first_rows(stream):
        """A (x_rows, y_rows) sample for metric building, without pulling
        a whole block."""
        yield (
            np.asarray(stream.x[0:1]),
            np.asarray(stream.y[0:1]),
        )

    def _gather(self, leaf) -> np.ndarray:
        """Full ``[W, ...]`` host value of a worker-sharded leaf — the
        shared cross-process read (:meth:`KerasIntrospection._host_read`)."""
        return self._host_read(leaf)

    # -- evaluation ----------------------------------------------------

    def _build_eval_fn(self, metric_objects, loss_keys):
        per_sample_loss = self._per_sample_loss_fn()

        def per_worker(tv, ntv, mvs, xb, yb, wb):
            # tv/ntv arrive as [1, ...] worker shards; mvs arrive whole
            # (replicated zeros) and leave whole (psum'd across workers)
            tv, ntv = _unstack0(tv), _unstack0(ntv)
            xb = xb[0]
            yb = jax.tree.map(lambda a: a[0], yb)
            wb = wb[0]
            model = self.model
            multi = len(self._output_names()) > 1

            def step(carry, batch):
                loss_sums, weight_sum, mvs = carry
                x, y, w = batch
                # return_losses: add_loss/regularizer penalties belong in
                # the reported total loss, as in keras's test_step
                y_pred, _, extra_losses = model.stateless_call(
                    tv, ntv, x, training=False, return_losses=True
                )
                extras = sum(extra_losses) if extra_losses else 0.0
                values = per_sample_loss(y, y_pred)
                loss_sums = {
                    k: loss_sums[k] + jnp.sum(values[k] * w) for k in loss_keys
                }
                # weight-scaled so the final divide leaves the penalty
                # un-normalized (it is per-model, not per-sample)
                loss_sums = dict(
                    loss_sums, loss=loss_sums["loss"] + extras * jnp.sum(w)
                )
                weight_sum = weight_sum + jnp.sum(w)
                new_mvs = []
                for (m, i, _name), mv in zip(metric_objects, mvs):
                    yi = y[i] if multi else y
                    ypi = y_pred[i] if multi else y_pred
                    new_mvs.append(
                        m.stateless_update_state(
                            mv, yi, ypi,
                            sample_weight=self._broadcast_sw(w, yi),
                        )
                    )
                return (loss_sums, weight_sum, new_mvs), None

            zeros = {k: jnp.float32(0) for k in loss_keys}
            (loss_sums, weight_sum, mvs), _ = jax.lax.scan(
                step, (zeros, jnp.float32(0), mvs), (xb, yb, wb)
            )
            # additive merge across workers (Mean-type metric states sum);
            # everything leaves replicated so any process reads it directly
            loss_sums = jax.tree.map(lambda a: jax.lax.psum(a, "workers"), loss_sums)
            weight_sum = jax.lax.psum(weight_sum, "workers")
            mvs = jax.tree.map(lambda a: jax.lax.psum(a, "workers"), mvs)
            return loss_sums, weight_sum, mvs

        sharded = jax.shard_map(
            per_worker,
            mesh=self.mesh,
            in_specs=(P("workers"), P("workers"), P(), P("workers"),
                      P("workers"), P("workers")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        return jax.jit(sharded)

    def evaluate(
        self,
        partitions: list[tuple[np.ndarray, np.ndarray]],
        batch_size: int = 32,
    ) -> dict[str, float]:
        """Distributed evaluate → ``{'loss': ..., <metric>: ...}``.

        Padding rows carry zero sample-weight, so aggregates are exact.
        Multi-output models (``y`` a list/tuple per partition, list/dict
        compiled losses) report keras-style ``<output>_loss`` and
        ``<output>_<metric>`` keys; dict insertion order is the keras
        reporting order (loss, per-output losses, metrics).
        """
        partitions = self._fit_partitions_to_mesh(partitions)
        counts = [len(x) for x, _ in partitions]
        nb = max(1, int(np.ceil(max(counts) / batch_size)))
        xs, ys, ws = [], [], []
        for x, y in partitions:
            n = len(x)
            total = nb * batch_size
            idx = np.arange(total) % n
            w = (np.arange(total) < n).astype(np.float32)
            xs.append(x[idx].reshape((nb, batch_size) + x.shape[1:]))
            ys.append(
                jax.tree.map(
                    lambda a: np.asarray(a)[idx].reshape(
                        (nb, batch_size) + np.asarray(a).shape[1:]
                    ),
                    y,
                )
            )
            ws.append(w.reshape((nb, batch_size)))
        xb = self._shard_data(np.stack(xs))
        yb = jax.tree.map(lambda *parts: self._shard_data(np.stack(parts)), *ys)
        wb = self._shard_data(np.stack(ws))

        metric_objects = self._unwrapped_metrics(partitions[0][0], partitions[0][1])
        loss_keys = self._loss_keys()
        mvs = self._zero_metric_state(metric_objects)
        tv, ntv, _ = self._device_state()

        if self._eval_fn is None:
            self._eval_fn = self._build_eval_fn(metric_objects, loss_keys)
        loss_sums, weight_sum, mvs = self._eval_fn(tv, ntv, mvs, xb, yb, wb)
        denom = float(np.asarray(weight_sum))  # replicated scalars: direct read
        results = {
            k: float(np.asarray(loss_sums[k])) / denom for k in loss_keys
        }
        tail: dict[str, list[float]] = {}
        self._history_from_metrics(tail, metric_objects, mvs)
        results.update({k: v[0] for k, v in tail.items()})
        return results

    def host_weights(self):
        """Full weights on host for parameter-server publication (the
        wire protocol is host numpy lists by contract). Current because
        run_epochs writes back ahead of a callback that reads the model
        (:func:`reads_model`), as the publication callback does."""
        return self.model.get_weights()

    # -- checkpointing (runner-dispatched; SparkModel stays agnostic) ----

    def save_checkpoint(self, directory: str, epoch: int, history=None) -> None:
        """Whole-model keras archive — data-parallel replicas are
        identical post-sync, so one archive is the canonical state and
        ONLY the coordinator writes it (N gang processes writing the
        same file on shared storage would race). The TP runner's orbax
        snapshots are collective instead — every process writes its own
        shards there."""
        multiproc = jax.process_count() > 1
        try:
            if not multiproc or jax.process_index() == 0:
                from elephas_tpu.utils import checkpoint as ckpt

                ckpt.save_checkpoint(self.model, directory, epoch, history)
        finally:
            if multiproc:
                # every process calls save_checkpoint (the callback runs
                # gang-wide); barrier so nobody races ahead into a resume
                # while the coordinator's archive is mid-write. In the
                # finally block so a coordinator write failure still
                # releases the gang (and then propagates) instead of
                # deadlocking the others at this barrier.
                from elephas_tpu.parallel.distributed import sync_global_devices

                sync_global_devices(f"ckpt-save-{epoch}")

    def restore_checkpoint(self, directory: str, custom_objects=None):
        from elephas_tpu.utils import checkpoint as ckpt

        return ckpt.restore_checkpoint(self.model, directory, custom_objects)

    # -- prediction ----------------------------------------------------

    def _build_predict_fn(self):
        def per_worker(tv, ntv, xb):
            tv, ntv = _unstack0(tv), _unstack0(ntv)
            xb = xb[0]
            model = self.model

            def step(_, x):
                y_pred, _unused = model.stateless_call(tv, ntv, x, training=False)
                return None, y_pred

            _, preds = jax.lax.scan(step, None, xb)
            return preds[None]

        sharded = jax.shard_map(
            per_worker,
            mesh=self.mesh,
            in_specs=(P("workers"), P("workers"), P("workers")),
            out_specs=P("workers"),
            check_vma=False,
        )
        return jax.jit(sharded)

    def predict(self, feature_partitions: list[np.ndarray], batch_size: int = 32) -> np.ndarray:
        feature_partitions = [p for p in feature_partitions if len(p)]
        if not feature_partitions:
            raise ValueError("predict: no input rows")
        if len(feature_partitions) > self.num_workers:
            feature_partitions = self._re_split(
                np.concatenate(feature_partitions), self.num_workers
            )
        # true row counts; mesh-filler partitions below contribute 0 rows
        counts = [len(x) for x in feature_partitions]
        while len(feature_partitions) < self.num_workers:
            feature_partitions.append(feature_partitions[-1][:1])
            counts.append(0)
        nb = max(1, int(np.ceil(max(counts) / batch_size)))
        xs = np.stack(
            [pad_to_batches(x, nb, batch_size) for x in feature_partitions]
        )
        xb = self._shard_data(xs)
        tv, ntv, _ = self._device_state()
        if self._predict_fn is None:
            self._predict_fn = self._build_predict_fn()
        preds = np.asarray(self._predict_fn(tv, ntv, xb))
        out = []
        for w, n in enumerate(counts):
            flat = preds[w].reshape((-1,) + preds.shape[3:])
            out.append(flat[:n])
        return np.concatenate(out)

    # -- partition shaping --------------------------------------------

    @staticmethod
    def _re_split(arrs, n):
        return [a for a in np.array_split(arrs, n) if len(a)]

    def _fit_partitions_to_mesh(self, partitions):
        """Coalesce/split (x, y) partitions to exactly ``num_workers``.

        ``y`` may be any pytree of row-aligned arrays (multi-output
        models evaluate with tuple/list targets).
        """
        if len(partitions) == self.num_workers:
            return partitions
        x = np.concatenate([p[0] for p in partitions])
        y = jax.tree.map(
            lambda *ps: np.concatenate([np.asarray(a) for a in ps]),
            *[p[1] for p in partitions],
        )
        xs = np.array_split(x, self.num_workers)
        offsets = np.cumsum([0] + [len(a) for a in xs])
        out = []
        for i, a in enumerate(xs):
            lo, hi = int(offsets[i]), int(offsets[i + 1])
            if len(a) == 0:
                # re-use a sample from the first shard; zero-weighted later
                a = xs[0][:1]
                b = jax.tree.map(lambda t: t[:1], y)
            else:
                b = jax.tree.map(lambda t: t[lo:hi], y)
            out.append((a, b))
        return out


# -- overlapped parameter sync (ISSUE 2 tentpole, part 3) ----------------


class OverlappedSync:
    """Background push(delta)/pull(weights) window for async/hogwild
    workers: one daemon thread owns the parameter client (a single
    connection — wire ops stay serialized), so a sync round overlaps the
    next period's compute instead of blocking it.

    Staleness bound: at most ``staleness`` rounds may be in flight;
    :meth:`submit` blocks until the oldest lands once the window is
    full. ``synchronous`` mode never routes through this class — it
    stays blocking and bit-exact.
    """

    def __init__(self, client, staleness: int = 1):
        self.client = client
        self.staleness = max(1, int(staleness))
        self._queue: queue.Queue = queue.Queue()
        self._pending: collections.deque[Future] = collections.deque()
        self.max_in_flight = 0  # high-water mark (tested staleness bound)
        # trace context is THREAD-local (ISSUE 13) and the wire ops
        # below run on this daemon thread — capture the constructing
        # thread's scope so overlapped rounds stamp (and forward) the
        # same trace id the blocking path would
        self._trace_id = telemetry.current_trace()
        self._thread = threading.Thread(
            target=self._run, name="elephas-ps-sync", daemon=True
        )
        self._thread.start()

    def _run(self):
        with telemetry.trace_scope(self._trace_id):
            while True:
                item = self._queue.get()
                if item is None:
                    return
                delta, fut = item
                try:
                    if delta is not None:
                        self.client.update_parameters(delta)
                    fut.set_result(self.client.get_parameters())
                except BaseException as e:  # surfaced at submit/drain
                    fut.set_exception(e)

    def submit(self, delta) -> Future:
        """Queue one round (push ``delta``, then pull fresh weights)."""
        while len(self._pending) >= self.staleness:
            self._pending.popleft().result()  # staleness bound: block
        fut: Future = Future()
        self._queue.put((delta, fut))
        self._pending.append(fut)
        self.max_in_flight = max(self.max_in_flight, len(self._pending))
        return fut

    def freshest(self):
        """Newest completed pull (dropping older ones), or None if every
        in-flight round is still on the wire — the caller then continues
        from its local weights, Hogwild-style."""
        newest = None
        while self._pending and self._pending[0].done():
            newest = self._pending.popleft().result()
        return newest

    def drain(self):
        """Wait for every in-flight round; returns the last pull."""
        out = None
        while self._pending:
            out = self._pending.popleft().result()
        return out

    def close(self) -> None:
        self._queue.put(None)
        self._thread.join(timeout=30)


# -- executor-side worker classes (reference API parity) ----------------


class SparkWorker:
    """Per-partition synchronous worker (``[U] elephas/worker.py::SparkWorker``).

    The compiled SPMD path above supersedes this for normal training; these
    classes are the reference-shaped escape hatch for custom per-partition
    execution (and they are what the parameter-server protocol tests drive).
    ``train(data_iterator)`` yields ``(trained_weights, history_dict)`` —
    the v3-lineage contract (SURVEY.md §2 "SparkWorker").
    """

    def __init__(
        self,
        json_model: str,
        parameters,
        train_config: dict | None = None,
        master_optimizer="rmsprop",
        master_loss="categorical_crossentropy",
        master_metrics=None,
        custom_objects: dict | None = None,
    ):
        self.json_model = json_model
        self.parameters = parameters
        self.train_config = dict(train_config or {})
        self.master_optimizer = master_optimizer
        self.master_loss = master_loss
        self.master_metrics = master_metrics
        self.custom_objects = custom_objects

    def _build(self):
        import keras

        model = keras.models.model_from_json(
            self.json_model, custom_objects=self.custom_objects
        )
        model.compile(
            optimizer=self.master_optimizer,
            loss=self.master_loss,
            metrics=self.master_metrics,
        )
        if self.parameters is not None:
            model.set_weights(self.parameters)
        return model

    @staticmethod
    def _stack(data_iterator):
        xs, ys = [], []
        for x, y in data_iterator:
            xs.append(np.asarray(x))
            ys.append(np.asarray(y))
        if not xs:
            return None, None
        return np.stack(xs), np.stack(ys)

    def train(self, data_iterator):
        """Train on one partition's rows; yields (weights, history)."""
        x, y = self._stack(data_iterator)
        if x is None:
            return
        model = self._build()
        history = model.fit(
            x,
            y,
            epochs=self.train_config.get("epochs", 1),
            batch_size=self.train_config.get("batch_size", 32),
            verbose=self.train_config.get("verbose", 0),
            validation_split=self.train_config.get("validation_split", 0.0),
        )
        yield model.get_weights(), history.history


class AsynchronousSparkWorker(SparkWorker):
    """Per-partition async worker: pull → local train → push delta
    (``[U] elephas/worker.py::AsynchronousSparkWorker``).

    Speaks the real parameter-server protocol through a
    :mod:`elephas_tpu.parameter` client, so it works against a weight
    store on another host over DCN. ``frequency='epoch'`` syncs once per
    epoch, ``'batch'`` once per mini-batch.

    ISSUE 2 knobs: ``compression``/``topk`` select the binary codec's
    int8 quantization (with error-feedback residuals held by the
    client) and top-k delta sparsification; ``overlap=True`` routes
    sync rounds through :class:`OverlappedSync` so the wire rides
    under the next period's compute, trading a bounded ``staleness``
    (in sync periods) for throughput — the async/hogwild trade, never
    applied to the synchronous worker.

    ISSUE 3 (fault tolerance): each sync period runs under a
    **supervised retry** — when a period's pull/push fails even after
    the client's own reconnect retries (a PS crash/restart, a severed
    wire), the worker backs off with capped exponential delays
    (``utils.sockets.retry_call``), re-pulls fresh weights, and re-runs
    that period, up to ``ps_retries`` times before giving up; a
    transient PS outage therefore pauses training instead of killing
    it. The worker registers under ``client_id`` and heartbeats the
    server once per sync period on the existing connection, so the
    server's ``status`` op reports live membership. On protocol-2
    servers every push carries a sequence ID, making the period
    re-run's resends effectively-once (a re-run period's *recompute*
    trains that period's rows again — the documented at-least-once
    training semantic of crash recovery). With lossy compression a
    re-encoded retry folds the previous attempt's residual into the
    fresh delta — DGC's delayed-error contract, preserved across
    failures.

    ISSUE 6 (sharded PS): ``master="host:p0,host:p1,..."`` — a
    comma-separated endpoint list — routes the same pull/train/push
    loop through a :class:`~elephas_tpu.parameter.client.ShardedClient`
    (scatter/gather over per-shard servers, per-shard sequence IDs,
    one dead shard pausing only its slice). Workers may join and leave
    such a topology mid-run: registration is implicit (first heartbeat
    or sequenced update) and a departed worker's lease simply goes
    stale, so elastic data-parallel membership needs no coordinator
    round-trip.
    """

    def __init__(
        self,
        json_model: str,
        parameters=None,
        train_config: dict | None = None,
        frequency: str = "epoch",
        parameter_server_mode: str = "http",
        master: str | None = None,
        port: int = 4000,
        master_optimizer="rmsprop",
        master_loss="categorical_crossentropy",
        master_metrics=None,
        custom_objects: dict | None = None,
        compression: str = "none",
        topk: float | None = None,
        pull_compression: str | None = None,
        overlap: bool = False,
        staleness: int = 1,
        ps_retries: int = 6,
        ps_retry_max_delay: float = 5.0,
        client_id: str | None = None,
        trace_id: str | None = None,
    ):
        super().__init__(
            json_model,
            parameters,
            train_config,
            master_optimizer,
            master_loss,
            master_metrics,
            custom_objects,
        )
        if frequency not in ("epoch", "batch"):
            raise ValueError(f"frequency must be 'epoch' or 'batch', got {frequency!r}")
        self.frequency = frequency
        self.parameter_server_mode = parameter_server_mode
        self.master = master
        self.port = port
        self.compression = compression
        self.topk = topk
        self.pull_compression = pull_compression
        self.overlap = bool(overlap)
        self.staleness = max(1, int(staleness))
        self.ps_retries = max(0, int(ps_retries))
        self.ps_retry_max_delay = float(ps_retry_max_delay)
        self.client_id = client_id
        # cross-process trace context (ISSUE 13): when set, train()
        # runs under this trace id — its sync spans, retries, and PS
        # round-trips all stamp it, and the clients forward it over
        # the wire so server-side applies join the same trace. When
        # None, train() inherits the caller's ambient scope (the chaos
        # harness / SparkModel.fit shape).
        self.trace_id = trace_id
        # telemetry (ISSUE 5): the supervised retry loop and sync
        # cadence become observable — a rising retry rate is the
        # earliest signal of a struggling PS, visible on the same
        # scrape as the server's own counters
        reg = telemetry.registry()
        wid = telemetry.instance_label()
        self.telemetry_label = wid
        self._tracer = telemetry.tracer()
        self._m_sync_periods = reg.counter(
            "elephas_worker_sync_periods_total",
            "Completed pull-train-push sync periods",
            labels=("worker",),
        ).labels(worker=wid)
        self._m_retries = reg.counter(
            "elephas_worker_ps_retries_total",
            "Supervised re-runs of a sync period after a PS failure",
            labels=("worker",),
        ).labels(worker=wid)

    def release_telemetry(self) -> None:
        """Retire this worker's labeled series from the process
        registry. Explicit-only (see ``Registry.remove_series``):
        post-fit scrapes showing what the partitions did are a
        supported shape, so retirement is the host's call."""
        telemetry.remove_series(worker=self.telemetry_label)

    def _client(self, model=None):
        from elephas_tpu.parameter.client import HttpClient, SocketClient

        if self.master and "," in str(self.master):
            # sharded topology (ISSUE 6): a comma-separated endpoint
            # list selects the scatter/gather client — the worker
            # derives the SAME deterministic shard map the server group
            # derived from the same weight template
            from elephas_tpu.parameter.client import ShardedClient
            from elephas_tpu.parameter.sharding import (
                ShardMap,
                shard_endpoints,
            )

            if self.parameter_server_mode not in ("http", "socket"):
                raise ValueError(
                    f"sharded endpoint lists need parameter_server_mode="
                    f"'http' or 'socket', got "
                    f"{self.parameter_server_mode!r}"
                )
            if model is None:
                raise ValueError(
                    "sharded endpoints need the built model to derive "
                    "the shard map from its weight template"
                )
            endpoints = shard_endpoints(self.master)
            return ShardedClient(
                endpoints,
                ShardMap.from_weights(model.get_weights(), len(endpoints)),
                transport=self.parameter_server_mode,
                client_id=self.client_id,
                compression=self.compression, topk=self.topk,
                pull_compression=self.pull_compression,
                retries=max(3, self.ps_retries) if self.overlap else 3,
            )
        if self.parameter_server_mode == "native":
            if (
                self.compression != "none"
                or self.topk is not None
                or self.pull_compression not in (None, "none")
            ):
                raise ValueError(
                    "the native parameter server speaks raw float32 "
                    "frames — compression/topk need "
                    "parameter_server_mode='http' or 'socket'"
                )
            from elephas_tpu.parameter.native import NativeClient, _Flattener

            host, _, p = (self.master or "127.0.0.1").partition(":")
            port = int(p) if p else self.port
            return NativeClient(host, port, _Flattener(model.get_weights()))
        cls = {"http": HttpClient, "socket": SocketClient}.get(
            self.parameter_server_mode
        )
        if cls is None:
            raise ValueError(
                f"parameter_server_mode must be 'http', 'socket' or "
                f"'native', got {self.parameter_server_mode!r}"
            )
        # overlap rounds ride a background thread where the supervised
        # period re-run below cannot reach them — give the client itself
        # the longer retry horizon there
        retries = max(3, self.ps_retries) if self.overlap else 3
        return cls(
            self.master, self.port,
            compression=self.compression, topk=self.topk,
            pull_compression=self.pull_compression,
            retries=retries, client_id=self.client_id,
        )

    def _periods(self, x, y, epochs: int, batch_size: int):
        """The sync-period stream: whole epochs or mini-batches."""
        for _ in range(epochs):
            if self.frequency == "epoch":
                yield x, y
            else:
                for start in range(0, len(x), batch_size):
                    yield x[start : start + batch_size], y[start : start + batch_size]

    def _fit_period(self, model, xp, yp, batch_size: int) -> None:
        if self.frequency == "epoch":
            model.fit(xp, yp, epochs=1, batch_size=batch_size, verbose=0)
        else:
            model.train_on_batch(xp, yp)

    def _heartbeat(self, client) -> None:
        """Best-effort lease refresh once per sync period (liveness is
        advisory; the period's own ops carry the hard failure path)."""
        beat = getattr(client, "heartbeat", None)
        if beat is None:
            return
        try:
            beat()
        except (ConnectionError, TimeoutError, OSError) as e:
            logger.debug("heartbeat failed (non-fatal): %r", e)

    def _supervised(self, fn):
        """One sync period under the ISSUE 3 supervision contract:
        capped-backoff re-runs survive a PS outage that outlasts the
        client's own reconnect retries; the final failure propagates
        so the driver's failure budget can count this worker. Each
        re-run counts in ``elephas_worker_ps_retries_total`` and lands
        as a trace event (ISSUE 5) so outage windows line up with the
        chaos timeline."""

        def on_retry(attempt, exc):
            self._m_retries.inc()
            self._tracer.emit(
                "worker.retry", worker=self.telemetry_label,
                attempt=attempt, error=repr(exc),
            )

        return sockets.retry_call(
            fn,
            retries=self.ps_retries,
            base_delay=0.25,
            max_delay=self.ps_retry_max_delay,
            on_retry=on_retry,
        )

    def train(self, data_iterator):
        from elephas_tpu.utils.functional_utils import subtract_params

        x, y = self._stack(data_iterator)
        if x is None:
            return
        # trace_scope(None) is a passthrough: without an explicit
        # trace_id this worker inherits whatever scope the caller set
        with telemetry.trace_scope(self.trace_id):
            yield from self._train_scoped(x, y, subtract_params)

    def _train_scoped(self, x, y, subtract_params):
        model = self._build()
        client = self._client(model)
        epochs = self.train_config.get("epochs", 1)
        batch_size = self.train_config.get("batch_size", 32)
        try:
            if self.overlap:
                self._train_overlapped(
                    model, client, x, y, epochs, batch_size
                )
            else:
                for xp, yp in self._periods(x, y, epochs, batch_size):

                    def sync_period(xp=xp, yp=yp):
                        # resume-from-last-PS-pull: every (re-)run of a
                        # period starts from fresh server weights, so a
                        # re-run after an outage trains on the
                        # post-recovery state, not a stale snapshot
                        self._heartbeat(client)
                        before = client.get_parameters()
                        model.set_weights(before)
                        self._fit_period(model, xp, yp, batch_size)
                        # server applies weights += delta, so the delta
                        # must be the descent step (after − before)
                        client.update_parameters(
                            subtract_params(model.get_weights(), before)
                        )

                    with self._tracer.span(
                        "worker.sync_period",
                        worker=self.telemetry_label,
                    ):
                        self._supervised(sync_period)
                    self._m_sync_periods.inc()
                # confirmed delivery: every pipelined push is acked (or
                # sequence-deduplicated-resent) before this partition
                # reports done — without this, a connection dying on
                # the run's FINAL pushes would lose them silently
                flush = getattr(client, "flush", None)
                if flush is not None:
                    self._supervised(flush)
        finally:
            if hasattr(client, "close"):
                client.close()
        yield model.get_weights(), {}

    def _train_overlapped(self, model, client, x, y, epochs, batch_size):
        """Double-buffered loop: period ``i``'s compute overlaps round
        ``i-1``'s push+pull; adopted weights are stale by at most
        ``staleness`` periods (else the worker continues from its own
        local weights, Hogwild-style)."""
        from elephas_tpu.utils.functional_utils import subtract_params

        sync = OverlappedSync(client, self.staleness)
        try:
            before = client.get_parameters()  # initial pull: blocking
            model.set_weights(before)
            for xp, yp in self._periods(x, y, epochs, batch_size):
                self._fit_period(model, xp, yp, batch_size)
                after = model.get_weights()
                sync.submit(subtract_params(after, before))
                self._m_sync_periods.inc()
                fresh = sync.freshest()
                if fresh is not None:
                    before = fresh
                    model.set_weights(fresh)
                else:
                    # round still on the wire: continue from local
                    # weights (Hogwild-style), no extra copies
                    before = after
            final = sync.drain()  # every push acked before we report
            if final is not None:
                model.set_weights(final)
        finally:
            sync.close()
