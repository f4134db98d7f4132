"""Pipeline parallelism — GPipe-style SPMD pipeline over a mesh axis.

Absent from the reference (SURVEY.md §2a); provided as the TPU-native
construction. Stage parameters are sharded over a ``('stages',)`` mesh
axis — device ``s`` holds stage ``s``'s weights — and microbatches flow
through the ring: each tick every device applies its stage to its
current activation and hands the result to the next device via
``lax.ppermute`` (one neighbor hop on ICI). With ``M`` microbatches and
``S`` stages the schedule runs ``M + S − 1`` ticks; the ``(S−1)/M``
bubble fraction is the standard GPipe cost, amortized by more
microbatches.

Two surfaces:

- :func:`gpipe` / :func:`gpipe_sharded` — the homogeneous-stack
  primitive (identical stage shapes: a transformer's repeated blocks).
  One ``lax.scan`` inside ``shard_map``, differentiable end-to-end (the
  backward pass pipelines in reverse through the transposed
  ``ppermute``\\ s automatically). Outputs stay on the last stage and
  are sliced out per-stage-sharded — no whole-activation broadcast.
- :class:`GPipeTrainer` — a *training loop* over heterogeneous stages:
  per-stage activation shapes may all differ (activations ride a flat
  padded buffer; ``lax.switch`` picks the device's stage, so shapes
  stay static), the last stage computes the microbatch loss, gradients
  accumulate across microbatches inside one backward pipeline, and an
  optax optimizer updates the stage-sharded flat parameters in place —
  weights, grads, and optimizer slots all live ``P('stages')``-sharded;
  only neighbor activations cross the ICI ring.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu.parallel.mesh import host_read, put_global

logger = logging.getLogger(__name__)


def gpipe(stage_fn, stage_params, x_microbatches, axis_name: str):
    """Run microbatches through the stage pipeline; call INSIDE shard_map.

    ``stage_fn(params, x) -> y`` applies one stage (same signature and
    shapes for every stage; ``y.shape == x.shape`` — heterogeneous
    stages go through :class:`GPipeTrainer`). ``stage_params`` is this
    device's stage's params (the caller shards a stacked-[S, ...] pytree
    over ``axis_name`` and passes the unstacked slice).
    ``x_microbatches``: ``[M, mb, ...]`` (replicated — only stage 0
    reads it). Returns ``[M, mb, ...]`` outputs, VALID ON THE LAST STAGE
    ONLY (zeros elsewhere) — the caller slices the last stage's shard
    out instead of paying an all-reduce broadcast of whole activations.
    """
    s = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    ticks = m + s - 1

    def one_tick(carry, t):
        recv, outputs = carry
        mb_idx = jnp.clip(t, 0, m - 1)
        inp = jnp.where(stage == 0, x_microbatches[mb_idx], recv)
        out = stage_fn(stage_params, inp)
        write_idx = t - (s - 1)
        is_valid = (stage == s - 1) & (write_idx >= 0)
        updated = outputs.at[jnp.clip(write_idx, 0, m - 1)].set(out)
        outputs = jnp.where(is_valid, updated, outputs)
        recv = jax.lax.ppermute(
            out, axis_name, [(i, (i + 1) % s) for i in range(s)]
        )
        return (recv, outputs), None

    recv0 = jnp.zeros_like(x_microbatches[0])
    out0 = jnp.zeros_like(x_microbatches)
    (recv, outputs), _ = jax.lax.scan(
        one_tick, (recv0, out0), jnp.arange(ticks)
    )
    return outputs


def gpipe_sharded(
    stage_fn,
    stacked_params,
    x,
    mesh,
    num_microbatches: int,
    axis_name: str = "stages",
):
    """Global-array wrapper: shards stacked ``[S, ...]`` stage params over
    ``mesh[axis_name]``, splits ``x [B, ...]`` into microbatches, runs
    :func:`gpipe`, and returns ``[B, ...]`` outputs (read from the last
    stage's shard — no cross-stage activation broadcast)."""
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} must divide into {num_microbatches} microbatches"
        )
    s = mesh.shape[axis_name]
    xm = x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])

    def fn(params_slice, xm):
        params = jax.tree.map(lambda a: a[0], params_slice)
        out = gpipe(stage_fn, params, xm, axis_name)
        return out[None]  # leading per-stage axis

    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(axis_name),
        check_vma=False,
    )
    out = sharded(stacked_params, xm)[s - 1]
    return out.reshape((b,) + out.shape[2:])


def pipeline_mesh(
    num_stages: int,
    data_parallel: int = 1,
    axis_name: str = "stages",
    data_axis: str = "data",
    model_parallel: int = 1,
    model_axis: str = "model",
) -> Mesh:
    """Mesh for a (possibly data-replicated) pipeline: 1-D
    ``('stages',)`` when ``data_parallel == 1``, else a
    ``(data_parallel, num_stages)`` grid ``('data', 'stages')`` — each
    data row runs its own activation ring. With ``model_parallel > 1``
    (PP×TP, r5) a trailing model axis joins:
    ``('data', 'stages', 'model')`` — stage weights width-shard over it
    inside each ring position."""
    dp = int(data_parallel)
    mp = int(model_parallel)
    devices = jax.devices()
    if len(devices) < num_stages * dp * mp:
        raise ValueError(
            f"{num_stages} stages × {dp} data replicas × {mp} model "
            f"shards need {num_stages * dp * mp} devices, have "
            f"{len(devices)}"
        )
    if mp > 1:
        return Mesh(
            np.array(devices[: dp * num_stages * mp]).reshape(
                dp, num_stages, mp
            ),
            (data_axis, axis_name, model_axis),
        )
    if dp > 1:
        return Mesh(
            np.array(devices[: dp * num_stages]).reshape(dp, num_stages),
            (data_axis, axis_name),
        )
    return Mesh(np.array(devices[:num_stages]), (axis_name,))


class GPipeTrainer:
    """Microbatched pipeline-parallel trainer over heterogeneous stages.

    ``stage_fns``: list of ``fn(params, x) -> y`` — activation shapes may
    differ at every boundary. ``stage_params``: list of per-stage pytrees.
    ``loss_fn(y_pred, y) -> scalar`` (mean over the microbatch).

    Stateful stages (r4, VERDICT r3 weak #5 — BatchNorm through the
    pipe): pass ``stage_states`` (per-stage pytrees of non-trainable
    state) and stage functions of the extended signature
    ``fn(params, state, x, training) -> (y, new_state)``. The state
    rides a second stacked flat buffer ``[S, N_max]`` sharded over the
    stage axis alongside the parameters — each tick the owning stage
    reads and (on training ticks that carry REAL microbatch data, not
    pipeline-bubble garbage) writes its own slice; state never crosses
    the ring. BN statistics are therefore per-microbatch moving
    averages, the standard GPipe semantics. ``training=False`` builds
    the inference program (moving statistics, no state writes).

    TPU mapping: stage ``s``'s parameters are flattened
    (``ravel_pytree``), padded to the widest stage, and stacked
    ``[S, P_max]`` sharded over the ``('stages',)`` axis — so are the
    optimizer's moment slots. Activations cross stages as flat padded
    buffers through ``lax.ppermute``; ``lax.switch`` selects each
    device's stage so every reshape is static. One jitted train step
    runs the full forward pipeline, a reversed backward pipeline
    (gradient accumulation over microbatches for free via the scan
    transpose), and the optax update.
    """

    def __init__(
        self,
        stage_fns,
        stage_params,
        loss_fn,
        optimizer=None,
        mesh: Mesh | None = None,
        num_microbatches: int = 4,
        axis_name: str = "stages",
        data_parallel: int = 1,
        data_axis: str = "data",
        stage_states=None,
        model_axis: str | None = None,
    ):
        """PP×TP (r5, VERDICT r4 #4): pass ``model_axis`` (a THIRD
        mapped mesh axis) and per-stage-per-rank parameter pytrees —
        ``stage_params[s]`` becomes a LIST of ``mp`` pytrees (identical
        structure, rank-local weight shards). Stage functions then run
        Megatron-style on their rank's shards and may invoke collectives
        (``lax.psum``) over ``model_axis``; such collectives are legal
        inside the stage ``lax.switch`` because every device of a model
        group sits in the same stage and takes the same branch (an
        AUTO/GSPMD model axis instead deadlocks — its partitioner emits
        global-group collectives inside the diverging switch). Storage
        splits ``[S, mp, P_max]`` over ``P(stages, model)`` — weights,
        grads, and optimizer slots all hold 1/(S·mp) per device."""
        import optax
        from jax.flatten_util import ravel_pytree

        self.has_state = stage_states is not None
        if not self.has_state:
            # pure-stage API: fn(params, x) -> y; normalize to the
            # stateful signature with empty state
            stage_fns = [
                (lambda fn: lambda p, st, x, training: (fn(p, x), st))(f)
                for f in stage_fns
            ]
            stage_states = [{} for _ in stage_fns]
        self.stage_fns = list(stage_fns)
        self.loss_fn = loss_fn
        self.S = len(self.stage_fns)
        if self.S < 2:
            raise ValueError("a pipeline needs at least 2 stages")
        if len(stage_params) != self.S:
            raise ValueError(
                f"{len(stage_params)} param trees for {self.S} stages"
            )
        self.M = int(num_microbatches)
        self.axis = axis_name
        self.data_axis = data_axis
        if mesh is None:
            mesh = pipeline_mesh(
                self.S, int(data_parallel), axis_name=axis_name,
                data_axis=data_axis,
            )
        elif int(data_parallel) > 1 and mesh.shape.get(data_axis, 1) != int(
            data_parallel
        ):
            raise ValueError(
                f"data_parallel={data_parallel} conflicts with the "
                f"explicit mesh (its {data_axis!r} axis has size "
                f"{mesh.shape.get(data_axis, 1)}) — pass one or the other"
            )
        if mesh.shape[axis_name] != self.S:
            raise ValueError(
                f"mesh axis {axis_name!r} has size {mesh.shape[axis_name]}, "
                f"need {self.S} (one device per stage)"
            )
        self.dp = mesh.shape.get(data_axis, 1)
        self.mesh = mesh
        self.optimizer = optimizer or optax.adam(1e-2)
        self.model_axis = model_axis
        if model_axis is not None and model_axis not in mesh.shape:
            raise ValueError(
                f"model_axis {model_axis!r} not in mesh axes "
                f"{tuple(mesh.shape)}"
            )
        self.mp = mesh.shape.get(model_axis, 1) if model_axis else 1

        if self.mp > 1:
            # per-stage-per-rank pytrees: ravel each rank's shard (same
            # structure/shapes across ranks, so one unravel per stage)
            for s, ranks in enumerate(stage_params):
                if len(ranks) != self.mp:
                    raise ValueError(
                        f"stage {s} has {len(ranks)} rank shards for a "
                        f"{self.mp}-way model axis"
                    )
            rank_flats = [
                [ravel_pytree(r)[0] for r in ranks]
                for ranks in stage_params
            ]
            self._unravels = tuple(
                ravel_pytree(ranks[0])[1] for ranks in stage_params
            )
            self._p_sizes = [int(f[0].size) for f in rank_flats]
            self.P_max = max(self._p_sizes)
            stacked = np.stack(
                [
                    np.stack(
                        [
                            np.pad(
                                np.asarray(f, np.float32),
                                (0, self.P_max - f.size),
                            )
                            for f in franks
                        ]
                    )
                    for franks in rank_flats
                ]
            )  # [S, mp, P_max]
        else:
            flats, self._unravels = zip(
                *[ravel_pytree(p) for p in stage_params]
            )
            self._p_sizes = [int(f.size) for f in flats]
            self.P_max = max(self._p_sizes)
            stacked = np.stack(
                [
                    np.pad(
                        np.asarray(f, np.float32), (0, self.P_max - f.size)
                    )
                    for f in flats
                ]
            )
        sflats, self._state_unravels = zip(
            *[ravel_pytree(s) for s in stage_states]
        )
        self._s_sizes = [int(f.size) for f in sflats]
        self.N_max = max(1, max(self._s_sizes))  # never a 0-width buffer
        stacked_state = np.stack(
            [
                np.pad(
                    np.asarray(f, np.float32).reshape(-1),
                    (0, self.N_max - f.size),
                )
                for f in sflats
            ]
        )
        self._stage_sh = NamedSharding(mesh, P(axis_name))
        # params (and their optimizer slots) also split over the model
        # axis when one exists: [S, mp, P_max] over P(stages, model)
        self._param_sh = (
            NamedSharding(mesh, P(axis_name, model_axis))
            if self.mp > 1
            else self._stage_sh
        )
        self._rep_sh = NamedSharding(mesh, P())
        # microbatch spec: [M, mb, ...] rows split over the data axis
        self._mb_spec = P(None, data_axis) if self.dp > 1 else P()
        self._mb_sh = NamedSharding(mesh, self._mb_spec)
        self.params = put_global(stacked, self._param_sh)
        self.state = put_global(stacked_state, self._stage_sh)
        # optimizer slots mirror the stacked layout; scalar counters
        # replicate
        state_struct = jax.eval_shape(self.optimizer.init, self.params)
        state_sh = jax.tree.map(
            lambda s_: self._param_sh if s_.shape[:1] == (self.S,) else self._rep_sh,
            state_struct,
        )
        self.opt_state = jax.jit(
            self.optimizer.init, out_shardings=state_sh
        )(self.params)
        self._shapes = None  # boundary ShapeDtypeStructs, set at first fit
        self._train_steps = {}  # keyed by collect_outputs
        self._predict_fn = None

    # -- shape plumbing --------------------------------------------------

    def _infer_shapes(self, mb_example):
        """Chain eval_shape through the stages → S+1 boundary shapes."""
        shapes = [jax.eval_shape(lambda a: a, mb_example)]
        for s in range(self.S):
            params_struct = jax.eval_shape(
                self._unravels[s],
                jax.ShapeDtypeStruct((self._p_sizes[s],), jnp.float32),
            )
            state_struct = jax.eval_shape(
                self._state_unravels[s],
                jax.ShapeDtypeStruct((self._s_sizes[s],), jnp.float32),
            )
            fn = self.stage_fns[s]
            out_struct = jax.eval_shape(
                lambda p, st, x, _fn=fn: _fn(p, st, x, True)[0],
                params_struct, state_struct, shapes[-1],
            )
            shapes.append(out_struct)
        self._shapes = shapes
        self._elems = [int(np.prod(s.shape)) for s in shapes]
        # the ring only carries boundaries 1..S (stage 0 reads the typed
        # microbatch directly — int token ids never round-trip float32)
        self.B_max = max(self._elems[1:])
        self.mb_rows = int(shapes[0].shape[0])

    def _branches(self, training: bool):
        """Per-stage flat-buffer transforms with static shapes. Each
        branch gets ``(p, st, buf, xm_mb)``; stage 0 reads the typed
        microbatch ``xm_mb``, later stages the flat ring buffer. Returns
        ``(out_flat [B_max], new_state_flat [N_max])``."""
        from jax.flatten_util import ravel_pytree

        branches = []
        for s in range(self.S):
            in_shape = self._shapes[s].shape
            in_elems = self._elems[s]
            out_pad = self.B_max - self._elems[s + 1]
            fn = self.stage_fns[s]
            unravel = self._unravels[s]
            s_unravel = self._state_unravels[s]
            p_size = self._p_sizes[s]
            s_size = self._s_sizes[s]
            s_pad = self.N_max - s_size
            first = s == 0

            def branch(p, st, buf, xm_mb, fn=fn, unravel=unravel,
                       s_unravel=s_unravel, p_size=p_size, s_size=s_size,
                       s_pad=s_pad, in_shape=in_shape, in_elems=in_elems,
                       out_pad=out_pad, first=first):
                x = xm_mb if first else buf[:in_elems].reshape(in_shape)
                out, st_new = fn(
                    unravel(p[:p_size]), s_unravel(st[:s_size]), x,
                    training,
                )
                flat = out.reshape(-1).astype(jnp.float32)
                st_flat = ravel_pytree(st_new)[0].astype(jnp.float32)
                return (
                    jnp.pad(flat, (0, out_pad)),
                    jnp.pad(st_flat.reshape(-1), (0, s_pad)),
                )

            branches.append(branch)
        return branches

    # -- forward/loss ----------------------------------------------------

    def _forward(self, collect_outputs: bool, with_loss: bool = True,
                 training: bool = True):
        """Build the shard_map'd pipeline program.

        Returns ``fn(params, state, xm, ym) -> (loss, outputs, state')``
        with ``xm [M, mb, ...]`` microbatches (replicated, original
        dtype — only stage 0 reads them) and ``ym [M, ...]`` targets
        (replicated; only the last stage reads them, and only when
        ``with_loss``). ``loss`` comes back replicated (scalar psum);
        outputs, if collected, come back per-stage-sharded
        ``[S, M, out_elems]`` — the caller reads shard ``S-1``; the
        non-trainable state comes back stage-sharded ``[S, N_max]``,
        updated only on ticks where the stage processed REAL microbatch
        data (bubble ticks carry garbage and must not touch BN stats)
        and only when ``training``.
        """
        S, M, axis = self.S, self.M, self.axis
        branches = self._branches(training)
        out_elems = self._elems[-1]
        out_shape = self._shapes[-1].shape
        loss_fn = self.loss_fn

        def per_device(pflat, stflat, xm, ym):
            # [1, P] per device — or [1, 1, P] with a mapped model axis
            p = pflat.reshape(pflat.shape[-1])
            stage = jax.lax.axis_index(axis)
            is_last = stage == S - 1
            ticks = M + S - 1

            def one_tick(carry, t):
                recv, outputs, loss_sum, st = carry
                mb_idx = jnp.clip(t, 0, M - 1)
                out, st_new = jax.lax.switch(
                    stage,
                    [
                        lambda pp, ss, b, xmb, br=br: br(pp, ss, b, xmb)
                        for br in branches
                    ],
                    p,
                    st,
                    recv,
                    xm[mb_idx],
                )
                if training:
                    # stage s holds microbatch s <= t < s + M; outside
                    # that window the input is pipeline-bubble garbage
                    processing = (t >= stage) & (t < stage + M)
                    st = jnp.where(processing, st_new, st)
                write_idx = t - (S - 1)
                is_valid = is_last & (write_idx >= 0)
                widx = jnp.clip(write_idx, 0, M - 1)
                if with_loss:
                    # sanitize before the loss: non-last stages feed zeros
                    # so the untaken where-branch cannot generate NaNs
                    # that leak through the gradient of where()
                    y_pred = jnp.where(
                        is_valid, out[:out_elems], jnp.zeros((out_elems,))
                    ).reshape(out_shape)
                    mb_loss = loss_fn(y_pred, ym[widx])
                    # rank-1 accumulator on purpose: a RANK-0 scan-carry
                    # residual breaks jax<=0.4.3x shard_map's transpose
                    # (_SpecError on the scalar residual) when the
                    # pipeline is differentiated through
                    loss_sum = loss_sum + jnp.where(is_valid, mb_loss, 0.0)[None]
                if collect_outputs:
                    updated = outputs.at[widx].set(out[:out_elems])
                    outputs = jnp.where(is_valid, updated, outputs)
                recv = jax.lax.ppermute(
                    out, axis, [(i, (i + 1) % S) for i in range(S)]
                )
                return (recv, outputs, loss_sum, st), None

            recv0 = jnp.zeros((self.B_max,), jnp.float32)
            outputs0 = jnp.zeros((M, out_elems), jnp.float32)
            (recv, outputs, loss_sum, st), _ = jax.lax.scan(
                one_tick,
                (recv0, outputs0, jnp.zeros((1,), jnp.float32),
                 stflat[0]),
                jnp.arange(ticks),
            )
            loss = jax.lax.psum(loss_sum[0], axis) / M
            if self.dp > 1:
                # each data replica's loss is the mean over its local
                # rows; the global mean averages the replicas (equal
                # row counts — the microbatch spec splits evenly)
                loss = jax.lax.pmean(loss, self.data_axis)
                if training:
                    # BN statistics must agree across data replicas
                    # (weights do implicitly via identical updates)
                    st = jax.lax.pmean(st, self.data_axis)
            return loss, outputs[None], st[None]

        out_mb_spec = (
            P(self.axis, None, self.data_axis) if self.dp > 1 else P(self.axis)
        )
        param_spec = (
            P(self.axis, self.model_axis) if self.mp > 1 else P(self.axis)
        )
        return jax.shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(param_spec, P(self.axis), self._mb_spec, self._mb_spec),
            out_specs=(P(), out_mb_spec, P(self.axis)),
            check_vma=False,
        )

    def _build_train_step(self, metric_update=None, mvs_example=None):
        """The jitted pipeline train step. With ``metric_update``, keras
        metric states accumulate INSIDE the compiled step on the last
        stage's predictions (r5, VERDICT r4 #5 — the r4 design returned
        per-step predictions as a gradient aux and updated metric states
        host-side: an O(dataset × output_dim) device→host transfer per
        epoch; now only the tiny metric-state pytree leaves the device,
        once per epoch)."""
        forward = self._forward(collect_outputs=metric_update is not None)
        optimizer = self.optimizer
        collect = metric_update is not None

        def loss_of(params, state, xm, ym):
            loss, outs, new_state = forward(params, state, xm, ym)
            # only the LAST stage's slice feeds the metric math —
            # reading the full stage-sharded [S, M, ·] buffer would
            # gather S× the needed bytes; when not collecting, nothing
            # is read and XLA DCEs the scan's outputs carry entirely
            # (code-review r4)
            aux = outs[self.S - 1] if collect else ()
            return loss, (new_state, aux)

        def base_step(params, state, opt_state, xm, ym):
            (loss, (new_state, outs)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params, state, xm, ym)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            import optax

            params = optax.apply_updates(params, updates)
            return params, new_state, opt_state, loss, outs

        state_sh = jax.tree.map(lambda l: l.sharding, self.opt_state)
        in_sh = (self._param_sh, self._stage_sh, state_sh,
                 self._mb_sh, self._mb_sh)
        out_sh = (self._param_sh, self._stage_sh, state_sh, self._rep_sh)

        if not collect:

            def step(params, state, opt_state, xm, ym):
                p, st, opt, loss, _ = base_step(params, state, opt_state,
                                                xm, ym)
                return p, st, opt, loss

            return jax.jit(
                step, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=(0, 1, 2),
            )

        mvs_rep = jax.tree.map(lambda _: self._rep_sh, mvs_example)

        def step(params, state, opt_state, xm, ym, mvs, sw):
            p, st, opt, loss, outs = base_step(params, state, opt_state,
                                               xm, ym)
            # [M, dp·elems] → [batch, ...] rows in input order (replica
            # r's rows are the r-th contiguous chunk of each
            # microbatch); ym flattens identically, so rows align. All
            # inside the jit — no host round-trip.
            out_tail = tuple(self._shapes[-1].shape[1:])
            batch = self.M * self.mb_rows * self.dp
            y_pred_rows = outs.reshape(
                (self.M, self.dp, self.mb_rows) + out_tail
            ).reshape((batch,) + out_tail)
            y_rows = ym.reshape((batch,) + tuple(ym.shape[2:]))
            mvs = metric_update(mvs, y_rows, y_pred_rows, sw.reshape(batch))
            return p, st, opt, loss, mvs

        return jax.jit(
            step,
            in_shardings=in_sh + (mvs_rep, self._mb_sh),
            out_shardings=out_sh + (mvs_rep,),
            donate_argnums=(0, 1, 2),
        )

    # -- data shaping ----------------------------------------------------

    def _microbatches(self, x, n_rows):
        """[B, ...] → [M, mb, ...] in the input's own dtype (stage 0
        consumes this directly — integer token ids stay integer)."""
        mb = n_rows // self.M
        return np.asarray(x).reshape((self.M, mb) + x.shape[1:])

    # -- API -------------------------------------------------------------

    def fit(self, x, y, epochs: int = 1, batch_size: int = 32, verbose: int = 0,
            callbacks=None, metric_state=None, metric_update=None,
            on_epoch_metrics=None):
        """Mini-batch training; returns ``{'loss': [...]}`` per epoch.
        ``callbacks`` are ``cb(epoch, loss)`` at epoch boundaries.

        Compiled training metrics (r5, VERDICT r4 #5): pass
        ``metric_state`` (an initial state pytree),
        ``metric_update(mvs, y_rows, y_pred_rows, sw_rows) -> mvs``
        (traced INTO the jitted step — it sees the last stage's
        predictions on device, wrap-padded duplicate rows zero-weighted
        via ``sw_rows``), and ``on_epoch_metrics(mvs_host)`` (called at
        each epoch boundary, BEFORE ``callbacks``, with the host-read
        accumulated state, after which the state resets). Only the tiny
        state pytree crosses to host, once per epoch — predictions
        never do.

        ``batch_size`` is rounded up to a multiple of ``M`` (each
        microbatch keeps a fixed shape); the final short batch wrap-pads
        rows at full weight for the LOSS — duplicated rows slightly
        overweight, the same semantics as the DP runner's staged
        :func:`~elephas_tpu.worker.pad_to_batches` (the masked-tail
        exactness of :class:`~elephas_tpu.parallel.tensor.ShardedTrainer`
        would need weight-aware user loss_fns here). Metrics DO
        zero-weight the pads, like keras.
        """
        x = np.asarray(x)
        y = np.asarray(y)
        n = len(x)
        M = self.M
        grain = M * self.dp  # microbatch rows must split over data replicas
        batch_size = max(grain, (batch_size // grain) * grain)
        if self._shapes is None:
            # boundary shapes are per-DEVICE: the local microbatch slice
            mb_x = jnp.zeros((batch_size // grain,) + x.shape[1:], x.dtype)
            self._infer_shapes(mb_x)
        # the compiled pipeline is specialized to one microbatch shape
        batch_size = self.M * self.mb_rows * self.dp
        nb = max(1, int(np.ceil(n / batch_size)))
        idx = np.arange(nb * batch_size) % n
        collect = metric_update is not None
        train_step = self._get_train_step(metric_update, metric_state)
        mvs = None
        sw_full = sw_tail = None
        if collect:
            mvs = jax.tree.map(
                lambda l: put_global(np.asarray(l), self._rep_sh),
                metric_state,
            )
            # only TWO masks exist — all-ones, and the wrap-padded tail
            # batch; stage each ONCE instead of re-uploading per step
            # (code-review r5)
            sw_full = put_global(
                np.ones((M, batch_size // M), np.float32), self._mb_sh
            )
            tail = (
                ((nb - 1) * batch_size + np.arange(batch_size)) < n
            ).astype(np.float32).reshape(M, batch_size // M)
            sw_tail = (
                sw_full if tail.all() else put_global(tail, self._mb_sh)
            )

        history = {"loss": []}
        for epoch in range(epochs):
            losses = []
            for b in range(nb):
                rows = idx[b * batch_size : (b + 1) * batch_size]
                xm = self._microbatches(x[rows], batch_size)
                ym = np.asarray(y[rows]).reshape(
                    (M, batch_size // M) + y.shape[1:]
                )
                args = (
                    self.params, self.state, self.opt_state,
                    put_global(xm, self._mb_sh),
                    put_global(ym, self._mb_sh),
                )
                if collect:
                    (self.params, self.state, self.opt_state, loss,
                     mvs) = train_step(
                        *args, mvs,
                        sw_tail if b == nb - 1 else sw_full,
                    )
                else:
                    self.params, self.state, self.opt_state, loss = (
                        train_step(*args)
                    )
                losses.append(loss)
            if collect:
                mvs = self._drain_metrics(
                    mvs, metric_state, on_epoch_metrics
                )
            self._finish_epoch(
                history, losses, epoch, epochs, verbose, callbacks
            )
        return history

    def _get_train_step(self, metric_update=None, metric_state=None):
        """Get-or-build the jitted step, cached per metrics-or-not. The
        cache pins the exact ``metric_update`` closure it traced — a
        DIFFERENT closure (or state pytree) on a later fit rebuilds
        instead of silently serving the stale traced math
        (code-review r5)."""
        key = metric_update is not None
        cached = self._train_steps.get(key)
        if cached is not None and cached[1] is metric_update:
            return cached[0]
        step = self._build_train_step(metric_update, metric_state)
        self._train_steps[key] = (step, metric_update)
        return step

    def _drain_metrics(self, mvs, metric_state, on_epoch_metrics):
        """Epoch-boundary metric handoff shared by the staged and
        streamed fits: host-read the accumulated state, hand it to the
        caller, reset to the initial state on device."""
        on_epoch_metrics(
            jax.tree.map(lambda l: host_read(l, self.mesh), mvs)
        )
        return jax.tree.map(
            lambda l: put_global(np.asarray(l), self._rep_sh),
            metric_state,
        )

    def _finish_epoch(self, history, losses, epoch, epochs, verbose,
                      callbacks):
        """Shared staged/streamed epoch bookkeeping: history append,
        logging, callback dispatch."""
        epoch_loss = float(np.mean([np.asarray(l) for l in losses]))
        history["loss"].append(epoch_loss)
        if verbose:
            logger.info(
                "epoch %d/%d - loss %.4f", epoch + 1, epochs, epoch_loss
            )
        if callbacks:
            for cb in callbacks:
                cb(epoch, epoch_loss)
        return epoch_loss

    def fit_stream(self, stream, epochs: int = 1, verbose: int = 0,
                   callbacks=None, metric_state=None, metric_update=None,
                   on_epoch_metrics=None):
        """Streamed training over :class:`ShardedStream` blocks shaped
        ``[dp, steps, B, ...]`` — each step's global batch is the
        ``dp`` row-shards concatenated (``dp·B`` rows), microbatched
        through the ring like :meth:`fit`. Blocks never all live in
        device memory at once; the next block's host gather runs under
        the current block's compute (async dispatch).

        The stream's (per-worker) batch must divide into the ``M``
        microbatches — every step then carries the exact compiled shape
        with no mid-epoch padding (the stream wrap-pads short shard
        tails internally, matching the staged path's tail semantics).

        Compiled training metrics (r5, VERDICT r4 #7): same
        ``metric_state`` / ``metric_update`` / ``on_epoch_metrics``
        contract as :meth:`fit` — states accumulate on device through
        every streamed block and cross to host once per epoch.
        Stream-internal wrap-pad rows are zero-weighted in the METRICS
        via the stream's valid-row counts (ADVICE r5 — streamed and
        staged fits report identical epoch metrics); the loss keeps
        counting them at full weight, like the staged path.
        """
        from elephas_tpu.data.streaming import prefetch_blocks

        if stream.num_workers != self.dp:
            raise ValueError(
                f"stream has {stream.num_workers} shards for a "
                f"{self.dp}-replica data axis"
            )
        M, dp = self.M, self.dp
        if stream.batch_size % M:
            raise ValueError(
                f"stream batch_size={stream.batch_size} must be a "
                f"multiple of num_microbatches={M} (else every step "
                f"would pad duplicated rows, biasing gradients)"
            )
        if self._shapes is None:
            x1 = np.asarray(stream.x[0:1])
            self._infer_shapes(
                jnp.zeros(
                    (stream.batch_size // M,) + x1.shape[1:], x1.dtype
                )
            )
        need = M * self.mb_rows * dp
        if dp * stream.batch_size != need:
            raise ValueError(
                f"stream supplies {dp * stream.batch_size} rows/step but "
                f"the compiled pipeline takes {need} — match the stream "
                f"batch_size to the fit batch_size"
            )
        collect = metric_update is not None
        train_step = self._get_train_step(metric_update, metric_state)
        mvs = None
        sw_full = None
        sw_cache: dict[tuple, object] = {}
        if collect:
            mvs = jax.tree.map(
                lambda l: put_global(np.asarray(l), self._rep_sh),
                metric_state,
            )
            # metric weights zero the stream-internal wrap-pad rows so
            # streamed and staged fits report IDENTICAL epoch metrics
            # (ADVICE r5 — the loss still counts pads at full weight,
            # the documented staged-path semantics). Only a handful of
            # distinct masks exist (all-ones plus each shard-tail
            # pattern); each stages ONCE and is reused every epoch —
            # no per-step upload (code-review r5)
            sw_full = put_global(
                np.ones((M, need // M), np.float32), self._mb_sh
            )

        def _sw_for(gs: int):
            counts = stream.step_valid_counts(gs)
            if (counts >= stream.batch_size).all():
                return sw_full
            key = tuple(int(c) for c in counts)
            staged = sw_cache.get(key)
            if staged is None:
                # [dp, B] row validity flattens worker-major, exactly
                # like the step's x rows, then microbatches like them
                mask = (
                    np.arange(stream.batch_size)[None, :]
                    < counts[:, None]
                ).astype(np.float32)
                staged = put_global(
                    mask.reshape(M, need // M), self._mb_sh
                )
                sw_cache[key] = staged
            return staged

        history: dict[str, list[float]] = {"loss": []}
        for epoch in range(epochs):
            losses = []
            gs = 0  # global step index within the epoch
            for xb, yb, steps in prefetch_blocks(stream.blocks()):
                for t in range(steps):
                    xt, yt = xb[:, t], yb[:, t]  # [dp, B, ...]
                    x_flat = xt.reshape((need,) + xt.shape[2:])
                    y_flat = np.asarray(yt).reshape(
                        (need,) + yt.shape[2:]
                    )
                    xm = self._microbatches(x_flat, need)
                    ym = y_flat.reshape((M, need // M) + y_flat.shape[1:])
                    args = (
                        self.params, self.state, self.opt_state,
                        put_global(xm, self._mb_sh),
                        put_global(ym, self._mb_sh),
                    )
                    if collect:
                        (self.params, self.state, self.opt_state, loss,
                         mvs) = train_step(*args, mvs, _sw_for(gs))
                    else:
                        (self.params, self.state, self.opt_state,
                         loss) = train_step(*args)
                    losses.append(loss)
                    gs += 1
            if collect:
                mvs = self._drain_metrics(
                    mvs, metric_state, on_epoch_metrics
                )
            self._finish_epoch(
                history, losses, epoch, epochs, verbose, callbacks
            )
        return history

    def predict(self, x, batch_size: int = 32) -> np.ndarray:
        x = np.asarray(x)
        n = len(x)
        M = self.M
        grain = M * self.dp
        batch_size = max(grain, (batch_size // grain) * grain)
        if self._shapes is None:
            mb_x = jnp.zeros((batch_size // grain,) + x.shape[1:], x.dtype)
            self._infer_shapes(mb_x)
        batch_size = self.M * self.mb_rows * self.dp  # fixed microbatch shape
        if self._predict_fn is None:
            # inference program: moving statistics, no state writes
            forward = self._forward(
                collect_outputs=True, with_loss=False, training=False
            )
            out_mb_spec = (
                P(self.axis, None, self.data_axis)
                if self.dp > 1
                else P(self.axis)
            )
            self._predict_fn = jax.jit(
                lambda p, st, xm, ym: forward(p, st, xm, ym)[1],
                in_shardings=(self._param_sh, self._stage_sh, self._mb_sh,
                              self._mb_sh),
                out_shardings=NamedSharding(self.mesh, out_mb_spec),
            )
        out_shape = self._shapes[-1].shape  # local microbatch output
        nb = max(1, int(np.ceil(n / batch_size)))
        idx = np.arange(nb * batch_size) % n
        # targets unused without loss; dp rows so the data spec splits
        # (staged once — it never changes across batches)
        ym0_dev = put_global(np.zeros((M, self.dp), np.float32), self._mb_sh)
        outs = []
        for b in range(nb):
            rows = idx[b * batch_size : (b + 1) * batch_size]
            xm = self._microbatches(x[rows], batch_size)
            res = host_read(
                self._predict_fn(
                    self.params, self.state, put_global(xm, self._mb_sh),
                    ym0_dev,
                ),
                self.mesh,
            )
            # last stage's shard: [M, dp·elems_local]; replica r's rows
            # are the r-th contiguous chunk of each microbatch, so
            # [M, dp, mb_local, ...] flattens back to the input order
            outs.append(
                res[self.S - 1].reshape(
                    (M, self.dp, self.mb_rows) + out_shape[1:]
                ).reshape((batch_size,) + out_shape[1:])
            )
        return np.concatenate(outs)[:n]

    def _stage_from_host(self, host, s: int):
        """Unravel stage ``s`` from the gathered ``[S, P_max]`` (or
        ``[S, mp, P_max]``) host params. With a model axis the result is
        the LIST of per-rank shard pytrees — the caller re-assembles
        full variables per its slicing convention."""
        if self.mp > 1:
            return [
                self._unravels[s](
                    jnp.asarray(host[s, r][: self._p_sizes[s]])
                )
                for r in range(self.mp)
            ]
        return self._unravels[s](jnp.asarray(host[s][: self._p_sizes[s]]))

    def stage_weights_all(self) -> list:
        """Every stage's parameter pytree (per-rank pytrees under a
        model axis) from ONE gather of the stacked params (cross-process
        shards all-gather first) — weight syncs walk all stages, so
        per-stage gathers would move the full parameter set S times."""
        host = host_read(self.params, self.mesh)
        return [self._stage_from_host(host, s) for s in range(self.S)]

    def stage_weights(self, s: int):
        """Stage ``s``'s parameter pytree (host copy, unflattened;
        one gather, one unravel — loop via :meth:`stage_weights_all`
        to amortize the gather across stages)."""
        return self._stage_from_host(host_read(self.params, self.mesh), s)

    def stage_states_all(self) -> list:
        """Every stage's non-trainable state pytree from ONE gather of
        the stacked ``[S, N_max]`` state (see :meth:`stage_weights_all`)."""
        host = host_read(self.state, self.mesh)
        return [
            self._state_unravels[s](
                jnp.asarray(host[s][: self._s_sizes[s]])
            )
            for s in range(self.S)
        ]
