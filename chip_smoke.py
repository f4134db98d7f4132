#!/usr/bin/env python3
"""The quickest proof that ``SparkModel.fit`` and ``SparkModel.serve``
still start on the chip.

    python chip_smoke.py             # one TPU chip: fit (ResNet-50, LM), serve
    python chip_smoke.py --chips 4   # four chips: data-parallel fit vs one worker

One process, which holds the chip. ``main()`` always runs at full width
and always requires the TPU: nothing selects the CPU. Every phase
prints lines that start with its name; a phase that fails raises, the
process exits non-zero and no result line is printed. The last line of
stdout is the result: ``{"ok": true, "device": {...}}``.

The phases are plain functions of their sizes, so the tests call them
at toy sizes on the virtual CPU mesh (``tests/test_tpu_smoke.py``).
Speeds printed here are a smoke run's, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import http.client
import json
import os
import socket
import sys
import time

os.environ.setdefault("KERAS_BACKEND", "jax")

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# -- the widths main() runs at --------------------------------------------
# ResNet-50 at the widths of the benchmark's resnet50-fit-staged cell.
CONVNET = dict(image=224, classes=1000, batch=256, steps=2, epochs=2)
# GPT-2-small, the shape models.transformer_lm's block implements.
LM = dict(vocab_size=50257, maxlen=1024, d_model=768, num_heads=12,
          num_layers=12)
# Trained until the periodic continuation is sharp (epoch loss under
# sharp_loss; ln 4 = 1.39 is the plateau where only the four tokens'
# frequencies are learned), so that serve's greedy tokens are compared
# where no two logits are close.
LM_FIT = dict(batch=8, steps=64, epochs=2, max_epochs=10, sharp_loss=0.1,
              lr=1e-3)
# 8 slots x 1024 positions of paged KV; every chunk one width, so the
# compiled set is the same with and without prefix hits.
SERVE = dict(num_slots=8, block_size=16, num_blocks=8 * 64,
             prefill_chunk=128)
SERVE_PROMPT_LENS = (12, 40, 100, 300)
SERVE_NEW_TOKENS = 16
# --chips 4: per-worker batch 2 on four workers against one worker at 8.
# The two jobs take the same gradient steps up to the order of the bf16
# sums, so their epoch losses may differ by a few bf16 roundings
# (2**-8 = 3.9e-3 each). Tolerance on |loss4 - loss1| / loss1, fixed
# before the chip run on the four-virtual-device CPU rehearsal in bf16,
# where the largest gap was 1.4e-4 (tests/test_tpu_smoke.py).
DP = dict(workers=4, batch=2, steps=4, epochs=3, lr=1e-3)
DP_LOSS_RTOL = 1e-2
PERIOD = 4  # the periodic token sequences of tests/conftest.py


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(phase: str, **fields) -> None:
    print(
        f"[{phase}] "
        + " ".join(f"{k}={json.dumps(v)}" for k, v in fields.items()),
        flush=True,
    )


class CompileMeter:
    """Counts what JAX's own monitoring reports about compilation:
    seconds in the backend compiler (cache retrieval included), compile
    requests that consulted the persistent cache, and its hits."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.requests, self.hits

    def since(self, mark: tuple) -> dict:
        return {
            "compile_s": round(self.seconds - mark[0], 2),
            "cache_requests": self.requests - mark[1],
            "cache_hits": self.hits - mark[2],
        }


def memory_line(devices) -> list:
    """Per device ``[bytes_in_use, peak_bytes_in_use]`` (None where the
    backend keeps no such statistics: the CPU)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(
            None if not stats
            else [stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")]
        )
    return out


def periodic_tokens(n: int, length: int, seed: int) -> np.ndarray:
    """``[n, length]`` sequences that step through ``PERIOD`` token ids:
    the next token is a function of the current one, so a few training
    steps make the greedy continuation sharp."""
    starts = np.random.default_rng(seed).integers(0, PERIOD, size=n)
    return ((starts[:, None] + np.arange(length)) % PERIOD + 2).astype(
        np.int32
    )


# -- fit ------------------------------------------------------------------

def _fit_and_inspect(phase, model, x, y, *, batch, epochs, num_workers,
                     platform, want_in_program=()):
    """``SparkModel(model, mode="synchronous").fit`` over an RDD, then
    look at what the runner stages and runs: every staged array on the
    mesh's devices (and those on ``platform``), the compiled epoch
    program's text and memory. Returns the losses, and the wrapper and
    RDD for a phase that trains on."""
    from elephas_tpu import SparkModel
    from elephas_tpu.data import SparkContext
    from elephas_tpu.utils import rdd_utils
    from elephas_tpu.worker import stack_worker_batches

    sm = SparkModel(model, mode="synchronous", num_workers=num_workers)
    workers = sm.num_workers
    rdd = rdd_utils.to_simple_rdd(
        SparkContext(f"local[{workers}]"), x, y, num_partitions=workers
    )
    t0 = time.perf_counter()
    history = sm.fit(rdd, epochs=epochs, batch_size=batch)
    fit_s = time.perf_counter() - t0
    losses = [float(v) for v in history["loss"]]
    check(
        len(losses) == epochs and all(np.isfinite(losses)),
        f"{phase}: losses not finite: {losses}",
    )

    # what run_epochs stages, staged again through the runner's own calls
    runner = sm._get_runner()
    mesh_devices = set(runner.mesh.devices.flat)
    parts = runner._fit_partitions_to_mesh(rdd_utils.partition_arrays(rdd))
    xs, ys, _counts, nb = stack_worker_batches(parts, batch)
    xb, yb = runner._shard_data(xs), runner._shard_data(ys)
    tv, ntv, ov = runner._device_state()
    staged = [xb, yb, *tv, *ntv, *ov]
    for arr in staged:
        check(
            set(arr.sharding.device_set) == mesh_devices,
            f"{phase}: a staged array sits on {arr.sharding.device_set}, "
            f"not on the mesh {mesh_devices}",
        )
    check(
        {d.platform for d in mesh_devices} == {platform},
        f"{phase}: mesh devices {mesh_devices} are not on {platform!r}",
    )
    held = memory_line(runner.mesh.devices.flat)
    check(
        all(m is None or m[0] > 0 for m in held),
        f"{phase}: a mesh device holds no bytes while staged: {held}",
    )
    metric_objects = runner._unwrapped_metrics(parts[0][0], parts[0][1])
    compiled = runner._epoch_fn.lower(
        tv, ntv, ov, runner._zero_metric_state(metric_objects), xb, yb
    ).compile()
    text = compiled.as_text()
    for needle in want_in_program:
        check(
            needle in text,
            f"{phase}: compiled epoch program has no {needle!r}",
        )
    mem = compiled.memory_analysis()
    say(
        phase, workers=workers, steps_per_epoch=int(nb), batch=batch,
        epochs=epochs, fit_s=round(fit_s, 2), losses=losses,
        staged_arrays=len(staged),
        staged_on=sorted(str(d) for d in mesh_devices),
        in_program=list(want_in_program),
        program_bytes={
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temps": mem.temp_size_in_bytes,
        },
        memory_in_use_peak=held,
    )
    return {"losses": losses, "workers": workers, "spark_model": sm,
            "rdd": rdd}


def phase_fit_convnet(make_model, *, image, classes, batch, steps, epochs,
                      seed, platform):
    """The north-star job: a ResNet through ``SparkModel.fit`` on
    seeded synthetic images. Loss finite, staged arrays on the device."""
    import jax

    rng = np.random.default_rng(seed)
    n = batch * steps * jax.device_count()  # fit() takes every device
    x = rng.normal(size=(n, image, image, 3)).astype(np.float32)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    out = _fit_and_inspect(
        "fit_convnet", make_model(), x, y, batch=batch, epochs=epochs,
        num_workers=None, platform=platform,
    )
    return {"losses": out["losses"]}


def _lm_data(maxlen, rows, seed):
    seq = periodic_tokens(rows, maxlen + 1, seed)
    return seq[:, :-1], seq[:, 1:]


def phase_fit_lm(make_model, *, maxlen, batch, steps, epochs, max_epochs,
                 sharp_loss, seed, platform,
                 kernel_marker="tpu_custom_call"):
    """The LM through ``SparkModel.fit``: the compiled epoch holds the
    Pallas kernel (``kernel_marker``; None where kernels interpret),
    the loss falls, and further epochs (at most ``max_epochs`` in all)
    bring it under ``sharp_loss``. Returns the trained model."""
    import jax

    model = make_model()
    x, y = _lm_data(maxlen, batch * steps * jax.device_count(), seed)
    out = _fit_and_inspect(
        "fit_lm", model, x, y, batch=batch, epochs=epochs,
        num_workers=None, platform=platform,
        want_in_program=(kernel_marker,) if kernel_marker else (),
    )
    losses = out["losses"]
    check(
        losses[-1] < losses[0],
        f"fit_lm: loss did not fall over {epochs * steps} steps: {losses}",
    )
    t0 = time.perf_counter()
    while losses[-1] >= sharp_loss and len(losses) < max_epochs:
        more = out["spark_model"].fit(out["rdd"], epochs=1, batch_size=batch)
        losses.append(float(more["loss"][0]))
    say("fit_lm", steps_taken=len(losses) * steps, losses=losses,
        sharp_loss=sharp_loss,
        further_epochs_s=round(time.perf_counter() - t0, 2))
    check(
        np.isfinite(losses[-1]) and losses[-1] < sharp_loss,
        f"fit_lm: loss {losses[-1]} after {len(losses) * steps} steps is "
        f"not under {sharp_loss}: {losses}",
    )
    return {"losses": losses, "model": model}


def phase_fit_dp(make_model, *, maxlen, workers, batch, steps, epochs, seed,
                 platform, rtol, kernel_marker="tpu_custom_call"):
    """Data-parallel ``fit`` on ``workers`` devices against the same job
    on one: every device holds its shard and a replica, the compiled
    epoch holds the all-reduce, and the losses agree within ``rtol``."""
    x, y = _lm_data(maxlen, workers * batch * steps, seed)
    wanted = ("all-reduce",) + ((kernel_marker,) if kernel_marker else ())
    many = _fit_and_inspect(
        "fit_dp", make_model(), x, y, batch=batch, epochs=epochs,
        num_workers=workers, platform=platform, want_in_program=wanted,
    )
    check(
        many["workers"] == workers,
        f"fit_dp: asked for {workers} workers, the mesh has "
        f"{many['workers']}",
    )
    # One worker, one step = the same rows the W workers saw in that
    # step: worker w trains on partition w (a contiguous 1/W of the
    # rows), batch by batch.
    n = len(x)
    order = (
        np.arange(n).reshape(workers, steps, batch).transpose(1, 0, 2)
    ).reshape(n)
    one = _fit_and_inspect(
        "fit_dp_one_worker", make_model(), x[order], y[order],
        batch=workers * batch, epochs=epochs, num_workers=1,
        platform=platform,
    )
    gaps = [
        abs(a - b) / abs(b) for a, b in zip(many["losses"], one["losses"])
    ]
    say("fit_dp", losses=many["losses"], losses_one_worker=one["losses"],
        relative_gaps=[round(g, 6) for g in gaps], rtol=rtol)
    check(
        many["losses"][-1] < many["losses"][0],
        f"fit_dp: loss did not fall: {many['losses']}",
    )
    check(
        max(gaps) <= rtol,
        f"fit_dp: {workers}-worker and 1-worker losses differ by "
        f"{max(gaps):.4g} > {rtol}: {many['losses']} vs {one['losses']}",
    )
    return {"losses": many["losses"], "losses_one_worker": one["losses"],
            "gaps": gaps}


# -- serve ----------------------------------------------------------------

def _sse_generate(port: int, prompt, max_new_tokens: int) -> list:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request(
            "POST", "/v1/generate",
            body=json.dumps({
                "prompt": [int(t) for t in prompt],
                "max_new_tokens": int(max_new_tokens),
            }),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        raw = resp.read().decode("utf-8")
    finally:
        conn.close()
    check(resp.status == 200, f"serve: gateway answered {resp.status}: {raw}")
    events = [
        json.loads(line[len("data: "):])
        for line in raw.splitlines() if line.startswith("data: ")
    ]
    tokens = [e for e in events if "token" in e]
    check(
        tokens and tokens[-1]["done"] and not events[-1].get("error"),
        f"serve: SSE stream did not end cleanly: {events[-2:]}",
    )
    return [e["token"] for e in tokens]


def phase_serve(model, *, prompt_lens, new_tokens, num_slots, block_size,
                num_blocks, prefill_chunk, seed, devices, min_prob=0.5):
    """``SparkModel.serve`` with the paged arena, the prefix cache and
    a live gateway. Requests of mixed lengths (one submitted mid-flight)
    in-process, one over HTTP/SSE; every stream must equal one-shot
    ``generate(..., kv_cache=True)`` token for token; ``engine.score``
    must find those tokens greedy and give each a probability of at
    least ``min_prob`` (so the token comparison is made where the
    chip's bf16 sums cannot flip an argmax); and a second identical
    pass must compile nothing. ``model`` computes in float32 (the
    engine refuses anything else)."""
    from elephas_tpu import SparkModel
    from elephas_tpu.models import generate

    seqs = periodic_tokens(len(prompt_lens) + 2, max(prompt_lens), seed)
    # in-process: one prompt per length; the late arrival and the
    # gateway's prompt reuse the second length (and its reference
    # program) with other contents
    first = [seqs[i, :n] for i, n in enumerate(prompt_lens)]
    late = seqs[len(prompt_lens), :prompt_lens[1]]
    over_http = seqs[len(prompt_lens) + 1, :prompt_lens[1]]

    t0 = time.perf_counter()
    refs = {}
    for prompt in [*first, late, over_http]:
        refs[prompt.tobytes()] = [int(t) for t in generate(
            model, prompt[None], new_tokens, kv_cache=True
        )[0]]
    say("serve", reference="generate(kv_cache=True)", requests=len(refs),
        reference_s=round(time.perf_counter() - t0, 2))

    def agree(what, prompt, got):
        want = refs[prompt.tobytes()]
        got = [int(t) for t in got]
        if got != want:
            j = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
            raise SmokeFailure(
                f"serve: {what} (prompt of {len(prompt)}) diverges from "
                f"one-shot generate at position {j}: got {got[j]}, "
                f"reference {want[j]}; got {got[len(prompt):]} reference "
                f"{want[len(prompt):]}"
            )

    def one_pass(engine, label):
        # the gateway's driver thread steps this engine whenever it has
        # work; an in-process drive holds the same lock its handlers do
        with engine.gateway._engine_lock:
            reqs = [engine.submit(p, new_tokens) for p in first]
            for _ in range(3):
                engine.step()
            reqs.append(engine.submit(late, new_tokens))
            engine.run()
        for req, prompt in zip(reqs, [*first, late]):
            check(req.done and req.error is None,
                  f"serve: request {req.rid} ended with {req.error!r}")
            agree(f"{label} in-process request {req.rid}", prompt,
                  req.full_sequence)
        streamed = _sse_generate(engine.gateway.port, over_http, new_tokens)
        agree(f"{label} gateway stream", over_http,
              [*over_http, *streamed])
        return [int(r.reused_tokens) for r in reqs]

    sm = SparkModel(model, mode="synchronous")
    before = memory_line(devices)
    t0 = time.perf_counter()
    with sm.serve(
        num_slots=num_slots, paged=True, block_size=block_size,
        num_blocks=num_blocks, prefix_cache=True,
        prefill_chunk=prefill_chunk, gateway_port=0,
    ) as engine:
        port = engine.gateway.port
        say("serve", engine_up_s=round(time.perf_counter() - t0, 2),
            port=port, kv_arena_bytes=engine.arena.nbytes(),
            table_buckets=list(engine.compile_stats()["table_buckets"]),
            memory_in_use_peak=memory_line(devices))
        t0 = time.perf_counter()
        reused_cold = one_pass(engine, "first pass")
        first_s = time.perf_counter() - t0
        # logits, not only tokens: the continuation the reference chose
        # is the greedy one under the engine's own forward
        prompt = first[-1]
        completion = refs[prompt.tobytes()][len(prompt):]
        scored = engine.score(prompt, completion)
        check(
            scored["greedy_tokens"] == completion
            and np.isfinite(scored["total_logprob"]),
            f"serve: score() disagrees with the reference: {scored}",
        )
        check(
            min(scored["logprobs"]) >= np.log(min_prob),
            f"serve: the model is too flat for a token comparison to "
            f"mean much: least probability of a reference token "
            f"{np.exp(min(scored['logprobs'])):.3f} < {min_prob}",
        )
        compiled_once = engine.compile_stats()
        t0 = time.perf_counter()
        reused_warm = one_pass(engine, "second pass")
        second_s = time.perf_counter() - t0
        engine.score(prompt, completion)
        compiled_twice = engine.compile_stats()
        check(
            compiled_twice == compiled_once,
            f"serve: the second identical pass compiled: "
            f"{compiled_once} -> {compiled_twice}",
        )
        check(
            sum(reused_warm) > 0,
            f"serve: the second pass reused no prefix: {reused_warm}",
        )
        stats = engine.stats()
        serving = memory_line(devices)
        say("serve", requests_per_pass=len(first) + 2,
            prompt_lens=[*prompt_lens, prompt_lens[1], prompt_lens[1]],
            new_tokens=new_tokens, first_pass_s=round(first_s, 2),
            second_pass_s=round(second_s, 2),
            tokens_exact_vs_generate=True,
            least_token_probability=round(
                float(np.exp(min(scored["logprobs"]))), 4),
            reused_tokens_first=reused_cold, reused_tokens_second=reused_warm,
            total_generated=stats["total_generated"],
            compile_stats={k: v for k, v in compiled_twice.items()
                           if k.endswith("_compiles")},
            second_pass_compiled_nothing=True,
            memory_in_use_peak=serving)
    # leaving the block: the port is free again, and with the last
    # reference gone so are the device buffers
    with contextlib.closing(socket.socket()) as probe:
        probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        probe.bind(("127.0.0.1", port))
    engine.release_telemetry()
    del engine
    gc.collect()
    after = memory_line(devices)
    if all(m is not None for m in serving + after):
        check(
            all(a[0] < s[0] for a, s in zip(after, serving)),
            f"serve: device bytes did not drop after the engine went: "
            f"{serving} -> {after}",
        )
    say("serve", port_released=port, memory_before=before,
        memory_after=after)
    return {"reused_warm": reused_warm, "compile_stats": compiled_twice}


# -- main -----------------------------------------------------------------

def _make_lm(dtype_policy, lr):
    from elephas_tpu.models import transformer_lm

    return transformer_lm(
        **LM, dropout=0.0, lr=lr, seed=0, dtype_policy=dtype_policy
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: the data-parallel fit and its one-worker "
                             "twin, and no other phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    from elephas_tpu.utils import backend_guard

    cache_dir = backend_guard.use_compile_cache(HERE)
    entries_before = backend_guard.compile_cache_entries(cache_dir)
    say("device", compile_cache=cache_dir, entries_before=entries_before,
        placed_by=(backend_guard.COMPILE_CACHE_ENV
                   if os.environ.get(backend_guard.COMPILE_CACHE_ENV)
                   else "chip_smoke.py"))

    device = backend_guard.require_accelerator("tpu")
    check(
        device["count"] == args.chips,
        f"device: --chips {args.chips} needs exactly that many, JAX "
        f"reports {device}",
    )
    import jax

    devices = jax.devices()
    say("device", **device, devices=[str(d) for d in devices],
        jax=jax.__version__)
    meter = CompileMeter()
    t_start = time.perf_counter()

    def timed(name, fn, **kw):
        mark, t0 = meter.snapshot(), time.perf_counter()
        out = fn(**kw)
        say(name, phase_s=round(time.perf_counter() - t0, 2),
            **meter.since(mark), memory_in_use_peak=memory_line(devices))
        return out

    if args.chips == 4:
        timed(
            "fit_dp", phase_fit_dp,
            make_model=lambda: _make_lm("mixed_bfloat16", DP["lr"]),
            maxlen=LM["maxlen"], workers=DP["workers"], batch=DP["batch"],
            steps=DP["steps"], epochs=DP["epochs"], seed=args.seed,
            platform="tpu", rtol=DP_LOSS_RTOL,
        )
    else:
        from elephas_tpu.models import resnet50

        timed(
            "fit_convnet", phase_fit_convnet,
            make_model=lambda: resnet50(
                input_shape=(CONVNET["image"],) * 2 + (3,),
                num_classes=CONVNET["classes"],
                dtype_policy="mixed_bfloat16",
            ),
            **CONVNET, seed=args.seed, platform="tpu",
        )
        trained = timed(
            "fit_lm", phase_fit_lm,
            make_model=lambda: _make_lm("mixed_bfloat16", LM_FIT["lr"]),
            maxlen=LM["maxlen"],
            **{k: v for k, v in LM_FIT.items() if k != "lr"},
            seed=args.seed, platform="tpu",
        )["model"]
        # serve() and generate(kv_cache=True) refuse a model that does
        # not compute in float32, so the engine gets the float32 twin of
        # the LM just trained: same graph, same (float32) variables.
        twin = _make_lm(None, LM_FIT["lr"])
        twin.set_weights(trained.get_weights())
        del trained
        timed(
            "serve", phase_serve, model=twin,
            prompt_lens=SERVE_PROMPT_LENS, new_tokens=SERVE_NEW_TOKENS,
            **SERVE, seed=args.seed + 1, devices=devices,
        )

    say("device", total_s=round(time.perf_counter() - t_start, 2),
        compile_cache=cache_dir, entries_before=entries_before,
        entries_after=backend_guard.compile_cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
