"""Test harness setup.

The reference tests "distribute" via Spark local-mode thread executors in
one JVM (SURVEY.md §4). The JAX analogue: an 8-device virtual CPU
platform, so a real ``('workers',)`` mesh exists on one machine, exactly
like the driver's multi-chip dry-run. Pinned here, before any test
import can create a backend.
"""

import os

os.environ.setdefault("KERAS_BACKEND", "jax")

from elephas_tpu.utils.backend_guard import force_cpu_devices

force_cpu_devices(8)

import signal
import threading

import numpy as np
import pytest

# modules under this per-test deadline: everything that opens parameter-
# server sockets (a hung read must FAIL the test, not hang tier-1; the
# image has no pytest-timeout, so SIGALRM does the job)
_PS_DEADLINE_MODULES = (
    "test_parameter_server",
    "test_native_ps",
    "test_ps_codec",
    "test_ps_overlap",
    "test_fault_tolerance",
    "test_ps_sharding",
    "test_telemetry",
    "test_telemetry_fleet",
    "test_fleet",
    "test_deploy",
)
PS_TEST_DEADLINE_S = 120


@pytest.fixture(autouse=True)
def _ps_socket_deadline(request):
    mod = getattr(request.module, "__name__", "")
    applies = any(mod.endswith(m) for m in _PS_DEADLINE_MODULES)
    if (
        not applies
        or not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return

    def _expired(signum, frame):
        raise TimeoutError(
            f"PS socket test exceeded the {PS_TEST_DEADLINE_S}s deadline"
        )

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(PS_TEST_DEADLINE_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def spark_context():
    from elephas_tpu.data import SparkContext

    return SparkContext("local[8]")


@pytest.fixture(scope="session")
def serving_lm():
    """A small trained LM (periodic sequences, as in test_mesh_generate)
    shared by the serving suites — training sharpens the logits so
    greedy parity across shardings is not a coin flip, and training it
    ONCE keeps tier-1 inside its wall-clock budget (test_serving and
    test_serving_prefix used to each pay the ~30s fit)."""
    from elephas_tpu import SparkModel
    from elephas_tpu.models import transformer_lm

    maxlen, vocab, n = 32, 8, 256
    rng = np.random.default_rng(0)
    starts = rng.integers(2, 6, size=n)
    seq = (starts[:, None] + np.arange(maxlen + 1)) % 4 + 2
    x, y = seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)
    m = transformer_lm(
        vocab_size=vocab, maxlen=maxlen, d_model=32, num_heads=2,
        num_layers=2, dropout=0.0, lr=1e-2, seed=0,
    )
    SparkModel(m, num_workers=4).fit((x, y), epochs=4, batch_size=32)
    return m


@pytest.fixture(scope="session")
def blobs():
    """Separable 3-class gaussian blobs — the MNIST stand-in (no network
    access for real dataset downloads; end-task-quality assertions follow
    the reference's loose-threshold style)."""
    rng = np.random.default_rng(42)
    n, d, k = 1600, 10, 3
    centers = rng.normal(size=(k, d)) * 2.0
    y = rng.integers(0, k, size=n)
    x = (centers[y] + rng.normal(size=(n, d)) * 0.6).astype(np.float32)
    return x, y.astype(np.int32), d, k


def make_mlp(input_dim: int, num_classes: int, lr: float = 1e-2, seed: int = 7):
    import keras

    keras.utils.set_random_seed(seed)
    model = keras.Sequential(
        [
            keras.layers.Input((input_dim,)),
            keras.layers.Dense(32, activation="relu"),
            keras.layers.Dense(num_classes, activation="softmax"),
        ]
    )
    model.compile(
        optimizer=keras.optimizers.Adam(lr),
        loss="sparse_categorical_crossentropy",
        metrics=["accuracy"],
    )
    return model


def peak_admitted(scheduler, workload) -> int:
    """Most requests in flight at once when ``workload`` (``(prompt,
    max_new_tokens)`` pairs, all submitted up front) runs through
    ``scheduler``'s own admission, each active request emitting one
    token a step as in the engine's loop. Host bookkeeping only: what
    the pool admits is a count, whatever a device would take per step."""
    for prompt, max_new in workload:
        scheduler.submit(scheduler.make_request(prompt, max_new))
    peak = 0
    while scheduler.has_work:
        if scheduler.allocator is None:
            admitted = scheduler.admit()
        else:
            admitted, _ = scheduler.admit_paged()
        assert admitted or scheduler.active, "queue head can never fit"
        peak = max(peak, len(scheduler.active))
        for slot in sorted(scheduler.active):
            if scheduler.on_token(slot, 0):
                scheduler.reclaim(slot)
    return peak
