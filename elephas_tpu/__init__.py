"""elephas_tpu — TPU-native distributed deep learning for Keras.

A from-scratch rebuild of the capabilities of the `elephas` reference
(Keras-on-Spark data-parallel training; see SURVEY.md) designed TPU-first
on JAX/XLA:

- Per-worker TensorFlow/CUDA execution becomes a single ``jax.jit``-compiled
  Keras-3 (jax backend) train program per epoch, sharded over a
  ``jax.sharding.Mesh`` worker axis via ``shard_map`` — zero Python in the
  hot loop.
- The reference's pickle-over-HTTP/TCP parameter server
  (``[U] elephas/parameter/``) is replaced in the hot path by XLA
  collectives (``lax.pmean``) over ICI/DCN. Parameter-server classes are
  still provided (``elephas_tpu.parameter``) for API parity and for
  cross-host weight stores over DCN.
- RDD partitions (``[U] elephas/utils/rdd_utils.py``) map onto mesh workers;
  a lightweight ``SparkContext``/``Rdd`` shim supplies the reference's data
  API without a JVM.

Public surface mirrors the reference (``[U] elephas/spark_model.py``,
``ml_model.py``, ``hyperparam.py``): ``SparkModel`` and ``SparkMLlibModel``
here; ``ElephasEstimator``/``ElephasTransformer`` in
``elephas_tpu.ml_model`` and ``HyperParamModel`` in
``elephas_tpu.hyperparam``.
"""

import importlib
import os
import sys

# Keras must run on the jax backend before anything imports keras.
os.environ.setdefault("KERAS_BACKEND", "jax")

# keras locks its backend at import; under any other backend every
# compiled path here would fail later with an opaque tracer error —
# fail loud and early instead. Two ways to get it wrong: keras already
# imported under another backend, or KERAS_BACKEND explicitly exported
# to something else with keras not yet imported.
_backend = (
    sys.modules["keras"].backend.backend()
    if "keras" in sys.modules
    else os.environ["KERAS_BACKEND"]
)
if _backend != "jax":
    raise ImportError(
        f"elephas_tpu requires the Keras jax backend, but the active "
        f"backend is {_backend!r}. Import elephas_tpu before keras and "
        f"leave KERAS_BACKEND unset, or set KERAS_BACKEND=jax."
    )

__version__ = "0.6.0"

# The public names resolve on first use (PEP 562), so that importing a
# light subpackage (``elephas_tpu.telemetry``: a load generator, the
# trace-merge CLI) imports neither JAX nor Keras.
_LAZY = {
    "SparkModel": "elephas_tpu.spark_model",
    "SparkMLlibModel": "elephas_tpu.spark_model",
    "load_spark_model": "elephas_tpu.spark_model",
    "ElephasEstimator": "elephas_tpu.ml_model",
    "ElephasTransformer": "elephas_tpu.ml_model",
    "load_ml_estimator": "elephas_tpu.ml_model",
    "load_ml_transformer": "elephas_tpu.ml_model",
    "HyperParamModel": "elephas_tpu.hyperparam",
    "ShardedTrainer": "elephas_tpu.parallel.tensor",
    "GPipeTrainer": "elephas_tpu.ops.pipeline",
    "SequenceShardedTrainer": "elephas_tpu.parallel.sequence",
}

__all__ = [*_LAZY, "__version__"]


def __getattr__(name):
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(module), name)
    globals()[name] = value
    return value


def __dir__():
    return sorted({*globals(), *__all__})
