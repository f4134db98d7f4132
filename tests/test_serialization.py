"""Model <-> dict round trip (reference: tests/utils/test_serialization.py)."""

import numpy as np

from elephas_tpu.utils.serialization import dict_to_model, model_to_dict
from tests.conftest import make_mlp


def test_model_dict_roundtrip():
    model = make_mlp(6, 3)
    d = model_to_dict(model)
    assert set(d) == {"model", "weights"}
    clone = dict_to_model(d)
    for a, b in zip(model.get_weights(), clone.get_weights()):
        np.testing.assert_array_equal(a, b)
    x = np.random.rand(4, 6).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(model(x)), np.asarray(clone(x)), rtol=1e-5, atol=1e-6
    )


def test_dict_is_plain_picklable():
    import pickle

    d = model_to_dict(make_mlp(4, 2))
    d2 = pickle.loads(pickle.dumps(d))
    clone = dict_to_model(d2)
    assert clone.count_params() > 0


def test_wrong_keras_backend_fails_loud():
    """Importing keras first under a non-jax backend must raise a clear
    ImportError, not a tracer error deep inside fit."""
    import subprocess
    import sys

    code = (
        "import os; os.environ['KERAS_BACKEND']='tensorflow'\n"
        "import keras\n"
        "try:\n"
        "    import elephas_tpu\n"
        "except ImportError as e:\n"
        "    assert 'jax backend' in str(e), e\n"
        "    print('GUARD_OK')\n"
        "else:\n"
        "    raise SystemExit('no ImportError raised')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=240,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert "GUARD_OK" in out.stdout, out.stdout + out.stderr
