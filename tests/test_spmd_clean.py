"""TP×SP compiles without SPMD rematerialization cliffs.

A multi-device dry run of 2026-07 logged ``spmd_partitioner.cc:652``
"Involuntary full rematerialization … SPMD will replicate the tensor" on
the TP×SP route: :func:`ring_mha` merged a data-sharded batch dim with a
model-sharded head dim in ONE global reshape before the shard_map, and
the backward cotangent's merged sharding had no efficient path back to
the (batch-over-data, features-over-model) layout the qkv projection
backward needs — XLA's last resort is a full replicate, a silent
memory+bandwidth multiplier on real hardware.  The fix keeps q/k/v 4-D
``[B, H, S, D]`` across the boundary (``P(data, model, seq, None)``)
and merges locally inside the shard_map.

The warning only fires in a specific compile sequence (an SP-only fit
FIRST, then the TP×SP fit — exactly the dryrun's order), so this test
replays that sequence in a subprocess and asserts the captured XLA
stderr carries ZERO replication warnings.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent(
    """
    from elephas_tpu.utils.backend_guard import force_cpu_devices
    force_cpu_devices(8)
    import jax
    import numpy as np
    from elephas_tpu import SparkModel
    from elephas_tpu.models import transformer_classifier

    rng = np.random.default_rng(0)
    xs = rng.integers(0, 32, size=(32, 16)).astype(np.int32)
    ys = rng.integers(0, 2, size=32).astype(np.int32)

    # the dryrun's warning-triggering order: SP-only fit, then TP x SP
    sp_model = transformer_classifier(
        vocab_size=32, maxlen=16, num_classes=2, d_model=8, num_heads=2,
        num_layers=1, dropout=0.0, seed=5,
    )
    h1 = SparkModel(sp_model, sequence_parallel=2).fit(
        (xs, ys), epochs=1, batch_size=16
    )
    tpsp_model = transformer_classifier(
        vocab_size=32, maxlen=16, num_classes=2, d_model=8, num_heads=2,
        num_layers=1, dropout=0.0, seed=7,
    )
    h2 = SparkModel(tpsp_model, sequence_parallel=2, model_parallel=2).fit(
        (xs, ys), epochs=1, batch_size=16
    )
    assert np.isfinite(h1["loss"][0]) and np.isfinite(h2["loss"][0])
    print("SPMD_CLEAN_OK")
    """
)


def test_tpsp_compile_has_no_involuntary_rematerialization(tmp_path):
    script = os.path.join(str(tmp_path), "spmd_script.py")
    with open(script, "w") as f:
        f.write(SCRIPT)
    env = dict(os.environ)
    env.update(
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        KERAS_BACKEND="jax",
        TF_CPP_MIN_LOG_LEVEL="0",  # the warning must be visible to fail
    )
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=540, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SPMD_CLEAN_OK" in proc.stdout, proc.stdout[-2000:]
    bad = [
        line
        for line in proc.stderr.splitlines()
        if "Involuntary full rematerialization" in line
        or "SPMD will replicate the tensor" in line
    ]
    assert not bad, bad
