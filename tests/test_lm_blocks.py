"""The five sparse LMs as programs: what ``qwen3_next_lm``,
``deepseek_v3_lm``, ``smallthinker_lm``, ``nemotron_h_lm`` and
``laguna_lm`` trace to, which variables they hold and which named scopes
their programs carry, held to what they were at ``f37516c`` (PR 46), the
last tree whose layer classes lived inside the model files' functions;
and ``granite_hybrid_lm``, held to what it was in the tree that added it
(``tests/lm_blocks_at_pr48.json``, PR 48).

Each model is built as its benchmark builder builds it, at its own test
file's small configuration with ``remat`` on and ``mixed_bfloat16``.
``tests/lm_blocks_at_f37516c.json`` is the record (``python
tests/test_lm_blocks.py <file> [model ...]`` writes one from the tree it
runs in, of the models named or of all: made once, at that commit, under
jax 0.9.0; a later model's entry lies in a file of its own beside it, so
that the first stays byte for byte): a change that moves a
model's layers about, or the code they share, leaves every entry as it
is; one that means to change a program says which entry moved and why.
The benchmark reads its per-layer times by the scopes' names and its
counters by ``COUNTER_NAMES``: a scope that is renamed or dropped turns a
ledger column to null and fails here first."""

import functools
import hashlib
import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDS = tuple(os.path.join(ROOT, "tests", name) for name in (
    "lm_blocks_at_f37516c.json", "lm_blocks_at_pr48.json"))
# model -> (its test file, whose CFG is the small configuration, its
# benchmark builder, and the entries of the configuration that the
# builder's ``_build`` takes after the configuration and its optimizer)
MODELS = {
    "qwen3_next": ("test_qwen3_next", "keras_qwen3_next",
                   ("dtype", "experts_held_first")),
    "deepseek_v3": ("test_deepseek_v3", "keras_deepseek_v3", ()),
    "smallthinker": ("test_smallthinker", "keras_smallthinker", ()),
    "nemotron_h": ("test_nemotron_h", "keras_nemotron_h", ()),
    "laguna": ("test_laguna", "keras_laguna", ()),
    "granite_hybrid": ("test_granite_hybrid", "keras_granite_hybrid", ()),
}
# a name the program gave with ``jax.named_scope``, as the benchmark's
# trace reader takes them (``benchmarks/harness/xplane_ops.py``)
NAMED_SCOPE = re.compile(r"[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+")
BATCH = 2


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _model(name):
    test_file, builder_file, more = MODELS[name]
    cfg = dict(
        _load(os.path.join(ROOT, "tests", test_file + ".py"),
              "lm_blocks_cfg_" + name).CFG,
        remat=True, dtype="mixed_bfloat16")
    builder = _load(
        os.path.join(ROOT, "benchmarks", "builders", builder_file + ".py"),
        "lm_blocks_builder_" + name)
    return builder._build(
        cfg, cfg["optimizer"], *(cfg[key] for key in more)), cfg


def _programs(name):
    """The model's stateless forward, the sum of its compiled loss, and
    the arguments both take."""
    model, cfg = _model(name)
    tv = [v.value for v in model.trainable_variables]
    ntv = [v.value for v in model.non_trainable_variables]
    tokens = jnp.zeros((BATCH, cfg["sequence_length"]), jnp.int32)

    def forward(tv, ntv, tokens):
        return model.stateless_call(tv, ntv, tokens)[0]

    def loss(tv, ntv, tokens):
        return jnp.sum(model.loss(tokens, forward(tv, ntv, tokens)))

    return forward, loss, (tv, ntv, tokens)


def _digest(text):
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


def jaxpr_text(name):
    forward, loss, args = _programs(name)
    return str(jax.make_jaxpr(forward)(*args)) + str(
        jax.make_jaxpr(jax.grad(loss))(*args))


def variables(name):
    model, _ = _model(name)
    return [[v.path, list(v.shape), str(v.dtype), bool(v.trainable)]
            for v in model.variables]


def initial_values_digest(name):
    model, _ = _model(name)
    digest = hashlib.sha256()
    for v in model.variables:
        digest.update(np.ascontiguousarray(np.asarray(v.value)).tobytes())
    return digest.hexdigest()[:16]


def scopes(name):
    """The named scopes of forward-and-gradient's lowered program, from
    the operations' locations."""
    _, loss, args = _programs(name)
    text = jax.jit(jax.value_and_grad(loss)).lower(*args).as_text(
        debug_info=True)
    found = set()
    for path in re.findall(r'loc\("([^"]*)"', text):
        if path.startswith("jit("):
            found.update(NAMED_SCOPE.findall(path))
    return sorted(found)


def observe(name):
    return {"jaxpr": _digest(jaxpr_text(name)), "variables": variables(name),
            "initial_values": initial_values_digest(name),
            "scopes": scopes(name)}


@functools.lru_cache(maxsize=None)
def _recorded():
    """The records as one: every file's models under the first's jax."""
    merged = None
    for path in RECORDS:
        with open(path) as f:
            record = json.load(f)
        if merged is None:
            merged = record
        else:
            assert record["jax"] == merged["jax"], path
            merged["models"].update(record["models"])
    return merged


@pytest.mark.parametrize("model", sorted(MODELS))
def test_model_traces_as_before(model):
    """The jaxpr of the model's forward pass and of its loss's gradient
    with respect to the trainable variables is, character for character
    (addresses blanked), the recorded one: no equation was added, lost
    or moved."""
    got = _digest(jaxpr_text(model))
    assert len(got) == 16
    if jax.__version__ == _recorded()["jax"]:
        assert got == _recorded()["models"][model]["jaxpr"]


@pytest.mark.parametrize("model", sorted(MODELS))
def test_model_variables_as_before(model):
    """``model.variables`` lists the recorded paths, shapes, dtypes and
    trainability in the recorded order (a saved model loads, the
    reference's weights find their variables), and the seeded initial
    values are the recorded ones: layers are built in the same order."""
    want = _recorded()["models"][model]
    got = variables(model)
    assert [v[0] for v in got] == [v[0] for v in want["variables"]]
    assert got == want["variables"]
    if jax.__version__ == _recorded()["jax"]:
        assert initial_values_digest(model) == want["initial_values"]


@pytest.mark.parametrize("model", sorted(MODELS))
def test_model_scopes_as_before(model):
    """The program of forward and gradient carries the recorded named
    scopes, no more and no fewer: the benchmark's per-layer metrics read
    the trace by these names."""
    got, want = scopes(model), _recorded()["models"][model]["scopes"]
    assert "lm.head_loss" in got
    assert got == want, (sorted(set(want) - set(got)),
                         sorted(set(got) - set(want)))


def test_the_recorded_scopes_are_the_ones_the_benchmark_reads():
    """Every scope a metric file of the benchmark names is in some
    model's record, and the sparse block's counters have the names the
    counter metrics read."""
    from elephas_tpu import models

    held = set()
    for entry in _recorded()["models"].values():
        held.update(entry["scopes"])
    metrics = os.path.join(ROOT, "benchmarks", "metrics")
    read = set()
    for file in sorted(os.listdir(metrics)):
        with open(os.path.join(metrics, file)) as f:
            read.update(re.findall(
                r'(?:scope_ms_per_step|roofline_share)\(\s*run,\s*"([^"]+)"',
                f.read()))
    assert len(read) >= 10, read
    assert read <= held, read - held
    assert {"ssm.conv", "ssm.norm", "mlp.dense"} <= read
    assert models.SparseMoeBlock.epoch_counters == {
        "route_counts": ("held_slots", "slots", "max_expert_tokens", "calls",
                         "blocked_calls")}


def test_attention_classes_share_one_path_to_the_kernel(monkeypatch):
    """``BandedAttention``, ``GatedAttention`` and ``LatentAttention``
    each reach ``flash_attention`` once a call, and through
    ``lm_mixers.causal_flash_attention``: a layer that calls the kernels
    by a path of its own is counted at the one and not at the other."""
    import importlib

    from elephas_tpu.models import lm_mixers

    # the module, not the function ``elephas_tpu.ops`` exports by its name
    fa = importlib.import_module("elephas_tpu.ops.flash_attention")
    kernel, shared = fa.flash_attention, lm_mixers.causal_flash_attention
    seen = {"kernel": [], "shared": []}

    def watched_kernel(q, k, v, **kwargs):
        seen["kernel"].append((q.shape, kwargs["causal"], kwargs["window"]))
        return kernel(q, k, v, **kwargs)

    def watched_shared(layer, *args, **kwargs):
        seen["shared"].append(layer.name)
        return shared(layer, *args, **kwargs)

    monkeypatch.setattr(fa, "flash_attention", watched_kernel)
    monkeypatch.setattr(lm_mixers, "causal_flash_attention", watched_shared)
    x = jnp.zeros((2, 24, 32))
    layers = [
        lm_mixers.BandedAttention(4, 2, 16, 6, name="banded"),
        lm_mixers.GatedAttention(4, 2, 16, 4, name="gated"),
        lm_mixers.LatentAttention(4, 16, 8, 16, 16, name="latent"),
    ]
    for layer in layers:
        assert layer(x).shape == x.shape
    assert seen["shared"] == ["banded", "gated", "latent"]
    assert seen["kernel"] == [
        ((2, 4, 24, 16), True, 6), ((2, 4, 24, 16), True, None),
        ((2, 4, 24, 24), True, None)]


def test_importing_the_zoo_imports_no_keras():
    """``import elephas_tpu.models`` and each of the six model modules
    in a fresh interpreter import neither keras nor jax: the benchmark's
    builders and metric readers import them as they are loaded."""
    code = (
        "import sys\n"
        "import elephas_tpu.models\n"
        "from elephas_tpu.models import (\n"
        "    deepseek_v3, granite_hybrid, laguna, nemotron_h, qwen3_next,\n"
        "    smallthinker)\n"
        "print('keras' not in sys.modules and 'jax' not in sys.modules)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "True"


if __name__ == "__main__":
    os.environ.setdefault("KERAS_BACKEND", "jax")
    sys.path.insert(0, ROOT)
    from elephas_tpu.utils.backend_guard import force_cpu_devices

    force_cpu_devices(8)  # as tests/conftest.py
    record = json.dumps({
        "commit": subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=ROOT,
            capture_output=True, text=True).stdout.strip(),
        "jax": jax.__version__,
        "models": {name: observe(name)
                   for name in sorted(sys.argv[2:] or MODELS)}}, indent=1)
    with open(sys.argv[1], "w") as f:  # a list a line
        f.write(re.sub(r"\n {4,}|\n {3}(?=\])", " ", record) + "\n")
