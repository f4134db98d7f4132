"""The example scripts run end-to-end (reference keeps runnable examples;
SURVEY.md §2 'Examples'). Fast configs only; heavy ones are covered by
the benchmark's cells / their own CLIs."""

import importlib
import sys
from pathlib import Path

import pytest

EXAMPLES = Path(__file__).resolve().parent.parent / "examples"


def run_example(name: str, argv: list[str]):
    sys.path.insert(0, str(EXAMPLES))
    old_argv = sys.argv
    try:
        module = importlib.import_module(name)
        sys.argv = [name] + argv
        module.main()
    finally:
        sys.argv = old_argv
        sys.path.remove(str(EXAMPLES))


def test_mnist_mlp_spark():
    run_example("mnist_mlp_spark", ["--epochs", "3", "--batch-size", "64"])


def test_ml_pipeline():
    run_example("ml_pipeline", ["--epochs", "4"])


def test_mllib_mlp():
    run_example("mllib_mlp", ["--epochs", "2"])


def test_hyperparam_optimization():
    run_example("hyperparam_optimization", ["--max-evals", "3", "--epochs", "1"])


def test_pipeline_parallel_mlp():
    run_example(
        "pipeline_parallel_mlp",
        ["--epochs", "2", "--stages", "2", "--batch-size", "64"],
    )


def test_resnet_pipeline_parallel():
    run_example(
        "resnet_pipeline_parallel",
        ["--epochs", "2", "--stages", "2", "--batch-size", "32"],
    )


def test_long_context_ring():
    run_example(
        "long_context_ring",
        ["--seq-len", "128", "--steps", "40", "--batch", "32"],
    )


def test_switch_moe_transformer():
    run_example(
        "switch_moe_transformer",
        ["--epochs", "2", "--maxlen", "16", "--vocab", "100",
         "--model-parallel", "2"],
    )


@pytest.mark.slow
def test_imdb_lstm():
    run_example("imdb_lstm", ["--epochs", "1", "--maxlen", "20", "--vocab", "200"])


@pytest.mark.slow
def test_resnet50_tiny():
    run_example("resnet50_imagenet", ["--tiny", "--epochs", "1"])


def test_lm_generate():
    run_example("lm_generate", ["--maxlen", "16", "--epochs", "8",
                                "--steps", "8"])


def test_pp_tp_transformer():
    run_example("pp_tp_transformer", ["--epochs", "6"])
