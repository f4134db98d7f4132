"""chip_smoke.py off the chip: its phase functions at toy sizes on the
virtual CPU mesh (the first two rehearsals of a chip run), its refusal
to pass without a TPU, the backend guard it asks for the chip through,
and the compile-cache helper.

Named to sort late: the tier-1 command's time limit cuts the suite
under halfway through, and these two minutes of toy training would
push faster tests out of what it counts."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke

TOY_LM = dict(vocab_size=16, maxlen=32, d_model=32, num_heads=2,
              num_layers=2)


def toy_lm(dtype_policy=None, lr=1e-2):
    from elephas_tpu.models import transformer_lm

    return transformer_lm(
        **TOY_LM, dropout=0.0, lr=lr, seed=0, dtype_policy=dtype_policy
    )


def test_fit_convnet_phase_at_toy_size(capsys):
    from elephas_tpu.models import resnet

    out = chip_smoke.phase_fit_convnet(
        lambda: resnet(input_shape=(16, 16, 3), num_classes=4,
                       depths=(1, 1), width=8),
        image=16, classes=4, batch=2, steps=2, epochs=2, seed=0,
        platform="cpu",
    )
    assert len(out["losses"]) == 2
    line = capsys.readouterr().out
    # all 8 virtual devices: fit() takes every device unless told
    assert line.startswith("[fit_convnet] workers=8 ")
    assert '"TFRT_CPU_7"' in line


def test_fit_lm_then_serve_phases_at_toy_size(capsys):
    """fit_lm hands its trained model to serve, as main() does; on the
    CPU the kernels interpret, so no kernel marker is asked for."""
    trained = chip_smoke.phase_fit_lm(
        toy_lm, maxlen=TOY_LM["maxlen"], batch=4, steps=4, epochs=2,
        max_epochs=20, sharp_loss=0.2, seed=0, platform="cpu",
        kernel_marker=None,
    )
    assert 2 < len(trained["losses"]) <= 20  # went on until sharp
    assert trained["losses"][-1] < 0.2
    import jax

    out = chip_smoke.phase_serve(
        trained["model"], prompt_lens=(3, 6, 9, 14), new_tokens=5,
        num_slots=4, block_size=4, num_blocks=32, prefill_chunk=4,
        seed=1, devices=jax.devices(),
    )
    assert sum(out["reused_warm"]) > 0
    assert out["compile_stats"]["decode_compiles"] >= 1
    lines = capsys.readouterr().out.splitlines()
    assert all(l.startswith(("[fit_lm] ", "[serve] ")) for l in lines)
    assert any("second_pass_compiled_nothing=true" in l for l in lines)
    assert any("port_released=" in l for l in lines)


def test_serve_phase_fails_on_a_wrong_token(monkeypatch):
    """Made to disagree with its reference, the phase raises: a check
    that cannot fail proves nothing."""
    import jax

    from elephas_tpu import models

    real = models.generate

    def off_by_one(model, prompt, steps, **kw):
        out = real(model, prompt, steps, **kw).copy()
        out[0, -1] += 1
        return out

    monkeypatch.setattr(models, "generate", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match="diverges from"):
        chip_smoke.phase_serve(
            toy_lm(), prompt_lens=(3, 6), new_tokens=3, num_slots=2,
            block_size=4, num_blocks=16, prefill_chunk=4, seed=1,
            devices=jax.devices(),
        )
    # and an untrained model is refused as too flat to compare tokens on
    monkeypatch.undo()
    with pytest.raises(chip_smoke.SmokeFailure, match="too flat"):
        chip_smoke.phase_serve(
            toy_lm(), prompt_lens=(3, 6), new_tokens=3, num_slots=2,
            block_size=4, num_blocks=16, prefill_chunk=4, seed=1,
            devices=jax.devices(),
        )


def test_fit_dp_phase_on_four_virtual_devices(capsys):
    """Rehearsal 2: the --chips 4 path on four of the virtual devices,
    in the bf16 policy main() uses."""
    out = chip_smoke.phase_fit_dp(
        lambda: toy_lm("mixed_bfloat16"), maxlen=TOY_LM["maxlen"],
        workers=4, batch=2, steps=4, epochs=3, seed=0, platform="cpu",
        rtol=chip_smoke.DP_LOSS_RTOL, kernel_marker=None,
    )
    assert max(out["gaps"]) <= chip_smoke.DP_LOSS_RTOL
    text = capsys.readouterr().out
    assert "[fit_dp] workers=4 " in text
    assert "[fit_dp_one_worker] workers=1 " in text
    assert '"all-reduce"' in text
    with pytest.raises(chip_smoke.SmokeFailure, match="differ by"):
        chip_smoke.phase_fit_dp(
            lambda: toy_lm("mixed_bfloat16"), maxlen=TOY_LM["maxlen"],
            workers=4, batch=2, steps=4, epochs=3, seed=0,
            platform="cpu", rtol=0.0, kernel_marker=None,
        )


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_script_on_the_cpu_exits_nonzero_without_ok(argv, tmp_path):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "found platform 'cpu'" in proc.stderr


def test_script_alone_in_a_directory_fails(tmp_path):
    """Copied out of the repo, without the program, it cannot pass."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(tmp_path),
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


_WHERE = (
    "import sys, jax;"
    "from elephas_tpu.utils.backend_guard import use_compile_cache;"
    "print(use_compile_cache(sys.argv[1]));"
    "print(jax.config.jax_compilation_cache_dir)"
)


def _where(env_dir, cwd):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _WHERE, REPO],
        capture_output=True, text=True, timeout=120, env=env, cwd=cwd,
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    return proc.stdout.split()


def test_cache_helper_sets_nothing_when_placed_from_outside(tmp_path):
    placed = str(tmp_path / "elsewhere")
    returned, configured = _where(placed, REPO)
    # JAX read the variable itself; the helper only reports it
    assert returned == configured == placed
    assert not os.path.exists(placed)  # nothing made, nothing compiled


def test_cache_helper_gives_one_fixed_path_in_the_checkout(tmp_path):
    fixed = os.path.join(REPO, ".jax_cache")
    assert _where(None, REPO) == [fixed, fixed]
    assert _where(None, str(tmp_path)) == [fixed, fixed]  # another process
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


class TestBackendGuard:
    """No path may let a run without a working chip look like a pass.
    JAX itself picks the CPU silently when no accelerator answers, so
    the guard's job is the opposite of a fallback: ask directly, and
    raise naming what was found."""

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def test_cpu_env_alone_selects_cpu_in_subprocess(self):
        """``JAX_PLATFORMS=cpu`` and nothing else yields the CPU in a
        child — and the child never maps the accelerator's runtime, so
        it neither needs nor disturbs a chip its parent holds (the
        only kind of child a process that has touched JAX may
        start)."""
        env = {
            k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_NUM_CPU_DEVICES")
        }
        env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax")
        proc = subprocess.run(
            [sys.executable, "-c",
             "from elephas_tpu.utils.backend_guard import device_record;"
             "import jax.numpy as jnp; jnp.ones(4).sum().block_until_ready();"
             "print('DEVICE=%r' % device_record());"
             "print('LIBTPU_MAPPED=%s' % "
             "('libtpu' in open('/proc/self/maps').read()))"],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=self.REPO,
        )
        assert proc.returncode == 0, proc.stderr[-1500:]
        assert (
            "DEVICE={'platform': 'cpu', 'kind': 'cpu', 'count': 1}"
            in proc.stdout
        )
        assert "LIBTPU_MAPPED=False" in proc.stdout

    def test_failing_probe_propagates(self, monkeypatch):
        """A backend that dies at initialisation is the caller's error
        to see: no thread, no timeout, no switch to the CPU."""
        import jax

        from elephas_tpu.utils import backend_guard

        def dying():
            raise RuntimeError(
                "Unable to initialize backend 'tpu': "
                "make_c_api_client failed: INTERNAL"
            )

        monkeypatch.setattr(jax, "devices", dying)
        with pytest.raises(RuntimeError, match="make_c_api_client"):
            backend_guard.require_accelerator()
        monkeypatch.undo()
        assert jax.config.jax_platforms == "cpu"  # conftest's, untouched
        assert backend_guard.device_record()["count"] == 8

    def test_asking_for_the_chip_on_cpu_raises_naming_the_platform(self):
        from elephas_tpu.utils import backend_guard

        for want in (None, "tpu"):
            with pytest.raises(RuntimeError) as ei:
                backend_guard.require_accelerator(want)
            assert "found platform 'cpu'" in str(ei.value)
            assert "8 x cpu" in str(ei.value)
        assert backend_guard.require_accelerator("cpu")["platform"] == "cpu"
