"""Which device this process runs on — asked directly, answered plainly.

JAX itself picks the CPU without a word when no accelerator answers at
start-up, so a run meant for the chip can finish on the host and look
like a pass. Nothing in this codebase adds a second fallback on top of
that one; the entry points that must not run on the host ask here.

- :func:`force_cpu_devices` — give this process ``n`` virtual CPU
  devices. For what is a CPU run by definition: the tests, the
  multi-device dry run, the examples' ``--cpu-sim``.
- :func:`require_accelerator` — the opposite: the record of the
  attached accelerator, or an error naming what was found instead.
- :func:`pallas_interpret` — the one rule every Pallas kernel shares:
  interpret on the ``cpu`` backend, compile everywhere else.
- :func:`use_compile_cache` — where an entry point keeps JAX's
  persistent compilation cache.
"""

from __future__ import annotations

import os

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_CACHE_DIRNAME = ".jax_cache"


def force_cpu_devices(n: int) -> None:
    """Switch THIS process to an ``n``-device virtual CPU platform.
    Backends are created lazily, so this works any time before (and,
    by clearing them, after) the first device query."""
    import jax
    from jax.extend.backend import clear_backends

    jax.config.update("jax_num_cpu_devices", n)
    jax.config.update("jax_platforms", "cpu")
    clear_backends()


def device_record() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX reports.
    A backend that fails to initialise raises here, from JAX."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_accelerator(platform: str | None = None) -> dict:
    """The :func:`device_record` of the attached accelerator.

    Raises ``RuntimeError`` naming the platform found when that is the
    CPU (or, with ``platform`` given, anything but ``platform``)."""
    found = device_record()
    wrong = (
        found["platform"] != platform if platform is not None
        else found["platform"] == "cpu"
    )
    if wrong:
        raise RuntimeError(
            f"this run needs {platform or 'an accelerator'}, but "
            f"jax.devices() found platform {found['platform']!r} "
            f"({found['count']} x {found['kind']}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})"
        )
    return found


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpreter mode: only on the
    ``cpu`` backend (the tests). On any other backend the kernel is
    compiled, and a compiler refusal is an error."""
    import jax

    return jax.default_backend() == "cpu"


def use_compile_cache(checkout: str) -> str:
    """Place JAX's persistent compilation cache; call before the first
    compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
    reads it and nothing is set here; otherwise the cache goes to one
    fixed directory inside ``checkout`` (the path is part of the cache
    key, so it must not move between runs). Returns the directory."""
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    import jax

    path = os.path.join(os.path.abspath(checkout), COMPILE_CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_cache_entries(path: str) -> int:
    """Number of compiled programs in the cache directory."""
    if not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
