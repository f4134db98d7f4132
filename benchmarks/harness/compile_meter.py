"""What JAX's own monitoring reports about compilation, and what each
compiled program holds in scratch memory."""

from __future__ import annotations


class CompileMeter:
    """Seconds in the backend compiler (cache retrieval included) and
    the number of compile requests, readable at any moment: the runner
    marks the window's edges and reads the difference."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_requests = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> dict:
        return {"seconds": self.seconds, "compiles": self.compiles,
                "cache_requests": self.cache_requests,
                "cache_hits": self.cache_hits}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}


class ProgramSizes:
    """``{program name: temp bytes}`` of every program this process
    compiled or loaded from the cache, read from each executable's own
    ``get_compiled_memory_stats()`` where JAX hands the executable
    over. ``memory_stats()["peak_bytes_in_use"]`` leaves a program's
    temporaries out on this runtime (PERF.md, PR 21), so the peak a
    cell reports is that reading plus the largest of these."""

    def __init__(self):
        from jax._src import compiler

        self.temps: dict[str, int] = {}
        self._compiler = compiler
        self._inner = compiler.compile_or_get_cached
        compiler.compile_or_get_cached = self._wrapped

    def _wrapped(self, backend, computation, *args, **kwargs):
        executable = self._inner(backend, computation, *args, **kwargs)
        try:
            name = str(
                computation.operation.attributes["sym_name"]
            ).strip('"')
            stats = executable.get_compiled_memory_stats()
            self.temps[name] = max(
                self.temps.get(name, 0), int(stats.temp_size_in_bytes)
            )
        except Exception:  # an executable without the statistics
            pass
        return executable

    def largest(self) -> int:
        return max(self.temps.values(), default=0)
