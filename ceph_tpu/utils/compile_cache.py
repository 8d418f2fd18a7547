"""Persistent JAX compile cache placement for the entry points.

`chip_smoke.py`, `bench.py` and the `tools/` mains call
`enable_compile_cache()` before their first compile; importing
`ceph_tpu` never touches the cache.  Where `JAX_COMPILATION_CACHE_DIR`
is set, JAX reads it itself and nothing here sets a directory.  Where
it is not, the cache lives at a fixed path inside the checkout (the
directory is part of the cache key's lookup, so it must not move between
runs: no temp name, pid or time).  The minimum compile time to cache is
lowered to zero, because the coding kernels compile in about a second
and would otherwise never be cached.

`CompileClock` sums the backend compile seconds JAX reports, so an entry
point can print what a run spent compiling (a warm cache shows up as
less of it).
"""

from __future__ import annotations

import os
import pathlib

from ceph_tpu.common.lockdep import make_lock

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    cache = os.environ.get(ENV_VAR)
    if not cache:
        cache = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


class CompileClock:
    """Running total of backend compile seconds in this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self._lock = make_lock("compile_clock")
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.seconds += duration

    def read(self) -> float:
        with self._lock:
            return self.seconds
