"""JAX's persistent compile cache, at a fixed path that can be placed from
outside.

A process on the chip starts with no compiled programs; the cache lets the
processes of one run, and later runs that find the same directory, reuse
them. The directory is part of what makes an entry hit, so it never moves:
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself and nothing here overrides it), otherwise ``<checkout>/.jax_cache``
(listed in ``.gitignore``). Entry points (``chip_smoke.py``,
``benchmarks/run.py``) call ``enable_compile_cache`` once at start-up;
importing the library never touches the cache, and neither do the tests.
"""
from __future__ import annotations

import contextlib
import os
import pathlib

CACHE_DIRNAME = ".jax_cache"

# jax.monitoring durations of loading one program: its lowering to MLIR,
# then either the backend compile or the read from the persistent cache
LOAD_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")


def enable_compile_cache(checkout: str | os.PathLike) -> pathlib.Path:
    """Turn the persistent compile cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``checkout/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return pathlib.Path(env)
    import jax
    path = pathlib.Path(checkout).resolve() / CACHE_DIRNAME
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path


@contextlib.contextmanager
def load_seconds(into: dict, key: str):
    """Add to ``into[key]`` the seconds that ``jax.monitoring`` reports for
    loading programs (``LOAD_EVENTS``) while the body runs. Tracing to a
    jaxpr is left out: jax times nested jits inside their parent, so its
    durations overlap. The listener is process-global: a compile on
    another thread meanwhile counts too."""
    import jax

    def on(event: str, duration: float, **kw) -> None:
        if event in LOAD_EVENTS:
            into[key] = into.get(key, 0.0) + duration

    into.setdefault(key, 0.0)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
