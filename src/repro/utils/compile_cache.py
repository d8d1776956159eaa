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

import os
import pathlib

CACHE_DIRNAME = ".jax_cache"


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
