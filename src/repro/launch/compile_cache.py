"""JAX's persistent compilation cache, kept at one fixed path.

Entry points (``chip_smoke.py``, ``python -m repro.launch.train``, the
sweep CLI's jax engine) call :func:`enable` before their first compile;
nothing calls it at import.  The cache key includes the directory, so a
path that moved between runs would never hit: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing
else is set, otherwise the cache lives in ``.jax_cache/`` at the root of
the checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The directory the cache uses: the environment variable when set,
    else ``<checkout>/.jax_cache``."""
    env = os.environ if environ is None else environ
    if env.get(ENV_VAR):
        return env[ENV_VAR]
    from repro.calibrate.paths import repo_root
    return str(repo_root() / ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on at :func:`cache_dir`; returns it."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
