"""Where JAX's persistent compilation cache lives.

The entry points (``repro.launch.serve``, ``repro.launch.train``,
``benchmarks/run.py`` and ``chip_smoke.py``) call :func:`use_compile_cache`
before they compile anything, so a second process finds the programs the
first one compiled.  Library code and the tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the checkout holding ``src/repro/launch/compile_cache.py``
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> Path:
    """Turn on the compilation cache and return its directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable
    itself and nothing here changes.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``, a fixed path: one made from a temp name,
    a pid or the time would never be found again."""

    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path


__all__ = ["use_compile_cache"]
