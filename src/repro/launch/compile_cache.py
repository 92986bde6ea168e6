"""Where jax keeps compiled programs between runs.

Every entry point (``chip_smoke.py``, ``scripts/sweep.py``,
``benchmarks/run.py``, ``python -m repro.launch.serve_codesign``) calls
``enable_compile_cache()`` before its first compile.  The directory is part
of a cache entry's key, so it must not move between runs:

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set -- jax reads the variable
  itself, and no path is set in code;
* otherwise ``.jax_cache/`` at the root of the checkout (gitignored).
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The fallback directory, fixed relative to the checkout.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache; returns its directory.

    Every program is cached: the scoring kernels compile in well under the
    one second jax waits for by default before it writes an entry.
    """
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
