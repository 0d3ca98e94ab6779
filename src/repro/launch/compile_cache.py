"""Where JAX keeps its persistent compilation cache for this repo's CLIs.

Called once at start-up by the command-line entry points (``chip_smoke.py``,
the ``repro.launch`` CLIs, ``benchmarks/run.py``) and never by the tests.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is changed; otherwise the cache goes to ``<repo>/.jax_cache`` — a fixed
path, since the path is part of the cache key and a moving directory never
hits.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
