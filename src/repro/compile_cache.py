"""JAX's persistent compilation cache, turned on by each entry point
(`chip_smoke.py`, `examples/serve_cluster.py`, `repro.launch.serve`)
before its first compile."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: `<checkout>/.jax_cache`: a fixed path, so that every run of this
#: checkout finds what an earlier run compiled
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Return the cache directory in use.  Where `JAX_COMPILATION_CACHE_DIR`
    is set, JAX read it at import and nothing is set here; otherwise the
    cache goes to `DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
