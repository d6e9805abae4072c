"""JAX's persistent compilation cache, placed from outside or in the checkout.

Every process that compiles a step (``repro.launch.train``,
``repro.launch.serve``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
from its entry point — never at import time.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set; otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, gitignored), so that a later run from the
same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the in-checkout default: <repo>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
