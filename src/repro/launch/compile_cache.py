"""Where entry points keep JAX's persistent compilation cache.

Called from each ``main()``, never at import, so the tests compile without
a persistent cache.  A ``JAX_COMPILATION_CACHE_DIR`` set in the environment
wins (JAX reads it itself); otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``, so every run from one checkout finds the
programs an earlier run compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
