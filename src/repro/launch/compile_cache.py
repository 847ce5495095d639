"""Where JAX keeps compiled programs between runs.

With a persistent compilation cache, a second run of the same program
reads its executables back instead of compiling them again — on a chip,
that is most of a cold start.  ``JAX_COMPILATION_CACHE_DIR``, where it is
set, names the directory; JAX reads it at import and nothing here
overrides it.  Otherwise the cache lives at a fixed ``.jax_cache`` in the
checkout: a fixed path, so the next run of this checkout finds the
entries.  JAX's other cache settings keep their defaults: programs that
compiled in under a second are not written (a cache capped in size would
otherwise churn through hundreds of them per run).  Call
:func:`use_compile_cache` before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
