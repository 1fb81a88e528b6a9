"""Where JAX's persistent compilation cache lives.

Entry scripts (`chip_smoke.py`, `__graft_entry__.py`) call `configure()`
before their first compile; the package never does at import. The suite
names its own in `tests/conftest.py` (the one `JAX_COMPILATION_CACHE_DIR`
names, or a fixed name under the temporary directory). The directory is
part of every cache key's lookup path, so it is fixed: the one
`JAX_COMPILATION_CACHE_DIR` names, or one inside the checkout — never a
pid or timestamp.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_compile_cache (this file is paddle_tpu/core/…)
_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def configure() -> str:
    """Return the cache directory in use. With JAX_COMPILATION_CACHE_DIR
    set, JAX already reads it and nothing is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_DIR)
    return _CHECKOUT_DIR
