"""Where the executables go: `core/compile_cache.configure()` for the entry
scripts, `tests/conftest.py` for this suite."""
import os

import jax
import jax.numpy as jnp

from benchmark.lib.compile_log import CompileLog
from paddle_tpu.core import compile_cache


def test_the_suite_keeps_every_executable_where_the_environment_says():
    where = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_compilation_cache_dir == where
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    # the entry scripts' rule names the same directory and sets nothing
    assert compile_cache.configure() == where
    assert jax.config.jax_compilation_cache_dir == where


def test_a_program_built_again_is_read_back_and_still_counts_as_made():
    """Two closures, one program: the second is served from the directory
    (by then the first has written it, if no earlier run had), and
    `CompileLog` counts it as an executable made all the same, so a
    measured window's "no compile" cannot be met by a cache."""
    log = CompileLog()
    x = jnp.arange(24.0).reshape(4, 6)

    def build():
        return jax.jit(lambda a: jnp.tanh(a @ a.T).sum(axis=0) * 3.25)

    first = build()(x)
    made, hits = log.made, log.hits
    assert made >= 1
    second = build()(x)
    assert log.hits == hits + 1 and log.made == made + 1
    assert jnp.array_equal(first, second)
