"""Test config: force XLA-CPU with 8 virtual devices.

This mirrors the reference's fake-backend strategy (SURVEY.md §4: the
`custom_cpu` plugin lets the whole stack run without the accelerator): all
tests run against XLA-CPU, with 8 virtual devices so multi-chip sharding
paths are exercised on one host. On the chip the program runs through
`chip_smoke.py`, never through this suite.

A pytest plugin may import jax before this file runs, after which the
JAX_PLATFORMS env var is no longer read: jax.config.update is what holds
(XLA_FLAGS is read lazily at CPU-client creation, so the env assignment
works for the device count).
"""
import os
import shutil
import sys
import tempfile

prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()
# For THIS process the config.update below is what counts; the env
# assignment is for SPAWNED SUBPROCESSES (multi-process store/collective/
# launch tests), which must never reach for a TPU.
os.environ["JAX_PLATFORMS"] = "cpu"

# The suite's executables are kept in JAX's persistent cache, so that a
# program is compiled once and not once a test and a worker: the suite
# builds the same tiny engines and the same eager operations in test after
# test, some 25,000 programs of ~0.1 s each, and most of its time on the
# CPU was XLA compiling them again (PR 45: 1,437 s -> 1,225 s of the run's
# 1,470 s limit from an empty directory, 686 s from a full one). An entry's
# key is the program's own (its HLO, XLA's flags, jaxlib's version), so a
# hit runs what a compile would have made, whatever tree wrote it, and
# `benchmark/lib/compile_log.py` counts a hit as an executable made, so
# "no compile in the window" means what it meant. The directory is
# `JAX_COMPILATION_CACHE_DIR` where set, else one fixed name under the
# temporary directory (never inside the checkout, which is copied about):
# a second run of the suite on one machine finds the first run's
# executables. TO TIME THE SUITE AS A FRESH MACHINE RUNS IT, name empty
# directories: `JAX_COMPILATION_CACHE_DIR=$(mktemp -d) PYTHONPYCACHEPREFIX=
# $(mktemp -d) python -m pytest ...`. Workers and the subprocesses tests
# spawn inherit the names.
_CACHE_LIMIT = 1 << 30      # bytes; past it a run starts from nothing
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _dir = os.path.join(tempfile.gettempdir(),
                        f"paddle_tpu_tests_xla_{os.getuid()}")
    try:                        # the starting process: workers inherit
        with os.scandir(_dir) as it:
            if sum(e.stat().st_size for e in it) > _CACHE_LIMIT:
                shutil.rmtree(_dir, ignore_errors=True)
    except OSError:             # no such directory yet, or another run's
        pass                    # clearing met this one's listing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _dir
# Python's own compiled modules likewise: where the environment forbids
# `__pycache__` (PYTHONDONTWRITEBYTECODE, as the builders' sandbox sets),
# every process compiles jax's and this package's sources again, 6.2 s for
# `import paddle_tpu` against 1.6 s, and the suite starts some hundreds of
# processes (workers, ranks, data-loader workers, launchers). They go
# under the temporary directory too (`PYTHONPYCACHEPREFIX` where set), so
# no checkout and no installation gains a file; a compiled module is
# checked against its source's time and size at every import.
if not os.environ.get("PYTHONPYCACHEPREFIX"):
    os.environ["PYTHONPYCACHEPREFIX"] = os.path.join(
        tempfile.gettempdir(), f"paddle_tpu_tests_pyc_{os.getuid()}")
os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
sys.pycache_prefix = os.environ["PYTHONPYCACHEPREFIX"]
sys.dont_write_bytecode = False
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
# XLA:CPU logs an ERROR of 3 KB at every executable it loads ("target
# machine feature +prefer-no-scatter is not supported on the host": its own
# tuning hints, compared with the host's instruction sets; the machine that
# compiled is the one that loads). Unsilenced it is most of what a failing
# test captures.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", float(
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
jax.config.update("jax_persistent_cache_min_entry_size_bytes", int(
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"]))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests driven by the chaos harness "
        "(FLAGS_chaos_spec)")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")

