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

prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()
# For THIS process the config.update below is what counts; the env
# assignment is for SPAWNED SUBPROCESSES (multi-process store/collective/
# launch tests), which must never reach for a TPU.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests driven by the chaos harness "
        "(FLAGS_chaos_spec)")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
