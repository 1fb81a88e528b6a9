"""Median host-clock time of `engine.step()`, which returns after the
step's device sync."""
from benchmark.lib.stats import percentile


def read(record):
    return percentile(record.samples["tick_ms"], 50)
