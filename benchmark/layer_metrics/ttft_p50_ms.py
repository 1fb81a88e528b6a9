"""Median time to first token: recorded, not judged (it is a whole number
of ticks)."""
from benchmark.lib.stats import percentile


def read(record):
    return percentile(record.samples["ttft_ms"], 50)
