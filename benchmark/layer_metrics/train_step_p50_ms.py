"""Median host-clock time of one step: batch, step and the
`block_until_ready` on its loss."""
from benchmark.lib.stats import percentile


def read(record):
    return percentile(record.samples["step_ms"], 50)
