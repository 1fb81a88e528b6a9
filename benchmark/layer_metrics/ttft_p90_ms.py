"""90th percentile of the time to first token: recorded, not judged (some
tens of samples, each a whole number of ticks)."""
from benchmark.lib.stats import percentile


def read(record):
    return percentile(record.samples["ttft_ms"], 90)
