"""90th percentile of the time between two consecutive tokens of one
request, over every gap that ended in the window."""
from benchmark.lib.stats import percentile


def read(record):
    return percentile(record.samples["gap_ms"], 90)
