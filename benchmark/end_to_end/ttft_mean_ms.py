"""Mean time from submit to first token, over every request whose first
token fell in the window. A mean, because a time to first token is a whole
number of ticks and an order statistic of some tens of them sits on a
step."""
from benchmark.lib.stats import mean


def read(record):
    return mean(record.samples["ttft_ms"])
