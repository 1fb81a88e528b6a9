"""Mean host time from a turn's submit to its first token, over the turns
whose first token fell in the window: what the agent's user feels of a
turn that re-sends a 32k-64k context (the hash of its pages at `submit`, a
tick with its new rows). Read, not judged: 16 clients' turns share ticks.
None where no turn's first token fell in the window."""
from benchmark.lib.stats import mean


def read(record):
    ms = record.samples.get("ttft_ms")
    return mean(ms) if ms else None
