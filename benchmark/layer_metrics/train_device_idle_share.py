"""Share of the traced window in which no operation ran on a device, the
mean over the mesh's devices (on a pipeline it holds the bubble)."""
from benchmark.lib.xplane import idle_share_percent


def read(record):
    return None if record.trace is None else idle_share_percent(record.trace)
