"""Share of the traced window in which device 0 ran nothing while the host was
in `ptpu.serve.wait`: `np.asarray(nxt)`, the step's one sync: a launch that
has not started yet, or a result on its way back. An idle interval is split
over the phases it runs through (benchmark/lib/program_trace.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.serve_idle_share(record, "wait")
