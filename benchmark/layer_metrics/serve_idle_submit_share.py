"""Share of the traced window in which device 0 ran nothing while the host was
in `ptpu.serve.submit`: `engine.submit` (outside `step`, called by the
benchmark's client loop). An idle interval is split over the phases it runs
through (benchmark/lib/program_trace.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.serve_idle_share(record, "submit")
