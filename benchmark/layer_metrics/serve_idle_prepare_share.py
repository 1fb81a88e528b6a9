"""Share of the traced window in which device 0 ran nothing while the host was
in `ptpu.serve.prepare`: copy-on-write page copies, the Pallas/FFN routing,
adapter residency, the speculative plan and the numpy fill of the step's
inputs. An idle interval is split over the phases it runs through
(benchmark/lib/program_trace.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.serve_idle_share(record, "prepare")
