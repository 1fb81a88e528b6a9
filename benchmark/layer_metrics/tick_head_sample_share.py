"""Share of device busy time that is self time of the final norm, the
vocabulary head and the sampler of the serve tick (scopes `head` and
`sample`). The scope of an operation is read from the trace
(benchmark/lib/program_trace.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.scope_share(record, "head", "sample")
