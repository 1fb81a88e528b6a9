"""Share of device busy time that is self time of what the layer scan itself
moves in the serve tick: operations under scope `layers` but under none of
its inner scopes (the slices of the stacked weights and pages, the update of
the stacked pages) and the copies and fills the compiler puts around the
loop for them. The scope of an operation is read from the trace
(benchmark/lib/program_trace.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.scope_share(record, "layers")
