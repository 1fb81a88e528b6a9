"""Share of device busy time that is self time of operations of the serve tick
that belong to no named scope, neither by their own `op_name` nor through
the values they move. The scope of an operation is read from the trace
(benchmark/lib/program_trace.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.scope_share(record, "")
