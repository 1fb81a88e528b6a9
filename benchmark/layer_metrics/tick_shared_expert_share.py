"""Share of device busy time that is self time of the operations under
scope `laguna_scopes.SHARED` of a layer plan's tick (the scope of an
operation is read from the trace: benchmark/lib/program_trace.py with the
scopes of benchmark/lib/laguna_scopes.py). None where the program writes no
such scope."""
from benchmark.lib import laguna_scopes, program_trace


def read(record):
    return program_trace.scope_share(record, laguna_scopes.SHARED) or None
