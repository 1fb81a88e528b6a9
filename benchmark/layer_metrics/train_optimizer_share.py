"""Share of device busy time that is self time of the optimizer of the train
step: scopes `adamw` and `grad_norm`. The scope of an operation is read from
the trace (benchmark/lib/program_trace.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.scope_share(record, "adamw", "grad_norm")
