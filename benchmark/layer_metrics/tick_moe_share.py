"""Share of device busy time that is self time of the routed-expert sub-block
of the serve tick: scope `moe` (the norm, the counters) and its inner
scopes `router`, `dispatch`, `experts`, `combine`. The scope of an
operation is read from the trace (benchmark/lib/program_trace.py, with
the scopes of benchmark/lib/moe_scopes.py)."""
from benchmark.lib import moe_scopes, program_trace


def read(record):
    return program_trace.scope_share(record, moe_scopes.MOE,
                                     *moe_scopes.INNER)
