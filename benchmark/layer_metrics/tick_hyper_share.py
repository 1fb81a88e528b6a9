"""Share of device busy time that is self time of the operations that mix
the lanes of a hyper-connected residual stream: scope `hyper` and, inside
it, `hyper_coeff` (row norm, projection, Sinkhorn iteration), `hyper_pre`
(the sub-block's input) and `hyper_post` (the lanes' update), read from
the trace (benchmark/lib/program_trace.py with the scopes of
benchmark/lib/hyper_scopes.py). None where the program writes no such
scope."""
from benchmark.lib import hyper_scopes, program_trace


def read(record):
    return program_trace.scope_share(record, *hyper_scopes.HYPER) or None
