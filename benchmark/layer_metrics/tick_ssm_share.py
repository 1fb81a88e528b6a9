"""Share of device busy time that is self time of the state-space mixers'
operations: scope `ssm` and, inside it, `ssm_in`, `ssm_conv`, `ssm_step`,
`ssm_scan`, `ssm_gate` and `ssm_out`, read from the trace
(benchmark/lib/program_trace.py with the scopes of
benchmark/lib/ssm_scopes.py). None where the program writes no such
scope."""
from benchmark.lib import program_trace, ssm_scopes


def read(record):
    return program_trace.scope_share(record, *ssm_scopes.SSM) or None
