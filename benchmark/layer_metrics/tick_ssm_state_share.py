"""Share of device busy time that is self time under `ssm_conv`, `ssm_step`
and `ssm_scan` alone: what of the state-space mixers is not a matrix
product with their weights (the convolution, the recurrent state's update
and the chunked scan). None where the program writes no such scope."""
from benchmark.lib import program_trace, ssm_scopes


def read(record):
    return program_trace.scope_share(record, *ssm_scopes.STATE) or None
