"""Share of device busy time that is self time of the operations under
scope `hyper_coeff`: a row's norm over all its lanes, its product with phi
and the 20 rounds of the Sinkhorn iteration on its 4 x 4 mix. The part of
the lane mixing that is bound by the latency of small operations and not
by the stream's bytes. None where the program writes no such scope."""
from benchmark.lib import hyper_scopes, program_trace


def read(record):
    return program_trace.scope_share(record, hyper_scopes.COEFF) or None
