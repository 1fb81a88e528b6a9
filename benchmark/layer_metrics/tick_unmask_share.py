"""Share of device busy time that is self time of the block-diffusion
tick's transfer: scope `unmask` inside `sample` (the softmax over the
vocabulary at every block row, the confidence, the choice of the rows
that become tokens). The scope of an operation is read from the trace
(benchmark/lib/program_trace.py, with the scope of
benchmark/lib/blockdiff_scopes.py). None for a program without the scope."""
from benchmark.lib import blockdiff_scopes, program_trace


def read(record):
    return program_trace.scope_share(record, blockdiff_scopes.UNMASK) or None
