"""Share of device busy time that is self time of what sparsity costs in the
serve tick beside the expert matmuls themselves: scopes `router` (logits,
softmax, top-k), `dispatch` (sort or one-hot, group sizes) and `combine`
(weights, un-sort, sum). The scope of an operation is read from the trace
(benchmark/lib/program_trace.py, benchmark/lib/moe_scopes.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.scope_share(record, "router", "dispatch", "combine")
