"""Share of device busy time that is self time of the attention sub-block of
the serve tick: scopes `qkv` (norm, projections, split, rope),
`paged_attention` (the Pallas launch or the stock read) and `attn_out`. The
scope of an operation is read from the trace
(benchmark/lib/program_trace.py)."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.scope_share(record, "qkv", "paged_attention", "attn_out")
