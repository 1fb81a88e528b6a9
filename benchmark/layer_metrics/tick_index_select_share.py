"""Share of device busy time that is self time of the operations under
scope `index_select` alone: the exact selection (`select_topk`, 32 counting
passes over [rows, max_len] scores whatever a row sees) and its positions.
At 65,536 keys a row it is what `tick_index_share` hides among the index's
four scopes. None where the program writes no such scope."""
from benchmark.lib import program_trace, sparse_gqa_scopes


def read(record):
    return program_trace.scope_share(record,
                                     sparse_gqa_scopes.SELECT) or None
