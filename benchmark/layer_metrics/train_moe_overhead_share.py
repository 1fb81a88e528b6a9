"""Share of device busy time that the routed FFN spends outside its grouped
products, forward and backward: scopes `router`, `dispatch` (the sort and
the gather of rows) and `combine` (the un-sort and the weighted sum)."""
from benchmark.lib import program_trace, train_plan_scopes


def read(record):
    return program_trace.scope_share(
        record, *train_plan_scopes.MOE_OVERHEAD) or None
