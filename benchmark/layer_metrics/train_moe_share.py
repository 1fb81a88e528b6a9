"""Share of device busy time that is self time of the routed FFN of the
train step, forward and backward: scope `moe` and inside it `router`,
`dispatch`, `experts`, `combine`."""
from benchmark.lib import program_trace, train_plan_scopes


def read(record):
    return program_trace.scope_share(record, *train_plan_scopes.MOE) or None
