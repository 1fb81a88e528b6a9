"""Share of device busy time that is self time of the operations under scope
`attention_full`: the full layers' attention kernel launches and what the
call puts beside them, forward and backward."""
from benchmark.lib import program_trace, train_plan_scopes


def read(record):
    return program_trace.scope_share(record, train_plan_scopes.FULL) or None
