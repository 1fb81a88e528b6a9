"""Share of device busy time that is self time of the operations under scope
`attention_window`: the window layers' attention kernel launches (forward,
its replay under remat, dq, dk/dv) and what the call puts beside them (the
layout transposes, delta), forward and backward. The projections lie
outside it, in `attention`."""
from benchmark.lib import program_trace, train_plan_scopes


def read(record):
    return program_trace.scope_share(record, train_plan_scopes.WINDOW) or None
