"""Share of the traced window that device 0 spent in all-reduce,
all-gather, reduce-scatter, collective-permute or all-to-all operations
(the union of their intervals, overlapped with compute or not)."""


def read(record):
    if record.trace is None:
        return None
    return 100.0 * record.trace["collective_s_chip0"] / record.trace["window_s"]
