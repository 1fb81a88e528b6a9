"""Share of the traced window's ticks that had been launched ahead: the
`ptpu.serve.step` spans that ran a batch and say `ahead` = 1 (the tick whose
events the call returned was called before the tick before it was read, its
decode rows fed on the device), over all that ran a batch. None on a
program whose step spans carry no `ahead` field."""
from benchmark.lib import program_trace


def read(record):
    trace = program_trace.of_record(record)
    if trace is None:
        return None
    ahead = [float(e[3]["ahead"]) for e in trace["program_spans"]
             if e[0] == program_trace.STEP and "batch" in e[3]
             and "ahead" in e[3]]
    return 100.0 * sum(ahead) / len(ahead) if ahead else None
