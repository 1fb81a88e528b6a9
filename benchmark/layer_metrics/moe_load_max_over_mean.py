"""Mean over the traced ticks of the largest number of rows one expert got
(over layers) divided by the mean rows an expert got in that tick, from the
fields `moe_max_load` and `moe_pairs` of the tick's `ptpu.serve.step`
span: 1 is a perfectly even router."""
from benchmark.lib import moe_math, program_trace


def read(record):
    trace = program_trace.of_record(record)
    if trace is None:
        return None
    ratios = [moe_math.load_max_over_mean(
        record.context.config, float(e[3]["moe_max_load"]),
        float(e[3]["moe_pairs"]))
        for e in trace["program_spans"]
        if e[0] == program_trace.STEP and float(e[3].get("moe_pairs", 0))]
    return sum(ratios) / len(ratios) if ratios else None
