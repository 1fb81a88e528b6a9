"""The least time the chip could take for the traced steps, over the time
it was busy in them. The floor is the larger of `train_moe_mfu`'s required
FLOPs over the bf16 peak and the optimizer's unavoidable 24 bytes a
parameter a step over the HBM bandwidth (benchmark/lib/train_plan_math.py);
the pairs on held experts are the traced steps' own count."""
from benchmark.lib import train_plan_math


def read(record):
    c = record.trace_counters
    if record.trace is None or not c or "moe_pairs_held" not in c:
        return None
    ctx = record.context
    flops = train_plan_math.step_flops(
        ctx.config, record.counters["seq_len"], c["sequences"],
        c["moe_pairs_held"])
    least, _ = train_plan_math.least_seconds(
        flops, c["steps"] * train_plan_math.step_bytes(ctx.config),
        ctx.peaks, record.counters["chips"])
    return 100.0 * least / record.trace["busy_s"]
