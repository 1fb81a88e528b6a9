"""The least time the chip could take for the traced ticks' expert matmuls,
over the self time of the operations under scope `experts`: the expert
form's (or an expert kernel's) share of its roofline. The floor is the
larger of the weights of every (layer, expert) group with a row read once
over the HBM bandwidth, and the pairs' FLOPs over the bf16 peak
(benchmark/lib/moe_math.py); the counts are the engine's own
(`stats["moe_experts_hit"]`, `stats["moe_pairs"]`) over the traced ticks."""
from benchmark.lib import moe_math, program_trace


def read(record):
    c = record.trace_counters
    if record.trace is None or not c or "moe_experts_hit" not in c:
        return None
    share = program_trace.scope_share(record, "experts")
    if not share:
        return None
    ctx = record.context
    least, _ = moe_math.experts_least_seconds(
        ctx.config, c["moe_experts_hit"], c["moe_pairs"], ctx.peaks)
    return 100.0 * least / (share / 100.0 * record.trace["busy_s"])
