"""The least time the chip could take for the traced steps' grouped expert
products, forward and backward, over the self time of the operations under
scope `experts`: the sorted form's share of its roofline in training. The
floor is the larger of 6 FLOPs a weight a pair on an expert held here (the
traced steps' own count, `moe_pairs_held`) over the bf16 peak and the held
experts' weights read twice and their gradient written once a launch over
the HBM bandwidth (benchmark/lib/train_plan_math.py)."""
from benchmark.lib import program_trace, train_plan_math, train_plan_scopes


def read(record):
    c = record.trace_counters
    if record.trace is None or not c or "moe_pairs_held" not in c:
        return None
    share = program_trace.scope_share(record, train_plan_scopes.EXPERTS)
    if not share:
        return None
    ctx = record.context
    least, _ = train_plan_math.least_seconds(
        train_plan_math.experts_flops(ctx.config, c["moe_pairs_held"]),
        train_plan_math.experts_bytes(ctx.config, c["moe_launches"]),
        ctx.peaks)
    return 100.0 * least / (share / 100.0 * record.trace["busy_s"])
