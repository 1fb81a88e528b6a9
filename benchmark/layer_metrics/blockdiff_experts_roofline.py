"""`moe_experts_roofline` for a configuration whose expert width is
`moe_intermediate_size` (SDAR, Qwen3-MoE's keys: `intermediate_size` is
the width of a dense layer it does not have, eight times an expert's): the
least time the chip could take for the traced ticks' expert matmuls
(`moe_math.experts_least_seconds`, the weights of every (layer, expert)
group with a row read once, or the pairs' FLOPs) over the self time of the
operations under scope `experts`. The counts are the engine's own over
the traced ticks."""
from benchmark.lib import moe_math, program_trace


def read(record):
    c = record.trace_counters
    cfg = record.context.config
    if (record.trace is None or not c or "moe_experts_hit" not in c
            or "moe_intermediate_size" not in cfg):
        return None
    share = program_trace.scope_share(record, "experts")
    if not share:
        return None
    least, _ = moe_math.experts_least_seconds(
        dict(cfg, intermediate_size=cfg["moe_intermediate_size"]),
        c["moe_experts_hit"], c["moe_pairs"], record.context.peaks)
    return 100.0 * least / (share / 100.0 * record.trace["busy_s"])
