"""The least time the chip could take for the traced ticks' attention reads
in the window layers, over the self time of the operations under scope
`paged_attention_window`: the walk's share of its roofline. The floor is the
larger of the keys inside the masks read once over the HBM bandwidth and
the (query row, key) pairs' FLOPs over the bf16 peak
(benchmark/lib/window_math.py); the counts are the engine's own
(`stats["attn_keys_window"]`, `stats["attn_pairs_window"]`) over the traced ticks,
never what the walk fetched."""
from benchmark.lib import laguna_scopes, program_trace, window_math


def read(record):
    c = record.trace_counters
    if record.trace is None or not c or "attn_keys_window" not in c:
        return None
    share = program_trace.scope_share(record, laguna_scopes.WINDOW)
    if not share:
        return None
    ctx = record.context
    least, _ = window_math.attention_least_seconds(
        ctx.config, "window", c["attn_keys_window"], c["attn_pairs_window"], ctx.peaks)
    return 100.0 * least / (share / 100.0 * record.trace["busy_s"])
