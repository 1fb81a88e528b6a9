"""Percent of the window's (tick, layer, expert) groups that had at least one
row, from the engine's `stats["moe_experts_hit"]`: how much of the expert
weights a tick has to read."""
from benchmark.lib import moe_math


def read(record):
    c = record.counters
    if "moe_experts_hit" not in c or not c["engine_steps"]:
        return None
    return moe_math.hit_share(record.context.config, c["moe_experts_hit"],
                              c["engine_steps"])
