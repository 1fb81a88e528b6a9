"""Percent of the window's (tick, sparse layer, expert) groups that had at
least one row, from the engine's `stats["moe_experts_hit"]`, for a layer
plan in which not every layer is sparse (`moe_experts_hit_share` would
divide by every layer): how much of the expert weights a tick has to
read."""
from benchmark.layer_metrics.sparse_experts_roofline import sparse_config
from benchmark.lib import moe_math


def read(record):
    c, cfg = record.counters, record.context.config
    if ("moe_experts_hit" not in c or "mlp_layer_types" not in cfg
            or not c["engine_steps"]):
        return None
    return moe_math.hit_share(sparse_config(cfg), c["moe_experts_hit"],
                              c["engine_steps"])
