"""Percent of the window's (tick, sparse layer, held expert) groups that had
at least one row, from the engine's `stats["moe_experts_hit"]` (counted
over the experts this chip holds): how much of the held experts' weights a
tick has to read."""
from benchmark.lib import latent_math


def read(record):
    c = record.counters
    if "moe_pairs_held" not in c or not c.get("engine_steps"):
        return None
    return latent_math.hit_share(record.context.config, c["moe_experts_hit"],
                                 c["engine_steps"])
