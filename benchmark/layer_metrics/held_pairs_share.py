"""Percent of the router's (row, expert) pairs, over all 384 experts and the
sparse layers, that fell on the experts this chip holds, from the engine's
`stats["moe_pairs_held"]` and `["moe_pairs"]` over the window: 3.125 % for
an even router over 12 of 384. It says that the router ran over its
published width and that the chip computed its own share."""
from benchmark.lib import latent_math


def read(record):
    c = record.counters
    if "moe_pairs_held" not in c or not c.get("moe_pairs"):
        return None
    return latent_math.held_pairs_share(record.context.config,
                                        c["moe_pairs_held"], c["moe_pairs"])
