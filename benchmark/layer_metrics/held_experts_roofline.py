"""The least time the chip could take for the traced ticks' matmuls of the
routed experts it HOLDS (a share of the model's: 12 of 384), over the self
time of the operations under scope `experts`. The floor is taken a tick:
the weights of every (sparse layer, held expert) group with a row read once
(3 x hidden x `moe_intermediate_size` x 2 B), or the FLOPs of the (row,
expert) pairs that fell on held experts (benchmark/lib/latent_math.py);
the counts are the engine's fields on each step span (`moe_experts_hit`,
`moe_pairs_held`). The shared expert runs under its own scope and is in
neither."""
from benchmark.lib import latent_math


def read(record):
    ctx = record.context
    return latent_math.roofline_percent(
        record, "experts", ("moe_experts_hit", "moe_pairs_held"),
        lambda f: latent_math.experts_least_seconds(
            ctx.config, f["moe_experts_hit"], f["moe_pairs_held"],
            ctx.peaks)[0])
