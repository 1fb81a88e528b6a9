"""The fullest held expert's rows over the mean held expert's rows, over
the window's launches of the routed FFN (a layer of a step), from the
step's own counters: the sum over launches of the largest load over the
sum of the mean loads (`moe_pairs_held` / the experts held). 1 is an even
load; the grouped products wait for the fullest group."""


def read(record):
    c = record.counters
    if not c.get("moe_pairs_held"):
        return None
    held = record.context.config["num_experts"]
    return c["moe_load_max"] / (c["moe_pairs_held"] / held)
