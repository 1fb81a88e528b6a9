"""Percent of the router's (row, expert) pairs, over all its published
outputs and the window's steps and layers, that fell on the experts this
chip holds, from the step's own counters (`moe_pairs_held`, `moe_pairs`):
25 % for an even router over 16 of 64. It says that the router ran over its
published width and that the chip computed its own share."""


def read(record):
    c = record.counters
    if not c.get("moe_pairs"):
        return None
    return 100.0 * c["moe_pairs_held"] / c["moe_pairs"]
