"""Percent of the causal (row, key) pairs of the sequences that select
which their rows attended over, from the engine's
`stats["sparse_pairs_selected"]` and `["index_pairs"]` over the window:
what the index leaves of a dense walk's work (2,048 keys of a 16k-33k
context: 6-12 %). None for a program without a sparse index."""
from benchmark.lib import sparse_latent_math


def read(record):
    c = record.counters
    if not c.get("index_pairs"):
        return None
    return sparse_latent_math.selected_share(c["sparse_pairs_selected"],
                                             c["index_pairs"])
