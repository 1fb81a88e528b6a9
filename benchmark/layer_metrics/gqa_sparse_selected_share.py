"""`sparse_selected_share` of a cell whose layers attend over heads' own
keys and values (PR 50): percent of the causal (row, key) pairs of the
sequences that select which their rows attended over (2,048 keys of a
32k-65k context: 3-6 %), from the engine's `stats["sparse_pairs_selected"]`
and `["index_pairs"]` by the accepted reader; an entry of its own because
that reader's cell list is the latent cell's alone. None for a program
without a sparse index."""
from benchmark.layer_metrics import sparse_selected_share

read = sparse_selected_share.read
