"""The least time the chip could take for the traced ticks' reads over the
selected positions' keys and values, over the self time of the operations
under scope `paged_attention_sparse` (the masked walk or the gather,
whichever the program's rule took). The floor is taken a tick: the larger
of the selected (row, key) pairs' FLOPs (2 x 32 x 256 a pair) over the bf16
peak and the positions read once, at most the distinct visible keys and at
most the selected pairs (2,048 B each), over the HBM bandwidth
(benchmark/lib/sparse_gqa_math.py); the counts are the engine's own fields
on each step span (`index_keys`, `sparse_pairs_selected`). None for a
program that writes neither."""
from benchmark.lib import latent_math, sparse_gqa_math, sparse_gqa_scopes


def read(record):
    ctx = record.context
    return latent_math.roofline_percent(
        record, sparse_gqa_scopes.SPARSE,
        ("index_keys", "sparse_pairs_selected"),
        lambda f: sparse_gqa_math.sparse_least_seconds(
            ctx.config, f["index_keys"], f["sparse_pairs_selected"],
            ctx.peaks)[0])
