"""The least time the chip could take for the traced ticks' reads over the
selected cache rows, over the self time of the operations under scope
`paged_attention_sparse`. The floor is taken a tick: the larger of the
selected (row, key) pairs' FLOPs in the cheaper, expanded form (2 x 128 x
320 a pair) over the bf16 peak and the cache rows read once, at most the
distinct visible keys and at most the selected pairs (1,152 B each), over
the HBM bandwidth (benchmark/lib/sparse_latent_math.py); the counts are
the engine's own fields on each step span (`index_keys`,
`sparse_pairs_selected`)."""
from benchmark.lib import latent_math, sparse_latent_math, sparse_latent_scopes


def read(record):
    ctx = record.context
    return latent_math.roofline_percent(
        record, sparse_latent_scopes.SPARSE,
        ("index_keys", "sparse_pairs_selected"),
        lambda f: sparse_latent_math.sparse_least_seconds(
            ctx.config, f["index_keys"], f["sparse_pairs_selected"],
            ctx.peaks)[0])
