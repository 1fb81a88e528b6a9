"""The least time the chip could take for the traced ticks' index walks,
over the self time of the operations under scope `index_scores`. The floor
is taken a tick: the larger of the distinct index keys read once (256 B a
key a layer) over the HBM bandwidth and the scored (row, key) pairs' FLOPs
(2 x 64 x 128 a pair) over the bf16 peak
(benchmark/lib/sparse_latent_math.py); the counts are the engine's own
fields on each step span (`index_keys`, `index_pairs`)."""
from benchmark.lib import latent_math, sparse_latent_math, sparse_latent_scopes


def read(record):
    ctx = record.context
    return latent_math.roofline_percent(
        record, sparse_latent_scopes.SCORES, ("index_keys", "index_pairs"),
        lambda f: sparse_latent_math.index_least_seconds(
            ctx.config, f["index_keys"], f["index_pairs"], ctx.peaks)[0])
