"""The least time the chip could take for the traced ticks' index walks
over heads' own keys' pages, over the self time of the operations under
scope `index_scores`. The floor is taken a tick: the larger of the distinct
index keys read once (128 B a key a layer) over the HBM bandwidth and the
scored (row, key) pairs' FLOPs (2 x 16 x 64 a pair) over the bf16 peak
(benchmark/lib/sparse_gqa_math.py); the counts are the engine's own fields
on each step span (`index_keys`, `index_pairs`). None for a program that
writes neither."""
from benchmark.lib import latent_math, sparse_gqa_math, sparse_gqa_scopes


def read(record):
    ctx = record.context
    return latent_math.roofline_percent(
        record, sparse_gqa_scopes.SCORES, ("index_keys", "index_pairs"),
        lambda f: sparse_gqa_math.index_least_seconds(
            ctx.config, f["index_keys"], f["index_pairs"], ctx.peaks)[0])
