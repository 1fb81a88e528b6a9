"""The least time the chip could take for the traced ticks' windowed latent
walks, over the self time of the operations under scope
`paged_attention_latent_window`. The floor is taken a tick: the larger of
the distinct keys inside the windows read once (2,176 B a key a layer)
over the HBM bandwidth and the (row, key) pairs' FLOPs in the expanded form
(2 x 64 x 384 a pair) over the bf16 peak
(benchmark/lib/sparse_latent_math.py); the counts are the engine's own
fields on each step span (`attn_keys_latent_window`,
`attn_pairs_latent_window`)."""
from benchmark.lib import latent_math, sparse_latent_math, sparse_latent_scopes


def read(record):
    ctx = record.context
    return latent_math.roofline_percent(
        record, sparse_latent_scopes.WINDOW,
        ("attn_keys_latent_window", "attn_pairs_latent_window"),
        lambda f: sparse_latent_math.window_least_seconds(
            ctx.config, f["attn_keys_latent_window"],
            f["attn_pairs_latent_window"], ctx.peaks)[0])
