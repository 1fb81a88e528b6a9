"""The least time the chip could take for the traced ticks' attention reads
over the latent pages, over the self time of the operations under scope
`paged_attention_latent`: the launches' share of their roofline. The floor
is taken a tick: the larger of the distinct keys inside the masks read once
(1,152 B a key a layer, whatever the pool pads a row to) over the HBM
bandwidth and the (query row, key) pairs' FLOPs in the cheaper, expanded
form over the bf16 peak (benchmark/lib/latent_math.py); the counts are the
engine's own fields on each step span (`attn_keys_latent`,
`attn_pairs_latent`), never what a walk fetched."""
from benchmark.lib import latent_math, latent_scopes


def read(record):
    ctx = record.context
    return latent_math.roofline_percent(
        record, latent_scopes.READ, ("attn_keys_latent", "attn_pairs_latent"),
        lambda f: latent_math.attention_least_seconds(
            ctx.config, f["attn_keys_latent"], f["attn_pairs_latent"],
            ctx.peaks)[0])
