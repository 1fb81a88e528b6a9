"""Share of the pages paged attention fetched that held a key some query
could see: the sum of `attn_pages_live` over the sum of
`attn_pages_fetched` of the traced window's `ptpu.serve.step` spans (the
host's mirror of the launches' walks, whole key blocks of P pages,
`paged_attention.decode_pages_walked` / `mixed_work`). None where no tick
went through a whole-page walk."""
from benchmark.lib import step_fields


def read(record):
    return step_fields.ratio_percent(record, "attn_pages_live",
                                     "attn_pages_fetched")
