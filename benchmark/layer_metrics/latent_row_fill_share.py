"""Percent of the latent pool's bytes a key a layer that are the cache row
itself (kv_lora_rank + qk_rope_head_dim values, 1,152 B): 100 for a pool of
576-wide rows, 90 where a row is padded to 640 lanes. The pool's bytes are
the engine's own page size (`kv_page_bytes` over layers and page slots),
which the driver reads into the window's counters."""
from benchmark.lib import latent_math


def read(record):
    c = record.counters
    if not c.get("latent_row_bytes"):
        return None
    return latent_math.row_fill_share(record.context.config,
                                      c["latent_row_bytes"])
