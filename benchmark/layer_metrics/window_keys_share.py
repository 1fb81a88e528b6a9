"""Percent of the keys a causal mask would show in every layer that the
layer plan's masks show, over the window's ticks, from the engine's
`stats["attn_keys_full"]`, `["attn_keys_window"]` and
`["attn_keys_causal"]`: what the window layers save the key walk."""
from benchmark.lib import window_math


def read(record):
    c = record.counters
    if not c.get("attn_keys_causal"):
        return None
    return window_math.keys_share(c["attn_keys_full"], c["attn_keys_window"],
                                  c["attn_keys_causal"])
