"""Bytes and operations the paged attention of a layer plan needs, from
counts the engine reports and the configuration's shapes alone, never from
what a walk fetched. Kept with the benchmark so that no PR which claims a
gain can change what a walk's roofline share is measured against.

The engine counts, a tick and summed over the layers of a kind (full or
window): `attn_keys_<kind>`, the distinct keys inside the masks of a
sequence's rows (a decode row at position p: p + 1 keys in a full layer,
min(p + 1, window) in a window layer; a chunk's rows share theirs, the
union is counted once), and `attn_pairs_<kind>`, the (query row, key)
pairs inside the masks. A key costs its K and V rows read once, 2 x
kv_heads x head_dim x 2 bytes (4,096 at Laguna's widths); a pair costs one
multiply-add in q.k and one in p.v for every query head, 4 x head_dim x
H_kind FLOPs. Conventions as in model_math.
"""
from __future__ import annotations

from .model_math import least_seconds

KINDS = {"full": "full_attention", "window": "sliding_attention"}


def heads_of(cfg: dict, kind: str) -> int:
    """Query heads of the layers of `kind`, from the published per-layer
    lists (one count a kind, or the configuration is not what this counts
    for)."""
    heads = {h for t, h in zip(cfg["layer_types"],
                               cfg["num_attention_heads_per_layer"])
             if t == KINDS[kind]}
    if len(heads) != 1:
        raise ValueError(f"{kind} layers have head counts {sorted(heads)}")
    return heads.pop()


def key_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of one position in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def attention_least_seconds(cfg: dict, kind: str, keys: int, pairs: int,
                            peaks: dict):
    """Roofline floor of the attention reads of one kind over some ticks:
    `keys` and `pairs` are the engine's counters, summed over ticks (and,
    by the engine, over the layers of the kind). Returns (seconds,
    "compute" | "memory")."""
    flops = 4.0 * cfg["head_dim"] * heads_of(cfg, kind) * pairs
    return least_seconds(flops, float(keys * key_bytes(cfg)), peaks)


def keys_share(keys_full: int, keys_window: int, keys_causal: int) -> float:
    """Percent of the keys a causal mask would show in every layer that
    the plan's masks show."""
    return 100.0 * (keys_full + keys_window) / keys_causal


def pool_pages_share(window_pages: int, full_pages: int) -> float:
    """Percent of the pages the window layers would hold without release
    (as many as the full layers' pool holds: the same sequences at the
    same lengths) that their pool holds."""
    return 100.0 * window_pages / full_pages
