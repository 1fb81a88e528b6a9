"""Bytes and operations that a learned sparse index over heads' own keys
and values and the read over its selection need, from counts the engine
reports and the configuration's shapes alone, never from what a kernel
fetched or multiplied (PR 50, Keye-VL-2.0). Conventions as in
`sparse_latent_math`: one multiply-add is 2 FLOPs, pages are bf16, and
every floor is the cheaper of the forms an implementation may choose, so
none reads over 100 %.

The engine counts, a tick and summed over the layers
(`PagedServingEngine._plan_keys`):

- `index_keys`, `index_pairs`: the distinct index keys the selecting
  sequences' rows see (a chunk's rows share theirs) and the (row, key)
  pairs scored. A key costs its index key read once, `indexer_head_dim`
  values = 128 B WHATEVER the pool's layout (the lanes a layout pads are
  its own cost and show as a lower share); a pair costs one multiply-add
  over `indexer_head_dim` for each of `indexer_num_heads` heads: 2 x 16 x
  64 FLOPs (the ReLU, the weights and the sum over heads are not counted).
- `sparse_pairs_selected`: the (row, selected key) pairs the sparse read
  attends over. A pair costs one multiply-add in q.k and one in p.v over
  `head_dim` for every query head: 2 x 32 x (128 + 128) FLOPs. The bytes:
  every selected position's keys and values read ONCE however many rows
  selected it, so at most the distinct visible keys (`index_keys`) and at
  most the selected pairs: min of the two x 2 x 4 x 128 x 2 B = 2,048 B.
"""
from __future__ import annotations

from .model_math import least_seconds

BF16 = 2


def index_key_bytes(cfg: dict) -> int:
    return cfg["sa_config"]["indexer_head_dim"] * BF16


def index_pair_flops(cfg: dict) -> int:
    sa = cfg["sa_config"]
    return 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def index_least_seconds(cfg: dict, keys: int, pairs: int, peaks: dict):
    """Floor of one tick's index walks. Returns (seconds, "compute" |
    "memory")."""
    return least_seconds(float(pairs * index_pair_flops(cfg)),
                         float(keys * index_key_bytes(cfg)), peaks)


def pair_flops(cfg: dict) -> int:
    """One (query row, selected key) pair, every query head: q.k and p.v."""
    return 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]


def position_bytes(cfg: dict) -> int:
    """One position's keys and values, every key-value head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def sparse_least_seconds(cfg: dict, keys: int, selected: int, peaks: dict):
    """Floor of one tick's sparse reads: `selected` pairs, over at most
    `keys` distinct positions."""
    return least_seconds(float(selected * pair_flops(cfg)),
                         float(min(keys, selected) * position_bytes(cfg)),
                         peaks)
