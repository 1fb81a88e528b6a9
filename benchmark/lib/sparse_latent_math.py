"""Bytes and operations that a learned sparse index, the read over its
selection and a windowed latent walk need, from counts the engine reports
and the configuration's shapes alone, never from what a kernel fetched or
multiplied (PR 43, dots3-note). Conventions as in `latent_math`: one
multiply-add is 2 FLOPs, pages are bf16, and every floor is the cheaper of
the forms an implementation may choose, so none reads over 100 %.

The engine counts, a tick and summed over the layers of the kind
(`PagedServingEngine._plan_keys`):

- `index_keys`, `index_pairs`: the distinct index keys the selecting
  sequences' rows see (a chunk's rows share theirs) and the (row, key)
  pairs scored. A key costs its index key read once, `index_head_dim`
  values = 256 B; a pair costs one multiply-add over `index_head_dim` for
  each of `index_n_heads` heads: 2 x 64 x 128 FLOPs (the ReLU, the weights
  and the sum over heads are not counted).
- `sparse_pairs_selected`: the (row, selected key) pairs the sparse read
  attends over. A pair costs, in the expanded form, 2 x H x (nope + rope +
  v) = 2 x 128 x 320 FLOPs (the absorbed form's 2 x 128 x 1,088 are an
  implementation's choice). The bytes: every selected cache row read ONCE
  however many rows selected it, so at most the distinct visible keys
  (`index_keys`) and at most the selected pairs: min of the two x 1,152 B.
- `attn_keys_latent_window`, `attn_pairs_latent_window`: the same of the
  window layers inside their windows; a key is 1,024 + 64 values = 2,176 B,
  a pair 2 x 64 x (192 + 64 + 128) FLOPs.
"""
from __future__ import annotations

from .model_math import least_seconds

BF16 = 2


def index_least_seconds(cfg: dict, keys: int, pairs: int, peaks: dict):
    """Floor of one tick's index walks. Returns (seconds, "compute" |
    "memory")."""
    return least_seconds(
        2.0 * pairs * cfg["index_n_heads"] * cfg["index_head_dim"],
        float(keys * cfg["index_head_dim"] * BF16), peaks)


def _pair_flops(cfg: dict, p: str) -> int:
    return 2 * cfg[p + "num_attention_heads"] * (
        cfg[p + "qk_nope_head_dim"] + cfg[p + "qk_rope_head_dim"]
        + cfg[p + "v_head_dim"])


def _key_bytes(cfg: dict, p: str) -> int:
    return (cfg[p + "kv_lora_rank"] + cfg[p + "qk_rope_head_dim"]) * BF16


def sparse_least_seconds(cfg: dict, keys: int, selected: int, peaks: dict):
    """Floor of one tick's sparse reads: `selected` pairs, over at most
    `keys` distinct cache rows."""
    return least_seconds(float(selected * _pair_flops(cfg, "")),
                         float(min(keys, selected) * _key_bytes(cfg, "")),
                         peaks)


def window_least_seconds(cfg: dict, keys: int, pairs: int, peaks: dict):
    """Floor of one tick's windowed latent walks."""
    return least_seconds(float(pairs * _pair_flops(cfg, "swa_")),
                         float(keys * _key_bytes(cfg, "swa_")), peaks)


def selected_share(selected: int, pairs: int) -> float:
    """Percent of the causal (row, key) pairs of the selecting sequences
    that their rows attended over."""
    return 100.0 * selected / pairs
