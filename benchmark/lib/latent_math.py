"""Bytes and operations that latent attention over its page pool and a
chip's held share of the routed experts need, from counts the engine
reports and the configuration's shapes alone, never from what a kernel
fetched or multiplied. Kept with the benchmark so that no PR which claims a
gain can change what a roofline share is measured against. Conventions as
in model_math: one multiply-add is 2 FLOPs, weights and pages are bf16.

The engine counts, a tick and summed over the layers: `attn_keys_latent`,
the distinct keys inside the causal masks of a sequence's rows (a chunk's
rows share theirs), and `attn_pairs_latent`, the (query row, key) pairs
inside them. A key costs its cache row read once: kv_lora_rank +
qk_rope_head_dim values, 1,152 B at Kimi's widths, WHATEVER the pool's
layout (lanes a layout pads are its own cost and show as a lower share).
A pair costs, in the cheaper of the two forms (the expanded one), one
multiply-add in q.k over qk_nope + qk_rope values and one in p.v over
v_head_dim for every head: 64 x 2 x 320 FLOPs; the absorbed form's 64 x 2 x
1,088 are an implementation's choice, so no implementation reads over
100 %. `moe_experts_hit` counts the (sparse layer, held expert) groups
with a row, `moe_pairs_held` the (row, expert) pairs on held experts,
summed over the sparse layers; an expert's width is
`moe_intermediate_size`.
"""
from __future__ import annotations

from typing import Callable, Optional

from . import program_trace
from .model_math import least_seconds


def key_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """The cache row of one position in one layer: latent | rope key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def pair_flops(cfg: dict) -> int:
    """One (query row, key) pair, every head, in the expanded form."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def attention_least_seconds(cfg: dict, keys: int, pairs: int, peaks: dict):
    """Roofline floor of one tick's latent attention reads (`keys` and
    `pairs` summed over the layers by the engine). Returns (seconds,
    "compute" | "memory")."""
    return least_seconds(float(pairs * pair_flops(cfg)),
                         float(keys * key_bytes(cfg)), peaks)


def expert_params(cfg: dict) -> int:
    """The three SwiGLU matrices of one routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def experts_least_seconds(cfg: dict, experts_hit: int, pairs_held: int,
                          peaks: dict):
    """Roofline floor of one tick's held-expert matmuls: every hit (layer,
    expert) group's weights read once, or the held pairs' FLOPs."""
    return least_seconds(2.0 * pairs_held * expert_params(cfg),
                         float(experts_hit * expert_params(cfg) * 2), peaks)


def hit_share(cfg: dict, experts_hit: int, ticks: int) -> float:
    """Percent of the (tick, sparse layer, held expert) groups with a row."""
    return (100.0 * experts_hit
            / (ticks * sparse_layers(cfg) * cfg["n_routed_experts"]))


def held_pairs_share(cfg: dict, pairs_held: int, pairs: int) -> float:
    """Percent of the router's (row, expert) pairs, over all its experts
    and the sparse layers, that fell on experts held here (`pairs` is the
    engine's `moe_pairs`, valid rows x top-k: a sparse layer's)."""
    return 100.0 * pairs_held / (pairs * sparse_layers(cfg))


def row_fill_share(cfg: dict, pool_row_bytes: float) -> float:
    """Percent of the pool's bytes a key a layer that are the cache row."""
    return 100.0 * key_bytes(cfg) / pool_row_bytes


def roofline_percent(record, scope: str, needs: tuple,
                     least: Callable[[dict], float]) -> Optional[float]:
    """100 x the sum over the traced window's step spans that carry the
    fields `needs` of `least(fields)` seconds (the floor is taken a tick,
    so a window that mixes compute-bound and memory-bound ticks is held to
    each tick's own), over the self time under `scope`. None where there
    is no trace, no such span or no such scope."""
    trace = program_trace.of_record(record)
    if trace is None or record.trace is None:
        return None
    ticks = [e[3] for e in trace["program_spans"]
             if e[0] == program_trace.STEP and all(n in e[3] for n in needs)]
    share = program_trace.scope_share(record, scope)
    if not ticks or not share:
        return None
    floor = sum(least(f) for f in ticks)
    return 100.0 * floor / (share / 100.0 * record.trace["busy_s"])
