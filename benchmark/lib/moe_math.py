"""Bytes and operations the routed experts of a tick need, from counts the
program reports and the configuration's shapes alone. Kept with the
benchmark so that no PR which claims a gain can change what an expert
form's (or a new kernel's) roofline share is measured against.

`cfg` is the configuration file's object (hidden_size, intermediate_size =
the width of ONE expert, num_experts, num_experts_per_tok,
num_hidden_layers). Conventions as in model_math: one multiply-add is 2
FLOPs, weights are bf16 (2 bytes), nothing recomputed is counted.
"""
from __future__ import annotations

from .model_math import least_seconds


def expert_params(cfg: dict) -> int:
    """The three SwiGLU matrices of one expert."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def experts_bytes(cfg: dict, experts_hit: int,
                  bytes_per_weight: int = 2) -> float:
    """HBM bytes the expert matmuls cannot avoid: the weights of every
    (layer, expert) group that has at least one row, read once. Rows and
    results are left out (a floor, as a roofline's bytes must be)."""
    return float(experts_hit * expert_params(cfg) * bytes_per_weight)


def experts_flops(cfg: dict, pairs: int) -> float:
    """FLOPs of `pairs` (row, expert) pairs in every layer: each pair is
    one row through one expert's three matrices."""
    return 2.0 * pairs * cfg["num_hidden_layers"] * expert_params(cfg)


def experts_least_seconds(cfg: dict, experts_hit: int, pairs: int,
                          peaks: dict):
    """Roofline floor of the expert matmuls of some ticks: `experts_hit`
    is summed over layers and ticks, `pairs` over ticks (each pair runs
    in every layer). Returns (seconds, "compute" | "memory")."""
    return least_seconds(experts_flops(cfg, pairs),
                         experts_bytes(cfg, experts_hit), peaks)


def hit_share(cfg: dict, experts_hit: int, ticks: int) -> float:
    """Percent of the (tick, layer, expert) groups that had a row."""
    return (100.0 * experts_hit
            / (ticks * cfg["num_hidden_layers"] * cfg["num_experts"]))


def load_max_over_mean(cfg: dict, max_load: int, pairs: int) -> float:
    """One tick's largest rows-on-one-expert (over layers) over the mean
    rows an expert gets in that tick, pairs / num_experts."""
    return max_load * cfg["num_experts"] / pairs
