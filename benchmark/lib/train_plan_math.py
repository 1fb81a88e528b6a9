"""Operations and bytes that training one chip's share of a model with a
layer plan and routed experts needs, from shapes and from the step's own
counters. Kept with the benchmark so that no PR which claims a gain can
change what its roofline share or its MFU is measured against.

`cfg` is the configuration file's object with the model's own keys
(`hidden_size`, `head_dim`, `num_attention_heads`, `num_key_value_heads`,
`layer_types` cut to `num_hidden_layers`, `sliding_window`,
`moe_intermediate_size`, `num_experts` = the experts HELD here,
`router_width` = the router's published outputs, `vocab_size` = the slice).

Conventions, as `model_math`: one multiply-add is 2 FLOPs; a matmul costs
2 FLOPs a weight a row forward and twice that backward (6); attention
costs 4 * hd FLOPs a (query head, visible key) forward (scores and the
mix) and twice that backward (12); nothing recomputed is counted. What is
a COUNT OF THE STEP (the pairs on held experts) is passed in; what is an
exact function of the shapes (the keys a mask leaves visible) is computed
here, and is no estimate: the masks are static.
"""
from __future__ import annotations

from . import model_math

WINDOW_TYPE = "sliding_attention"


def visible_keys(seq_len: int, window: int = 0) -> int:
    """(query, key) pairs of one sequence that a causal mask leaves
    visible: sum over positions t of t + 1, or under a window of W keys
    (the query's own among them) of min(t + 1, W)."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_windows(cfg: dict):
    """The window of each layer (0: full), in order."""
    return [cfg["sliding_window"] if t == WINDOW_TYPE else 0
            for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def attention_params(cfg: dict) -> int:
    """q, k, v and o projections of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg.get("router_width", cfg["num_experts"])


def expert_params(cfg: dict) -> int:
    """The three SwiGLU matrices of ONE expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """Every parameter this chip holds: the layers outside their experts
    (projections, router, two norm gains), the experts held, embedding,
    final norm and head over the vocabulary's slice."""
    d = cfg["hidden_size"]
    layer = (attention_params(cfg) + router_params(cfg) + 2 * d
             + cfg["num_experts"] * expert_params(cfg))
    return cfg["num_hidden_layers"] * layer + 2 * head_params(cfg) + d


def attention_flops(cfg: dict, seq_len: int, sequences: int,
                    windows) -> float:
    """Forward and backward FLOPs of the attention itself (no projection)
    of `sequences` sequences through layers with these windows."""
    per_pair = 12 * cfg["num_attention_heads"] * cfg["head_dim"]
    return float(per_pair * sequences
                 * sum(visible_keys(seq_len, w) for w in windows))


def experts_flops(cfg: dict, held_pairs: int) -> float:
    """Forward and backward FLOPs of the grouped products: 6 a weight a
    (row, expert) pair on an expert held here."""
    return 6.0 * expert_params(cfg) * held_pairs


def step_flops(cfg: dict, seq_len: int, sequences: int,
               held_pairs: int) -> float:
    """FLOPs the forward and backward passes of `sequences` sequences
    REQUIRE of this chip: every token through every layer's projections and
    router and through the head over the slice, the visible keys by layer
    type, and the pairs that fell on the experts held here (`held_pairs`,
    summed over the layers: the step's own count)."""
    tokens = sequences * seq_len
    dense = cfg["num_hidden_layers"] * (attention_params(cfg)
                                        + router_params(cfg))
    return (6.0 * tokens * (dense + head_params(cfg))
            + attention_flops(cfg, seq_len, sequences, layer_windows(cfg))
            + experts_flops(cfg, held_pairs))


def attention_bytes(cfg: dict, seq_len: int, sequences: int,
                    layers: int) -> float:
    """bf16 bytes the three launches cannot avoid: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_token = 2 * ((2 * q + 2 * kv) + (4 * q + 4 * kv))
    return float(per_token * seq_len * sequences * layers)


def experts_bytes(cfg: dict, launches: int) -> float:
    """bf16 bytes of the held experts' weights read for the forward
    product and for the input's gradient, and their gradient written
    once, in each launch (a layer of a step)."""
    return float(launches * cfg["num_experts"] * expert_params(cfg) * 2 * 3)


def step_bytes(cfg: dict) -> float:
    """The optimizer's unavoidable bytes a step (`model_math`: 24 a
    parameter)."""
    return model_math.train_step_bytes(cfg, n_params(cfg))


least_seconds = model_math.least_seconds
