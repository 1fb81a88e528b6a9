"""Operations and bytes a decoder of the configured widths needs, from
shapes alone. Kept with the benchmark so that no PR which claims a gain can
change what its roofline share or its MFU is measured against.

`cfg` is the configuration file's object with the model's own keys
(hidden_size, intermediate_size, num_hidden_layers, num_attention_heads,
num_key_value_heads, head_dim, vocab_size).

Conventions: one multiply-add is 2 FLOPs; weights and activations that the
matrix units read are bf16 (2 bytes); nothing recomputed is counted.
"""
from __future__ import annotations


def block_matmul_params(cfg: dict) -> int:
    """Weights of one block that a token is multiplied with: q, k, v and o
    projections and the three SwiGLU matrices. Norm gains are vectors."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    return q + kv + o + 3 * d * f


def head_params(cfg: dict) -> int:
    """The untied output head. The embedding is a row lookup, not a
    matmul, so it costs no FLOPs."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * block_matmul_params(cfg)
            + head_params(cfg))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes REQUIRE for one token of a
    sequence of `seq_len`, no recomputation counted.

    Matmuls: 2 FLOPs per weight in the forward pass, and twice that in the
    backward pass (one product for the input's gradient, one for the
    weight's): 6 per weight.
    Causal attention: the token at position t scores t+1 keys (2*hd FLOPs
    per key and query head) and mixes t+1 values (the same again). Over a
    sequence the mean of t+1 is (seq_len+1)/2, so the forward pass costs
    4 * H * hd * (seq_len+1)/2 per token and layer; the backward pass
    twice that (dq, dk, dv, dp are four products against the forward's
    two): 3x in all.
    """
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    attn_fwd = 4 * heads * hd * (seq_len + 1) / 2
    return (6 * matmul_params(cfg)
            + 3 * cfg["num_hidden_layers"] * attn_fwd)


def train_step_bytes(cfg: dict, n_params: int) -> float:
    """HBM bytes one optimizer step cannot avoid: f32 master weights and
    both AdamW moments read and written once (24 bytes per parameter).
    Activations are left out (with enough on-chip reuse they need not
    leave the chip), so this is a floor, as a roofline's bytes must be."""
    return 24.0 * n_params


def n_params(cfg: dict) -> int:
    """All parameters, embedding and norm gains included."""
    d = cfg["hidden_size"]
    return (cfg["vocab_size"] * d + matmul_params(cfg)
            + cfg["num_hidden_layers"] * 2 * d + d)


def tick_flops(cfg: dict, new_tokens: int, logit_rows: int,
               attended_keys: int) -> float:
    """FLOPs one serving tick requires: `new_tokens` rows through every
    block's matmuls, `logit_rows` rows through the head (one per sequence
    that samples), and attention over `attended_keys` = the sum, over the
    tick's new tokens, of the keys each one attends to."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    blocks = 2 * new_tokens * cfg["num_hidden_layers"] * block_matmul_params(cfg)
    head = 2 * logit_rows * head_params(cfg)
    attn = 4 * heads * hd * attended_keys * cfg["num_hidden_layers"]
    return blocks + head + attn


def weight_bytes(cfg: dict, bytes_per_weight: int = 2) -> float:
    """Every block's matmul weights and the head, read once."""
    return float(bytes_per_weight * matmul_params(cfg))


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> float:
    """Keys and values of one position over all layers."""
    return float(2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
                 * cfg["head_dim"] * bytes_per_value)


def ticks_bytes(cfg: dict, ticks: int, positions_written: int,
                context_read: int) -> float:
    """HBM bytes `ticks` serving ticks cannot avoid: the weights read once
    in every tick, the cached keys and values of `context_read` positions
    (summed over the rows that read them) read once, and
    `positions_written` new positions' keys and values written once."""
    return (ticks * weight_bytes(cfg)
            + kv_bytes_per_position(cfg) * (context_read + positions_written))


def least_seconds(flops: float, bytes_moved: float, peaks: dict,
                  chips: int = 1):
    """Roofline floor: the larger of FLOPs over peak and bytes over
    bandwidth, and which of the two it is."""
    t_flops = flops / (chips * peaks["bf16_flops_per_s"])
    t_bytes = bytes_moved / (chips * peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
