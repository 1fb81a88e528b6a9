"""From a configuration file's published keys to the program's own config
object. Shared by the drivers; knows no configuration by name."""
from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.models import llama as L

from .harness import BenchmarkError

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def llama_config(cfg: dict, param_dtype) -> L.LlamaConfig:
    """The program's config object from the published keys."""
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise BenchmarkError("LlamaConfig derives head_dim from hidden_size")
    return L.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.bfloat16, param_dtype=param_dtype)
