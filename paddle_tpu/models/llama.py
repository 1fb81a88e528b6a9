"""Functional LLaMA-family decoder — the flagship model of the framework.

Role in the framework (SURVEY.md §6/§7): the reference's headline benchmark is
LLaMA-13B trained through fleet hybrid parallel (BASELINE.json config 4, built
in model code on top of fleet primitives: mp_layers.py ColumnParallelLinear /
RowParallelLinear / VocabParallelEmbedding, pipeline_parallel.py schedules).
Here the flagship is a pure-functional JAX model: a params pytree + jittable
forward/loss, designed so the hybrid-parallel engine
(paddle_tpu.distributed.hybrid) can shard the SAME pytree over a
('dp','pp','tp') mesh with shard_map — layers are stacked on a leading axis
(lax.scan-able, pp-splittable), and every projection is written so tp sharding
of its output/input dim is valid.

TPU-first choices: bf16 compute / f32 master params, static shapes, scan over
stacked layer params (one compiled block body, not L unrolled layers), GQA,
RoPE computed in f32, optional MoE (top-k routing; the hybrid engine dispatches
tokens with all_to_all over the ep axis).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    # MoE: 0 = dense MLP. When >0, every layer's MLP is a top-k gated MoE.
    num_experts: int = 0
    top_k: int = 2
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def num_params(self) -> int:
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        hd = self.head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd + self.num_heads * hd * d
        if self.num_experts:
            mlp = self.num_experts * 3 * d * f + d * self.num_experts
        else:
            mlp = 3 * d * f
        per_layer = attn + mlp + 2 * d
        return v * d + self.num_layers * per_layer + d + d * v

    def flops_per_token(self) -> int:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6·N_active)."""
        d, f = self.hidden_size, self.intermediate_size
        hd = self.head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd + self.num_heads * hd * d
        mlp = 3 * d * f * (min(self.top_k, self.num_experts) if self.num_experts else 1)
        dense = self.num_layers * (attn + mlp) + 2 * self.hidden_size * self.vocab_size
        return 6 * dense


# Predefined sizes (the reference's headline configs; LLaMA-7B/13B per
# BASELINE.json config 4).
CONFIGS = {
    "llama-test": LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                              num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=128),
    "llama-7b": LlamaConfig(hidden_size=4096, intermediate_size=11008, num_layers=32,
                            num_heads=32, num_kv_heads=32),
    "llama-13b": LlamaConfig(hidden_size=5120, intermediate_size=13824, num_layers=40,
                             num_heads=40, num_kv_heads=40),
}


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Build the parameter pytree. Block params are stacked on a leading
    num_layers axis so the forward is a lax.scan and the pipeline engine can
    reshape to [pp, layers_per_stage, ...]."""
    d, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hd, nh, nkv, L = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    pt = cfg.param_dtype
    keys = jax.random.split(key, 10)

    def normal(k, shape, scale=0.02):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(pt)

    blocks = {
        "wq": normal(keys[0], (L, d, nh * hd)),
        "wk": normal(keys[1], (L, d, nkv * hd)),
        "wv": normal(keys[2], (L, d, nkv * hd)),
        "wo": normal(keys[3], (L, nh * hd, d)),
        "attn_norm": jnp.ones((L, d), pt),
        "mlp_norm": jnp.ones((L, d), pt),
    }
    if cfg.num_experts:
        e = cfg.num_experts
        blocks["router"] = normal(keys[4], (L, d, e))
        blocks["w1"] = normal(keys[5], (L, e, d, f))
        blocks["w3"] = normal(keys[6], (L, e, d, f))
        blocks["w2"] = normal(keys[7], (L, e, f, d))
    else:
        blocks["w1"] = normal(keys[5], (L, d, f))
        blocks["w3"] = normal(keys[6], (L, d, f))
        blocks["w2"] = normal(keys[7], (L, f, d))
    return {
        "embed": normal(keys[8], (v, d)),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), pt),
        "lm_head": normal(keys[9], (d, v)),
    }


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    x32 = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * w.astype(jnp.float32)).astype(x.dtype)


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """positions [T] int → (cos, sin) [T, head_dim/2] in f32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, T, H, hd]; rotate-half convention, f32 math."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, impl: str = "auto") -> jax.Array:
    """Causal MHA/GQA. q [B,T,H,hd], k/v [B,T,KV,hd] → [B,T,H,hd].

    impl: 'auto' uses the Pallas flash kernel on TPU when available, else the
    XLA einsum path (which XLA fuses well on its own).
    """
    if impl == "flash":
        # explicit request: no silent fallback — unsupported shapes raise
        from ..ops.pallas import flash_attention as _fa

        return _fa.flash_attention(q, k, v, causal=True)
    if impl == "auto":
        from ..ops.pallas import flash_attention as _fa

        if (_fa.available() and q.shape[1] == k.shape[1]
                and _fa.supported(q.shape, k.shape)):
            return _fa.flash_attention(q, k, v, causal=True)
    B, T, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scale = 1.0 / (hd ** 0.5)
    scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def moe_mlp(x: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig) -> jax.Array:
    """Dense (compute-all-experts) MoE for the single-device path. The hybrid
    engine replaces this with an all_to_all token dispatch over the ep axis."""
    gate = jax.nn.softmax(
        (x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)), axis=-1)
    topw, topi = lax.top_k(gate, cfg.top_k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    # combine weights [B, T, E]
    comb = jnp.sum(jax.nn.one_hot(topi, cfg.num_experts, dtype=gate.dtype)
                   * topw[..., None], axis=-2)
    h = jnp.einsum("btd,edf->btef", x, lp["w1"].astype(x.dtype))
    g = jnp.einsum("btd,edf->btef", x, lp["w3"].astype(x.dtype))
    h = jax.nn.silu(h) * g
    out = jnp.einsum("btef,efd->bted", h, lp["w2"].astype(x.dtype))
    return jnp.einsum("bted,bte->btd", out, comb.astype(x.dtype))


def ffn(h: jax.Array, lp: Dict[str, jax.Array], impl: str = "stock") -> jax.Array:
    """SwiGLU FFN body over normed activations h [..., d].

    impl: 'stock' is the three-matmul XLA path; 'pallas' routes supported
    shapes through the one-launch fused kernel (ops/pallas/fused_ffn.py)
    and falls back to stock otherwise, mirroring attention's 'auto'.
    """
    if impl == "pallas":
        from ..ops.pallas import fused_ffn as _ff

        rows = math.prod(h.shape[:-1])
        d, f = lp["w1"].shape
        if _ff.supported(rows, d, f):
            return _ff.fused_ffn(h, lp["w1"].astype(h.dtype),
                                 lp["w3"].astype(h.dtype),
                                 lp["w2"].astype(h.dtype))
    gate = jax.nn.silu(h @ lp["w1"].astype(h.dtype)) * (h @ lp["w3"].astype(h.dtype))
    return gate @ lp["w2"].astype(h.dtype)


def block(x: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
          cos: jax.Array, sin: jax.Array, attn_impl: str = "auto",
          ffn_impl: str = "stock") -> jax.Array:
    """One transformer block; lp leaves have the layer axis already indexed."""
    B, T, d = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q = (h @ lp["wq"].astype(h.dtype)).reshape(B, T, nh, hd)
    k = (h @ lp["wk"].astype(h.dtype)).reshape(B, T, nkv, hd)
    v = (h @ lp["wv"].astype(h.dtype)).reshape(B, T, nkv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = attention(q, k, v, impl=attn_impl).reshape(B, T, nh * hd)
    x = x + o @ lp["wo"].astype(o.dtype)
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if cfg.num_experts:
        x = x + moe_mlp(h, lp, cfg)
    else:
        x = x + ffn(h, lp, impl=ffn_impl)
    return x


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            attn_impl: str = "auto", ffn_impl: str = "stock") -> jax.Array:
    """tokens [B, T] int32 → logits [B, T, vocab] (f32)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    T = tokens.shape[1]
    cos, sin = rope_cos_sin(jnp.arange(T), cfg.head_dim, cfg.rope_theta)

    def body(carry, lp):
        return block(carry, lp, cfg, cos, sin, attn_impl, ffn_impl), None

    x, _ = lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)


def loss_fn(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array,
            cfg: LlamaConfig, attn_impl: str = "auto",
            ffn_impl: str = "stock") -> jax.Array:
    """Next-token cross entropy, mean over tokens."""
    logits = forward(params, tokens, cfg, attn_impl, ffn_impl)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - true)
