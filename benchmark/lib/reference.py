"""The plain reference of the Mistral/LLaMA decoder the configurations
describe: RMSNorm, rotary positions (rotate-half, as the published code),
grouped-query causal attention, SwiGLU, an untied head. Straightforward
`jax.numpy` in float32 with no kernel, cache or batching, and independent
of the program under test: it shares only the layout of the weight tree
(`embed`, `blocks` stacked on a leading layer axis, `final_norm`,
`lm_head`).

Departures from a textbook forward pass, both for memory alone: a block's
weights are cast to float32 one layer at a time inside a scan (a float32
copy of all sixteen blocks does not fit beside the served weights), and
the head is applied only to the positions asked for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, positions, theta):
    """x [T, heads, hd]: rotate the two halves of each head by the
    position's angle."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps"))
def logits_at(params, tokens, out_positions, *, heads: int, kv_heads: int,
              theta: float, eps: float):
    """Float32 logits [n_out, vocab] of one sequence `tokens` [T] at
    `out_positions` [n_out] (the logits that predict the NEXT token of
    each). Call under `jax.default_matmul_precision("highest")`: on a TPU
    a float32 matmul otherwise runs in bf16 passes."""
    f32 = lambda a: a.astype(jnp.float32)
    T = tokens.shape[0]
    x = f32(jnp.take(params["embed"], tokens, axis=0))          # [T, d]
    positions = jnp.arange(T)
    causal = positions[None, :] <= positions[:, None]           # [T, S]

    def block(x, lp):
        lp = jax.tree.map(f32, lp)
        hd = lp["wq"].shape[-1] // heads
        h = _rms_norm(x, lp["attn_norm"], eps)
        q = _rope((h @ lp["wq"]).reshape(T, heads, hd), positions, theta)
        k = _rope((h @ lp["wk"]).reshape(T, kv_heads, hd), positions, theta)
        v = (h @ lp["wv"]).reshape(T, kv_heads, hd)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v).reshape(T, heads * hd)
        x = x + o @ lp["wo"]
        h = _rms_norm(x, lp["mlp_norm"], eps)
        x = x + (jax.nn.silu(h @ lp["w1"]) * (h @ lp["w3"])) @ lp["w2"]
        return x, None

    x, _ = lax.scan(block, x, params["blocks"])
    x = _rms_norm(x[out_positions], f32(params["final_norm"]), eps)
    return x @ f32(params["lm_head"])


def loss(params, tokens, targets, **kw):
    """Mean next-token cross entropy of one sequence, from the same
    forward pass (for a training configuration's reference)."""
    T = tokens.shape[0]
    logits = logits_at(params, tokens, jnp.arange(T), **kw)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - true)
