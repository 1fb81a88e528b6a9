"""The plain reference of the OLMoE decoder (allenai/OLMoE-1B-7B-0125, as
the published `modeling_olmoe.py` computes it): pre-norm blocks, QK-norm,
rotary positions (rotate-half), causal attention, a top-k router over
routed SwiGLU experts, an untied head. One layer:

    h = RMSNorm(x; attn_norm)
    q = RMSNorm(h Wq; q_norm)   k = RMSNorm(h Wk; k_norm)   v = h Wv
        (the norm runs over the WHOLE projected vector, before the split
        into heads)
    q, k = rope(q), rope(k);   x = x + causal_attention(q, k, v) Wo
    h = RMSNorm(x; mlp_norm)
    p = softmax(h Wr) in float32 over all experts;  (w, e) = top_k(p)
        w is renormalised to sum to one only with `norm_topk_prob`
    x = x + sum_j w_j * (silu(h W1[e_j]) * (h W3[e_j])) W2[e_j]

Straightforward `jax.numpy` in float32 with no kernel, cache, sort or
batching, and independent of the program under test (it shares only the
layout of the weight tree: `embed`, `blocks` stacked on a leading layer
axis with `router [L, d, E]` and `w1/w3 [L, E, d, f]`, `w2 [L, E, f, d]`,
`q_norm`/`k_norm`, `final_norm`, `lm_head`).

Departures from a textbook forward pass, all for memory alone (the
reference runs beside 12.9 GiB of served weights): the experts are walked
one at a time inside a scan, every row through every expert with a weight
that is zero for the experts the row did not choose (so nothing is
sorted or gathered), and an expert's three matrices are cast to float32
inside that scan, 25 MB at a time; a block's other weights are cast one
layer at a time; the head is applied only to the positions asked for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .reference import _rms_norm, _rope


def route(h, router, *, top_k: int, norm_topk_prob: bool):
    """h [T, d], router [d, E], both float32: the [T, E] weight of every
    expert for every row, zero outside the row's top k."""
    p = jax.nn.softmax(h @ router, axis=-1)
    w, e = lax.top_k(p, top_k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, e].set(w)


def expert_block(h, lp, *, top_k: int, norm_topk_prob: bool):
    """The routed FFN of one layer on float32 rows h [T, d]; `lp` holds the
    layer's `router`, `w1`, `w3`, `w2` in whatever dtype they are served
    in."""
    f32 = lambda a: a.astype(jnp.float32)
    weight = route(h, f32(lp["router"]), top_k=top_k,
                   norm_topk_prob=norm_topk_prob)                  # [T, E]

    def one_expert(acc, xs):
        w1, w3, w2, col = xs
        y = (jax.nn.silu(h @ f32(w1)) * (h @ f32(w3))) @ f32(w2)
        return acc + col[:, None] * y, None

    acc, _ = lax.scan(one_expert, jnp.zeros_like(h),
                      (lp["w1"], lp["w3"], lp["w2"], weight.T))
    return acc


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "top_k", "norm_topk_prob",
    "qk_norm", "stream_dtype"))
def logits_at(params, tokens, out_positions, *, heads: int, kv_heads: int,
              theta: float, eps: float, top_k: int, norm_topk_prob: bool,
              qk_norm: bool = True, stream_dtype=None):
    """Float32 logits [n_out, vocab] of one sequence `tokens` [T] at
    `out_positions` [n_out] (the logits that predict the NEXT token of
    each). Call under `jax.default_matmul_precision("highest")`: on a TPU
    a float32 matmul otherwise runs in bf16 passes.

    `stream_dtype` (None, or jnp.bfloat16) rounds the residual stream to
    that dtype at every block boundary and nothing else: the run that
    tells a routing flip from a fault when the token judge reads low."""
    f32 = lambda a: a.astype(jnp.float32)
    T = tokens.shape[0]
    x = f32(jnp.take(params["embed"], tokens, axis=0))          # [T, d]
    positions = jnp.arange(T)
    causal = positions[None, :] <= positions[:, None]           # [T, S]
    experts = ("router", "w1", "w3", "w2")

    def block(x, lp):
        ep = {k: lp[k] for k in experts}
        lp = {k: f32(v) for k, v in lp.items() if k not in experts}
        hd = lp["wq"].shape[-1] // heads
        h = _rms_norm(x, lp["attn_norm"], eps)
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        if qk_norm:
            q = _rms_norm(q, lp["q_norm"], eps)
            k = _rms_norm(k, lp["k_norm"], eps)
        q = _rope(q.reshape(T, heads, hd), positions, theta)
        k = _rope(k.reshape(T, kv_heads, hd), positions, theta)
        v = v.reshape(T, kv_heads, hd)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v).reshape(T, heads * hd)
        x = x + o @ lp["wo"]
        h = _rms_norm(x, lp["mlp_norm"], eps)
        x = x + expert_block(h, ep, top_k=top_k,
                             norm_topk_prob=norm_topk_prob)
        if stream_dtype is not None:
            x = f32(x.astype(stream_dtype))
        return x, None

    x, _ = lax.scan(block, x, params["blocks"])
    x = _rms_norm(x[out_positions], f32(params["final_norm"]), eps)
    return x @ f32(params["lm_head"])
