"""The plain reference of Xing4.0-29B-A4B's language model (XingChen-AGI,
`model_type: xing4_0`): the DeepSeek-V3 block that `reference_kimi` writes
down, at this model's numbers, inside a residual stream of n = `hc_mult` = 4
lanes mixed by manifold-constrained hyper-connections (mHC,
arXiv:2512.24880), whose constants are the config's own keys.

A row's state is X = (X_1 .. X_n), each [d]. Every sub-block F (attention;
FFN: two a layer) has its own phi [n d, n^2 + 2n], b [n^2 + 2n] and alpha
[3] (pre, post, res):

    v      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)   over all n d
             values, no weight (*assumed*: the norm's weight is folded
             into phi)
    u      = v phi                                                [n^2 + 2n]
    Hpre   = sigmoid(alpha_pre u[0:n] + b[0:n])                          [n]
    Hpost  = 2 sigmoid(alpha_post u[n:2n] + b[n:2n])                     [n]
    M      = exp(clip(alpha_res mat(u[2n:]) + mat(b[2n:]),
                      mhc_h_res_clamp_min, mhc_h_res_clamp_max))      [n, n]
             (row j = lane out, column i = lane in)
    `hc_sinkhorn_iters` times:  M = M / (column sums + hc_eps)
                                M = M / (row sums + hc_eps)
             (*assumed*: columns then rows, `hc_eps` in every denominator,
             the clamp on the logits before the exp: read from the keys'
             names and the paper, no modelling code at hand)
    h      = sum_i Hpre_i X_i                        what the sub-block sees
    y      = F(RMSNorm(h; attn_norm | mlp_norm))
    X'_j   = sum_i M[j, i] X_i + Hpost_j y                        every lane

Model: X_i = embed[token] for every i (*assumed*: the lanes are copies at
the input), logits = RMSNorm(sum_i X_i; final_norm) W_head (*assumed*:
summed at the output). F is `reference_kimi`'s: latent attention with H =
32 heads, q rank 768, latent 512 + rope 64, scale 192^-1/2 m^2 with m = 0.1
ln 64 + 1, YaRN x64 from 4,096 at theta 10,000 (rotate-half inside the
slice, Kimi's note); layer 0's FFN a SwiGLU of width 9,216; layers >= 1
the top 4 of 64 experts by g + b (sigmoid scores, `noaux_tc`), weights
2 g_top / sum(g_top), beside one shared expert of width 1,024. Every
expert is held (`ep_size` 1), so no share is left out of the sum. The
multi-token-prediction layer (`num_nextn_predict_layers` 1) is left out:
the main model's logits do not read it.

Straightforward `jax.numpy` in float32, the lanes as an axis [S, n, d],
with no kernel, page, sort, cache, absorption, flat layout or unrolled
iteration, independent of the program under test: it shares the layout of
the weight tree alone (`reference_kimi`'s, with every expert in `w1 w3 w2`
[layers, 64, ...], and a sub-block's `hc_attn_*` / `hc_mlp_*`: `_phi`
[layers, n^2 + 2n, n d] (phi TRANSPOSED, as it is served), `_b` [layers,
n^2 + 2n], `_alpha` [layers, 3]). What `reference_kimi` says of memory
holds here: a layer's weights are cast to float32 a layer at a time, an
expert's an expert at a time, scores are made a block of queries at a
time, and the head is applied to the positions asked for, `_HEAD_BLOCK`
columns at a time. Call everything under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import reference_kimi as K

_HEAD_BLOCK = 8192
_f32 = K._f32
_rms_norm = K._rms_norm


def _to_bf16(a):
    """float32 values rounded to bfloat16's 8 bits of exponent and 7 of
    mantissa. Not `astype` there and back: on the TPU XLA may keep the
    excess precision of such a pair, and the rounding would not happen."""
    return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def model_kw(cfg: dict) -> dict:
    """What the equations above read of a configuration file."""
    kw = K.model_kw({**cfg, "router_width": cfg["n_routed_experts"]})
    assert kw.pop("held") is None
    kw["hyper"] = (cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"],
                   cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return kw


def coefficients(X, phi, b, alpha, *, eps: float, hyper, fault: str = ""):
    """(Hpre [S, n], Hpost [S, n], M [S, n, n]) of rows X [S, n, d] for one
    sub-block: phi [n d, n^2 + 2n], b [n^2 + 2n], alpha [3]. `fault`:
    "alpha_zero" drops the dynamic term, "one_iteration_less" stops the
    Sinkhorn one round early, "rows_first" swaps its order,
    "bf16_coefficients" rounds u and the three results to bfloat16."""
    n, iters, hc_eps, lo, hi = hyper
    S = X.shape[0]
    v = X.reshape(S, -1)
    v = v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    u = v @ phi
    rounded = _to_bf16 if fault == "bf16_coefficients" else (lambda a: a)
    u = rounded(u)
    if fault == "alpha_zero":
        alpha = jnp.zeros_like(alpha)
    pre = jax.nn.sigmoid(alpha[0] * u[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * u[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(alpha[2] * u[:, 2 * n:].reshape(S, n, n)
                         + b[2 * n:].reshape(n, n), lo, hi))
    cols, rows = (1, 2) if fault != "rows_first" else (2, 1)
    for _ in range(iters - (fault == "one_iteration_less")):
        M = M / (jnp.sum(M, axis=cols, keepdims=True) + hc_eps)
        M = M / (jnp.sum(M, axis=rows, keepdims=True) + hc_eps)
    return rounded(pre), rounded(post), rounded(M)


def hyper_block(X, lp, which: str, F, norm, *, eps: float, hyper,
                fault: str = ""):
    """X' of one sub-block: F the sub-block on normed rows [S, d], `norm`
    its norm's weight; `lp` holds float32 `hc_<which>_phi` (as served:
    transposed), `_b`, `_alpha`."""
    pre, post, M = coefficients(
        X, lp[f"hc_{which}_phi"].T, lp[f"hc_{which}_b"],
        lp[f"hc_{which}_alpha"], eps=eps, hyper=hyper, fault=fault)
    h = jnp.einsum("si,sid->sd", pre, X)
    y = F(_rms_norm(h, norm, eps))
    return jnp.einsum("sji,sid->sjd", M, X) + post[:, :, None] * y[:, None]


def attention(h, lp, positions, *, heads: int, nope: int, rope_dim: int,
              kv_rank: int, eps: float, scale: float, yarn):
    """The latent attention sub-block on NORMED rows h [S, d] (`lp`
    float32), expanded form: `reference_kimi.attention_block` without its
    norm and its residual sum, which are the lanes' here."""
    S = h.shape[0]
    q = (_rms_norm(h @ lp["wqa"], lp["qa_norm"], eps) @ lp["wqb"]
         ).reshape(S, heads, nope + rope_dim)
    ckr = h @ lp["wkva"]
    c = _rms_norm(ckr[:, :kv_rank], lp["kva_norm"], eps)
    kv = (c @ lp["wkvb"]).reshape(S, heads, -1)
    o = K.causal_attention(
        q[..., :nope], K.rope(q[..., nope:], positions, yarn),
        kv[..., :nope], K.rope(ckr[:, None, kv_rank:], positions, yarn)[:, 0],
        kv[..., nope:], positions, scale)
    return o.reshape(S, -1) @ lp["wo"]


_ATTN = K._ATTN[:-1]                       # attn_norm is the lanes' to apply
_HYPER = tuple(f"hc_{w}_{p}" for w in ("attn", "mlp")
               for p in ("phi", "b", "alpha"))


@functools.partial(jax.jit, static_argnames=(
    "sparse", "heads", "nope", "rope_dim", "kv_rank", "eps", "top_k",
    "router_scale", "scale", "yarn", "hyper", "stream_dtype", "fault"))
def layer(X, stack, place, *, sparse: bool, top_k: int, router_scale: float,
          hyper, eps: float, stream_dtype=None, fault: str = "", **attn):
    """One layer on the rows X [S, n, d] of one sequence at positions 0 ..
    S - 1: layer `place` (traced: one executable a kind of layer) of
    `stack`, its kind's stacked leaves as served."""
    def rounded(x):
        if stream_dtype is None:
            return x
        assert stream_dtype == jnp.bfloat16, stream_dtype
        return _to_bf16(x)

    lp = {n: (w if n in K._BIG else w[place]) for n, w in stack.items()}
    mix = {n: _f32(lp[n]) for n in _HYPER}
    wa = {n: _f32(lp[n]) for n in _ATTN}
    X = rounded(hyper_block(
        X, mix, "attn",
        lambda h: attention(h, wa, jnp.arange(X.shape[0]), eps=eps, **attn),
        _f32(lp["attn_norm"]), eps=eps, hyper=hyper, fault=fault))
    if sparse:
        F = lambda h: K.sparse_ffn(h, lp, top_k=top_k,
                                   router_scale=router_scale, place=place)
    else:
        F = lambda h: K.dense_ffn(h, lp, place)
    return rounded(hyper_block(X, mix, "mlp", F, _f32(lp["mlp_norm"]),
                               eps=eps, hyper=hyper, fault=fault))


def head_logits(params, x, eps: float):
    """RMSNorm and the untied head on rows x [n, d], `_HEAD_BLOCK` of the
    vocabulary's columns at a time."""
    h = _rms_norm(x, _f32(params["final_norm"]), eps)
    w = params["lm_head"]
    blk = _HEAD_BLOCK if w.shape[1] % _HEAD_BLOCK == 0 else w.shape[1]
    return jnp.concatenate([h @ _f32(w[:, i:i + blk])
                            for i in range(0, w.shape[1], blk)], axis=-1)


_embed = jax.jit(lambda params, tokens, n: jnp.repeat(_f32(
    jnp.take(params["embed"], tokens, axis=0))[:, None], n, axis=1),
    static_argnames=("n",))
_head = jax.jit(lambda params, X, at, eps: head_logits(
    params, jnp.sum(X[at], axis=1), eps), static_argnames=("eps",))


def logits_at(params, tokens, out_positions, *, dense_layers: int,
              eps: float, hyper, **kw):
    """Float32 logits [n_out, vocab] of one sequence `tokens` [S] at
    `out_positions` [n_out] (the logits that predict the NEXT token of
    each); the rest as `model_kw` gives it. `stream_dtype` (None, or
    jnp.bfloat16) rounds the lanes at sub-block boundaries; `fault` seeds
    a mistake (`coefficients`)."""
    X = _embed(params, tokens, hyper[0])
    for sparse, stack, place in K.layers_of(params, dense_layers):
        X = layer(X, stack, jnp.int32(place), sparse=sparse, eps=eps,
                  hyper=hyper, **kw)
    return _head(params, X, out_positions, eps=eps)


def generate(params, prompt, new_tokens: int, width: int, **kw):
    """Greedy decoding by the full forward over the sequence so far, padded
    to `width` (one compile; a causal model does not see the padding).
    Returns (tokens [new_tokens], logits [new_tokens, vocab])."""
    seq = np.zeros((width,), np.int32)
    seq[:len(prompt)] = prompt
    out, rows = [], []
    for i in range(new_tokens):
        at = len(prompt) + i - 1
        logits = np.asarray(logits_at(params, jnp.asarray(seq),
                                      jnp.asarray([at]), **kw))[0]
        out.append(int(logits.argmax()))
        rows.append(logits)
        seq[at + 1] = out[-1]
    return out, np.stack(rows)
