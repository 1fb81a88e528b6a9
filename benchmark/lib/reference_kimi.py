"""The plain reference of Kimi-K2.6's language model (moonshotai,
`model_type: kimi_k2`: the DeepSeek-V3 block), for ONE chip's share of its
deployment. x is the residual stream, h = RMSNorm(x) with eps 1e-5, no
biases but the router's selection bias. Every layer's attention is
multi-head latent attention (MLA) with H = 64 heads; layer 0's FFN is
dense, layers >= 1 are sparse.

Attention, on rows x [S, d] (the EXPANDED form: keys and values a head):
    h = RMSNorm(x; attn_norm)
    c_q = RMSNorm(h Wqa; qa_norm) [1536];  q = c_q Wqb -> H x (q_nope [128]
        | q_r [64]);  q_r under rope
    (c | k_r) = h Wkva [512 | 64];  c = RMSNorm(c; kva_norm);  k_r under
        rope, ONE vector for all heads
    (k_nope_h | v_h) = c Wkvb -> H x (128 | 128)
    score_h = s (q_nope_h . k_nope_h + q_r,h . k_r), softmax in float32
        over keys j <= i;  o_h = sum_j p_j v_h,j;  x = x + concat(o) Wo
    s = 192^(-1/2) m^2, m = 0.1 mscale_all_dim ln(factor) + 1 = 1.4159
    rope: YaRN on the 64-wide slice, theta 50,000, factor 64, original
        4,096, beta_fast 32, beta_slow 1: f_i = theta^(-2i/64), i = 0..31;
        d(b) = 64 ln(4096 / (2 pi b)) / (2 ln theta); low = max(floor(
        d(32)), 0), high = min(ceil(d(1)), 63); ramp_i = clip((i - low) /
        (high - low), 0, 1); f'_i = (f_i / 64) ramp_i + f_i (1 - ramp_i);
        cos and sin times mscale / mscale_all_dim = 1
        (*departure*: rotate-half inside the slice, (x1, x2) -> (x1 cos -
        x2 sin, x2 cos + x1 sin) with x1 the first 32 values; the published
        code rotates interleaved pairs (x_2i, x_2i+1), which is this up to
        a fixed permutation of the columns of Wqb and Wkva that hold the
        slice, and with weights drawn from a seed the two are one model)
FFN:
    h = RMSNorm(x; mlp_norm)
    layer 0:   x = x + (silu(h W1) * (h W3)) W2, width 18,432
    layers>=1: g = sigmoid(h Wr) in float32 over ALL 384 experts; the 8 of
               the largest g + b (`topk_method: noaux_tc`; `n_group` =
               `topk_group` = 1: no group limit), b [384] the learned
               selection bias (*departure*: drawn from the seed like every
               weight, at the weights' scale 0.02, and then balanced by
               the cell's driver as `noaux_tc` training balances it, until
               the 384 experts carry equal load on a seeded batch; this
               reads whatever `router_bias` the weights hold);
               w = 2.827 g_top / sum(g_top) (the scores
               WITHOUT b; `norm_topk_prob`, `routed_scaling_factor`);
               x = x + sum_{j held} w_j E_{e_j}(h) + E_shared(h), every E a
               SwiGLU of width 2,048
               (*the chip's share*: of the 384 experts this chip holds
               `held` = (first, count) = 12 by index; the sum runs over a
               row's chosen experts that are held HERE and leaves out what
               the absent ones would add, in the program and here alike;
               the partial result goes on to the next layer; `held` None:
               every expert, the uncut layer)
logits = RMSNorm(x; final_norm) W_head  (untied; *the chip's share*: the
    20,480 rows of 163,840 it holds: a smaller vocabulary)
(*departure*: the MoonViT vision tower is left out; the language model
alone is served.)

Straightforward `jax.numpy` in float32 with no kernel, page, sort, cache,
absorption or batching, independent of the program under test: it shares
the layout of the weight tree alone (`embed`, `final_norm`, `lm_head`,
`blocks` = (the dense layer's stack, the sparse layers' stack) with `wqa
wqb wkva wkvb wo qa_norm kva_norm attn_norm mlp_norm`, and `w1 w3 w2`
[n, d, f] or `router` [n, d, 384], `router_bias` [n, 384], `w1 w3` [n, 12,
d, f], `w2`, `ws1 ws3 ws2`).

Departures from a textbook forward pass, for memory alone (on the chip it
runs beside 13 GB of served weights and pages): a layer's weights are cast
to float32 a layer at a time, an expert's an expert at a time, the dense
FFN's `_FFN_BLOCK` columns at a time; scores are made for `_QUERY_BLOCK`
queries at a time; the head is applied to the positions asked for,
`_HEAD_BLOCK` columns at a time. Call everything under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_QUERY_BLOCK = 128
_FFN_BLOCK = 3072
_HEAD_BLOCK = 5120
_f32 = lambda a: a.astype(jnp.float32)


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def model_kw(cfg: dict, held="file") -> dict:
    """What the equations above read of a configuration file. `held`: the
    file's share (`held_experts_first`, `n_routed_experts`), or None for
    the uncut layer (then the weights hold every expert)."""
    r = cfg["rope_scaling"]
    m = mscale(r["factor"], r["mscale_all_dim"])
    if held == "file":
        held = (None if cfg["n_routed_experts"] == cfg["router_width"] else
                (cfg["held_experts_first"], cfg["n_routed_experts"]))
    return dict(
        dense_layers=cfg["first_k_dense_replace"],
        heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], kv_rank=cfg["kv_lora_rank"],
        eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
        router_scale=float(cfg["routed_scaling_factor"]), held=held,
        scale=(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
        * m * m,
        yarn=(float(cfg["rope_theta"]), float(r["factor"]),
              int(r["original_max_position_embeddings"]),
              float(r["beta_fast"]), float(r["beta_slow"]),
              mscale(r["factor"], r["mscale"]) / m))


def _rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def inv_freq(rot: int, yarn):
    """f'_i for i = 0 .. rot / 2 - 1, as the docstring writes them."""
    theta, factor, original, beta_fast, beta_slow, _ = yarn
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / rot)

    def d(beta):
        return (rot * math.log(original / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    low = max(math.floor(d(beta_fast)), 0)
    high = min(math.ceil(d(beta_slow)), rot - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def rope(x, positions, yarn):
    """x [T, heads, rot]: rotate-half by the position's angles."""
    rot = x.shape[-1]
    angles = _f32(positions)[:, None] * inv_freq(rot, yarn)[None, :]
    cos, sin = (jnp.cos(angles) * yarn[5])[:, None], \
        (jnp.sin(angles) * yarn[5])[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def causal_attention(q_nope, q_rope, k_nope, k_rope, v, q_pos, scale: float):
    """Dense float32 attention of query rows (q_nope [T, H, n] | q_rope
    [T, H, r]) at absolute positions `q_pos` [T] over the keys (k_nope
    [S, H, n] | k_rope [S, r], one rope key for all heads) and values v
    [S, H, dv] at positions 0 .. S - 1: score_h = scale (q_nope_h .
    k_nope_h + q_rope_h . k_rope), key j is seen iff j <= q_pos.
    `_QUERY_BLOCK` queries at a time (T a multiple of it, or smaller)."""
    j = jnp.arange(k_nope.shape[0])

    def rows(args):
        qn, qr, pb = args
        s = (jnp.einsum("thd,shd->hts", qn, k_nope)
             + jnp.einsum("thd,sd->hts", qr, k_rope)) * scale
        p = jax.nn.softmax(
            jnp.where((j[None, :] <= pb[:, None])[None], s, -jnp.inf),
            axis=-1)
        return jnp.einsum("hts,shd->thd", p, v)

    T = q_nope.shape[0]
    if T <= _QUERY_BLOCK or T % _QUERY_BLOCK:
        return rows((q_nope, q_rope, q_pos))
    blocks = lambda a: a.reshape(-1, _QUERY_BLOCK, *a.shape[1:])
    out = lax.map(rows, (blocks(q_nope), blocks(q_rope), blocks(q_pos)))
    return out.reshape(T, *out.shape[2:])


def attention_block(x, lp, positions, *, heads: int, nope: int,
                    rope_dim: int, kv_rank: int, eps: float, scale: float,
                    yarn, fault: str = ""):
    """x + the attention sub-block of one layer on rows x [S, d] (`lp`
    float32), in the expanded form. `fault` seeds one of the mistakes the
    checks must catch: "no_inner_norm", "rope_on_nope", "v_from_k",
    "scale_without_m2"."""
    S = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    inner = (lambda a, g: a) if fault == "no_inner_norm" else (
        lambda a, g: _rms_norm(a, g, eps))
    q = (inner(h @ lp["wqa"], lp["qa_norm"]) @ lp["wqb"]
         ).reshape(S, heads, nope + rope_dim)
    ckr = h @ lp["wkva"]
    c, k_r = inner(ckr[:, :kv_rank], lp["kva_norm"]), ckr[:, None, kv_rank:]
    kv = (c @ lp["wkvb"]).reshape(S, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if fault == "v_from_k":
        v = kv[..., :v.shape[-1]]
    q_nope, q_rope, k_rope = q[..., :nope], q[..., nope:], k_r
    if fault == "rope_on_nope":     # the leading slice, as a partial rope
        turned = lambda a: jnp.concatenate(
            [rope(a[..., :rope_dim], positions, yarn), a[..., rope_dim:]],
            axis=-1)
        q_nope, k_nope = turned(q_nope), turned(k_nope)
    else:
        q_rope, k_rope = (rope(q_rope, positions, yarn),
                          rope(k_r, positions, yarn))
    if fault == "scale_without_m2":
        scale = (nope + rope_dim) ** -0.5
    o = causal_attention(q_nope, q_rope, k_nope, k_rope[:, 0], v, positions,
                         scale)
    return x + o.reshape(S, -1) @ lp["wo"]


def _part(w, place, start, size, axis: int):
    """w[place] (place None: w itself) cut to [start, start + size) on
    `axis`, as ONE dynamic slice of the served array: a layer of the stack
    is never copied whole."""
    if place is None:
        return lax.dynamic_slice_in_dim(w, start, size, axis=axis)
    at = [place] + [0] * (w.ndim - 1)
    sizes = [1] + list(w.shape[1:])
    at[axis + 1], sizes[axis + 1] = start, size
    return lax.dynamic_slice(w, [jnp.asarray(a, jnp.int32) for a in at],
                             sizes)[0]


def dense_ffn(h, lp, place=None):
    """(silu(h W1) * (h W3)) W2 on float32 rows, `_FFN_BLOCK` columns of
    the width at a time; `lp` in any dtype, its `w1 w3 w2` one layer's, or
    with `place` the stacked [n, ...] leaves of which layer `place` is
    read."""
    f = lp["w1"].shape[-1]
    blk = _FFN_BLOCK if f % _FFN_BLOCK == 0 else f

    def part(acc, i):
        w1, w3 = (_part(lp[n], place, i * blk, blk, 1) for n in ("w1", "w3"))
        w2 = _part(lp["w2"], place, i * blk, blk, 0)
        return acc + (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))
                      ) @ _f32(w2), None

    return lax.scan(part, jnp.zeros_like(h), jnp.arange(f // blk))[0]


def chosen_experts(h, lp, top_k: int):
    """The `top_k` experts [T, top_k] of float32 rows h, by g + b."""
    g = jax.nn.sigmoid(h @ _f32(lp["router"]))
    return lax.top_k(g + _f32(lp["router_bias"]), top_k)[1]


def sparse_ffn(h, lp, *, top_k: int, router_scale: float, held=None,
               shared: bool = True, fault: str = "", place=None):
    """The routed experts held here and the shared one of one layer on
    float32 rows h [T, d]; `lp` holds the layer's `router`, `router_bias`,
    `ws*` and the held experts' `w1`, `w3`, `w2` in whatever dtype they are
    served in; `held` (first, count) or None (every expert). `shared`
    False leaves the shared expert out (the share test counts it once);
    `fault` "bias_as_weight" weighs by g + b, "no_bias" chooses by g. With
    `place` the expert leaves `w1 w3 w2` are the stacked [n, E, ...] ones
    and layer `place`'s are read, an expert at a time."""
    g = jax.nn.sigmoid(h @ _f32(lp["router"]))
    biased = g + _f32(lp["router_bias"])
    _, idx = lax.top_k(g if fault == "no_bias" else biased, top_k)
    top = jnp.take_along_axis(biased if fault == "bias_as_weight" else g,
                              idx, axis=-1)
    w = router_scale * top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, g.shape[-1], dtype=jnp.float32)
                     * w[..., None], axis=-2)                     # [T, 384]
    if held is not None:
        weight = weight[:, held[0]:held[0] + held[1]]

    def one_expert(acc, xs):
        e, col = xs
        w1, w3, w2 = (_part(lp[n], place, e, 1, 0)[0]
                      for n in ("w1", "w3", "w2"))
        y = (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)
        return acc + col[:, None] * y, None

    acc, _ = lax.scan(one_expert, jnp.zeros_like(h),
                      (jnp.arange(weight.shape[1]), weight.T))
    if shared:
        acc = acc + dense_ffn(h, {"w1": lp["ws1"], "w3": lp["ws3"],
                                  "w2": lp["ws2"]})
    return acc


_ATTN = ("wqa", "wqb", "wkva", "wkvb", "wo", "qa_norm", "kva_norm",
         "attn_norm")
_BIG = ("w1", "w3", "w2")       # read a block or an expert at a time


def head_logits(params, x, eps: float):
    """RMSNorm and the untied head on rows x [n, d], a block of the
    vocabulary's columns at a time."""
    h = _rms_norm(x, _f32(params["final_norm"]), eps)
    w = params["lm_head"]
    blk = _HEAD_BLOCK if w.shape[1] % _HEAD_BLOCK == 0 else w.shape[1]
    return jnp.concatenate([h @ _f32(w[:, i:i + blk])
                            for i in range(0, w.shape[1], blk)], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "sparse", "heads", "nope", "rope_dim", "kv_rank", "eps", "top_k",
    "router_scale", "held", "scale", "yarn", "stream_dtype", "fault"))
def layer(x, stack, place, *, sparse: bool, top_k: int, router_scale: float,
          held, stream_dtype=None, fault: str = "", **attn):
    """One layer on the rows x [S, d] of one sequence at positions 0 ..
    S - 1: layer `place` (traced: one executable a kind of layer) of
    `stack`, its kind's stacked leaves as served."""
    def rounded(x):
        return x if stream_dtype is None else _f32(x.astype(stream_dtype))

    lp = {n: (w if n in _BIG else w[place]) for n, w in stack.items()}
    x = rounded(attention_block(
        x, {n: _f32(lp[n]) for n in _ATTN}, jnp.arange(x.shape[0]),
        fault=fault, **attn))
    h = _rms_norm(x, _f32(lp["mlp_norm"]), attn["eps"])
    if not sparse:
        return rounded(x + dense_ffn(h, lp, place))
    return rounded(x + sparse_ffn(h, lp, top_k=top_k,
                                  router_scale=router_scale, held=held,
                                  fault=fault, place=place))


_embed = jax.jit(lambda params, tokens: _f32(
    jnp.take(params["embed"], tokens, axis=0)))
_head = jax.jit(lambda params, x, at, eps: head_logits(params, x[at], eps),
                static_argnames=("eps",))


def layers_of(params, dense_layers: int):
    """(sparse?, the kind's stack, the layer's place in it) a layer, in
    order."""
    dense, sparse = params["blocks"]
    n = dense["attn_norm"].shape[0]
    assert n == dense_layers, (n, dense_layers)
    return ([(False, dense, i) for i in range(n)]
            + [(True, sparse, i)
               for i in range(sparse["attn_norm"].shape[0])])


def logits_at(params, tokens, out_positions, *, dense_layers: int,
              eps: float, **kw):
    """Float32 logits [n_out, vocab] of one sequence `tokens` [S] at
    `out_positions` [n_out] (the logits that predict the NEXT token of
    each); the rest as `model_kw` gives it. `stream_dtype` (None, or
    jnp.bfloat16) rounds the residual stream at sub-block boundaries;
    `fault` seeds a mistake (tests)."""
    x = _embed(params, tokens)
    for sparse, stack, place in layers_of(params, dense_layers):
        x = layer(x, stack, jnp.int32(place), sparse=sparse, eps=eps, **kw)
    return _head(params, x, out_positions, eps=eps)


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


def latent_attention(q, rows, past, scale: float, value_dim: int):
    """Dense float32 attention of one sequence's new query rows in the
    latent space, for the direct check of the paged read: q [this, H, W]
    at positions past .. past + this - 1 (`past` may be traced), rows
    [S, W] the cache rows of positions 0 .. S - 1; a head's output is its
    probabilities' sum over the rows' first `value_dim` values.
    `_QUERY_BLOCK` queries at a time."""
    q, rows = _f32(jnp.asarray(q)), _f32(jnp.asarray(rows))
    T = q.shape[0]
    j = jnp.arange(rows.shape[0])

    def block(args):
        qb, pb = args
        s = jnp.einsum("thw,sw->hts", qb, rows) * scale
        p = jax.nn.softmax(
            jnp.where((j[None, :] <= pb[:, None])[None], s, -jnp.inf),
            axis=-1)
        return jnp.einsum("hts,sv->thv", p, rows[:, :value_dim])

    pos = past + jnp.arange(T)
    if T <= _QUERY_BLOCK:
        return block((q, pos))
    pad = -T % _QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    pos = jnp.pad(pos, (0, pad))
    out = lax.map(block, (q.reshape(-1, _QUERY_BLOCK, *q.shape[1:]),
                          pos.reshape(-1, _QUERY_BLOCK)))
    return out.reshape(-1, *out.shape[2:])[:T]
