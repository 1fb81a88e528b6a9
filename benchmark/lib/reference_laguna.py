"""The plain reference of Laguna-XS.2 (poolside, `model_type: laguna`):
a stack that is not uniform. x is the residual stream, h = RMSNorm(x) with
eps 1e-6, no biases anywhere. Layer l has an attention of kind t in
{full, window} with H_t in {48, 64} query heads over 8 key-value heads of
128, and an FFN that is dense (layer 0) or sparse (layers >= 1).

Attention, on rows x [S, d]:
    h = RMSNorm(x; attn_norm)
    q = h Wq [S, H_t, 128]   k = h Wk, v = h Wv [S, 8, 128]
    g = sigmoid(h Wg) [S, H_t]
        (*assumed*: a sigmoid, one value a head, from the layer's normed
        input; config.json says `gating: true`, the sibling Laguna-S-2.1
        says "per-head", and a [2048, H_t] gate is what makes the
        parameter count come out at 33.4 B)
    rope on the first r_t * 128 values of each head of q and k, rotate-half
        inside them, the rest passes through (*assumed*: the leading
        slice, as the `partial_rotary_factor` convention has it):
      full:   r = 0.5, YaRN: theta 5e5, factor 64, original 4096,
              beta_fast 64, beta_slow 1. f_i = theta^(-2i/64), i = 0..31;
              d(b) = 64 ln(4096 / (2 pi b)) / (2 ln theta);
              low = max(floor(d(beta_fast)), 0),
              high = min(ceil(d(beta_slow)), 63);
              ramp_i = clip((i - low) / (high - low), 0, 1);
              f'_i = (f_i / 64) ramp_i + f_i (1 - ramp_i);
              cos and sin times attention_factor 1.41589 (= 0.1 ln 64 + 1);
              computed once, whatever the length
      window: r = 1, plain rope, theta 1e4
    scores q.k / sqrt(128), query head a on key-value head a // (H_t / 8),
        softmax in float32 over keys j <= i (full) or i - 511 <= j <= i
        (window: 512 keys with the query's own)
    o_head = g_head * sum_j p_j v_j;   x = x + concat(o) Wo
    (*assumed*: no QK-norm; the config names none)
FFN:
    h = RMSNorm(x; mlp_norm)
    layer 0:   x = x + (silu(h W1) * (h W3)) W2, width 8192
    layers>=1: s = sigmoid(h Wr) in float32 over all 256 experts
               (*assumed*: a sigmoid, the convention that
               `moe_routed_scaling_factor` 2.5 comes from); the 8 largest;
               w = 2.5 * s_top / sum(s_top) (*assumed*: `norm_topk_prob`
               true, as the sibling's config has the key); the weights on
               the experts' outputs (`moe_apply_router_weight_on_input`
               false):
               x = x + sum_j w_j E_{e_j}(h) + E_shared(h), every E a SwiGLU
               of width 512 (*assumed*: no gate on the shared expert and no
               selection bias: the config names neither)
logits = RMSNorm(x; final_norm) W_head   (untied, 100352)

Straightforward `jax.numpy` in float32 with no kernel, page, sort, cache
or batching, independent of the program under test: it shares the layout
of the weight tree alone (`embed`, `final_norm`, `lm_head`, and `blocks`
a tuple of stacks, one a kind of layer in order of first occurrence, with
`wq wk wv wo wg attn_norm mlp_norm`, and `w1 w3 w2` [n, d, f] of a dense
kind or `router` [n, d, E], `w1 w3` [n, E, d, f], `w2` [n, E, f, d], `ws1
ws3 ws2` of a sparse one). What a layer is comes in as `layers`, one
(stack, place in it, attention kind, heads, ffn kind) a layer, which
`layers_of` reads off a configuration file's own keys.

Departures from a textbook forward pass, all for memory alone (on the chip
it runs beside 11 GB of served weights and pages): a layer's weights are
cast to float32 one layer at a time (and a layer is one jitted call, one
executable a kind, so that the check compiles three small programs and
not one of the whole depth), an expert's three matrices one expert at a
time; the scores are made for `_QUERY_BLOCK` queries at a time (a
[64, 6144, 6144] float32 array would be 9.7 GB); the head is applied only
to the positions asked for, `_HEAD_BLOCK` columns of the vocabulary at a
time. Call everything under `jax.default_matmul_precision("highest")`: on
a TPU a float32 matmul otherwise runs in bf16 passes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_QUERY_BLOCK = 512
_HEAD_BLOCK = 12544          # 100352 / 8
_f32 = lambda a: a.astype(jnp.float32)

def layers_of(cfg: dict):
    """One (stack, place, attention, heads, ffn) a layer from the published
    keys `layer_types`, `num_attention_heads_per_layer`, `mlp_layer_types`:
    layers alike in all three are one kind, the kinds in order of first
    occurrence, a layer's place is its index among its kind."""
    out, kinds, seen = [], [], {}
    for attn, heads, ffn in zip(cfg["layer_types"],
                                cfg["num_attention_heads_per_layer"],
                                cfg["mlp_layer_types"]):
        kind = ("window" if attn == "sliding_attention" else "full", heads,
                ffn)
        if kind not in kinds:
            kinds.append(kind)
        k = kinds.index(kind)
        out.append((k, seen.get(k, 0)) + kind)
        seen[k] = seen.get(k, 0) + 1
    return tuple(out)


def rope_kw(cfg: dict):
    """(name, theta, partial, yarn) of each attention kind's rope, from the
    published `rope_parameters`; yarn is None or (factor, original,
    beta_fast, beta_slow, attention_factor)."""
    out = []
    for name, key in (("full", "full_attention"),
                      ("window", "sliding_attention")):
        r = cfg["rope_parameters"][key]
        yarn = None
        if r["rope_type"] == "yarn":
            yarn = (float(r["factor"]),
                    int(r["original_max_position_embeddings"]),
                    float(r["beta_fast"]), float(r["beta_slow"]),
                    float(r["attention_factor"]))
        out.append((name, float(r["rope_theta"]),
                    float(r["partial_rotary_factor"]), yarn))
    return tuple(out)


def model_kw(cfg: dict) -> dict:
    """What the equations above read of a configuration file, for
    `logits_at` and `generate`."""
    return dict(layers=layers_of(cfg), kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], window=cfg["sliding_window"],
                eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
                router_scale=float(cfg["moe_routed_scaling_factor"]),
                ropes=rope_kw(cfg))


def _rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def inv_freq(rot: int, theta: float, yarn):
    """f_i (plain rope) or f'_i (YaRN) for i = 0 .. rot / 2 - 1, as the
    docstring writes them."""
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / rot)
    if yarn is None:
        return f
    factor, original, beta_fast, beta_slow, _ = yarn

    def d(beta):
        return (rot * math.log(original / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    low = max(math.floor(d(beta_fast)), 0)
    high = min(math.ceil(d(beta_slow)), rot - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def rope(x, positions, theta: float, partial: float, yarn):
    """x [T, heads, hd]: rotate-half inside the first partial * hd values
    of each head by the position's angles, cos and sin times YaRN's
    attention factor; the rest of the head passes through."""
    rot = int(x.shape[-1] * partial)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq(
        rot, theta, yarn)[None, :]
    scale = 1.0 if yarn is None else yarn[4]
    cos, sin = (jnp.cos(angles) * scale)[:, None], \
        (jnp.sin(angles) * scale)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def masked_attention(q, k, v, q_pos, window: int):
    """Dense float32 attention of query rows q [T, H, hd] at absolute
    positions `q_pos` [T] over keys k / v [S, KV, hd] at positions 0 ..
    S - 1: key j is seen iff j <= q_pos and, with a window, j > q_pos -
    window. `_QUERY_BLOCK` queries at a time (T a multiple of it, or
    smaller)."""
    group = q.shape[1] // k.shape[1]
    kk, vv = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(k.shape[0])

    def rows(args):
        qb, pb = args
        see = j[None, :] <= pb[:, None]
        if window:
            see &= j[None, :] > pb[:, None] - window
        s = jnp.einsum("thd,shd->hts", qb, kk) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, vv)

    T = q.shape[0]
    if T <= _QUERY_BLOCK or T % _QUERY_BLOCK:
        return rows((q, q_pos))
    out = lax.map(rows, (q.reshape(-1, _QUERY_BLOCK, *q.shape[1:]),
                         q_pos.reshape(-1, _QUERY_BLOCK)))
    return out.reshape(q.shape)


def attention_block(x, lp, positions, *, attn: str, heads: int,
                    kv_heads: int, head_dim: int, window: int, eps: float, ropes,
                    gate: bool = True):
    """x + the attention sub-block of one layer on rows x [S, d] (`lp`
    float32). `gate` False drops g (a fault the checks must catch)."""
    S = x.shape[0]
    _, theta, partial, yarn = next(r for r in ropes if r[0] == attn)
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = rope((h @ lp["wq"]).reshape(S, heads, head_dim), positions, theta,
             partial, yarn)
    k = rope((h @ lp["wk"]).reshape(S, kv_heads, head_dim), positions, theta,
             partial, yarn)
    v = (h @ lp["wv"]).reshape(S, kv_heads, head_dim)
    o = masked_attention(q, k, v, positions,
                         window if attn == "window" else 0)
    if gate:
        o = o * jax.nn.sigmoid(h @ lp["wg"])[..., None]
    return x + o.reshape(S, heads * head_dim) @ lp["wo"]


def dense_ffn(h, lp):
    """(silu(h W1) * (h W3)) W2 on float32 rows; `lp` in any dtype."""
    return (jax.nn.silu(h @ _f32(lp["w1"])) * (h @ _f32(lp["w3"]))
            ) @ _f32(lp["w2"])


def sparse_ffn(h, lp, *, top_k: int, router_scale: float,
               shared: bool = True, score: str = "sigmoid"):
    """The routed experts and the shared one of one layer on float32 rows
    h [T, d]; `lp` holds the layer's `router`, `w1`, `w3`, `w2`, `ws1`,
    `ws3`, `ws2` in whatever dtype they are served in. `shared` False and
    `score` "softmax" are faults the checks must catch."""
    logits = h @ _f32(lp["router"])
    s = (jax.nn.sigmoid(logits) if score == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    top, idx = lax.top_k(s, top_k)
    w = router_scale * top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)
                     * w[..., None], axis=-2)                     # [T, E]

    def one_expert(acc, xs):
        w1, w3, w2, col = xs
        y = (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)
        return acc + col[:, None] * y, None

    acc, _ = lax.scan(one_expert, jnp.zeros_like(h),
                      (lp["w1"], lp["w3"], lp["w2"], weight.T))
    if shared:
        acc = acc + dense_ffn(h, {"w1": lp["ws1"], "w3": lp["ws3"],
                                  "w2": lp["ws2"]})
    return acc


_ATTN = ("wq", "wk", "wv", "wo", "wg", "attn_norm")


def head_logits(params, x, eps: float):
    """RMSNorm and the untied head on rows x [n, d], a block of the
    vocabulary's columns at a time."""
    h = _rms_norm(x, _f32(params["final_norm"]), eps)
    w = params["lm_head"]
    cols = [h @ _f32(w[:, i:i + _HEAD_BLOCK])
            for i in range(0, w.shape[1], _HEAD_BLOCK)]
    return jnp.concatenate(cols, axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "attn", "heads", "ffn", "kv_heads", "head_dim", "window", "eps", "top_k",
    "router_scale", "ropes", "stream_dtype"))
def layer(x, lp, *, attn: str, heads: int, ffn: str, kv_heads: int,
          head_dim: int, window: int, eps: float, top_k: int,
          router_scale: float, ropes, stream_dtype=None):
    """One layer on the rows x [S, d] of one sequence at positions 0 ..
    S - 1; `lp` is the layer's slice of its kind's stack, as served. One
    executable a kind of layer (three in all, however deep the stack)."""
    def rounded(x):
        return x if stream_dtype is None else _f32(x.astype(stream_dtype))

    positions = jnp.arange(x.shape[0])
    x = rounded(attention_block(
        x, {n: _f32(lp[n]) for n in _ATTN}, positions, attn=attn,
        heads=heads, kv_heads=kv_heads, head_dim=head_dim, window=window,
        eps=eps, ropes=ropes))
    h = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
    if ffn == "dense":
        return rounded(x + dense_ffn(h, lp))
    return rounded(x + sparse_ffn(h, lp, top_k=top_k,
                                  router_scale=router_scale))


_embed = jax.jit(lambda params, tokens: _f32(
    jnp.take(params["embed"], tokens, axis=0)))
_head = jax.jit(lambda params, x, at, eps: head_logits(params, x[at], eps),
                static_argnames=("eps",))


def logits_at(params, tokens, out_positions, *, layers, eps: float, **kw):
    """Float32 logits [n_out, vocab] of one sequence `tokens` [S] at
    `out_positions` [n_out] (the logits that predict the NEXT token of
    each); `layers` and the rest as `model_kw` gives them. `stream_dtype`
    (None, or jnp.bfloat16) rounds the residual stream to that dtype at
    every sub-block boundary and nothing else."""
    x = _embed(params, tokens)                                   # [S, d]
    for stack, place, attn, heads, ffn in layers:
        lp = {n: w[place] for n, w in params["blocks"][stack].items()}
        x = layer(x, lp, attn=attn, heads=heads, ffn=ffn, eps=eps, **kw)
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


def window_attention(q, k, v, past, window: int):
    """Dense float32 attention of one sequence's new rows under the
    window, for the direct check of the paged read: q [this, H, hd] at
    positions past .. past + this - 1 (`past` may be traced, so this maps
    over sequences), k / v [S, KV, hd] of which positions 0 .. past + this
    - 1 hold the sequence's keys. `window` 0 is the causal mask."""
    q, k, v = (_f32(jnp.asarray(a)) for a in (q, k, v))
    return masked_attention(q, k, v, past + jnp.arange(q.shape[0]), window)
