"""The plain reference of Keye-VL-2.0-30B-A3B's language model (Kwai-Keye,
`model_type: KeyeVL2`), for ONE chip's share of its deployment. x is the
residual stream [S, 2048], positions t, s; every one of the layers is this
(a Qwen3-MoE block whose attention runs under DeepSeek-V3.2's lightning
indexer, `sa_config`):

    h = RMSNorm(x; attn_norm), eps 1e-6
    q = h Wq -> 32 heads of 128;  k = h Wk -> 4 heads;  v = h Wv -> 4 heads
        (no bias)
    q, k <- RMSNorm over each head's 128 values (weights q_norm, k_norm
        [128]; *assumed*: the Qwen3-MoE modelling code's per-head QK-norm),
        then rope of theta 1e7 over the whole head
    INDEX: qI_t = rope(h_t Wiq) -> 16 heads of 64, from the layer's NORMED
        INPUT (the model has no query latent: *the config forces it*);
        kI_s = rope(LayerNorm(h_s Wik; ik_norm, ik_bias)) [64] (weight AND
        bias, eps as the model's); the rope of theta 1e7 over the WHOLE
        64-wide index head and key (the config has no rope slice: *the
        config forces it*; its angles are theta^(-2i/64));
        w_t = (h_t Wiw) 16^(-1/2) 64^(-1/2) [16];
        I(t, s) = sum_j w_tj ReLU(qI_tj . kI_s) in float32;
        S_t = the 2,048 positions s <= t of largest I(t, s), all of them
        while t + 1 <= 2,048, ties to the lower position: a stable FULL
        SORT of the row's scores here
        (*assumed*: the indexer is DeepSeek-V3.2-Exp's as published;
        *departures*: without that code's fp8 cast of qI and kI and without
        its Hadamard rotation, an orthogonal map that leaves qI . kI as it
        is; `topk` counts positions, and `q_chunk_size` / `kv_chunk_size`
        512 are the tiles of the published kernel's computation, not a
        selection by blocks)
    o_t = concat over heads of softmax_{s in S_t}(128^(-1/2) q_t,head .
        k_s,kv(head)) v_s,kv(head), head j reading kv head j // 8;
        x = x + o Wo
    h' = RMSNorm(x; mlp_norm);  g = softmax over ALL 128 experts of h' Wr in
        float32 (*assumed*: Qwen3-MoE's router);  the 8 largest,
        renormalised to sum 1 (`norm_topk_prob`);
        x = x + sum_{e held} g_e W2_e(silu(W1_e h') * (W3_e h'))
        (*the chip's share*: `held` = (first, count) = 16 of the 128 by
        index; what the absent experts would add is left out, as in the
        program; the model has no shared expert)
    (*departures*: rotate-half, (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1
    sin) with x1 the first half of the head. `mrope_section` [16, 24, 24]:
    the three position axes of a TEXT token are equal, so with token ids
    alone the rope is the plain one of theta 1e7 and the sections are read
    by nobody. The vision tower and image inputs are left out: no key of
    the catalog's config describes them. `intermediate_size` 6144 is
    unused: `mlp_only_layers` [], `decoder_sparse_step` 1.)
logits = RMSNorm(x; final_norm) W_head  (untied; *the chip's share*: 18,992
    of the 151,936 rows: a smaller vocabulary)

Straightforward `jax.numpy` in float32 with no kernel, page, cache,
threshold search or batching, independent of the program under test: it
shares the layout of the weight tree alone (`embed`, `final_norm`,
`lm_head`, `blocks` = ONE dict of leaves stacked over the layers: `wq wk
wv wo q_norm k_norm attn_norm mlp_norm`, the index's `wiq wik wiw ik_norm
ik_bias`, `router` and the held experts' `w1 w3 w2` [L, E, ...]). The
index's plain functions (`rope`, `index_scores`, the stable sort in
`selected`, `seen_keys`, `attention_rows`) are `reference_dots3`'s: the
same equations, stated there.

Departures from a textbook forward pass, for memory alone (a sequence of
41,088 positions is judged beside an engine that holds 10 GB of pages): a
layer's weights are cast to float32 a layer at a time, an expert's an
expert at a time; index scores, their sort, the selection's mask and the
attention under it are made for 128 queries at a time, so no [S, S] array
exists, the attention a key-value head's group of 8 heads at a time; the
selection is kept for the rows asked for alone (`watch`), made a second
time for them; `logits_at` keeps no layer's input; the head is applied to
the positions asked for. Call everything under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .reference_dots3 import _QUERY_BLOCK, attention_rows, rope, seen_keys
from .reference_kimi import _f32, _part, _rms_norm, head_logits

FAULTS = ("no_index", "index_no_bias", "select_one_fewer", "scores_bf16",
          "index_query_unturned")


def model_kw(cfg: dict, held="file") -> dict:
    """What the equations above read of a configuration file. `held`: the
    file's share (`held_experts_first`, `num_experts` held of
    `router_width`), or None for the uncut layer."""
    if held == "file":
        held = (None if cfg["num_experts"] == cfg["router_width"] else
                (cfg["held_experts_first"], cfg["num_experts"]))
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or cfg["rope_scaling"].get(
            "rope_type", "default") != "default":
        raise NotImplementedError("one index key a position and the plain "
                                  "rope are what the equations state")
    return dict(
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
        index=(sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]),
        held=held)


def attention_block(x, lp, positions, watch, *, heads: int, kv_heads: int,
                    head_dim: int, theta: float, eps: float, index,
                    fault: str = ""):
    """x + the attention sub-block of one layer on rows x [S, d] (`lp`
    float32). `fault` seeds one of the mistakes the checks must catch
    (`FAULTS`). Returns (x, the keys the rows `watch` [n] attended over
    [n, S] bool)."""
    S, G = x.shape[0], heads // kv_heads
    ih, idim, topk = index
    h = _rms_norm(x, lp["attn_norm"], eps)
    per_head = lambda a, n, w: rope(
        _rms_norm(a.reshape(S, n, head_dim), w, eps), positions, theta)
    q = per_head(h @ lp["wq"], heads, lp["q_norm"])
    k = per_head(h @ lp["wk"], kv_heads, lp["k_norm"])
    v = (h @ lp["wv"]).reshape(S, kv_heads, head_dim)
    selection = None
    if fault != "no_index":
        qi = (h @ lp["wiq"]).reshape(S, ih, idim)
        if fault != "index_query_unturned":
            qi = rope(qi, positions, theta)
        ki = h @ lp["wik"]
        ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
        ki = ki * lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True) + eps)
        ki = ki * lp["ik_norm"]
        if fault != "index_no_bias":
            ki = ki + lp["ik_bias"]
        ki = rope(ki[:, None], positions, theta)[:, 0]
        w = (h @ lp["wiw"]) * (ih ** -0.5 * idim ** -0.5)
        if fault == "scores_bf16":      # the operands of I(t, s) in 16 bits
            qi, ki = (_f32(a.astype(jnp.bfloat16)) for a in (qi, ki))
        selection = (qi, ki, w, topk - (fault == "select_one_fewer"))

    def seen_by(rows):
        """The keys the rows `rows` [n] attend over [n, S]."""
        mine = selection and (qi[rows], ki, w[rows], selection[3])
        return seen_keys(positions[rows], S, 0, mine)

    def block(rows):
        seen, qb = seen_by(rows), q[rows]
        return jnp.concatenate([attention_rows(
            qb[:, g * G:(g + 1) * G],
            *(jnp.broadcast_to(a[:, g:g + 1], (S, G, head_dim))
              for a in (k, v)), seen, head_dim ** -0.5)
            for g in range(kv_heads)], axis=1).reshape(len(rows), -1)

    each = jnp.arange(S)
    if S > _QUERY_BLOCK and S % _QUERY_BLOCK == 0:
        o = lax.map(block, each.reshape(-1, _QUERY_BLOCK)).reshape(S, -1)
    else:
        o = block(each)
    return x + o @ lp["wo"], seen_by(watch)


def chosen_experts(h, lp, top_k: int):
    """(weights [T, k] renormalised to sum 1, experts [T, k]) of the
    router's float32 softmax over ALL experts."""
    g = jax.nn.softmax(h @ _f32(lp["router"]), axis=-1)
    top, idx = lax.top_k(g, top_k)
    return top / jnp.sum(top, axis=-1, keepdims=True), idx


def sparse_ffn(h, lp, *, top_k: int, held=None, place=None):
    """The routed experts held here of one layer on float32 rows h [T, d];
    `lp` holds the layer's `router` and the held experts' `w1`, `w3`, `w2`
    in whatever dtype they are served in; `held` (first, count) or None
    (every expert). With `place` the expert leaves are the stacked
    [n, E, ...] ones and layer `place`'s are read, an expert at a time."""
    w, idx = chosen_experts(h, lp, top_k)
    weight = jnp.sum(jax.nn.one_hot(idx, lp["router"].shape[-1],
                                    dtype=jnp.float32) * w[..., None],
                     axis=-2)                                     # [T, 128]
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
    return acc


_ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "attn_norm", "wiq",
         "wik", "wiw", "ik_norm", "ik_bias")
_BIG = ("w1", "w3", "w2")       # read an expert at a time


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "top_k", "index",
    "held", "stream_dtype", "fault"))
def layer(x, stack, place, watch, *, top_k: int, held, stream_dtype=None,
          fault: str = "", **attn):
    """One layer on the rows x [S, d] of one sequence at positions 0 ..
    S - 1: layer `place` (traced: one executable) of `stack`, the stacked
    leaves as served. Returns (x, the keys the rows `watch` [n] attended
    over [n, S] bool)."""
    def rounded(x):
        return x if stream_dtype is None else _f32(x.astype(stream_dtype))

    lp = {n: (w if n in _BIG else w[place]) for n, w in stack.items()}
    x, seen = attention_block(x, {n: _f32(lp[n]) for n in _ATTN},
                              jnp.arange(x.shape[0]), watch, fault=fault,
                              **attn)
    x = rounded(x)
    h = _rms_norm(x, _f32(lp["mlp_norm"]), attn["eps"])
    return rounded(x + sparse_ffn(h, lp, top_k=top_k, held=held,
                                  place=place)), seen


_embed = jax.jit(lambda params, tokens: _f32(
    jnp.take(params["embed"], tokens, axis=0)))
_head = jax.jit(lambda params, x, at, eps: head_logits(params, x[at], eps),
                static_argnames=("eps",))


def forward(params, tokens, out_positions, *, watch=None, **kw):
    """One sequence `tokens` [S] through the model: (float32 logits [n_out,
    vocab] at `out_positions` [n_out], the logits that predict the NEXT
    token of each; the residual stream going into each layer, a list of
    [S, d]; the keys the rows `watch` (None: `out_positions`) attended over
    in each layer, a list of [n, S] bool). `stream_dtype` (None, or
    jnp.bfloat16) rounds the residual stream at sub-block boundaries;
    `fault` seeds a mistake (tests)."""
    watch = out_positions if watch is None else watch
    x = _embed(params, tokens)
    streams, seen = [], []
    for place in range(params["blocks"]["wq"].shape[0]):
        streams.append(x)
        x, rows = layer(x, params["blocks"], jnp.int32(place), watch, **kw)
        seen.append(rows)
    return _head(params, x, out_positions, eps=kw["eps"]), streams, seen


def logits_at(params, tokens, out_positions, **kw):
    """`forward`'s logits alone, no layer's input kept."""
    x = _embed(params, tokens)
    for place in range(params["blocks"]["wq"].shape[0]):
        x = layer(x, params["blocks"], jnp.int32(place), out_positions,
                  **kw)[0]
    return _head(params, x, out_positions, eps=kw["eps"])


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
