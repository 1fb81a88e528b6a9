"""The plain reference of dots3-note-prev's language model (dots-studio,
`model_type: dots3_note`, 288B-A17B), for ONE chip's share of its
deployment. x is the residual stream [S, 5120], h = RMSNorm(x) with eps
1e-5, no biases but the index key's LayerNorm and the router's selection
bias. Every layer's attention is multi-head latent attention (DeepSeek-V3's
block) in one of TWO kinds with their own widths, `layer_types` says which;
layer 0's FFN is dense, layers >= 1 are sparse.

Full layer (`full_attention`; H = 128, q_lora_rank 1024, kv_lora_rank 512,
nope 128, rope 64, v 128, theta 8e7, no rope scaling), the EXPANDED form:
    h = RMSNorm(x; attn_norm)
    c_q = s_q RMSNorm(h Wqa; qa_norm) [1024];  q = c_q Wqb -> H x (q_nope
        [128] | q_r [64]);  q_r under rope
    (c | k_r) = h Wkva [512 | 64];  c = s_kv RMSNorm(c; kva_norm);  k_r
        under rope, ONE vector for all heads
    s_q = (5120 / q_lora_rank)^(1/2), s_kv = (5120 / kv_lora_rank)^(1/2):
        `apply_mla_qkv_lora_rescale`, read by LongCat-Flash's convention
        (`mla_scale_q_lora`, `mla_scale_kv_lora`) (*assumed*: the catalog
        has no code beside the key)
    (k_nope_j | v_j) = c Wkvb -> H x (128 | 128)
    INDEX (DeepSeek-V3.2's lightning indexer): qI = c_q Wiq -> 64 heads of
        128, rope on the first 64 of each; kI = LayerNorm(h Wik; ik_norm,
        ik_bias) [128] (weight AND bias, eps as the model's), rope on its
        first 64; w = (h Wiw) 64^(-1/2) 128^(-1/2) [64];
        I(t, s) = sum_j w_tj ReLU(qI_tj . kI_s);
        S_t = the 2,048 positions s <= t of largest I(t, s), all of them
        while t + 1 <= 2,048, ties to the lower position: a stable FULL
        SORT of the row's scores here
        (*departures*: without the published code's fp8 cast of qI and kI
        and without its Hadamard rotation, an orthogonal map that leaves
        qI . kI as it is)
    a_j = softmax over s in S_t of 192^(-1/2) (q_nope_j . k_nope_sj + q_r,j
        . k_r,s) in float32;  o_j = sum a_js v_sj
    g = sigmoid(h Wg) [128];  o_j <- g_j o_j;  x = x + concat(o) Wo
Window layer (`sliding_attention`; H = 64, swa_q_lora_rank 1024,
swa_kv_lora_rank 1024, nope 192, rope 64, v 128, theta 50,000): the same
latent equations at its own widths and its own s_q, s_kv, scale 256^(-1/2),
over the keys t - 512 <= s <= t (`sliding_window_size` 513 with the
query's own), no index, gate [64].
    (*departure*, both kinds: rotate-half inside the 64-wide rope slices,
    (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin) with x1 the first 32
    values; interleaved pairs are this up to a fixed permutation of the
    columns that hold the slice, one model under seeded weights)
FFN:
    h = RMSNorm(x; mlp_norm)
    layer 0:   x = x + (silu(h W1) * (h W3)) W2, width 13,824
    layers>=1: g = sigmoid(h Wr) in float32 over ALL 256 experts; the 8 of
               the largest g + b (`noaux_tc`, no expert groups: the config
               has no `n_group`), b drawn from the seed and balanced by the
               cell's driver as Kimi's is; w = g_top / sum(g_top) x
               `routed_scaling_factor` 1; x = x + sum_{j held} w_j E_j(h) +
               E_shared(h), every E a SwiGLU of width 1,536
               (*the chip's share*: `held` = (first, count) = 32 of the 256
               by index, as `reference_kimi.sparse_ffn` has it, whose
               functions these are)
logits = RMSNorm(x; final_norm) W_head  (untied; *the chip's share*: 19,008
    of the 152,064 rows: a smaller vocabulary)
(*departures*: the vision and audio towers and the MTP module are left
out.)

Straightforward `jax.numpy` in float32 with no kernel, page, cache,
absorption, threshold search or batching, independent of the program under
test: it shares the layout of the weight tree alone (`embed`, `final_norm`,
`lm_head`, `blocks` = one stack a kind of layer in order of first
occurrence, a kind being (layer type, dense or sparse FFN); leaves `wqa wqb
wkva wkvb wo wg qa_norm kva_norm attn_norm mlp_norm`, a full layer's `wiq
wik wiw ik_norm ik_bias`, and the FFN's as `reference_kimi` names them).

Departures from a textbook forward pass, for memory alone: a layer's
weights are cast to float32 a layer at a time, an expert's an expert at a
time; index scores, their sort and the attention scores are made for
`_QUERY_BLOCK` queries at a time, the attention for `_HEAD_GROUP` heads at
a time (each group's output through its own rows of Wo, summed); the head
is applied to the positions asked for. Call everything under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .reference_kimi import (_f32, _rms_norm, dense_ffn, head_logits,
                             sparse_ffn)

_QUERY_BLOCK = 128
_HEAD_GROUP = 16
FAULTS = ("no_rescale", "window_without_self", "no_index", "index_no_bias",
          "select_one_fewer", "no_gate")


def kind_kw(cfg: dict, kind: str) -> tuple:
    """One kind of layer's widths, as a hashable tuple of pairs."""
    p = "" if kind == "full_attention" else "swa_"
    nope, rope = cfg[p + "qk_nope_head_dim"], cfg[p + "qk_rope_head_dim"]
    rescale = bool(cfg["apply_mla_qkv_lora_rescale"])
    d = cfg["hidden_size"]
    return tuple(dict(
        heads=cfg[p + "num_attention_heads"], nope=nope, rope_dim=rope,
        q_rank=cfg[p + "q_lora_rank"], kv_rank=cfg[p + "kv_lora_rank"],
        theta=float(cfg[p + "rope_theta"]), scale=(nope + rope) ** -0.5,
        s_q=(d / cfg[p + "q_lora_rank"]) ** 0.5 if rescale else 1.0,
        s_kv=(d / cfg[p + "kv_lora_rank"]) ** 0.5 if rescale else 1.0,
        window=0 if p == "" else cfg["sliding_window_size"],
        index=(cfg["index_n_heads"], cfg["index_head_dim"],
               cfg["index_topk"]) if p == "" else None).items())


def model_kw(cfg: dict, held="file") -> dict:
    """What the equations above read of a configuration file. `held`: the
    file's share (`held_experts_first`, `n_routed_experts` of
    `router_width`), or None for the uncut layer."""
    if held == "file":
        held = (None if cfg["n_routed_experts"] == cfg["router_width"] else
                (cfg["held_experts_first"], cfg["n_routed_experts"]))
    types = tuple(cfg["layer_types"])
    return dict(
        layer_types=types, dense_layers=cfg["first_k_dense_replace"],
        kinds=tuple((t, kind_kw(cfg, t)) for t in dict.fromkeys(types)),
        eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
        router_scale=float(cfg["routed_scaling_factor"]), held=held)


def rope(x, positions, theta: float):
    """x [T, heads, rot]: rotate-half by the position's angles."""
    rot = x.shape[-1]
    f = theta ** (-2.0 * jnp.arange(rot // 2, dtype=jnp.float32) / rot)
    angles = _f32(positions)[:, None] * f[None, :]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def index_scores(qi, ki, w):
    """I(t, s) [T, S] of index queries qi [T, IH, ID], index keys ki
    [S, ID] and head weights w [T, IH]."""
    return jnp.sum(jnp.maximum(jnp.einsum("thd,sd->ths", qi, ki), 0.0)
                   * w[..., None], axis=1)


def selected(scores, visible, topk: int):
    """S_t as a mask [T, S]: the `topk` visible keys of largest score,
    ties to the lower position, by a stable full sort."""
    T = scores.shape[0]
    order = jnp.argsort(-jnp.where(visible, scores, -jnp.inf), axis=-1,
                        stable=True)[:, :topk]
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(T)[:, None], order].set(True)
    return mask & visible


def seen_keys(q_pos, n_keys: int, window: int, index,
              window_without_self: bool = False):
    """The keys each query row attends over [T, S] bool: causal, under a
    window the last `window`, under an index (qi [T, IH, ID], ki [S, ID],
    w [T, IH], topk) the selected ones; `_QUERY_BLOCK` rows at a time."""
    j = jnp.arange(n_keys)

    def rows(args):
        pb, qi, w = args
        seen = j[None, :] <= pb[:, None]
        if window:
            first = pb - window + (2 if window_without_self else 1)
            seen = seen & (j[None, :] >= first[:, None])
        if index is not None:
            seen = selected(index_scores(qi, index[1], w), seen, index[3])
        return seen

    T = q_pos.shape[0]
    per_row = (q_pos,) + ((index[0], index[2]) if index is not None
                          else (q_pos, q_pos))
    if T <= _QUERY_BLOCK or T % _QUERY_BLOCK:
        return rows(per_row)
    blocks = lambda a: a.reshape(-1, _QUERY_BLOCK, *a.shape[1:])
    return lax.map(rows, jax.tree.map(blocks, per_row)).reshape(T, -1)


def attention_rows(q, k, v, seen, scale: float):
    """Dense float32 attention of query rows q [T, G, n + r] over keys k
    [S, G, n + r] and values v [S, G, dv], row t over the keys `seen[t]`
    alone; `_QUERY_BLOCK` queries at a time. Returns o [T, G, dv]."""
    def rows(args):
        qb, sb = args
        s = jnp.einsum("thd,shd->hts", qb, k) * scale
        p = jax.nn.softmax(jnp.where(sb[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v)

    T = q.shape[0]
    if T <= _QUERY_BLOCK or T % _QUERY_BLOCK:
        return rows((q, seen))
    blocks = lambda a: a.reshape(-1, _QUERY_BLOCK, *a.shape[1:])
    o = lax.map(rows, (blocks(q), blocks(seen)))
    return o.reshape(T, *o.shape[2:])


def attention_block(x, lp, positions, kind: tuple, eps: float,
                    fault: str = ""):
    """x + the attention sub-block of one layer on rows x [S, d] (`lp`
    float32) of the kind `kind` (`kind_kw`); the heads `_HEAD_GROUP` at a
    time, each group's output through its rows of Wo. `fault` seeds one of
    the mistakes the checks must catch (`FAULTS`). Returns (x, the keys
    each row attended over [S, S] bool)."""
    kw = dict(kind)
    S, H, nope, C = x.shape[0], kw["heads"], kw["nope"], kw["kv_rank"]
    rot, theta = kw["rope_dim"], kw["theta"]
    s_q, s_kv = ((1.0, 1.0) if fault == "no_rescale"
                 else (kw["s_q"], kw["s_kv"]))
    h = _rms_norm(x, lp["attn_norm"], eps)
    c_q = s_q * _rms_norm(h @ lp["wqa"], lp["qa_norm"], eps)
    ckr = h @ lp["wkva"]
    c = s_kv * _rms_norm(ckr[:, :C], lp["kva_norm"], eps)
    k_r = rope(ckr[:, None, C:], positions, theta)              # [S, 1, r]
    index = None
    if kw["index"] is not None and fault != "no_index":
        ih, idim, topk = kw["index"]
        turned = lambda a: jnp.concatenate(
            [rope(a[..., :rot], positions, theta), a[..., rot:]], -1)
        qi = turned((c_q @ lp["wiq"]).reshape(S, ih, idim))
        ki = h @ lp["wik"]
        ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
        ki = ki * lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True) + eps)
        ki = ki * lp["ik_norm"]
        if fault != "index_no_bias":
            ki = ki + lp["ik_bias"]
        ki = turned(ki[:, None])[:, 0]
        w = (h @ lp["wiw"]) * (ih ** -0.5 * idim ** -0.5)
        index = (qi, ki, w, topk - (fault == "select_one_fewer"))
    seen = seen_keys(positions, S, kw["window"], index,
                     window_without_self=fault == "window_without_self")
    gate = (jnp.ones((S, H), x.dtype) if fault == "no_gate"
            else jax.nn.sigmoid(h @ lp["wg"]))
    G = _HEAD_GROUP if H % _HEAD_GROUP == 0 else H
    dq, dkv = lp["wqb"].shape[1] // H, lp["wkvb"].shape[1] // H
    dv = dkv - nope

    def group(acc, g):
        cut = lambda w, per: lax.dynamic_slice_in_dim(w, g * G * per, G * per,
                                                      axis=1)
        q = (c_q @ cut(lp["wqb"], dq)).reshape(S, G, dq)
        kv = (c @ cut(lp["wkvb"], dkv)).reshape(S, G, dkv)
        q = jnp.concatenate([q[..., :nope],
                             rope(q[..., nope:], positions, theta)], -1)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_r, (S, G, rot))], -1)
        o = attention_rows(q, k, kv[..., nope:], seen, kw["scale"])
        o = o * lax.dynamic_slice_in_dim(gate, g * G, G, axis=1)[..., None]
        return acc + o.reshape(S, -1) @ lax.dynamic_slice_in_dim(
            lp["wo"], g * G * dv, G * dv, axis=0), None

    out, _ = lax.scan(group, jnp.zeros_like(x), jnp.arange(H // G))
    return x + out, seen


_ATTN = ("wqa", "wqb", "wkva", "wkvb", "wo", "wg", "qa_norm", "kva_norm",
         "attn_norm")
_INDEX = ("wiq", "wik", "wiw", "ik_norm", "ik_bias")
_BIG = ("w1", "w3", "w2")       # read a block or an expert at a time


@functools.partial(jax.jit, static_argnames=(
    "sparse", "kind", "eps", "top_k", "router_scale", "held",
    "stream_dtype", "fault"))
def layer(x, stack, place, watch, *, sparse: bool, kind: tuple, eps: float,
          top_k: int, router_scale: float, held, stream_dtype=None,
          fault: str = ""):
    """One layer on the rows x [S, d] of one sequence at positions 0 ..
    S - 1: layer `place` (traced: one executable a kind of layer) of
    `stack`, its kind's stacked leaves as served. Returns (x, the keys the
    rows `watch` [n] attended over [n, S] bool)."""
    def rounded(x):
        return x if stream_dtype is None else _f32(x.astype(stream_dtype))

    lp = {n: (w if n in _BIG else w[place]) for n, w in stack.items()}
    names = _ATTN + (_INDEX if dict(kind)["index"] is not None else ())
    x, seen = attention_block(x, {n: _f32(lp[n]) for n in names},
                              jnp.arange(x.shape[0]), kind, eps, fault)
    x = rounded(x)
    h = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
    if not sparse:
        return rounded(x + dense_ffn(h, lp, place)), seen[watch]
    return rounded(x + sparse_ffn(h, lp, top_k=top_k,
                                  router_scale=router_scale, held=held,
                                  place=place)), seen[watch]


_embed = jax.jit(lambda params, tokens: _f32(
    jnp.take(params["embed"], tokens, axis=0)))
_head = jax.jit(lambda params, x, at, eps: head_logits(params, x[at], eps),
                static_argnames=("eps",))


def layers_of(params, layer_types, dense_layers: int):
    """(layer type, sparse?, the kind's stack, the layer's place in it) a
    layer, in order; a kind is (type, sparse?), the stacks in order of
    first occurrence."""
    kinds = [(t, i >= dense_layers) for i, t in enumerate(layer_types)]
    order = list(dict.fromkeys(kinds))
    assert len(order) == len(params["blocks"]), (order, len(params["blocks"]))
    seen = dict.fromkeys(order, 0)
    out = []
    for kind in kinds:
        out.append((*kind, params["blocks"][order.index(kind)], seen[kind]))
        seen[kind] += 1
    return out


def forward(params, tokens, out_positions, *, layer_types, dense_layers: int,
            kinds, eps: float, watch=None, fault: str = "", **kw):
    """One sequence `tokens` [S] through the model: (float32 logits [n_out,
    vocab] at `out_positions` [n_out], the logits that predict the NEXT
    token of each; the residual stream going into each layer, a list of
    [S, d]; the keys the rows `watch` (None: `out_positions`) attended over
    in each layer, a list of [n, S] bool). `stream_dtype` (None, or
    jnp.bfloat16) rounds the residual stream at sub-block boundaries;
    `fault` seeds a mistake (tests)."""
    widths = dict(kinds)
    watch = out_positions if watch is None else watch
    x = _embed(params, tokens)
    streams, seen = [], []
    for t, sparse, stack, place in layers_of(params, layer_types,
                                             dense_layers):
        streams.append(x)
        x, rows = layer(x, stack, jnp.int32(place), watch, sparse=sparse,
                        kind=widths[t], eps=eps, fault=fault, **kw)
        seen.append(rows)
    return _head(params, x, out_positions, eps=eps), streams, seen


def logits_at(params, tokens, out_positions, **kw):
    """`forward`'s logits alone."""
    return forward(params, tokens, out_positions, **kw)[0]


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
