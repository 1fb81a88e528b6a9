"""The plain reference of SDAR-30B-A3B-Chat (JetLM, `model_type: sdar_moe`):
Qwen3-MoE's block under a block-causal mask, and generation by diffusion
over blocks. Write Bd for the block length and blk(i) = i // Bd. One layer
on rows x [S, d]:

    h = RMSNorm(x; attn_norm)
    q = h Wq [S, H, hd]   k = h Wk, v = h Wv [S, KV, hd]   (no bias;
        H * hd need not equal d)
    q = RMSNorm(q; q_norm [hd])   k = RMSNorm(k; k_norm [hd])   per head,
        after the split into heads, before rope
    q, k = rope(q), rope(k)   (rotate-half, at the absolute position)
    scores q.k / sqrt(hd), query head a on key-value head a // (H / KV),
        mask M[i, j] = 1 iff blk(j) <= blk(i): full inside a block, causal
        across blocks, the prompt under the same mask; softmax in float32
    x = x + (P v) Wo
    h = RMSNorm(x; mlp_norm)
    g = softmax(h Wr) in float32 over all experts; the top_k largest,
        renormalised to sum to one (`norm_topk_prob`)
    x = x + sum_e w_e * (silu(h W1_e) * (h W3_e)) W2_e   (no shared expert)
    logits = RMSNorm(x; final_norm) W_head   (untied)

Generation (`generate`; the sampler loop of the SDAR repository's
`generate.py` as ISSUE 32's author recalls it, at temperature 0 with
remasking `low_confidence_static`): of a prompt of p tokens the first
n0 = (p // Bd) * Bd are context, the tail opens the first block as known
tokens and the block's other rows are masked (input id `mask_id`). A block
gets denoise forwards while a row is masked: the whole sequence so far and
the block x_t go through the model, every masked row proposes
x0 = argmax(logits) with confidence softmax(logits)[x0], and the
k = min(masks left, ceil(Bd / T)) masked rows of highest confidence (ties
to the lower position) take their x0. Whether a row is masked is the loop's
own state, never `id == mask_id`. Where T divides Bd this is the published
even schedule; for other T this rule is the definition. The loop returns
exactly `new_tokens` tokens; what the last block holds beyond them is
dropped. (The served path also runs one commit forward a block, which
keeps the block's keys and values; the mathematics has nothing to commit.)

Straightforward `jax.numpy` in float32 with no kernel, page, sort or
batching, independent of the program under test (it shares the layout of
the weight tree alone: `embed`, `blocks` stacked on a leading layer axis
with `q_norm`/`k_norm [L, hd]`, `router [L, d, E]`, `w1/w3 [L, E, d, f]`,
`w2 [L, E, f, d]`, `final_norm`, `lm_head`). The expert block is
`reference_olmoe.expert_block`, the same equations.

Departures from a textbook forward pass, all for memory or time alone (on
the chip it runs beside 10 GB of served weights): a layer's weights are
cast to float32 one layer at a time inside a scan, an expert's three
matrices one expert at a time; the head is applied only to the positions
asked for; and `block_logits_kv` takes the keys and values of the
positions before the block from ONE `forward_full` over the request's
final tokens instead of recomputing the prefix for each of a request's
forwards: under M they do not depend on anything behind their own block
(tests/test_sdar_paged.py shows both forms agree to float32 rounding). Call everything under `jax.default_matmul_precision("highest")`:
on a TPU a float32 matmul otherwise runs in bf16 passes.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .reference import _rms_norm, _rope
from .reference_olmoe import expert_block

_STATIC = ("block_length", "heads", "kv_heads", "head_dim", "theta", "eps",
           "top_k", "norm_topk_prob")
_EXPERTS = ("router", "w1", "w3", "w2")
_f32 = lambda a: a.astype(jnp.float32)


def _layer(x, lp, positions, keys_before, see, *, heads, kv_heads, head_dim,
           theta, eps, top_k, norm_topk_prob):
    """One block on rows x [T, d] at absolute `positions` [T]. The rows
    attend to `keys_before` (None, or (k, v) [S, KV, hd] of earlier
    positions) followed by their own keys, under the boolean mask `see`
    [T, S + T]. Returns (x, (k, v) of these rows)."""
    T = x.shape[0]
    ep = {n: lp[n] for n in _EXPERTS}
    lp = {n: _f32(w) for n, w in lp.items() if n not in _EXPERTS}
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h @ lp["wq"]).reshape(T, heads, head_dim)
    k = (h @ lp["wk"]).reshape(T, kv_heads, head_dim)
    v = (h @ lp["wv"]).reshape(T, kv_heads, head_dim)
    q = _rope(_rms_norm(q, lp["q_norm"], eps), positions, theta)
    k = _rope(_rms_norm(k, lp["k_norm"], eps), positions, theta)
    ks, vs = k, v
    if keys_before is not None:
        ks = jnp.concatenate([keys_before[0], k], axis=0)
        vs = jnp.concatenate([keys_before[1], v], axis=0)
    group = heads // kv_heads
    s = jnp.einsum("thd,shd->hts", q, jnp.repeat(ks, group, axis=1))
    s = jnp.where(see[None], s / jnp.sqrt(jnp.float32(head_dim)), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", p, jnp.repeat(vs, group, axis=1))
    x = x + o.reshape(T, heads * head_dim) @ lp["wo"]
    h = _rms_norm(x, lp["mlp_norm"], eps)
    x = x + expert_block(h, ep, top_k=top_k, norm_topk_prob=norm_topk_prob)
    return x, (k, v)


def _head(params, x, eps):
    return (_rms_norm(x, _f32(params["final_norm"]), eps)
            @ _f32(params["lm_head"]))


@functools.partial(jax.jit, static_argnames=_STATIC + ("with_kv",))
def forward_full(params, tokens, out_positions=None, *, block_length: int,
                 with_kv: bool = False, **kw):
    """Float32 logits of one sequence `tokens` [S] under the block-causal
    mask, at `out_positions` [n] (None: all S). With `with_kv`, also the
    per-layer keys and values ([L, S, KV, hd] each, after norm and rope)
    for `block_logits_kv`."""
    S = tokens.shape[0]
    x = _f32(jnp.take(params["embed"], tokens, axis=0))           # [S, d]
    positions = jnp.arange(S)
    blk = positions // block_length
    see = blk[None, :] <= blk[:, None]                            # M [S, S]

    def block(x, lp):
        x, kv = _layer(x, lp, positions, None, see, **kw)
        return x, (kv if with_kv else None)

    x, kv = lax.scan(block, x, params["blocks"])
    if out_positions is not None:
        x = x[out_positions]
    logits = _head(params, x, kw["eps"])
    return (logits, kv) if with_kv else logits


def block_logits(params, prefix, x_t, **kw):
    """Logits [Bd, V] of the block `x_t` [Bd] behind `prefix` [n0] (n0 a
    multiple of Bd): the last Bd rows of `forward_full(prefix || x_t)`."""
    tokens = jnp.concatenate([jnp.asarray(prefix, jnp.int32),
                              jnp.asarray(x_t, jnp.int32)])
    n = tokens.shape[0]
    return forward_full(params, tokens, jnp.arange(n - len(x_t), n), **kw)


@functools.partial(jax.jit, static_argnames=_STATIC)
def block_logits_kv(params, kv, x_t, start, *, block_length: int, **kw):
    """`block_logits` with the prefix's keys and values handed in: `kv`
    from `forward_full(..., with_kv=True)` over a sequence whose first
    `start` tokens are the prefix (start a multiple of Bd, traced: one
    compile serves every block of every request of that length). The
    block's rows see the keys before `start` and one another."""
    del block_length                      # a block sees all of itself
    Bd, S = x_t.shape[0], kv[0].shape[1]
    x = _f32(jnp.take(params["embed"], x_t, axis=0))
    positions = start + jnp.arange(Bd)
    see = jnp.concatenate([jnp.broadcast_to(jnp.arange(S) < start, (Bd, S)),
                           jnp.ones((Bd, Bd), bool)], axis=1)

    def block(x, layer):
        lp, k, v = layer
        return _layer(x, lp, positions, (k, v), see, **kw)[0], None

    x, _ = lax.scan(block, x, (params["blocks"], *kv))
    return _head(params, x, kw["eps"])


def propose(logits):
    """A denoise forward's proposals from float32 logits [Bd, V]: (x0 [Bd],
    conf [Bd]) with conf = softmax(logits)[x0]."""
    logits = np.asarray(logits, np.float32)
    x0 = logits.argmax(axis=-1)
    top = logits.max(axis=-1)
    conf = 1.0 / np.exp(logits - top[:, None]).sum(axis=-1)
    return x0.astype(np.int32), conf.astype(np.float32)


def transfer(masked, conf, steps: int):
    """low_confidence_static: which of the `masked` rows [Bd] take their
    proposal in this forward, the min(masks left, ceil(Bd / steps)) of
    highest `conf`, ties to the lower position. Returns bool [Bd]."""
    masked = np.asarray(masked, bool)
    quota = min(int(masked.sum()), -(-len(masked) // steps))
    order = sorted(np.nonzero(masked)[0], key=lambda r: (-conf[r], r))
    take = np.zeros(len(masked), bool)
    take[order[:quota]] = True
    return take


def generate(params, prompt, new_tokens: int, *, block_length: int,
             steps: int, mask_id: int, **kw):
    """The generation loop over `forward_full`. Returns (tokens
    [new_tokens], forwards): one dict per denoise forward with the block's
    `start`, its `ids` and `masked` flags going in, every row's `proposed`
    token and `conf`, and the rows `taken`."""
    Bd = block_length
    tokens = [int(t) for t in prompt]
    out, forwards = [], []
    while len(out) < new_tokens:
        n0 = len(tokens) // Bd * Bd
        tail = tokens[n0:]
        ids = tail + [mask_id] * (Bd - len(tail))
        masked = [False] * len(tail) + [True] * (Bd - len(tail))
        while any(masked):
            logits = block_logits(params, tokens[:n0], ids,
                                  block_length=Bd, **kw)
            x0, conf = propose(logits)
            take = transfer(masked, conf, steps)
            forwards.append(dict(start=n0, ids=list(ids), masked=list(masked),
                                 proposed=x0.tolist(), conf=conf.tolist(),
                                 taken=take.tolist()))
            for r in np.nonzero(take)[0]:
                ids[r], masked[r] = int(x0[r]), False
        out.extend(ids[len(tail):])
        tokens = tokens[:n0] + ids
    return out[:new_tokens], forwards


def block_causal_attention(q, k, v, past, block_length: int):
    """Dense float32 attention of one sequence's new rows under M, for the
    direct check of the paged read: q [this, H, hd] at positions past ..
    past + this - 1 (`past` may be traced, so this maps over sequences),
    k / v [S, KV, hd] of which positions 0 .. past + this - 1 hold the
    sequence's keys. `block_length` 0 is the causal mask (a fault the
    check must catch, not SDAR's)."""
    q, k, v = (_f32(jnp.asarray(a)) for a in (q, k, v))
    this, group = q.shape[0], q.shape[1] // k.shape[1]
    pos = past + jnp.arange(this)
    see = ((pos // block_length + 1) * block_length if block_length
           else pos + 1)
    ok = jnp.arange(k.shape[0])[None, :] < jnp.minimum(see, past + this
                                                       )[:, None]
    s = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, group, axis=1))
    s = jnp.where(ok[None], s / jnp.sqrt(jnp.float32(q.shape[-1])), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hts,shd->thd", p, jnp.repeat(v, group, axis=1))
