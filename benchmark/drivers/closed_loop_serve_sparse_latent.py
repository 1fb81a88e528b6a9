"""Closed-loop serving of a model whose latent layers are of two kinds, one
under a learned sparse index and one under a window, and which holds a
chip's share of its routed experts (dots3-note-prev: 2 of 5 layers attend
over the 2,048 keys a lightning indexer selects from a latent page pool
whose pages carry an index key beside each cache row, 3 of 5 over a window
of 513 in a latent pool of their own width, released behind the window; 32
of 256 routed experts held here beside a shared one) through
`PagedServingEngine` on long contexts: `closed_loop_serve`'s loop, clients
and window (`lib/serve_window.run`, the one window every cell of that
harness is judged on; the rate is the whole window's, `run` says which of
its two books), with the program's config object built from the published
keys, the engine's index, selection, window and expert counters in the
books, and `correct` judged against
`reference_dots3` in four parts, of what the served path produced at the
published widths (all outside the window, in `setup_s`):

1. every generated token of the correctness requests (a prompt under
   `index_topk`: every key selected, the dense walk; one that crosses it
   in its second chunk; a long one; all past the window's first release),
   teacher-forced against the reference's full expanded-form forward of
   `reference_len` positions: its logit there ties with the reference's
   best (`agreement.judge`) at `agreement_sparse_latent.MIN_AGREEMENT` of
   the positions;
2. the layer's ops directly, at a timed tick's shapes (`max_batch` slots
   at contexts spread from under `index_topk` to `max_len`; a decode tick,
   and a tick with a chunk that fills the token budget), on seeded bf16
   inputs, because tokens cannot see a few keys of two thousand go missing
   nor pages kept in fewer bits:
   - `paged_index_select` (the index key's page write, the index walk, the
     exact selection) against the reference's stable full sort of float32
     scores of the same inputs (every one-row sequence's row and every
     eighth row of the chunk): the share of rows whose selected SET equals
     the reference's is held to `agreement_sparse_latent.MIN_SELECTION`
     (which says why a share), and the index pool must come back holding
     the new keys bit for bit and every other page untouched;
   - `paged_latent_attention` over that selection (the sparse read, and
     the dense walk for the slots under `index_topk`) against dense
     float32 attention in the latent space over the SAME selected keys
     (`agreement_blockdiff.judge_attention`), the latent pool holding the
     new rows bit for bit;
   - the same op under the window layers' widths and window against dense
     float32 windowed attention, its pool (whose tables hold -1 behind the
     windows) holding the new rows bit for bit;
3. one sparse layer's routed FFN with the 32 held experts, as
   `closed_loop_serve_latent.check_layers` judges Kimi's 12
   (`agreement_moe.judge`, padding rows zero, rows with no held expert
   equal to the shared expert alone);
4. every request returns exactly its `max_new_tokens` (here, and in the
   window by the loop's `failed`).

A program without the sparse index (the parent of PR 43) fails here when
this module is imported (`paddle_tpu.ops.kernels.sparse_index` is not
there), before any weight is made.
"""
from __future__ import annotations

import functools
import importlib
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core import flags
from paddle_tpu.models import llama as L
from paddle_tpu.ops.kernels import sparse_index as SI
from paddle_tpu.ops.pallas import paged_attention_latent as PL

from ..lib import (agreement, agreement_blockdiff, agreement_sparse_latent,
                   program_trace, reference_dots3 as R, serve_window,
                   sparse_latent_scopes)
from ..lib.harness import Context, Record
from . import closed_loop_serve_latent as kimi
from .closed_loop_serve import Loop

sparse_latent_scopes.register()     # before any reader loads a trace

# summed over ticks (`moe_max_load` is read per tick from the step span)
STATS = ("moe_pairs", "moe_experts_hit", "moe_pairs_held",
         "attn_keys_latent", "attn_pairs_latent", "latent_pages_live",
         "attn_keys_latent_window", "attn_pairs_latent_window",
         "index_keys", "index_pairs", "sparse_pairs_selected",
         "sparse_rows_dense", "index_pages_live", "window_pages_live")

KIND = {"full_attention": "", "sliding_attention": "swa_"}


def dots3_config(cfg: dict, param_dtype) -> "L.LlamaConfig":
    """The program's config object from the published keys and the
    configuration file's share (`held_experts_first`, `n_routed_experts`
    held of `router_width`): one `LatentSpec` a kind of layer, the full
    kind with its index, the sliding kind with its window. The engine's
    window pool takes its size from `engine.window_blocks` through the
    flag the engine reads where its constructor is told nothing."""
    if (cfg["attention_bias"] or cfg["moe_layer_freq"] != 1
            or cfg["scoring_func"] != "sigmoid" or cfg["rope_scaling"]
            or cfg["topk_method"] != "noaux_tc"
            or {cfg["attention_gate_type"],
                cfg["swa_attention_gate_type"]} != {"headwise"}):
        raise NotImplementedError(
            "attention biases, another layer frequency, scoring function "
            "or selection method, a scaled rope, a gate that is not "
            "head-wise: the program computes none of them here")
    d = cfg["hidden_size"]
    rescale = bool(cfg["apply_mla_qkv_lora_rescale"])

    def widths(p, **more):
        r, c = cfg[p + "q_lora_rank"], cfg[p + "kv_lora_rank"]
        return L.LatentSpec(
            r, c, cfg[p + "qk_nope_head_dim"], cfg[p + "qk_rope_head_dim"],
            cfg[p + "v_head_dim"],
            q_scale=(d / r) ** 0.5 if rescale else 1.0,
            kv_scale=(d / c) ** 0.5 if rescale else 1.0, **more)

    latent = {
        "full_attention": widths("", index=L.IndexSpec(
            cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"])),
        "sliding_attention": widths("swa_",
                                    window=cfg["sliding_window_size"])}
    dense = cfg["first_k_dense_replace"]
    plan = tuple(
        L.LayerSpec(attn="latent", heads=cfg[KIND[t] + "num_attention_heads"],
                    rope=L.RopeSpec(theta=float(cfg[KIND[t] + "rope_theta"])),
                    ffn="dense" if i < dense else "sparse", latent=latent[t])
        for i, t in enumerate(cfg["layer_types"]))
    flags.set_flags({"serving_window_blocks":
                     cfg["engine"].get("window_blocks", 0)})
    width, held = cfg["router_width"], cfg["n_routed_experts"]
    return L.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=d,
        intermediate_size=cfg["moe_intermediate_size"],
        dense_intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=1,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"], num_experts=width,
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), layer_plan=plan,
        shared_expert_width=(cfg["n_shared_experts"]
                             * cfg["moe_intermediate_size"]),
        router_score="sigmoid",
        router_scale=float(cfg["routed_scaling_factor"]), router_bias=True,
        attn_gate=True,
        experts_held=(cfg["held_experts_first"], held) if held < width
        else (), dtype=jnp.bfloat16, param_dtype=param_dtype)


def balanced_bias(params, lcfg, seed: int, tokens: int, steps: int,
                  rate: float, seqs: int = 2):
    """The router's selection bias as `noaux_tc` training leaves it, as
    `closed_loop_serve_latent.balanced_bias` balances Kimi's (that
    function's words hold here; its stacks are Kimi's two, so this walks
    the plan's): the model runs (`llama` functions, expanded form) over
    `seqs` seeded sequences of `tokens` / `seqs` ids, and at each sparse
    layer, on that layer's own inputs, b_e moves `steps` times by `rate`
    (decaying to a tenth) toward the side that evens expert e's count among
    the rows' top-k of g + b; the layer then runs with the bias it got.
    Returns {kind: the kind's new `router_bias` [layers, experts]}."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 5])
    T = tokens // seqs
    ids = jnp.asarray(rng.integers(1, lcfg.vocab_size, (seqs, T),
                                   dtype=np.int32))
    big = ("w1", "w3", "w2")
    kinds = lcfg.kinds

    def layer(x, stack, i, kind):
        spec = kinds[kind]
        cos, sin = L.rope_table(jnp.arange(T), lcfg.rope_width(spec),
                                spec.rope)
        sparse = spec.ffn == "sparse"
        lp = {n: (w if sparse and n in big else w[i])
              for n, w in stack.items()}
        h = L.rms_norm(x, lp["attn_norm"], lcfg.rms_eps)
        x = x + L.latent_self_attention(h, lp, lcfg, spec.heads, cos, sin,
                                        spec.latent)
        h = L.rms_norm(x, lp["mlp_norm"], lcfg.rms_eps)
        if not sparse:
            return x + L.ffn(h, lp), jnp.zeros((lcfg.num_experts,))
        g = jax.nn.sigmoid(h.reshape(-1, h.shape[-1]).astype(jnp.float32)
                           @ lp["router"].astype(jnp.float32))
        even = tokens * lcfg.top_k / lcfg.num_experts

        def step(n, b):
            _, e = lax.top_k(g + b, lcfg.top_k)
            load = jnp.sum(jax.nn.one_hot(e, lcfg.num_experts,
                                          dtype=jnp.float32), axis=(0, 1))
            return b + rate * (1.0 - 0.9 * n / steps) * jnp.sign(even - load)

        b = lax.fori_loop(0, steps, step, lp["router_bias"])
        y, _ = L.routed_ffn_load(h, {**lp, "router_bias": b}, lcfg, layer=i)
        return x + y, b

    layer = jax.jit(layer, static_argnames=("kind",))
    x = jnp.take(params["embed"], ids, axis=0).astype(lcfg.dtype)
    done = [0] * len(kinds)
    bias = {k: [] for k, s in enumerate(kinds) if s.ffn == "sparse"}
    for kind in lcfg.kind_of_layer:
        x, b = layer(x, params["blocks"][kind], jnp.int32(done[kind]),
                     kind=kind)
        done[kind] += 1
        if kind in bias:
            bias[kind].append(b)
    return {k: jnp.stack(v) for k, v in bias.items()}


def balance(eng, params, lcfg, seed: int):
    """`params` with the balanced bias, handed to the engine too."""
    bias = balanced_bias(params, lcfg, seed, **kimi.BALANCE)
    params = {**params, "blocks": tuple(
        {**stack, "router_bias": bias[k]} if k in bias else stack
        for k, stack in enumerate(params["blocks"]))}
    eng.params = params
    return params


def check_tokens(eng, cfg: dict, params, seed: int, **fault):
    """Part 1 (and 4). `fault` goes to the reference: the tests run it
    under the mistakes the check must catch."""
    c = cfg["correctness"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    prompts = [rng.integers(1, cfg["vocab_size"], n, dtype=np.int32)
               for n in c["prompt_lens"]]
    rids = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    width, kw = c["reference_len"], R.model_kw(cfg)
    agreed, worst, judged, each = 0.0, 0.0, 0, []
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        for rid, prompt in zip(rids, prompts):
            out = np.asarray(done[rid], np.int32)
            if len(out) != c["new_tokens"]:
                return False, {"why": f"request {rid} returned {len(out)} "
                                      f"tokens, not {c['new_tokens']}"}
            seq = np.zeros((width,), np.int32)
            seq[:len(prompt)] = prompt
            seq[len(prompt):len(prompt) + len(out)] = out
            at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
            logits = R.logits_at(params, jnp.asarray(seq), jnp.asarray(at),
                                 **kw, **fault)
            share, gap = agreement.judge(np.asarray(logits), out)
            each.append(share)
            agreed += share * len(out)
            judged += len(out)
            worst = max(worst, gap)
    share = agreed / judged
    return share >= agreement_sparse_latent.MIN_AGREEMENT, {
        "positions_judged": judged, "agreement": share,
        "agreement_by_request": each, "largest_gap_over_tolerance": worst,
        "reference_s": time.perf_counter() - t0}


def op_case(cfg: dict, seed: int, dtype, kind: str, decode: bool):
    """Part 2's inputs for one kind of layer at a timed tick's shapes:
    `max_batch` slots at contexts spread from under `index_topk` to
    max_len, each one decode row, or (not `decode`) the last slot a chunk
    that leaves the token budget one padding row. Seeded: a token's
    queries, its cache row [tok, w] and, for the kind with an index, its
    index queries qi [tok, IH, ID], head weights iw [tok, IH] float32 and
    index key ki [tok, ID]; Wkvb's halves wk (scaled so that an absorbed
    query is of unit size) and wv; a one-layer pool [1, pages, 1, bs, W]
    whose rows hold seeded values at every position the slots hold and
    zeros in the lanes behind w, and for the index an index-key pool
    [1, pages, 1, bs, ID] over the same pages. A window kind's tables hold
    only the pages of a slot's window and chunk, -1 before them."""
    e, p = cfg["engine"], KIND[kind]
    B, bs = e["max_batch"], e["block_size"]
    H, C = cfg[p + "num_attention_heads"], cfg[p + "kv_lora_rank"]
    rope, nope = cfg[p + "qk_rope_head_dim"], cfg[p + "qk_nope_head_dim"]
    window = cfg["sliding_window_size"] if p else 0
    w = C + rope
    W = PL.padded_width(w)
    this = np.ones((B,), np.int32)
    if not decode:
        this[-1] = e["token_budget"] - B
    lo = cfg["index_topk"] * 3 // 4
    hi = e["max_len"] - int(this[-1])
    past = (lo + (hi - lo) * np.arange(B) // (B - 1)).astype(np.int32)
    first = (np.maximum(past - (window - 1), 0) // bs if window
             else np.zeros_like(past))
    last = -(-(past + this) // bs)
    held = last - first
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4,
                                 len(p), int(decode)])
    pages = rng.permutation(int(held.sum())).astype(np.int32)
    tables = np.full((B, e["max_len"] // bs), -1, np.int32)
    at = 0
    for b in range(B):
        tables[b, first[b]:last[b]] = pages[at:at + held[b]]
        at += held[b]
    keys = jax.random.split(jax.random.PRNGKey(seed & 0x7FFFFFFF), 10)
    tok = int(this.sum())
    normal = lambda k, shape, scale=1.0, dt=dtype: (
        scale * jax.random.normal(k, shape, jnp.float32)).astype(dt)
    live = (jnp.arange(W) < w).astype(jnp.float32)
    case = dict(
        kind=kind, window=window,
        q_nope=normal(keys[1], (tok, H, nope)),
        q_rope=normal(keys[2], (tok, H, rope)),
        rows=normal(keys[3], (tok, w)),
        wk=normal(keys[4], (C, H, nope), nope ** -0.5),
        wv=normal(keys[5], (C, H, cfg[p + "v_head_dim"]), C ** -0.5),
        pool=(jax.random.normal(keys[0], (1, int(held.sum()), 1, bs, W),
                                jnp.float32) * live).astype(dtype),
        tables=jnp.asarray(tables), past=jnp.asarray(past),
        this=jnp.asarray(this), scale=(nope + rope) ** -0.5)
    if not p:
        IH, ID = cfg["index_n_heads"], cfg["index_head_dim"]
        case.update(
            topk=cfg["index_topk"],
            qi=normal(keys[6], (tok, IH, ID)), ki=normal(keys[7], (tok, ID)),
            iw=normal(keys[8], (tok, IH), IH ** -0.5 * ID ** -0.5,
                      jnp.float32),
            index_pool=normal(keys[9], (1, int(held.sum()), 1, bs, ID)))
    return case


_CHUNK_STRIDE = 8   # of a chunk's rows part 2 judges every eighth
_CHECK_ROWS = 16    # query rows of one call of part 2's reference


def _sequence_rows(pool, table, n: int):
    """Positions 0 .. n - 1 of one sequence out of a one-layer pool, by its
    table (a page of -1 reads page 0: a position no row may see)."""
    bs = pool.shape[3]
    return pool[0][jnp.maximum(table[:-(-n // bs)], 0)][:, 0].reshape(
        -1, pool.shape[-1])[:n]


_f32 = lambda a: a.astype(jnp.float32)


# Part 2's programs take every array as an argument and close over none,
# and its reference takes `_CHECK_ROWS` query rows a call whatever the
# slot holds: what they compile to depends on the kind of layer alone, so
# each is compiled once a run (the reference's sort, which the chip's
# compiler takes a minute over at 33,280 keys, is numpy's on the host) and
# one seed's executables serve the next seed's run out of the compile
# cache, where the machine keeps one.

@functools.partial(jax.jit, static_argnames=("scale",))
def _dense_reference(qn, qr, table, seen, want, wk, wv, *, scale: float):
    """Dense float32 attention in the latent space of a slot's rows [n]
    over the keys `seen` [n, S] of its sequence, S the table's width."""
    S, W, C = seen.shape[1], want.shape[-1], wk.shape[0]
    rows = _f32(_sequence_rows(want, table, S))
    q = jnp.concatenate(
        [jnp.einsum("thn,chn->thc", _f32(qn), _f32(wk)), _f32(qr),
         jnp.zeros((*qr.shape[:2], W - C - qr.shape[-1]), jnp.float32)],
        axis=-1)
    s = jnp.einsum("thw,sw->hts", q, rows) * scale
    pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,sv->thv", pr, rows[:, :C])
    return jnp.einsum("thc,chv->thv", o, _f32(wv))


@functools.partial(jax.jit, static_argnames=("S",))
def _reference_scores(qi, iw, table, iwant, *, S: int):
    """The reference's float32 index scores [n, S] of a slot's rows."""
    return R.index_scores(_f32(qi), _f32(_sequence_rows(iwant, table, S)),
                          iw)


def _reference_selection(scores: np.ndarray, row_pos: np.ndarray, topk: int):
    """`reference_dots3.selected` on the host: the `topk` visible keys of
    largest score [n, S] bool, ties to the lower position, by numpy's
    stable full sort."""
    visible = np.arange(scores.shape[1])[None, :] <= row_pos[:, None]
    order = np.argsort(-np.where(visible, scores, -np.inf), axis=-1,
                       kind="stable")[:, :topk]
    mask = np.zeros(scores.shape, bool)
    mask[np.arange(len(scores))[:, None], order] = True
    return mask & visible


def _in_blocks(fn, n: int, *arrays):
    """`fn` on `_CHECK_ROWS` leading rows of `arrays` a call (the last
    block padded with its last row), the results' first n rows."""
    out = []
    for lo in range(0, n, _CHECK_ROWS):
        at = np.minimum(np.arange(lo, lo + _CHECK_ROWS), n - 1)
        out.append(np.asarray(fn(*(a[at] for a in arrays))))
    return np.concatenate(out)[:n]


def _layer_ops(pools, g, plan, *, decode: bool, topk: int, window: int,
               scale: float):
    """The layer's ops as the tick calls them (traced anew at every call:
    a variant steers the program's own functions)."""
    from paddle_tpu.ops.kernels.serving_attention import (
        paged_index_select, paged_latent_attention)
    mode = "decode" if decode else True
    past, this, cu, tables = (plan[n] for n in ("past", "this", "cu",
                                                "tables"))
    select, ipool = None, None
    if "index_pool" in pools:
        *select, ipool = paged_index_select(
            g["qi"], g["iw"], g["ki"], pools["index_pool"], jnp.int32(0),
            past, this, cu, tables, topk, use_pallas=mode)
    out, pool = paged_latent_attention(
        g["q_nope"], g["q_rope"], g["rows"], g["wk"], g["wv"],
        pools["pool"], jnp.int32(0), past, this, cu, tables, scale,
        use_pallas=mode, window=window,
        select=tuple(select) if select else None)
    return out, pool, ipool, select


_same_array = jax.jit(jnp.array_equal)


def op_outputs(cfg: dict, case: dict, decode: bool, corrupt=None,
               consume: bool = False):
    """One `op_case` through the layer's ops as the tick calls them (the
    index key's write, the walk and the selection where the kind has an
    index; the cache row's write and the read), and the same through the
    reference's. Returns a dict: `out` [tok, H * v] and `ref`, the dense
    float32 attention in the latent space over the keys the op itself
    selected (under a window: the window's); `pool_ok` (the latent pool
    came back holding the new rows bit for bit and nothing else changed),
    and for an index `index_pool_ok` and `selection`: a bool a row of the
    sequences that select, whether its selected set equals the reference's
    stable full sort of float32 scores of the same inputs. `corrupt` (a
    variant the limits must catch): a function of the case that returns
    the case the OP gets; the reference keeps the sound one. `consume`:
    the case is left without its pools, so that beside the engine only the
    pools with the rows where they belong stay on the device."""
    c = case
    given = corrupt(dict(c)) if corrupt else dict(c)
    bs = cfg["engine"]["block_size"]
    tables, past, this = c["tables"], c["past"], c["this"]
    w = c["rows"].shape[-1]
    indexed = "qi" in c
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(this).astype(jnp.int32)])

    # where the new rows belong, by the tables alone
    this_n, cu_n, past_n = np.asarray(this), np.asarray(cu), np.asarray(past)
    tok_b = np.repeat(np.arange(len(this_n)), this_n)
    pos = past_n[tok_b] + (np.arange(len(tok_b)) - cu_n[tok_b])
    page = np.asarray(tables)[tok_b, pos // bs]
    want = c["pool"].at[0, page, 0, pos % bs, :w].set(c["rows"])
    iwant = (c["index_pool"].at[0, page, 0, pos % bs].set(c["ki"])
             if indexed else None)
    # the op keeps the pools it is given: the case's own, or copies
    pools = {n: given.pop(n) if consume else jnp.copy(given.pop(n))
             for n in ("pool", "index_pool") if n in given}
    plan = dict(past=past, this=this, cu=cu, tables=tables)
    arrays = {k: v for k, v in given.items()
              if isinstance(v, jax.Array) and k not in plan}
    if consume:
        for name in pools:
            del c[name]
    # the pools are given up to the op, as a tick's are
    out, pool, ipool, select = jax.jit(functools.partial(
        _layer_ops, decode=decode, topk=c.get("topk", 0), window=c["window"],
        scale=c["scale"]), donate_argnums=(0,))(pools, arrays, plan)
    del pools, given
    res = {"pool_ok": bool(_same_array(pool, want))}
    del pool
    if indexed:
        res["index_pool_ok"] = bool(_same_array(ipool, iwant))
        idx = np.asarray(select[0])
        # each selected key's page, as the row's block table has it
        held = idx >= 0
        res["pages_ok"] = bool(np.array_equal(
            np.asarray(select[1])[:len(tok_b)][held[:len(tok_b)]],
            np.take_along_axis(np.asarray(tables)[tok_b],
                               np.maximum(idx[:len(tok_b)], 0) // bs,
                               axis=1)[held[:len(tok_b)]]))
    del ipool, select

    S = tables.shape[1] * bs        # every slot at the table's width
    # every one-row sequence's row, and of a chunk every `_CHUNK_STRIDE`-th
    # (the pools above are judged whole)
    judged, ref, same = [], [], []
    key_pos = np.arange(S)[None, :]
    q_nope, q_rope = np.asarray(c["q_nope"]), np.asarray(c["q_rope"])
    if indexed:
        qi, iw = np.asarray(c["qi"]), np.asarray(c["iw"])
    with jax.default_matmul_precision("highest"):
        for b in range(len(this_n)):
            n, ends = int(this_n[b]), int(past_n[b] + this_n[b])
            local = np.arange(0, n, _CHUNK_STRIDE if n > 1 else 1)
            r = int(cu_n[b]) + local
            row_pos = (past_n[b] + local)[:, None]
            seen = key_pos <= row_pos
            if c["window"]:
                seen &= key_pos > row_pos - c["window"]
            if indexed and ends > c["topk"]:
                mine = np.zeros((len(r), S), bool)
                rows_i, cols = np.nonzero(idx[r] >= 0)
                mine[rows_i, idx[r][rows_i, cols]] = True
                scores = _in_blocks(
                    lambda q, w_: _reference_scores(q, w_, tables[b], iwant,
                                                    S=S), len(r), qi[r], iw[r])
                theirs = _reference_selection(scores, row_pos[:, 0],
                                              c["topk"])
                same.extend(np.all(mine == theirs, axis=1).tolist())
                seen = mine
            ref.append(_in_blocks(
                lambda qn, qr, sb: _dense_reference(
                    qn, qr, tables[b], sb, want, c["wk"], c["wv"],
                    scale=c["scale"]),
                len(r), q_nope[r], q_rope[r], seen).reshape(len(r), -1))
            judged.append(r)
    judged = np.concatenate(judged)
    res.update(out=np.asarray(out.astype(jnp.float32))[judged],
               ref=np.concatenate(ref))
    if indexed:
        res["selection"] = np.asarray(same, bool)
    return res


def check_ops(cfg: dict, seed: int, corrupt=None):
    """Part 2."""
    ok, notes, same = True, {}, []
    for kind in dict.fromkeys(cfg["layer_types"]):
        for decode in (True, False):
            res = op_outputs(cfg, op_case(cfg, seed, jnp.bfloat16, kind,
                                          decode), decode, corrupt,
                             consume=True)
            good, worst = agreement_blockdiff.judge_attention(res["out"],
                                                              res["ref"])
            ok = (ok and good and res["pool_ok"]
                  and res.get("index_pool_ok", True)
                  and res.get("pages_ok", True))
            name = KIND[kind] + ("decode" if decode else "mixed")
            notes[name + "_largest_error_over_tolerance"] = worst
            notes[name + "_pages_hold_the_rows"] = res["pool_ok"]
            if "selection" in res:
                notes[name + "_index_pages_hold_the_keys"] = \
                    res["index_pool_ok"]
                same.extend(res["selection"].tolist())
    share = float(np.mean(same)) if same else 1.0
    notes.update(selection_rows_judged=len(same),
                 selection_equal_share=share)
    return ok and share >= agreement_sparse_latent.MIN_SELECTION, notes


def check(eng, cfg: dict, params, lcfg, seed: int):
    t = [time.perf_counter()]

    def lap():
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    params = balance(eng, params, lcfg, seed)
    phases = {"balance_s": lap()}
    ok_tokens, notes = check_tokens(eng, cfg, params, seed)
    phases["tokens_s"] = lap()
    ok_ops, op_notes = check_ops(cfg, seed)
    phases["ops_s"] = lap()
    ok_layer, layer_notes = kimi.check_layers(cfg, params, lcfg, seed)
    phases["layers_s"] = lap()
    notes.update(op_notes, **layer_notes, experts=L.expert_form(lcfg),
                 prefix_cache=eng.engine_stats.get("prefix_cache", "on"),
                 check_phases=phases)
    return ok_tokens and ok_ops and ok_layer, notes


class SparseLatentLoop(Loop):
    """The closed loop, with the engine's key, pair, page and expert
    counters in its books, and the count of its ticks that held a chunk (a
    tick that computed more rows than the engine has slots)."""

    def reset_books(self):
        super().reset_books()
        self.chunk_ticks = 0

    def tick(self):
        before = self.eng.stats["tokens_computed"]
        super().tick()
        self.chunk_ticks += (self.eng.stats["tokens_computed"] - before
                             > self.eng.max_batch)

    def counters(self) -> dict:
        out = super().counters()
        out["chunk_ticks"] = self.chunk_ticks
        for name in STATS:
            out[name] = self.eng.stats[name] - self.stats0[name]
        out["window_pages_released"] = self.eng.stats[
            "window_pages_released"]
        return out


def run(ctx: Context) -> Record:
    record = serve_window.run(ctx, dots3_config, check, SparseLatentLoop)
    # The judged rate is the whole window's, pauses of the machine counted
    # in, as in Kimi's cell and for its reason: a tick is 25 ms (decode) or
    # some 380 (with a chunk) of device work launched a tick ahead, a pause
    # of the host is mostly hidden behind it, and taking the tick out of
    # the books over-corrects (two runs of one tree that both held 4,059
    # tokens read 79.588 and 79.578 raw, 79.850 and 79.689 with 2 ticks
    # each left out: PERF.md section 6, PR 43). The books with the pauses
    # left out stay in the notes.
    c = record.counters
    record.notes["pauses_left_out"] = {
        "tokens_out": c["tokens_out"], "elapsed_s": c["elapsed_s"],
        "decode_tokens_per_s": c["tokens_out"] / c["elapsed_s"]}
    c["tokens_out"], c["elapsed_s"] = c["tokens_out_raw"], c["elapsed_raw_s"]
    # What the window held. Its ticks are of two very different lengths
    # for the same 31-32 tokens, and it ends on the first tick boundary
    # behind --seconds: the kinds of its ticks (of those no pause fell
    # into) and the lengths of its last ones say where in the schedule
    # that boundary fell (PERF.md section 6, PR 43: in a run of decode
    # ticks one tick more or less is 0.8 % of the window's tokens).
    record.notes["window_ticks"] = {
        "decode": c["ticks"] - c["chunk_ticks"], "chunk": c["chunk_ticks"],
        "last_ms": [round(t, 1) for t in record.samples["tick_ms"][-12:]]}
    # what the traced window held: the per-layer metrics of this cell are
    # of decode ticks and chunk ticks together, and of the stretch BEHIND
    # the judged window (the harness traces there in every cell)
    if record.trace is not None:
        trace = program_trace.of_record(record)
        kinds = [e[3].get("kind") for e in trace["program_spans"]
                 if e[0] == program_trace.STEP and "batch" in e[3]]
        record.notes["traced_ticks"] = {
            "decode": kinds.count("decode"), "chunk": kinds.count("mixed")}

    def read_of(kind, name):
        # a window of a few hundred long ticks may hold no first token,
        # a traced stretch no row that takes the dense walk: a reader with
        # no sample has nothing to say here
        try:
            return importlib.import_module(
                f"benchmark.{kind}.{name}").read(record)
        except ValueError:
            return None

    read = {name: read_of("end_to_end", name)
            for name in ("gap_p90_ms", "ttft_mean_ms")}
    for name in (*kimi.NOT_JUDGED, "latent_attention_roofline"):
        read[name] = read_of("layer_metrics", name)
    read["tick_attention_share"] = program_trace.scope_share(
        record, *sparse_latent_scopes.ATTENTION)
    read["tick_moe_share"] = program_trace.scope_share(
        record, *sparse_latent_scopes.MOE)
    record.notes["not_judged"] = {k: float(v) for k, v in read.items()
                                  if v is not None}
    return record
