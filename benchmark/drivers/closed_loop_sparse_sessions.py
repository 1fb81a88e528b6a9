"""Closed-loop SESSIONS over long contexts through `PagedServingEngine`, for a
model whose every layer attends under a learned sparse index over the
heads' own keys and values and which holds a chip's share of its routed
experts (Keye-VL-2.0-30B-A3B: 32 query heads over 4 key-value heads of 128,
a 16 x 64 index keeping 2,048 positions, 16 of 128 experts held): K
clients, each with ONE context of its own (the traffic file's
`context_grid`, dealt by `order_seed`, ids from `--seed`), running sessions
of `turns` turns over it. A turn's prompt is the context, the session's
earlier new parts with the engine's own answers, and a new part whose
length comes from `prompt_grid`; after the last turn a new session over
the SAME context, which the prefix cache serves. `closed_loop_serve`'s
loop, clients and window (`lib/serve_window.run`, the one window every
closed-loop cell is judged on; `run` says which of its two books the rate
is read from). The window starts when every client has its first
token, so each context's prefill lies before it, in `setup_s`.

`correct`, all outside the window, of what the served path produced at the
published widths, against `reference_keye`:

1. requests under, across and far above `topk` (every key selected; the
   selection starts in a later chunk; a row keeps a sixth of its keys) and
   one LONGER THAN THE CROSSING of the two sparse reads
   (`long_prompt_len`: its decode rows take the GATHER, the read most of
   the window's rows take, beside the others' masked walks in the same
   ticks), then a SESSION'S NEXT TURN over the 12,000-token one (its answer
   and more ids: new rows selecting over cached pages' index keys) and a
   prompt that leaves a cached page HALF WAY (the page is copied, with its
   index keys): every generated token teacher-forced against the
   reference's full forward of its length, its logit there tying with the
   reference's best (`agreement.judge`) at
   `agreement_sparse_gqa.MIN_AGREEMENT` of the positions; the hits, the
   copy and the gathered rows must be found in the books;
2. the layer's ops at a timed tick's shapes (`max_batch` slots at contexts
   spread from under `topk` to `max_len`: a decode tick, a tick with a
   turn's 128 new rows, a tick with a chunk that fills the token budget) on
   seeded bf16 inputs: `paged_index_select`'s selected SETS against the
   reference's stable sort of float32 scores (`MIN_SELECTION`);
   `paged_layer_attention`'s read under that selection, in whichever form
   the rule gives each row, against dense float32 attention over the same
   selected keys (`agreement_blockdiff.judge_attention`); the three pools
   holding the new rows bit for bit and every other page untouched;
3. one layer's routed FFN with the held experts at a decode tick's and a
   chunk tick's rows (`agreement_moe.judge`'s largest row error, held to
   `agreement_sparse_gqa.MAX_FFN_ERROR`, which says why not to 1; padding
   rows zero; rows with no held expert exactly zero: the model has no
   shared expert);
4. every request returns exactly its `max_new_tokens` (here, and in the
   window by the loop's `failed`).

A program without an index over heads' own keys (the parent of PR 50)
fails when `keye_config` builds the config (`LlamaConfig` takes no
`index`), before any weight is made.
"""
from __future__ import annotations

import functools
import importlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L

from ..lib import (agreement, agreement_blockdiff, agreement_moe,
                   agreement_sparse_gqa, program_trace, reference_keye as R,
                   serve_window, sparse_gqa_scopes, traffic as T)
from ..lib.harness import Context, Record
from .closed_loop_serve import Loop
from .closed_loop_serve_sparse_latent import (_CHUNK_STRIDE, _in_blocks,
                                              _reference_selection,
                                              _same_array)

sparse_gqa_scopes.register()     # before any reader loads a trace

# summed over ticks (`moe_max_load` is read per tick from the step span)
STATS = ("moe_pairs", "moe_experts_hit", "moe_pairs_held", "index_keys",
         "index_pairs", "sparse_pairs_selected", "sparse_rows_dense",
         "sparse_rows_walked", "sparse_pairs_walked", "index_pages_live",
         "cow_block_copies")


def keye_config(cfg: dict, param_dtype) -> "L.LlamaConfig":
    """The program's config object from the published keys and the
    configuration file's share (`held_experts_first`, `num_experts` held
    of `router_width`): a uniform stack whose every layer carries the
    index of `sa_config`, its query from the layer's normed input."""
    sa = cfg["sa_config"]
    if (cfg["attention_bias"] or cfg["mlp_only_layers"]
            or cfg["decoder_sparse_step"] != 1 or cfg["use_sliding_window"]
            or cfg["tie_word_embeddings"] or sa["indexer_num_kv_heads"] != 1
            or cfg["rope_scaling"].get("rope_type") != "default"):
        raise NotImplementedError(
            "attention biases, dense layers among the sparse ones, a "
            "window, a tied head, more than one index key a position or a "
            "scaled rope: the program computes none of them here")
    width, held = cfg["router_width"], cfg["num_experts"]
    return L.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        num_experts=width, top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), qk_norm=True,
        qk_norm_per_head=True,
        experts_held=(cfg["held_experts_first"], held) if held < width
        else (),
        index=L.IndexSpec(sa["indexer_num_heads"], sa["indexer_head_dim"],
                          sa["topk"]),
        dtype=jnp.bfloat16, param_dtype=param_dtype)


def context_length(traffic: dict, client: int) -> int:
    """Client `client`'s context length: the grid's lengths dealt one a
    client (round again where the clients outnumber them) by the file's
    `order_seed`."""
    g = traffic["context_grid"]
    grid = list(range(g["first"], g["last"] + 1, g["step"]))
    perm = T._rng(traffic["order_seed"], 3).permutation(len(grid))
    return grid[perm[client % len(grid)]]


def context_tokens(traffic: dict, seed: int, client: int,
                   vocab_size: int) -> np.ndarray:
    """The ids of that client's context: seeded, its own (no two clients
    share a page), never 0."""
    return T._rng(seed, 4, client).integers(
        1, vocab_size, context_length(traffic, client), dtype=np.int32)


class SparseSessionsLoop(Loop):
    """The closed loop whose requests are the turns of sessions over each
    client's own context, with the engine's index, selection, page and
    expert counters and the block manager's hits in its books.

    Every context is prefilled ONCE, here, before the first turn is
    submitted (a request of the context alone and one token, run to its
    end): its pages then stand in the prefix cache, every client's first
    turn is a hit like every later one, and all clients get their first
    token within the same few ticks. Prefilled as the clients' first
    requests instead, the contexts would queue behind one another for two
    minutes while the early clients' next turns queue behind THEM, and the
    window (which starts with the last client's first token) would open on
    those turns' backlog."""

    def __init__(self, eng, ctx: Context, spans):
        self.contexts = [context_tokens(ctx.traffic, ctx.seed, c,
                                        ctx.config["vocab_size"])
                         for c in range(ctx.traffic["clients"])]
        for ids in self.contexts:
            eng.submit(ids, max_new_tokens=1)
        eng.run()
        self.pending = []       # (rid, prompt length) not yet admitted
        super().__init__(eng, ctx, spans)

    def reset_books(self):
        super().reset_books()
        self.prompt_tokens_submitted = 0
        self.blocks0 = dict(self.eng.blocks.stats)
        self.chunk_ticks = 0

    def submit(self, client):
        tr = self.ctx.traffic
        # the answer to the turn before: what the engine streamed for it
        answer = ([] if client.rid is None
                  else list(self.eng.stream(client.rid)))
        client.j += 1
        new = T.request_tokens(tr, self.ctx.seed, client.index, client.j,
                               self.ctx.config["vocab_size"])
        if client.j % tr["turns"] == 0:
            client.history = [self.contexts[client.index]]
        else:
            client.history.append(np.asarray(answer, np.int32))
        client.history.append(new)
        tokens = np.concatenate(client.history)
        client.prompt_len, client.got = len(tokens), 0
        client.want = T.new_tokens(tr, client.index, client.j)
        client.submitted_s = time.perf_counter()
        client.rid = self.eng.submit(tokens, max_new_tokens=client.want,
                                     eos_token_id=None)
        self.by_rid[client.rid] = client
        self.pending.append((client.rid, len(tokens)))

    def tick(self):
        before = self.eng.stats["tokens_computed"]
        super().tick()
        self.count_admitted()
        self.chunk_ticks += (self.eng.stats["tokens_computed"] - before
                             > self.eng.max_batch)

    def count_admitted(self):
        """A prompt's tokens enter the books with its hits, in the tick
        that admits it (the block manager counts a hit when it allocates
        the sequence, a tick behind `submit`): a tick taken out of the
        books then takes both, and a turn admitted before the window
        brings neither into it."""
        admitted = [x for x in self.pending
                    if self.eng.blocks.has_sequence(x[0])]
        self.pending = [x for x in self.pending if x not in admitted]
        self.prompt_tokens_submitted += sum(n for _, n in admitted)

    def counters(self) -> dict:
        out = super().counters()
        out["chunk_ticks"] = self.chunk_ticks
        out["prompt_tokens_submitted"] = self.prompt_tokens_submitted
        out["prefix_hit_tokens"] = (
            self.eng.blocks.stats["prefix_hit_tokens"]
            - self.blocks0["prefix_hit_tokens"])
        for name in STATS:
            out[name] = self.eng.stats[name] - self.stats0[name]
        return out


def check_tokens(eng, cfg: dict, params, seed: int, **fault):
    """Part 1 (and 4). `fault` goes to the reference: the tests run it
    under the mistakes the check must catch."""
    c = cfg["correctness"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    draw = lambda n: rng.integers(1, cfg["vocab_size"], n, dtype=np.int32)
    prompts = [draw(n) for n in c["prompt_lens"]]
    more, other = draw(c["turn_more"]), draw(c["copy_other"])
    long = draw(c["long_prompt_len"])
    kw = R.model_kw(cfg)
    hits0 = eng.blocks.stats["prefix_hit_tokens"]
    stats0 = dict(eng.stats)
    agreed, worst, judged, each = 0.0, 0.0, 0, []
    t0 = time.perf_counter()

    def served(batch):
        rids = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in batch]
        done = {d.rid: d.output_tokens for d in eng.run()}
        return [np.asarray(done[rid], np.int32) for rid in rids]

    # the requests together (their chunks share ticks with decode rows,
    # the long one's gathered rows with the others' walks), then the next
    # turn of the last of `prompt_lens`, then the prompt that leaves one
    # of its cached pages half way
    *outs, long_out = served(prompts + [long])
    turn = np.concatenate([prompts[-1], outs[-1], more])
    half = np.concatenate([prompts[-1][:c["copy_keep"]], other])
    asked = len(prompts[-1])
    prompts += [turn, half, long]
    outs += served([turn]) + served([half]) + [long_out]
    with jax.default_matmul_precision("highest"):
        for prompt, out in zip(prompts, outs):
            width = c["long_reference_len" if prompt is long
                      else "reference_len"]
            if len(out) != c["new_tokens"]:
                return False, {"why": f"a request of {len(prompt)} returned "
                                      f"{len(out)} tokens, not "
                                      f"{c['new_tokens']}"}
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
    hits = eng.blocks.stats["prefix_hit_tokens"] - hits0
    moved = {n: eng.stats[n] - stats0[n] for n in (
        "cow_block_copies", "tokens_computed", "sparse_rows_dense",
        "sparse_rows_walked")}
    copies = moved["cow_block_copies"]
    bs, layers = cfg["engine"]["block_size"], cfg["num_hidden_layers"]
    # the next turn finds every full page of the request before it and its
    # answer but the last token's; the half-way prompt the full pages of
    # what it keeps, and the ids of the page it copies
    want_hits = ((asked + c["new_tokens"] - 1) // bs * bs + c["copy_keep"])
    # the rows that selected and did not walk (the engine counts a row a
    # layer): at the least the long request's decode rows
    gathered = (layers * moved["tokens_computed"] - moved["sparse_rows_dense"]
                - moved["sparse_rows_walked"])
    want_gathered = layers * (c["new_tokens"] - 1)
    share = agreed / judged
    found = (hits >= want_hits and copies >= 1
             and gathered >= want_gathered)
    return share >= agreement_sparse_gqa.MIN_AGREEMENT and found, {
        "positions_judged": judged, "agreement": share,
        "agreement_by_request": each, "largest_gap_over_tolerance": worst,
        "cached_hit_tokens": hits, "cached_hit_tokens_wanted": want_hits,
        "cached_page_copies": copies, "rows_gathered": gathered,
        "rows_gathered_wanted": want_gathered,
        "reference_s": time.perf_counter() - t0}


TICKS = ("decode", "turn", "chunk")


def op_case(cfg: dict, seed: int, dtype, tick: str):
    """Part 2's inputs at a timed tick's shapes: `max_batch` slots at
    contexts spread from under `topk` to max_len, each one decode row, or
    the last slot a turn's 128 new rows ("turn": the 256-row executable)
    or a chunk that leaves the token budget one padding row ("chunk").
    Seeded: the rows' qkv [tok, (H + 2 KV) hd] (as after QK-norm and rope:
    the op is handed no table), index queries qi [tok, IH, ID], head
    weights iw [tok, IH] float32 and index keys ki [tok, ID]; one-layer
    pools of keys and values [1, pages, KV, bs, hd] and of index keys
    [1, pages, 1, bs, IW] (zeros in the lanes behind ID) holding seeded
    values at every position. (One layer is what fits beside the engine's
    pages on the chip, where part 1's long request reads every layer's;
    `op_outputs` judges the LAST layer of whatever pools it is handed, and
    the CPU tests put another in front, so a read of layer 0 shows.)"""
    e, sa = cfg["engine"], cfg["sa_config"]
    B, bs, hd = e["max_batch"], e["block_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    IH, ID = sa["indexer_num_heads"], sa["indexer_head_dim"]
    IW = -(-ID // 128) * 128
    this = np.ones((B,), np.int32)
    if tick != "decode":
        this[-1] = (e["token_budget"] - B if tick == "chunk"
                    else min(128, max(e["token_budget"] // 8 - B, 2)))
    lo, hi = sa["topk"] * 3 // 4, e["max_len"] - int(this[-1])
    past = (lo + (hi - lo) * np.arange(B) // (B - 1)).astype(np.int32)
    held = -(-(past + this) // bs)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4,
                                 TICKS.index(tick)])
    pages = rng.permutation(int(held.sum())).astype(np.int32)
    tables = np.full((B, e["max_len"] // bs), -1, np.int32)
    at = 0
    for b in range(B):
        tables[b, :held[b]] = pages[at:at + held[b]]
        at += held[b]
    keys = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), TICKS.index(tick)), 8)
    tok, n = int(this.sum()), int(held.sum())
    normal = lambda k, shape, scale=1.0, dt=dtype: (
        scale * jax.random.normal(k, shape, jnp.float32)).astype(dt)
    lanes = (jnp.arange(IW) < ID).astype(jnp.float32)
    return dict(
        tick=tick, topk=sa["topk"],
        qkv=normal(keys[0], (tok, (H + 2 * KV) * hd)),
        qi=normal(keys[1], (tok, IH, ID)), ki=normal(keys[2], (tok, ID)),
        iw=normal(keys[3], (tok, IH), IH ** -0.5 * ID ** -0.5, jnp.float32),
        key_pool=normal(keys[4], (1, n, KV, bs, hd)),
        value_pool=normal(keys[5], (1, n, KV, bs, hd)),
        index_pool=(jax.random.normal(keys[6], (1, n, 1, bs, IW), jnp.float32)
                    * lanes).astype(dtype),
        tables=jnp.asarray(tables), past=jnp.asarray(past),
        this=jnp.asarray(this))


_CHECK_ROWS = 16    # query rows of one call of part 2's reference
_f32 = lambda a: a.astype(jnp.float32)


def _positions(pool, table, n: int):
    """Positions 0 .. n - 1 of one sequence out of the last layer of a
    pool [layers, pages, heads, bs, w], by its table: [n, heads, w]."""
    bs = pool.shape[3]
    rows = pool[-1][jnp.maximum(table[:-(-n // bs)], 0)]  # [p, heads, bs, w]
    return rows.transpose(0, 2, 1, 3).reshape(-1, *rows.shape[1::2])[:n]


@jax.jit
def _dense_reference(q, table, seen, kwant, vwant):
    """Dense float32 attention of a slot's rows q [n, H, hd] over the keys
    `seen` [n, S] of its sequence, S the table's width, head j reading
    key-value head j // G."""
    S = seen.shape[1]
    k, v = (_f32(_positions(p, table, S)) for p in (kwant, vwant))
    n, H, hd = q.shape
    qg = _f32(q).reshape(n, k.shape[1], -1, hd)
    s = jnp.einsum("nvgd,svd->nvgs", qg, k) * hd ** -0.5
    pr = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("nvgs,svd->nvgd", pr, v).reshape(n, H * hd)


@functools.partial(jax.jit, static_argnames=("S", "ID"))
def _reference_scores(qi, iw, table, iwant, *, S: int, ID: int):
    """The reference's float32 index scores [n, S] of a slot's rows."""
    keys = _f32(_positions(iwant, table, S))[:, 0, :ID]
    return jnp.sum(jnp.maximum(jnp.einsum("thd,sd->ths", _f32(qi), keys),
                               0.0) * iw[..., None], axis=1)


def _layer_ops(pools, g, plan, *, decode: bool, topk: int):
    """The layer's ops as the tick calls them, on the pools' last layer."""
    from paddle_tpu.ops.kernels.serving_attention import (
        paged_index_select, paged_layer_attention)
    mode = "decode" if decode else True
    layer = jnp.int32(pools["key_pool"].shape[0] - 1)
    past, this, cu, tables = (plan[n] for n in ("past", "this", "cu",
                                                "tables"))
    *select, ipool = paged_index_select(
        g["qi"], g["iw"], g["ki"], pools["index_pool"], layer, past,
        this, cu, tables, topk, use_pallas=mode)
    out, _, kpool, vpool = paged_layer_attention(
        g["qkv"], pools["key_pool"], pools["value_pool"], layer, past,
        this, cu, tables, use_pallas=mode, select=tuple(select))
    return out, kpool, vpool, ipool, select


def op_outputs(cfg: dict, case: dict, corrupt=None):
    """One `op_case` through the layer's ops as the tick calls them (the
    index key's write, the walk and the selection; the keys' and values'
    write and the read under the selection), and the same through the
    reference's. Returns a dict: `out` [rows, H * hd] of the judged rows
    and `ref`, dense float32 attention over the keys the op itself
    selected; `pools_ok` (each pool came back holding the new rows bit for
    bit and nothing else changed) and `selection`: a bool a row of the
    sequences that select, whether its selected set equals the reference's
    stable full sort of float32 scores of the same inputs. `corrupt` (a
    variant the limits must catch): a function of the case that returns
    the case the OP gets; the reference keeps the sound one. The case is
    left without its pools."""
    c = case
    given = corrupt(dict(c)) if corrupt else dict(c)
    bs, hd = cfg["engine"]["block_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ID = cfg["sa_config"]["indexer_head_dim"]
    tables, past, this = c["tables"], c["past"], c["this"]
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(this).astype(jnp.int32)])
    # where the new rows belong, by the tables alone
    this_n, cu_n, past_n = np.asarray(this), np.asarray(cu), np.asarray(past)
    tok_b = np.repeat(np.arange(len(this_n)), this_n)
    tok = len(tok_b)
    pos = past_n[tok_b] + (np.arange(tok) - cu_n[tok_b])
    page = np.asarray(tables)[tok_b, pos // bs]
    qkv3 = c["qkv"].reshape(tok, H + 2 * KV, hd)
    want = {
        "key_pool": c["key_pool"].at[-1, page, :, pos % bs].set(
            qkv3[:, H:H + KV]),
        "value_pool": c["value_pool"].at[-1, page, :, pos % bs].set(
            qkv3[:, H + KV:]),
        "index_pool": c["index_pool"].at[-1, page, 0, pos % bs, :ID].set(
            c["ki"])}
    pools = {n: given.pop(n) for n in want}
    for n in want:
        del c[n]
    plan = dict(past=past, this=this, cu=cu, tables=tables)
    pad = lambda a: jnp.pad(a, ((0, _tok_pad(cfg, tok) - tok),)
                            + ((0, 0),) * (a.ndim - 1))
    arrays = {k: pad(v) for k, v in given.items()
              if isinstance(v, jax.Array) and k not in plan}
    # the pools are given up to the op, as a tick's are
    out, kpool, vpool, ipool, select = jax.jit(functools.partial(
        _layer_ops, decode=c["tick"] == "decode", topk=c["topk"]),
        donate_argnums=(0,))(pools, arrays, plan)
    del pools, given
    res = {"pools_ok": {n: bool(_same_array(got, want[n])) for n, got in (
        ("key_pool", kpool), ("value_pool", vpool), ("index_pool", ipool))}}
    del kpool, vpool, ipool
    idx = np.asarray(select[0])[:tok]
    del select
    S = tables.shape[1] * bs
    judged, ref, same = [], [], []
    key_pos = np.arange(S)[None, :]
    q = np.asarray(qkv3[:, :H])
    qi, iw = np.asarray(c["qi"]), np.asarray(c["iw"])
    with jax.default_matmul_precision("highest"):
        for b in range(len(this_n)):
            n, ends = int(this_n[b]), int(past_n[b] + this_n[b])
            local = np.arange(0, n, _CHUNK_STRIDE if n > 1 else 1)
            r = int(cu_n[b]) + local
            row_pos = (past_n[b] + local)[:, None]
            seen = key_pos <= row_pos
            if ends > c["topk"]:
                mine = np.zeros((len(r), S), bool)
                rows_i, cols = np.nonzero(idx[r] >= 0)
                mine[rows_i, idx[r][rows_i, cols]] = True
                scores = _in_blocks(
                    lambda a, w_: _reference_scores(
                        a, w_, tables[b], want["index_pool"], S=S, ID=ID),
                    len(r), qi[r], iw[r])
                same.extend(np.all(mine == _reference_selection(
                    scores, row_pos[:, 0], c["topk"]), axis=1).tolist())
                seen = mine
            ref.append(_in_blocks(
                lambda qb, sb: _dense_reference(
                    qb, tables[b], sb, want["key_pool"], want["value_pool"]),
                len(r), q[r], seen))
            judged.append(r)
    judged = np.concatenate(judged)
    res.update(out=np.asarray(out.astype(jnp.float32))[judged],
               ref=np.concatenate(ref), selection=np.asarray(same, bool))
    return res


def _tok_pad(cfg: dict, tok: int) -> int:
    """The padded row count of the executable a tick of `tok` rows takes
    (the engine's rule: `max_batch` for a decode tick is the caller's)."""
    B, budget = cfg["engine"]["max_batch"], cfg["engine"]["token_budget"]
    eighth = (budget // 8,) if budget // 8 >= 2 * B else ()
    return next(p for p in (B, *eighth, budget) if p >= tok)


def check_ops(cfg: dict, seed: int, corrupt=None):
    """Part 2."""
    ok, notes, same = True, {}, []
    for tick in TICKS:
        res = op_outputs(cfg, op_case(cfg, seed, jnp.bfloat16, tick), corrupt)
        good, worst = agreement_blockdiff.judge_attention(res["out"],
                                                          res["ref"])
        ok = ok and good and all(res["pools_ok"].values())
        notes[tick + "_largest_error_over_tolerance"] = worst
        notes[tick + "_pages_hold_the_rows"] = res["pools_ok"]
        same.extend(res["selection"].tolist())
    share = float(np.mean(same)) if same else 1.0
    notes.update(selection_rows_judged=len(same),
                 selection_equal_share=share)
    return ok and share >= agreement_sparse_gqa.MIN_SELECTION, notes


def ffn_outputs(lcfg, params, h, valid):
    """(the program's routed FFN of layer 0 on rows h, the reference's on
    the valid rows, which valid rows have none of their experts held),
    float32 numpy."""
    n_valid, held, place = int(valid.sum()), lcfg.held, jnp.int32(0)

    def layer_of(stack):
        # as the tick hands a layer over: the expert matrices stay whole
        return {n: (w if n in ("w1", "w3", "w2") else w[0])
                for n, w in stack.items()}

    def reference(stack, h):
        lp, h = layer_of(stack), h[:n_valid].astype(jnp.float32)
        _, e = R.chosen_experts(h, lp, lcfg.top_k)
        mine = (e >= held[0]) & (e < held[0] + held[1])
        return (R.sparse_ffn(h, lp, top_k=lcfg.top_k, held=held, place=place),
                ~jnp.any(mine, axis=-1))

    out = jax.jit(lambda stack, h: L.routed_ffn_load(
        h, layer_of(stack), lcfg, valid, layer=place)[0])(params["blocks"], h)
    with jax.default_matmul_precision("highest"):
        ref, none_held = jax.jit(reference)(params["blocks"], h)
    return (np.asarray(out.astype(jnp.float32)), np.asarray(ref),
            np.asarray(none_held))


def check_layers(cfg: dict, params, lcfg, seed: int):
    """Part 3."""
    ok, notes = True, {}
    e = cfg["engine"]
    for rows, n_valid in ((e["max_batch"], e["max_batch"]),
                          (e["token_budget"], e["token_budget"] - 1)):
        key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), rows)
        h = jax.random.normal(key, (rows, cfg["hidden_size"]),
                              jnp.float32).astype(lcfg.dtype)
        out, ref, none_held = ffn_outputs(lcfg, params, h,
                                          jnp.arange(rows) < n_valid)
        _, worst = agreement_moe.judge(out[:n_valid], ref)
        quiet = not np.any(out[n_valid:])
        alone = not np.any(out[:n_valid][none_held])
        ok = (ok and worst <= agreement_sparse_gqa.MAX_FFN_ERROR and quiet
              and alone)
        notes[f"sparse_rows_{rows}"] = {
            "largest_error_over_tolerance": worst,
            "padding_rows_zero": bool(quiet),
            "rows_with_no_held_expert": int(none_held.sum()),
            "those_are_zero": bool(alone)}
    return ok, notes


def check(eng, cfg: dict, params, lcfg, seed: int):
    t = [time.perf_counter()]

    def lap():
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    ok_tokens, notes = check_tokens(eng, cfg, params, seed)
    phases = {"tokens_s": lap()}
    ok_ops, op_notes = check_ops(cfg, seed)
    phases["ops_s"] = lap()
    ok_layer, layer_notes = check_layers(cfg, params, lcfg, seed)
    phases["layers_s"] = lap()
    notes.update(op_notes, **layer_notes, experts=L.expert_form(lcfg),
                 prefix_cache=eng.engine_stats.get("prefix_cache", "on"),
                 check_phases=phases)
    return ok_tokens and ok_ops and ok_layer, notes


# not judged in this cell (`closed_loop_serve_latent.NOT_JUDGED` says why
# such readers are left in the notes): 16 clients' turns share ticks
NOT_JUDGED = ("tick_p50_ms", "serve_device_idle_share",
              "serve_idle_schedule_share", "serve_idle_prepare_share",
              "serve_idle_dispatch_share", "serve_idle_wait_share",
              "serve_idle_harvest_share", "serve_idle_submit_share",
              "serve_idle_outside_share", "tick_ahead_share",
              "tick_cache_write_share", "tick_head_sample_share",
              "tick_layer_carry_share", "tick_unscoped_share",
              "cow_copies_per_tick")


def run(ctx: Context) -> Record:
    record = serve_window.run(ctx, keye_config, check, SparseSessionsLoop)
    # The judged rate is `serve_window.run`'s own: of the ticks that no
    # pause of the whole machine fell into, as in the cells with ticks of
    # 10-30 ms (a decode tick is 20 ms here, so a pause of a tenth of a
    # second is hidden behind nothing; Kimi's and dots3's cells, whose
    # ticks are long, judge the raw window). On the chip the raw window's
    # rate spread 1.5-1.8 % over six seeds and this one 0.9-1.4 (PERF.md
    # section 6, PR 50). The raw rate stays in the notes.
    c = record.counters
    record.notes["raw_window"] = {
        "tokens_out": c["tokens_out_raw"], "elapsed_s": c["elapsed_raw_s"],
        "decode_tokens_per_s": c["tokens_out_raw"] / c["elapsed_raw_s"]}
    record.notes["window_ticks"] = {
        "decode": c["ticks"] - c["chunk_ticks"], "with_new_rows":
        c["chunk_ticks"], "turns_finished": c["requests_completed"],
        "last_ms": [round(t, 1) for t in record.samples["tick_ms"][-12:]]}
    trace = program_trace.of_record(record)
    if trace is not None:
        record.notes["scope_shares"] = program_trace.scope_shares(trace)
        record.notes["idle_shares"] = program_trace.idle_shares(trace)

    def read_of(kind, name):
        try:
            return importlib.import_module(
                f"benchmark.{kind}.{name}").read(record)
        except (ValueError, KeyError):
            return None

    read = {name: read_of("layer_metrics", name) for name in NOT_JUDGED}
    read["gap_p90_ms"] = read_of("end_to_end", "gap_p90_ms")
    read["tick_attention_share"] = program_trace.scope_share(
        record, *sparse_gqa_scopes.ATTENTION)
    read["tick_moe_share"] = program_trace.scope_share(
        record, *sparse_gqa_scopes.MOE)
    record.notes["not_judged"] = {k: float(v) for k, v in read.items()
                                  if v is not None}
    return record
