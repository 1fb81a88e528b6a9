"""Closed-loop serving of a model with a layer plan (Laguna-XS.2: a leading
dense layer, window and full attention with their own head counts and
ropes, a per-head attention gate, routed experts beside a shared one)
through `PagedServingEngine` on long contexts: `closed_loop_serve`'s loop,
clients and window (over `lib/serve_window.run`), with the program's
config object built from the published per-layer lists, the engine's
window, page and expert counters in the books, and `correct` judged against
`reference_laguna` in four parts, of what the served path produced at the
published widths (all outside the window, in `setup_s`):

1. every generated token of the correctness requests (prompts that cross
   the window, YaRN's original length and several page releases; prefill
   in chunks, then decode, through both pools), teacher-forced against the
   reference's full forward of `reference_len` positions: it ties with the
   reference's best at its position (`agreement.judge`);
2. one layer's FFN of each kind (dense; sparse beside the shared expert, in
   the window kind and in the full kind) at a decode tick's rows and a
   chunk tick's rows with its valid count, through the served weights,
   against the reference in float32 (`agreement_moe.judge`), padding rows
   zero;
3. the window walk directly, because tokens cannot see sixteen keys of five
   hundred go missing: the decode launch and the mixed launch on seeded
   bf16 q, k, v at the timed shapes (32 decode rows; 31 decode rows beside
   a 481-row chunk; 8 query rows a key-value head; contexts to max_len;
   the pages behind every window out of the table) against dense float32
   attention under the window (`agreement_blockdiff.judge_attention`);
4. every request returns exactly its `max_new_tokens` (here, and in the
   window by the loop's `failed`).

A program without `LlamaConfig.layer_plan` (the parent of PR 34) fails here
with an AttributeError on `llama.LayerSpec` before any weight is made.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.core import flags
from paddle_tpu.models import llama as L
from paddle_tpu.ops.pallas import paged_attention as PA

from ..lib import (agreement, agreement_blockdiff, agreement_moe,
                   agreement_plan, laguna_scopes, program_trace,
                   reference_laguna as R, serve_window, window_math)
from ..lib.harness import Context, Record
from .closed_loop_serve import Loop

laguna_scopes.register()     # before any reader loads a trace

# summed over ticks (`moe_max_load` is read per tick from the step span;
# `window_pages_released` is the pool's own running count)
STATS = ("moe_pairs", "moe_experts_hit", "attn_keys_full",
         "attn_keys_window", "attn_keys_causal", "attn_pairs_full",
         "attn_pairs_window", "full_pages_live", "window_pages_live",
         "window_pages_released")
KIND = {published: kind for kind, published in window_math.KINDS.items()}


def rope_spec(r: dict) -> "L.RopeSpec":
    yarn = r["rope_type"] == "yarn"
    return L.RopeSpec(
        theta=float(r["rope_theta"]),
        partial=float(r["partial_rotary_factor"]),
        yarn_factor=float(r["factor"]) if yarn else 0.0,
        yarn_original=int(r["original_max_position_embeddings"])
        if yarn else 0,
        yarn_beta_fast=float(r["beta_fast"]) if yarn else 32.0,
        yarn_beta_slow=float(r["beta_slow"]) if yarn else 1.0,
        attention_factor=float(r["attention_factor"]) if yarn else 1.0)


def laguna_config(cfg: dict, param_dtype) -> "L.LlamaConfig":
    """The program's config object from the published keys and the
    configuration file's `assumed` ones (the per-head gate, the sigmoid
    router, `norm_topk_prob`). The engine's window pool takes its size
    from `engine.window_blocks` through the flag the engine reads where
    its constructor is told nothing (`lib/serve_window.run` builds the
    engine with the arguments every cell has)."""
    if cfg["moe_apply_router_weight_on_input"] or cfg["attention_bias"]:
        raise NotImplementedError(
            "router weights on the experts' input, or attention biases: "
            "the program computes neither")
    plan = tuple(
        L.LayerSpec(attn=KIND[t], heads=h,
                    rope=rope_spec(cfg["rope_parameters"][t]), ffn=f)
        for t, h, f in zip(cfg["layer_types"],
                           cfg["num_attention_heads_per_layer"],
                           cfg["mlp_layer_types"]))
    flags.set_flags({"serving_window_blocks":
                     cfg["engine"].get("window_blocks", 0)})
    return L.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_intermediate_size"],
        dense_intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"], num_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], norm_topk_prob=True,
        layer_plan=plan, sliding_window=cfg["sliding_window"],
        shared_expert_width=cfg["shared_expert_intermediate_size"],
        router_score="sigmoid",
        router_scale=float(cfg["moe_routed_scaling_factor"]),
        attn_gate=bool(cfg["gating"]),
        dtype=jnp.bfloat16, param_dtype=param_dtype)


def check_tokens(eng, cfg: dict, params, seed: int):
    """Part 1 (and 4): as `closed_loop_serve_moe.check_tokens`, against
    `reference_laguna`. Where the share reads under the limit, the
    reference runs again with its stream rounded to bfloat16 at sub-block
    boundaries, and that run's agreement is reported beside the first (it
    decides nothing). The share that must agree is
    `agreement_plan.MIN_AGREEMENT`, which says why it is not
    `agreement.MIN_AGREEMENT`."""
    c = cfg["correctness"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    prompts = [rng.integers(1, cfg["vocab_size"], n, dtype=np.int32)
               for n in c["prompt_lens"]]
    released0 = eng.stats.get("window_pages_released", 0)
    rids = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    width, kw = c["reference_len"], R.model_kw(cfg)
    rows = []
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(done[rid], np.int32)
        if len(out) != c["new_tokens"]:
            return False, {"why": f"request {rid} returned {len(out)} "
                                  f"tokens, not {c['new_tokens']}"}
        seq = np.zeros((width,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(out)] = out
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        rows.append((jnp.asarray(seq), jnp.asarray(at), out))

    def judged(**more):
        agreed, worst = 0.0, 0.0
        with jax.default_matmul_precision("highest"):
            for seq, at, out in rows:
                logits = R.logits_at(params, seq, at, **kw, **more)
                share, gap = agreement.judge(np.asarray(logits), out)
                agreed += share * len(out)
                worst = max(worst, gap)
        return agreed / sum(len(out) for _, _, out in rows), worst

    share, worst = judged()
    notes = {"positions_judged": sum(len(out) for _, _, out in rows),
             "agreement": share, "largest_gap_over_tolerance": worst,
             "window_pages_released_in_check":
                 eng.stats.get("window_pages_released", 0) - released0}
    if share < agreement_plan.MIN_AGREEMENT:
        notes["agreement_bf16_stream_reference"] = judged(
            stream_dtype=jnp.bfloat16)[0]
    return share >= agreement_plan.MIN_AGREEMENT, notes


def chunk_rows(cfg: dict):
    """(rows, valid rows) of the two timed ticks: a decode tick's
    `max_batch`, and a chunk tick's `token_budget` of which the chunk and
    the other slots' decode rows are valid when one slot has just left."""
    e = cfg["engine"]
    return ((e["max_batch"], e["max_batch"]),
            (e["token_budget"], e["token_budget"] - e["max_batch"] + 1))


def ffn_outputs(lcfg, params, h, valid, kinds=None, **fault):
    """({kind: the program's FFN of the kind's first layer on rows h},
    {kind: the reference's on the valid ones}), every kind of `kinds`
    (None: all) in one executable a side. `fault` goes to the reference's
    sparse layers: the tests run it under the faults the check must
    catch."""
    kinds = tuple(range(len(lcfg.kinds))) if kinds is None else tuple(kinds)
    n_valid = int(valid.sum())

    def first(blocks, kind):
        return {n: w[0] for n, w in blocks[kind].items()}

    def program(blocks, h):
        return {k: (L.routed_ffn(h, first(blocks, k), lcfg, valid)
                    if lcfg.kinds[k].ffn == "sparse" else
                    jnp.where(valid[:, None], L.ffn(h, first(blocks, k)), 0))
                for k in kinds}

    def reference(blocks, h):
        h = h[:n_valid].astype(jnp.float32)
        return {k: (R.sparse_ffn(h, first(blocks, k), top_k=lcfg.top_k,
                                 router_scale=lcfg.router_scale, **fault)
                    if lcfg.kinds[k].ffn == "sparse" else
                    R.dense_ffn(h, first(blocks, k))) for k in kinds}

    out = jax.jit(program)(params["blocks"], h)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(reference)(params["blocks"], h)
    return ({k: np.asarray(v.astype(jnp.float32)) for k, v in out.items()},
            {k: np.asarray(v) for k, v in ref.items()})


def check_layers(cfg: dict, params, lcfg, seed: int):
    """Part 2."""
    ok, notes = True, {}
    for rows, n_valid in chunk_rows(cfg):
        key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), rows)
        h = jax.random.normal(key, (rows, cfg["hidden_size"]),
                              jnp.float32).astype(lcfg.dtype)
        outs, refs = ffn_outputs(lcfg, params, h, jnp.arange(rows) < n_valid)
        for kind, spec in enumerate(lcfg.kinds):
            good, worst = agreement_moe.judge(outs[kind][:n_valid],
                                              refs[kind])
            quiet = not np.any(outs[kind][n_valid:])
            ok = ok and good and quiet
            notes[f"{spec.attn}_{spec.ffn}_rows_{rows}"] = {
                "largest_error_over_tolerance": worst,
                "padding_rows_zero": bool(quiet)}
    return ok, notes


def attention_case(cfg: dict, seed: int, dtype, decode: bool):
    """Part 3's inputs at a timed tick's shapes: `max_batch` slots at
    contexts spread from 5/4 of the window to max_len less a quarter of
    it, each one decode row, or (not
    `decode`) the last slot a chunk that fills the token budget; seeded q
    [tok, KV, 8, hd] and a one-layer pool that holds seeded keys and
    values on the pages a window pool would hold for the slot (those that
    hold one of the last `sliding_window` positions before its first row,
    and its rows' own), every other table entry -1."""
    e, W = cfg["engine"], cfg["sliding_window"]
    B, bs, hd = e["max_batch"], e["block_size"], cfg["head_dim"]
    KV = cfg["num_key_value_heads"]
    G = max(cfg["num_attention_heads_per_layer"]) // KV
    width = e["max_len"] // bs
    this = np.ones((B,), np.int32)
    if not decode:
        this[-1] = e["token_budget"] - (B - 1)
    lo, hi = W + W // 4, e["max_len"] - W // 4 - int(this[-1])
    past = (lo + (hi - lo) * np.arange(B) // (B - 1)).astype(np.int32)
    first = np.maximum(past - (W - 1), 0) // bs
    last = (past + this - 1) // bs
    held = int((last - first + 1).sum())
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4])
    pages = rng.permutation(held).astype(np.int32)
    tables = np.full((B, width), -1, np.int32)
    at = 0
    for b in range(B):
        n = int(last[b] - first[b] + 1)
        tables[b, first[b]:last[b] + 1] = pages[at:at + n]
        at += n
    keys = jax.random.split(jax.random.PRNGKey(seed & 0x7FFFFFFF), 3)
    pool = (1, held, KV, bs, hd)
    q = jax.random.normal(keys[0], (int(this.sum()), KV, G, hd), jnp.float32)
    k = jax.random.normal(keys[1], pool, jnp.float32)
    v = jax.random.normal(keys[2], pool, jnp.float32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
            jnp.asarray(tables), jnp.asarray(past), jnp.asarray(this))


def attention_outputs(cfg: dict, case, window: int, decode: bool):
    """(the launch's output, the dense reference's) [tok, H, hd] for one
    `attention_case`: the launch under `window` (the check: the
    configuration's; the tests: also a page more and a page less, which
    must fail), the reference under the configuration's, on the keys from
    the first page a slot holds on (positions shifted: the mask reads
    differences alone)."""
    q, k, v, tables, past, this = case
    W, bs, hd = cfg["sliding_window"], cfg["engine"]["block_size"], \
        cfg["head_dim"]
    B, KV = tables.shape[0], k.shape[2]
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(this).astype(jnp.int32)])
    if decode:
        out = jax.jit(lambda q, k, v: PA.paged_attention(
            q, k, v, tables, past, this, q.shape[2], float(hd) ** -0.5,
            layer=jnp.int32(0), window=window))(q, k, v)
    else:
        out = jax.jit(lambda q, k, v: PA.paged_attention_packed(
            q, k, v, tables, past, this, cu, float(hd) ** -0.5,
            layer=jnp.int32(0), window=window))(q, k, v)
    # the dense side: every slot's first row in one vmapped call (the same
    # executable for both launches), a chunk's rows in one more
    span = -(-(W + cfg["engine"]["token_budget"]) // bs) + 1
    first = jnp.maximum(past - (W - 1), 0) // bs                     # [B]
    ids = jnp.clip(jnp.take_along_axis(
        tables, jnp.clip(first[:, None] + jnp.arange(span)[None], 0,
                         tables.shape[1] - 1), axis=1), 0)           # [B, span]

    def keys_of(pool):                               # [B, span * bs, KV, hd]
        return pool[0][ids].transpose(0, 1, 3, 2, 4).reshape(B, -1, KV, hd)

    shifted = past - first * bs
    heads = q.shape[1] * q.shape[2]
    with jax.default_matmul_precision("highest"):
        ref = np.array(jax.jit(jax.vmap(
            lambda qb, kb, vb, pb: R.window_attention(qb, kb, vb, pb, W)))(
            q[cu[:-1]].reshape(B, 1, heads, hd), keys_of(k), keys_of(v),
            shifted)).reshape(B, heads, hd)
        ref = np.repeat(ref, np.asarray(this), axis=0)
        for b in np.flatnonzero(np.asarray(this) > 1):
            ref[int(cu[b]):int(cu[b + 1])] = np.asarray(jax.jit(
                lambda qb, kb, vb, pb: R.window_attention(qb, kb, vb, pb, W))(
                q[int(cu[b]):int(cu[b + 1])].reshape(-1, heads, hd),
                keys_of(k)[b], keys_of(v)[b], shifted[b]))
    out = np.asarray(out.astype(jnp.float32)).reshape(q.shape[0], -1, hd)
    return out, ref


def check_attention(cfg: dict, seed: int):
    """Part 3."""
    ok, notes = True, {}
    for decode in (True, False):
        out, ref = attention_outputs(
            cfg, attention_case(cfg, seed, jnp.bfloat16, decode),
            cfg["sliding_window"], decode)
        good, worst = agreement_blockdiff.judge_attention(out, ref)
        ok = ok and good
        notes["window_walk_" + ("decode" if decode else "mixed")
              + "_largest_error_over_tolerance"] = worst
    return ok, notes


def check(eng, cfg: dict, params, lcfg, seed: int):
    t = [time.perf_counter()]

    def lap():
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    ok_tokens, notes = check_tokens(eng, cfg, params, seed)
    phases = {"tokens_s": lap()}
    ok_attn, attn_notes = check_attention(cfg, seed)
    phases["window_walk_s"] = lap()
    ok_layer, layer_notes = check_layers(cfg, params, lcfg, seed)
    phases["layers_s"] = lap()
    notes.update(attn_notes, **layer_notes, experts=L.expert_form(lcfg),
                 prefix_cache=eng.engine_stats.get("prefix_cache", "on"),
                 check_phases=phases)
    return ok_tokens and ok_attn and ok_layer, notes


class LongLoop(Loop):
    """The closed loop, with the engine's key, page and expert counters in
    its books."""

    def counters(self) -> dict:
        out = super().counters()
        stats = self.eng.stats
        for name in STATS:
            out[name] = stats[name] - self.stats0[name]
        return out


# `gap_p90_ms` and `ttft_mean_ms` are not judged in this cell: one tick in
# four carries a prefill chunk, so the p90 gap sits between two modes, and a
# per-layer metric may list only a cell that reports the end-to-end metric
# it moves. So they and the per-layer metrics that move them are computed by
# their own readers and left in the run's notes, as
# `closed_loop_serve_blockdiff` leaves its shares; the attention and expert
# shares are read with this model's inner scopes counted in
NOT_JUDGED = (
    "tick_p50_ms", "ttft_p50_ms", "ttft_p90_ms", "serve_device_idle_share",
    "serve_idle_schedule_share", "serve_idle_prepare_share",
    "serve_idle_dispatch_share", "serve_idle_wait_share",
    "serve_idle_harvest_share", "serve_idle_submit_share",
    "serve_idle_outside_share", "serve_trace_overhead",
    "tick_cache_write_share", "tick_ffn_share", "tick_head_sample_share",
    "tick_layer_carry_share", "tick_unscoped_share",
    "moe_load_max_over_mean", "prefill_tokens_per_s")


def run(ctx: Context) -> Record:
    record = serve_window.run(ctx, laguna_config, check, LongLoop)
    read = {name: importlib.import_module(
        f"benchmark.end_to_end.{name}").read(record)
        for name in ("gap_p90_ms", "ttft_mean_ms")}
    for name in NOT_JUDGED:
        read[name] = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(record)
    read["tick_attention_share"] = program_trace.scope_share(
        record, *laguna_scopes.ATTENTION)
    read["tick_moe_share"] = program_trace.scope_share(
        record, *laguna_scopes.MOE)
    record.notes["not_judged"] = {k: float(v) for k, v in read.items()
                                  if v is not None}
    return record
