"""Closed-loop serving of a block-diffusion model (SDAR) through
`PagedServingEngine`: `closed_loop_serve`'s loop, clients and window (over
`lib/serve_window.run`), with the program's config object built with the
keys that `lib/program.llama_config` leaves out or refuses (a head width
that is not hidden / heads, per-head QK-norm, the experts, the block
length), the engine's block-diffusion counters in the books, and `correct`
judged against `reference_sdar` in six parts, of what the served path
produced at the published widths (all outside the window, in `setup_s`):

1. every token proposed at a masked row, in every denoise forward of the
   correctness requests, ties with the reference's best at that row
   (`agreement.judge`), the reference fed the engine's own block;
2. log conf of the engine against the reference's log-probability of the
   same token at the same row (`agreement_blockdiff.judge_confidence`);
3. the transfer, exactly, on the engine's own confidences, and the count
   of forwards: every block took ceil(masked rows / ceil(Bd / T)) denoise
   forwards and one commit forward (`judge_transfer`, the counters);
4. the block-causal read directly: the mixed launch on seeded bf16 q, k,
   v at the cell's shapes against dense float32 attention under the mask
   (`judge_attention`);
5. one layer's routed FFN at a block tick's and a mixed tick's rows
   (`agreement_moe.judge`), padding rows zero;
6. every request returns exactly its `max_new_tokens` (here, and in the
   window by the loop's `failed`).

A program without `LlamaConfig.head_dim` as a field (the parent of PR 32)
fails here with a TypeError before any weight is made.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L
from paddle_tpu.ops.pallas import paged_attention as PA

from ..lib import (agreement, agreement_blockdiff, agreement_moe,
                   blockdiff_scopes, moe_scopes, reference_olmoe,
                   reference_sdar, serve_window, traffic as T)
from ..lib.harness import Context, Record
from .closed_loop_serve import Loop

moe_scopes.register()        # `moe` and its inner scopes
blockdiff_scopes.register()  # `unmask`, before any reader loads a trace

# summed over ticks (`moe_max_load` is read per tick from the step span)
DIFF_STATS = ("diff_denoise_forwards", "diff_commit_forwards",
              "diff_blocks_committed", "diff_tokens_unmasked", "diff_rows")
STATS = ("moe_pairs", "moe_experts_hit") + DIFF_STATS


def sdar_config(cfg: dict, param_dtype) -> L.LlamaConfig:
    """The program's config object from the published keys and the
    configuration file's `assumed` ones. The engine serves one remasking
    rule and takes no argument for it: a file that names another is
    refused here, not served under the static rule."""
    if cfg["remasking"] != "low_confidence_static":
        raise NotImplementedError(
            f"remasking={cfg['remasking']!r}: the engine serves "
            "'low_confidence_static' alone (the dynamic-threshold rule is "
            "queued, ROADMAP.md)")
    return L.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        qk_norm=True, qk_norm_per_head=True,
        norm_topk_prob=cfg["norm_topk_prob"],
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"],
        dtype=jnp.bfloat16, param_dtype=param_dtype)


def reference_kw(cfg: dict) -> dict:
    return dict(block_length=cfg["block_length"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], theta=float(cfg["rope_theta"]),
                eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"])


def check_generation(eng, cfg: dict, params, seed: int):
    """Parts 1, 2, 3 and 6: the correctness requests through chunked
    prefill and block decoding with the engine recording every denoise
    forward, each judged against the reference's logits for the same
    block behind the same (final) tokens."""
    c, Bd, steps = cfg["correctness"], cfg["block_length"], \
        cfg["denoising_steps"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    prompts = [rng.integers(1, cfg["vocab_size"], n, dtype=np.int32)
               for n in c["prompt_lens"]]
    stats0 = dict(eng.stats)
    harvest = eng._harvest_blocks
    record = agreement_blockdiff.record_forwards(eng)
    rids = [eng.submit(p, max_new_tokens=c["new_tokens"],
                       denoising_steps=steps) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    eng._harvest_blocks = harvest       # the window runs the engine's own
    kw = reference_kw(cfg)
    rows = tied = near = 0
    worst_gap = worst_conf = 0.0
    transfers_exact, blocks = True, 0
    with jax.default_matmul_precision("highest"):
        for rid, prompt in zip(rids, prompts):
            out = np.asarray(done[rid], np.int32)
            if len(out) != c["new_tokens"]:
                return False, {"why": f"request {rid} returned {len(out)} "
                                      f"tokens, not {c['new_tokens']}"}
            seq = np.zeros((c["reference_len"],), np.int32)
            seq[:len(prompt)] = prompt
            seq[len(prompt):len(prompt) + len(out)] = out
            _, kv = reference_sdar.forward_full(
                params, jnp.asarray(seq), jnp.zeros((1,), jnp.int32),
                with_kv=True, **kw)
            forwards = [f for f in record if f["rid"] == rid]
            by_block = {}
            for f in forwards:
                by_block.setdefault(f["start"], []).append(f)
                m = np.asarray(f["masked"], bool)
                logits = np.asarray(reference_sdar.block_logits_kv(
                    params, kv, jnp.asarray(f["ids"], jnp.int32),
                    jnp.int32(f["start"]), **kw))[m]
                proposed = np.asarray(f["proposed"])[m]
                share, gap = agreement.judge(logits, proposed)
                ok, over = agreement_blockdiff.judge_confidence(
                    logits, proposed, np.asarray(f["conf"])[m])
                rows += int(m.sum())
                tied += round(share * m.sum())
                near += ok
                worst_gap, worst_conf = max(worst_gap, gap), \
                    max(worst_conf, over)
                transfers_exact &= agreement_blockdiff.judge_transfer(
                    m, f["conf"], f["taken"], steps)
            # a block opened with u masked rows takes ceil(u / ceil(Bd / T))
            # denoise forwards, whatever the weights say
            for start, fs in by_block.items():
                u = sum(fs[0]["masked"])
                transfers_exact &= len(fs) == -(-u // -(-Bd // steps))
            want = -(-(len(prompt) % Bd + len(out)) // Bd)
            transfers_exact &= len(by_block) == want
            blocks += want
    used = {k: eng.stats[k] - stats0[k] for k in DIFF_STATS}
    counted = (used["diff_commit_forwards"] == blocks
               == used["diff_blocks_committed"]
               and used["diff_denoise_forwards"] == len(record))
    notes = {"rows_judged": rows, "agreement": tied / rows,
             "largest_gap_over_tolerance": worst_gap,
             "confidence_agreement": near / rows,
             "largest_log_conf_error_over_tolerance": worst_conf,
             "transfers_exact": bool(transfers_exact),
             "forwards_counted": bool(counted), "blocks": blocks}
    ok = (tied / rows >= agreement.MIN_AGREEMENT
          and near / rows >= agreement.MIN_AGREEMENT
          and transfers_exact and counted)
    return ok, notes


def attention_case(cfg: dict, seed: int, dtype):
    """Part 4's inputs at the cell's shapes: `max_batch` slots of one block
    each, contexts spread from 64 to max_len - 24 in whole blocks, seeded
    q [B * Bd, KV, G, hd] and a one-layer pool filled with seeded keys and
    values (the block's own among them, as after the tick's write), every
    sequence on its own shuffled pages."""
    e, Bd = cfg["engine"], cfg["block_length"]
    B, bs, hd = e["max_batch"], e["block_size"], cfg["head_dim"]
    KV = cfg["num_key_value_heads"]
    G = cfg["num_attention_heads"] // KV
    width = e["max_len"] // bs
    span = (e["max_len"] - 24 - Bd - 64) // Bd
    past = (64 + Bd * (np.arange(B) * span // (B - 1))).astype(np.int32)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4])
    tables = rng.permutation(B * width).reshape(B, width).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(seed & 0x7FFFFFFF), 3)
    pool = (1, B * width, KV, bs, hd)
    q = jax.random.normal(keys[0], (B * Bd, KV, G, hd), jnp.float32)
    k = jax.random.normal(keys[1], pool, jnp.float32)
    v = jax.random.normal(keys[2], pool, jnp.float32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
            jnp.asarray(tables), jnp.asarray(past))


def attention_outputs(cfg: dict, case, block_len: int, short: int = 0):
    """(the mixed launch's output, the dense reference's) [B * Bd, H, hd]
    for one `attention_case`. The check runs the launch under the
    configuration's `block_len`; the tests also run it under the faults
    the check must catch: the causal mask (`block_len` 0) and a view
    `short` keys short (the launch told the block starts that much
    earlier, so that it ends before the rows' own block)."""
    q, k, v, tables, past = case
    Bd, hd = cfg["block_length"], cfg["head_dim"]
    B, KV = tables.shape[0], k.shape[2]
    this = jnp.full((B,), Bd, jnp.int32)
    cu = jnp.arange(B + 1, dtype=jnp.int32) * Bd
    out = jax.jit(lambda q, k, v: PA.paged_attention_packed(
        q, k, v, tables, past - short, this, cu, float(hd) ** -0.5,
        layer=jnp.int32(0), block_len=block_len))(q, k, v)

    def dense(pool):                          # [B, S, KV, hd]
        return pool[0][tables].transpose(0, 1, 3, 2, 4).reshape(
            B, -1, KV, hd)

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(jax.vmap(
            lambda qb, kb, vb, pb: reference_sdar.block_causal_attention(
                qb, kb, vb, pb, Bd)))(
            q.reshape(B, Bd, -1, hd), dense(k), dense(v), past)
    return (np.asarray(out.astype(jnp.float32)).reshape(B * Bd, -1, hd),
            np.asarray(ref).reshape(B * Bd, -1, hd))


def check_attention(cfg: dict, seed: int):
    """Part 4."""
    out, ref = attention_outputs(
        cfg, attention_case(cfg, seed, jnp.bfloat16), cfg["block_length"])
    ok, worst = agreement_blockdiff.judge_attention(out, ref)
    return ok, {"attention_largest_error_over_tolerance": worst}


def check_one_layer(cfg: dict, params, lcfg, seed: int):
    """Part 5: `routed_ffn` on seeded bf16 rows (a mixed tick's 512 with
    259 valid, and a block tick's 64) through layer 0's served weights,
    against the reference's expert block in float32 on the same rows.
    At these widths the worst row reads 0.30-0.44 of `agreement_moe`'s
    tolerance on the chip, and a program that computes with the expert
    weights rounded to 8 bits 1.14-1.60 (my chip runs, PR 32; on the CPU
    through this function: benchmark/tests/test_blockdiff.py)."""
    e = cfg["engine"]
    lp = {k: params["blocks"][k][0] for k in ("router", "w1", "w3", "w2")}
    kw = dict(top_k=cfg["num_experts_per_tok"],
              norm_topk_prob=cfg["norm_topk_prob"])
    ok, notes = True, {}
    block_rows = e["max_batch"] * cfg["block_length"]
    for rows, n_valid in ((e["token_budget"], 259), (block_rows, block_rows)):
        key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), rows)
        h = jax.random.normal(key, (rows, cfg["hidden_size"]),
                              jnp.float32).astype(lcfg.dtype)
        valid = jnp.arange(rows) < n_valid
        out = jax.jit(lambda h, lp, valid: L.routed_ffn(h, lp, lcfg, valid)
                      )(h, lp, valid)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda h, lp: reference_olmoe.expert_block(
                h.astype(jnp.float32), lp, **kw))(h[:n_valid], lp)
        out = np.asarray(out.astype(jnp.float32))
        good, worst = agreement_moe.judge(out[:n_valid], np.asarray(ref))
        quiet = not np.any(out[n_valid:])       # a padding row yields zeros
        ok = ok and good and quiet
        notes[f"layer_rows_{rows}"] = {"largest_error_over_tolerance": worst,
                                       "padding_rows_zero": bool(quiet)}
    return ok, notes


def check(eng, cfg: dict, params, lcfg, seed: int):
    ok_gen, notes = check_generation(eng, cfg, params, seed)
    ok_attn, attn_notes = check_attention(cfg, seed)
    ok_layer, layer_notes = check_one_layer(cfg, params, lcfg, seed)
    notes.update(attn_notes, **layer_notes, experts=L.expert_form(lcfg))
    return ok_gen and ok_attn and ok_layer, notes


class BlockLoop(Loop):
    """The closed loop for requests whose tokens come a block at a time:
    the request carries the traffic's denoising steps, and the engine's
    block-diffusion and expert counters are in the books.
    A block's tokens are harvested in one tick, so all but one of a
    block's gaps are 0 ms (`Loop.tick` stamps them alike)."""

    def submit(self, client):
        client.j += 1
        tr, seed = self.ctx.traffic, self.ctx.seed
        tokens = T.request_tokens(tr, seed, client.index, client.j,
                                  self.ctx.config["vocab_size"])
        client.prompt_len, client.got = len(tokens), 0
        client.want = T.new_tokens(tr, client.index, client.j)
        client.submitted_s = time.perf_counter()
        client.rid = self.eng.submit(
            tokens, max_new_tokens=client.want, eos_token_id=None,
            denoising_steps=tr["denoising_steps"])
        self.by_rid[client.rid] = client

    def counters(self) -> dict:
        out = super().counters()
        stats = self.eng.stats
        for name in STATS:
            out[name] = stats[name] - self.stats0[name]
        return out


# `gap_p90_ms` is not judged in this cell: over the builder's sets of six
# runs it spread by more than half its bound (PERF.md section 6, PR 32), and
# a per-layer metric may list only a cell that reports the end-to-end metric
# it moves. So it and the per-layer metrics that move it are computed by
# their own readers and left in the run's notes, as `closed_loop_sessions`
# leaves its shares
NOT_JUDGED = (
    "tick_p50_ms", "serve_device_idle_share", "serve_idle_schedule_share",
    "serve_idle_prepare_share", "serve_idle_dispatch_share",
    "serve_idle_wait_share", "serve_idle_harvest_share",
    "serve_idle_submit_share", "serve_idle_outside_share",
    "serve_trace_overhead", "tick_attention_share", "tick_cache_write_share",
    "tick_head_sample_share", "tick_layer_carry_share", "tick_unscoped_share",
    "tick_moe_share", "tick_moe_overhead_share", "moe_load_max_over_mean")


def run(ctx: Context) -> Record:
    record = serve_window.run(ctx, sdar_config, check, BlockLoop)
    read = {"gap_p90_ms": importlib.import_module(
        "benchmark.end_to_end.gap_p90_ms").read(record)}
    for name in NOT_JUDGED:
        read[name] = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(record)
    record.notes["not_judged"] = {k: float(v) for k, v in read.items()
                                  if v is not None}
    return record
