"""Closed-loop serving of a routed-expert model (OLMoE) through
`PagedServingEngine`: `closed_loop_serve`'s loop, clients and window, with
the program's config object built with the expert and QK-norm keys that
`lib/program.llama_config` leaves out, `correct` judged against
`reference_olmoe` in two parts (every generated token, teacher-forced, by
`agreement.judge`; one layer's routed FFN directly, by `agreement_moe`),
and the engine's expert counters in the books.

A program without `LlamaConfig.qk_norm` (the parent of PR 27) fails here
with a TypeError before any weight is made.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L

from ..lib import (agreement, agreement_moe, moe_scopes, reference_olmoe,
                   serve_window)
from ..lib.harness import Context, Record
from ..lib.program import llama_config
from .closed_loop_serve import Loop

moe_scopes.register()   # the `moe` scopes, before any reader loads a trace

# summed over ticks; `moe_max_load` is a running maximum and is read per
# tick from the step span instead (layer_metrics/moe_load_max_over_mean)
MOE_STATS = ("moe_pairs", "moe_experts_hit")


def moe_config(cfg: dict, param_dtype) -> L.LlamaConfig:
    """The program's config object from the published keys, experts and
    QK-norm included."""
    return dataclasses.replace(
        llama_config(cfg, param_dtype), num_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], qk_norm=cfg["qk_norm"],
        norm_topk_prob=cfg["norm_topk_prob"])


def reference_kw(cfg: dict) -> dict:
    return dict(heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"], qk_norm=cfg["qk_norm"])


def check_tokens(eng, cfg: dict, params, seed: int):
    """As `closed_loop_serve.check_against_reference`, against the OLMoE
    reference. Where the share reads under the limit, the reference runs
    again with its stream rounded to bfloat16 at block boundaries, and
    that run's agreement is reported beside the first (it decides
    nothing)."""
    c = cfg["correctness"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    prompts = [rng.integers(1, cfg["vocab_size"], n, dtype=np.int32)
               for n in c["prompt_lens"]]
    rids = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    width, kw = c["reference_len"], reference_kw(cfg)
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
                logits = reference_olmoe.logits_at(params, seq, at, **kw,
                                                   **more)
                share, gap = agreement.judge(np.asarray(logits), out)
                agreed += share * len(out)
                worst = max(worst, gap)
        return agreed / sum(len(out) for _, _, out in rows), worst

    share, worst = judged()
    notes = {"positions_judged": sum(len(out) for _, _, out in rows),
             "agreement": share, "largest_gap_over_tolerance": worst}
    if share < agreement.MIN_AGREEMENT:
        notes["agreement_bf16_stream_reference"] = judged(
            stream_dtype=jnp.bfloat16)[0]
    return share >= agreement.MIN_AGREEMENT, notes


def check_one_layer(cfg: dict, params, lcfg, seed: int):
    """`routed_ffn` on seeded bf16 rows (a mixed tick's 512 with 259
    valid, and a decode tick's 16) through layer 0's served weights,
    against the reference's expert block in float32 on the same rows."""
    lp = {k: params["blocks"][k][0] for k in ("router", "w1", "w3", "w2")}
    kw = dict(top_k=cfg["num_experts_per_tok"],
              norm_topk_prob=cfg["norm_topk_prob"])
    ok, notes = True, {}
    for rows, n_valid in ((cfg["engine"]["token_budget"], 259),
                          (cfg["engine"]["max_batch"], 16)):
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
    ok_tokens, notes = check_tokens(eng, cfg, params, seed)
    ok_layer, layer_notes = check_one_layer(cfg, params, lcfg, seed)
    notes.update(layer_notes, experts=L.expert_form(lcfg))
    return ok_tokens and ok_layer, notes


class MoeLoop(Loop):
    """The closed loop, with the engine's expert counters in its books."""

    def counters(self) -> dict:
        out = super().counters()
        stats = self.eng.stats
        for name in MOE_STATS:
            out[name] = stats[name] - self.stats0[name]
        return out


def run(ctx: Context) -> Record:
    return serve_window.run(ctx, moe_config, check, MoeLoop)
