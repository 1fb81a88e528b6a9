"""Closed-loop serving of a model whose residual stream is lanes mixed by
manifold-constrained hyper-connections around latent attention and routed
experts held whole (Xing4.0-29B-A4B: four lanes, a Sinkhorn iteration on
every row twice a layer, multi-head latent attention over a latent page
pool, a leading dense layer, all 64 routed experts beside a shared one)
through `PagedServingEngine`: `closed_loop_serve_latent`'s loop, clients
and window (`lib/serve_window.run`; the judged rate is the raw window's, as
that driver's), with the program's config object built from the
published keys, the engine's key, page, expert and lane-row counters in the
books, and `correct` judged against `reference_xing4` in five parts, of
what the served path produced at the published widths (all outside the
window, in `setup_s`):

1. every generated token of the correctness requests (prompts that span a
   page edge, YaRN's original length and the cell's longest contexts;
   prefill in chunks, then decode, through the latent pool and the lanes),
   teacher-forced against the reference's full forward of `reference_len`
   positions: its logit there ties with the reference's best
   (`agreement.judge`) at `agreement_hyper.MIN_AGREEMENT` of the positions;
2. the layer's attention op directly at the timed shapes and this model's
   32 heads, and the pool holding the new rows bit for bit
   (`closed_loop_serve_latent.check_attention`, unchanged);
3. the lanes' mixing directly (`llama.hyper_coeff`, and `llama.residual`'s
   two ends as the tick calls them) on seeded bf16 streams at a decode
   tick's and a chunk tick's row counts through the served phi, b and
   alpha of a dense and of a sparse layer, against
   `reference_xing4.coefficients` and the reference's two sums in float32:
   every coefficient within `agreement_hyper.COEFF_TOL`, the sub-block's
   input and the updated stream within `ROWS_TOL_ULPS` a row;
4. one sparse layer's routed FFN over all 64 experts at both ticks' rows
   (`closed_loop_serve_latent.check_layers`, unchanged: every expert is
   held, so no row is the shared expert alone);
5. every request returns exactly its `max_new_tokens` (here, and in the
   window by the loop's `failed`).

A program without hyper-connections (the parent of PR 54) fails in
`xing_config`, before any weight is made.
"""
from __future__ import annotations

import dataclasses
import importlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L

from ..lib import (agreement, agreement_hyper, hyper_scopes, program_trace,
                   reference_xing4 as R, serve_window)
from ..lib.harness import Context, Record
from . import closed_loop_serve_latent as K
from .closed_loop_serve import Loop
from .closed_loop_serve_longctx import chunk_rows

hyper_scopes.register()      # before any reader loads a trace

# summed over ticks (`moe_max_load` is read per tick from the step span)
STATS = ("moe_pairs", "moe_experts_hit", "attn_keys_latent",
         "attn_pairs_latent", "latent_pages_live", "hyper_rows")


def as_kimi(cfg: dict) -> dict:
    """The file as `closed_loop_serve_latent`'s functions read one: every
    routed expert held (the router's width is the file's own count)."""
    return {**cfg, "router_width": cfg["n_routed_experts"],
            "held_experts_first": 0}


def xing_config(cfg: dict, param_dtype) -> "L.LlamaConfig":
    """The program's config object from the published keys: Kimi's latent
    plan at this file's numbers, with the lanes."""
    if "hyper_lanes" not in {f.name for f in
                             dataclasses.fields(L.LlamaConfig)}:
        raise NotImplementedError(
            "this program has no hyper-connections (LlamaConfig."
            "hyper_lanes): it cannot run hc_mult "
            f"{cfg['hc_mult']}")
    return dataclasses.replace(
        K.kimi_config(as_kimi(cfg), param_dtype),
        hyper_lanes=cfg["hc_mult"],
        hyper_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hyper_eps=float(cfg["hc_eps"]),
        hyper_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                     float(cfg["mhc_h_res_clamp_max"])))


def check_tokens(eng, cfg: dict, params, seed: int, **fault):
    """Part 1 (and 5). `fault` goes to the reference: the tests run it
    under the mistakes the check must catch."""
    c = cfg["correctness"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    prompts = [rng.integers(1, cfg["vocab_size"], n, dtype=np.int32)
               for n in c["prompt_lens"]]
    rids = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    width, kw = c["reference_len"], R.model_kw(cfg)
    agreed, worst, judged = 0.0, 0.0, 0
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
            agreed += share * len(out)
            judged += len(out)
            worst = max(worst, gap)
    share = agreed / judged
    return share >= agreement_hyper.MIN_AGREEMENT, {
        "positions_judged": judged, "agreement": share,
        "largest_gap_over_tolerance": worst}


def mix_case(cfg: dict, seed: int, rows: int, dtype):
    """Part 3's inputs: a stream [rows, n d] whose lanes differ (a common
    part, as copies of one embedding leave, and a lane's own) and a
    sub-block's output [rows, d], seeded."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    k = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), rows), 3)
    common = jnp.tile(jax.random.normal(k[0], (rows, d), jnp.float32),
                      (1, n))
    own = jax.random.normal(k[1], (rows, n * d), jnp.float32)
    return ((common + 0.5 * own).astype(dtype),
            jax.random.normal(k[2], (rows, d), jnp.float32).astype(dtype))


def mix_outputs(lcfg, cfg: dict, stack, which: str, x, y, **fault):
    """((coefficients, the sub-block's input, the updated stream) of the
    program, the same of the reference), float32 numpy, for sub-block
    `which` of the first layer of `stack` on stream x and output y."""
    names = [f"hc_{which}_{p}" for p in ("phi", "b", "alpha")]
    lp = {n: stack[n][0] for n in names}
    kw = R.model_kw(cfg)
    n = kw["hyper"][0]

    def program(lp, x, y):
        h, out = L.residual(x, lp, lcfg, which)
        return L.hyper_coeff(x, lp, lcfg, which), h, out(y)

    def reference(lp, x, y):
        X = R._f32(x).reshape(x.shape[0], n, -1)
        # phi as the tick multiplies with it: in the stream's dtype, which
        # is the served weights' own (a float32 fixture's is rounded)
        pre, post, M = R.coefficients(
            X, R._f32(lp[names[0]].astype(x.dtype)).T, lp[names[1]],
            lp[names[2]],
            eps=kw["eps"], hyper=kw["hyper"], **fault)
        new = (jnp.einsum("sji,sid->sjd", M, X)
               + post[:, :, None] * R._f32(y)[:, None])
        return (jnp.concatenate([pre, post, M.reshape(len(X), -1)], axis=-1),
                jnp.einsum("si,sid->sd", pre, X), new.reshape(x.shape))

    got = jax.jit(program)(lp, x, y)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(lp, x, y)
    f32 = lambda t: tuple(np.asarray(a.astype(jnp.float32)) for a in t)
    return f32(got), f32(want)


def check_mixing(cfg: dict, params, lcfg, seed: int, **fault):
    """Part 3."""
    ok, notes = True, {}
    dense, sparse = params["blocks"]
    for rows, _ in chunk_rows(cfg):
        x, y = mix_case(cfg, seed, rows, lcfg.dtype)
        for stack, which in ((dense, "attn"), (sparse, "mlp")):
            got, want = mix_outputs(lcfg, cfg, stack, which, x, y, **fault)
            good_c, worst_c = agreement_hyper.judge_coefficients(
                got[0], want[0])
            good_h, worst_h = agreement_hyper.judge_rows(got[1], want[1])
            good_x, worst_x = agreement_hyper.judge_rows(got[2], want[2])
            ok = ok and good_c and good_h and good_x
            notes[f"mix_{which}_rows_{rows}"] = {
                "coefficients_largest_error_over_tolerance": worst_c,
                "input_largest_row_error_over_tolerance": worst_h,
                "update_largest_row_error_over_tolerance": worst_x}
    return ok, notes


def check(eng, cfg: dict, params, lcfg, seed: int):
    t = [time.perf_counter()]

    def lap():
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    params = K.balance(eng, params, lcfg, seed)
    phases = {"balance_s": lap()}
    ok_tokens, notes = check_tokens(eng, cfg, params, seed)
    phases["tokens_s"] = lap()
    ok_attn, attn_notes = K.check_attention(as_kimi(cfg), seed)
    phases["latent_walk_s"] = lap()
    ok_mix, mix_notes = check_mixing(cfg, params, lcfg, seed)
    phases["mixing_s"] = lap()
    ok_layer, layer_notes = K.check_layers(cfg, params, lcfg, seed)
    phases["layers_s"] = lap()
    notes.update(attn_notes, **mix_notes, **layer_notes,
                 experts=L.expert_form(lcfg),
                 prefix_cache=eng.engine_stats.get("prefix_cache", "on"),
                 check_phases=phases)
    return ok_tokens and ok_attn and ok_mix and ok_layer, notes


class HyperLoop(Loop):
    """The closed loop, with the engine's key, page, expert and lane-row
    counters in its books, and the pool's bytes a key a layer."""

    def counters(self) -> dict:
        out = super().counters()
        stats, eng = self.eng.stats, self.eng
        for name in STATS:
            out[name] = stats[name] - self.stats0[name]
        out["latent_row_bytes"] = eng.kv_page_bytes / (
            eng.cfg.num_layers * eng.block_size)
        return out


# as `closed_loop_serve_latent` leaves them: `gap_p90_ms` and
# `ttft_mean_ms` are not judged in this cell (one tick of two carries a
# prefill chunk, so the p90 gap sits between two modes), and a per-layer
# metric may list only a cell that reports the end-to-end metric it moves
NOT_JUDGED = K.NOT_JUDGED


def run(ctx: Context) -> Record:
    record = serve_window.run(ctx, xing_config, check, HyperLoop)
    # The judged rate is the window's own, pauses of the machine counted
    # in, as `closed_loop_serve_latent` judges the same path and for its
    # reason: a tick is 17 to 63 ms of device work launched a tick ahead,
    # so a short pause of the host is mostly hidden behind it, and taking
    # the paused ticks out (`serve_window`'s books) keeps the short tick
    # behind each and over-corrects: of twelve runs of one tree those with
    # 0.33 to 0.66 s of pauses taken out read 0.65 to 0.97 % over their
    # own raw rates, and the twelve spread by 0.60 % where their raw rates
    # spread by 0.21 % (PERF.md section 6, PR 54). The books with the
    # pauses left out stay in the notes.
    c = record.counters
    record.notes["pauses_left_out"] = {
        "tokens_out": c["tokens_out"], "elapsed_s": c["elapsed_s"],
        "decode_tokens_per_s": c["tokens_out"] / c["elapsed_s"]}
    c["tokens_out"], c["elapsed_s"] = c["tokens_out_raw"], c["elapsed_raw_s"]
    # a tick is 17 ms (decode) or 63 (with a chunk): what took longer than
    # 250 ms is neither, and a reader of a slow run wants to see it
    slow = [t for t in record.samples["tick_ms"] if t > 250.0]
    record.notes["ticks_over_250_ms"] = {"count": len(slow),
                                         "total_ms": sum(slow)}
    read = {name: importlib.import_module(
        f"benchmark.end_to_end.{name}").read(record)
        for name in ("gap_p90_ms", "ttft_mean_ms")}
    for name in NOT_JUDGED:
        read[name] = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(record)
    read["tick_attention_share"] = program_trace.scope_share(
        record, *hyper_scopes.ATTENTION)
    read["tick_moe_share"] = program_trace.scope_share(
        record, *hyper_scopes.MOE)
    read["tick_experts_share"] = program_trace.scope_share(record, "experts")
    record.notes["not_judged"] = {k: float(v) for k, v in read.items()
                                  if v is not None}
    return record
