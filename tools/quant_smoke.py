"""Quantized-serving smoke: w8 weights + int8 paged KV cache against the
fp paged engine on the same request trace. Prints ONE JSON line; exit 0
iff ok.

The drill for the quant subsystem:
- logit parity: quantized LLMPredictor logits stay within tolerance of
  the fp predictor on the same prompt (weight-only int8 tracks fp32 to
  well under 5% relative error on this model)
- token agreement: the quantized engine's greedy outputs agree with the
  fp engine on >= 90% of tokens across the trace (exact equality is not
  a sane gate on a random-init tiny model whose near-uniform logits
  flip argmax under <1% perturbation; determinism WITHIN the quantized
  path is gated bit-exactly below)
- capacity: effective KV capacity ratio (fp page bytes / int8 page
  bytes) >= 1.8x — the point of the int8 cache
- preemption bit-exactness: the same trace on a starved pool (forced
  preemptions > 0) reproduces the ample-pool outputs bit-for-bit —
  static calibrated scales make int8 page recompute deterministic
- steady state: the timed passes add ZERO step-executable builds

The quant engine is warmed on the full trace first (populating the
prefix cache with int8 pages), so the timed pass also proves prefix
sharing serves quantized pages.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_REQS = 16
SHARED_LEN = 40      # shared prompt prefix (5 full 8-token pages)
UNIQ_LEN = 4
NEW_TOKENS = 6
TIMED_REPEATS = 2
LOGIT_REL_TOL = 0.05
CAPACITY_FLOOR = 1.8
AGREEMENT_FLOOR = 0.9


def _trace(vocab: int, seed: int = 0):
    rs = np.random.RandomState(seed)
    shared = rs.randint(1, vocab, size=SHARED_LEN).tolist()
    return [shared + rs.randint(1, vocab, size=UNIQ_LEN).tolist()
            for _ in range(N_REQS)]


def _drain(eng, rids):
    by_rid = {c.rid: c.output_tokens for c in eng.run()}
    return [by_rid[r] for r in rids]


def _engine(cfg, params, manifest, num_blocks, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine

    return PagedServingEngine(cfg, params, num_blocks=num_blocks,
                              block_size=8, max_batch=N_REQS,
                              token_budget=32, max_len=cfg.max_seq_len,
                              quant_manifest=manifest, **kw)


def _run_trace(eng, prompts):
    return _drain(eng, [eng.submit(p, max_new_tokens=NEW_TOKENS)
                        for p in prompts])


def _logit_parity(cfg, params, manifest):
    import jax.numpy as jnp

    from paddle_tpu.inference.llm import LLMPredictor

    rs = np.random.RandomState(3)
    toks = jnp.asarray(rs.randint(1, cfg.vocab_size, (1, 12)), jnp.int32)
    fp = LLMPredictor(cfg, params, max_len=cfg.max_seq_len,
                      attn_impl="xla")
    q = LLMPredictor(cfg, params, max_len=cfg.max_seq_len,
                     attn_impl="xla", quant_mode="w8",
                     quant_manifest=manifest)
    _, sc_fp = fp.generate(toks, max_new_tokens=4, return_scores=True)
    _, sc_q = q.generate(toks, max_new_tokens=4, return_scores=True)
    sc_fp, sc_q = np.asarray(sc_fp), np.asarray(sc_q)
    return float(np.max(np.abs(sc_fp - sc_q))
                 / (np.max(np.abs(sc_fp)) + 1e-9))


def run() -> dict:
    import jax

    from paddle_tpu.inference import quant as Q
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=np.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _trace(cfg.vocab_size)
    rs = np.random.RandomState(7)
    calib = [rs.randint(1, cfg.vocab_size, (2, 16)) for _ in range(2)]
    manifest = Q.calibrate(cfg, params, calib)

    logit_rel = _logit_parity(cfg, params, manifest)

    fp_eng = _engine(cfg, params, None, num_blocks=160)
    fp_out = _run_trace(fp_eng, prompts)

    q_eng = _engine(cfg, params, manifest, num_blocks=160,
                    quant_mode="w8", quant_kv=True)
    q_out = _run_trace(q_eng, prompts)        # warm + seed prefix cache
    builds_warm = q_eng.stats["step_builds"]
    hits0 = q_eng.blocks.stats["prefix_hit_tokens"]
    best_tps = 0.0
    for _ in range(TIMED_REPEATS):
        t0 = time.perf_counter()
        q_out = _run_trace(q_eng, prompts)
        wall = time.perf_counter() - t0
        best_tps = max(best_tps, N_REQS * NEW_TOKENS / wall)
    builds_timed = q_eng.stats["step_builds"] - builds_warm
    prefix_hit = q_eng.blocks.stats["prefix_hit_tokens"] - hits0

    # pallas leg: int8 pages read through the paged-attention kernel
    # (in-register dequant; interpret mode on CPU, real kernel on TPU).
    # Token parity with the stock quant engine gates everywhere; the
    # throughput ratio only REDs where the flag would actually enable the
    # kernel (available() == real TPU).
    from paddle_tpu.ops.pallas import paged_attention as PA
    p_eng = _engine(cfg, params, manifest, num_blocks=160,
                    quant_mode="w8", quant_kv=True, pallas=True)
    p_out = _run_trace(p_eng, prompts)        # warm
    p_builds_warm = p_eng.stats["step_builds"]
    pallas_tps = 0.0
    for _ in range(TIMED_REPEATS):
        t0 = time.perf_counter()
        p_out = _run_trace(p_eng, prompts)
        wall = time.perf_counter() - t0
        pallas_tps = max(pallas_tps, N_REQS * NEW_TOKENS / wall)
    p_builds_timed = p_eng.stats["step_builds"] - p_builds_warm
    pallas_ratio = pallas_tps / best_tps if best_tps else None

    # fused decode tick over quantized weights: the int8-dequant fused FFN
    # kernel (w8 leaves consumed in-register) plus the fused sampler prep,
    # stacked on the pallas paged-attention leg above. Token parity vs the
    # stock quant engine gates bit-exactly; zero retraces in the timed
    # passes; the per-tick traced-launch count stays within 3·layers + 1.
    f_eng = _engine(cfg, params, manifest, num_blocks=160,
                    quant_mode="w8", quant_kv=True, pallas=True,
                    pallas_ffn=True)
    f_out = _run_trace(f_eng, prompts)        # warm
    f_builds_warm = f_eng.stats["step_builds"]
    fused_tps = 0.0
    for _ in range(TIMED_REPEATS):
        t0 = time.perf_counter()
        f_out = _run_trace(f_eng, prompts)
        wall = time.perf_counter() - t0
        fused_tps = max(fused_tps, N_REQS * NEW_TOKENS / wall)
    f_builds_timed = f_eng.stats["step_builds"] - f_builds_warm
    fused_ratio = fused_tps / best_tps if best_tps else None
    launch_budget = 3 * cfg.num_layers + 1
    tick_launches = f_eng.stats["tick_pallas_launches"]

    # forced preemption on a starved pool must reproduce bit-for-bit
    tight = _engine(cfg, params, manifest, num_blocks=14,
                    quant_mode="w8", quant_kv=True)
    tight_out = _run_trace(tight, prompts)
    preemptions = tight.engine_stats["preemptions"]

    capacity_ratio = fp_eng.kv_page_bytes / q_eng.kv_page_bytes
    pairs = [(x, y) for a, b in zip(q_out, fp_out) for x, y in zip(a, b)]
    agreement = sum(x == y for x, y in pairs) / max(len(pairs), 1)
    checks = {
        "logit_parity": logit_rel < LOGIT_REL_TOL,
        "token_agreement": bool(agreement >= AGREEMENT_FLOOR),
        "kv_capacity_ratio": bool(capacity_ratio >= CAPACITY_FLOOR),
        "preemption_bit_exact": (preemptions > 0
                                 and tight_out == q_out),
        "zero_retraces_steady_state": builds_timed == 0,
        "prefix_cache_served": prefix_hit > 0,
        "pallas_parity": p_out == q_out,
        "pallas_zero_retraces": p_builds_timed == 0,
        "pallas_not_slower_when_enabled": bool(
            not PA.available() or (pallas_ratio or 0.0) >= 1.0),
        "fused_parity": f_out == q_out,
        "fused_zero_retraces": f_builds_timed == 0,
        "fused_ticks_ran": f_eng.stats["fused_ticks"] > 0,
        "fused_tick_launch_budget": bool(
            0 < tick_launches <= launch_budget),
        "fused_not_slower_when_enabled": bool(
            not PA.available() or (fused_ratio or 0.0) >= 1.0),
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "requests": N_REQS,
        "prompt_len": SHARED_LEN + UNIQ_LEN,
        "new_tokens": NEW_TOKENS,
        "logit_rel_err_w8": round(logit_rel, 5),
        "token_agreement_vs_fp": round(agreement, 4),
        "kv_capacity_ratio": round(capacity_ratio, 3),
        "fp_page_bytes": fp_eng.kv_page_bytes,
        "quant_page_bytes": q_eng.kv_page_bytes,
        "preemptions_starved": preemptions,
        "quant_tokens_per_s": round(best_tps, 1),
        "prefix_hit_tokens_timed": prefix_hit,
        "step_builds_timed": builds_timed,
        "pallas_tokens_per_s": round(pallas_tps, 1),
        "pallas_throughput_ratio": round(pallas_ratio, 3)
        if pallas_ratio is not None else None,
        "pallas_available": PA.available(),
        "pallas_steps": p_eng.stats["pallas_steps"],
        "pallas_decode_fast_steps": p_eng.stats["decode_fast_steps"],
        "fused_tokens_per_s": round(fused_tps, 1),
        "fused_throughput_ratio": round(fused_ratio, 3)
        if fused_ratio is not None else None,
        "fused_ticks": f_eng.stats["fused_ticks"],
        "ffn_steps": f_eng.stats["ffn_steps"],
        "tick_pallas_launches": tick_launches,
        "tick_launch_budget": launch_budget,
    }


def main() -> int:
    t0 = time.perf_counter()
    try:
        payload = run()
    except Exception as e:  # noqa: BLE001 — the artifact must exist
        payload = {"ok": False, "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-800:]}
    payload["wall_s"] = round(time.perf_counter() - t0, 1)
    print(json.dumps(payload))
    return 0 if payload.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
