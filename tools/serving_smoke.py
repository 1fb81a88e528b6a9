"""Paged-vs-dense serving smoke: the paged continuous-batching engine
against the dense slot engine on the same request trace. Prints ONE JSON
line; exit 0 iff ok.

The drill for the serving subsystem:
- parity: paged greedy outputs must match the dense-slot engine
  token-for-token across the whole trace
- throughput: paged tokens/s >= dense tokens/s on a production-shaped
  trace (shared prompt prefixes, more requests than dense slots, short
  generations) — the prefix cache and the single fused mixed step are
  what buy the margin, so this is the acceptance line for the subsystem
- steady state: the timed passes add ZERO step-executable builds
  (engine.stats["step_builds"]), i.e. no retraces after warmup
- the prefix cache actually served tokens during the timed pass

Both engines are warmed on the full trace first; for the paged engine the
warm pass also populates the prefix cache, which is the point — a serving
pool in steady state has seen its traffic's shared prefixes. TTFT is
measured for both (time to the first harvested token after submission)
and reported for trend logging; only throughput is gated because CPU
timing ratios at this scale are noisy.
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

N_REQS = 24          # > dense slots, so the dense engine queues
SHARED_LEN = 56      # shared prompt prefix (7 full 8-token pages)
UNIQ_LEN = 4         # per-request unique suffix
NEW_TOKENS = 6
TIMED_REPEATS = 2    # best-of to tame CPU scheduling noise


def _trace(vocab: int, seed: int = 0):
    rs = np.random.RandomState(seed)
    shared = rs.randint(1, vocab, size=SHARED_LEN).tolist()
    return [shared + rs.randint(1, vocab, size=UNIQ_LEN).tolist()
            for _ in range(N_REQS)]


def _submit_all(eng, prompts, sampled=False):
    # sampled=True mixes greedy and sampled rows in one batch (odd
    # requests sample with fixed per-request temperature/top_p/seed), so
    # parity legs exercise BOTH tails of the step executable
    rids = []
    for i, p in enumerate(prompts):
        kw = {"max_new_tokens": NEW_TOKENS}
        if sampled and i % 2:
            kw.update(temperature=0.7 + 0.02 * i, top_p=0.85,
                      seed=1000 + i)
        rids.append(eng.submit(p, **kw))
    return rids


def _drain(eng, rids):
    by_rid = {c.rid: c.output_tokens for c in eng.run()}
    return [by_rid[r] for r in rids]


def _run_dense(cfg, params, prompts):
    from paddle_tpu.inference.serving import ServingEngine

    eng = ServingEngine(cfg, params, num_slots=4, max_len=cfg.max_seq_len,
                        chunk=NEW_TOKENS)
    _drain(eng, _submit_all(eng, prompts))            # warm (compiles)
    best_tps, ttft_ms, outputs = 0.0, None, None
    for _ in range(TIMED_REPEATS):
        t0 = time.perf_counter()
        rids = _submit_all(eng, prompts)
        eng.step()                                    # first tokens exist now
        ttft = time.perf_counter() - t0
        outputs = _drain(eng, rids)
        wall = time.perf_counter() - t0
        best_tps = max(best_tps, N_REQS * NEW_TOKENS / wall)
        ttft_ms = ttft * 1e3 if ttft_ms is None else min(ttft_ms, ttft * 1e3)
    return outputs, best_tps, ttft_ms


def _run_paged(cfg, params, prompts, pallas=None, pallas_ffn=None,
               sampled=False):
    from paddle_tpu.inference.serving import PagedServingEngine

    # paged memory is why the batch can be wider than the dense engine's
    # slot count: no per-slot max_len reservation, and the shared prefix
    # is stored once — the whole trace decodes in one wave
    eng = PagedServingEngine(cfg, params, num_blocks=224, block_size=8,
                             max_batch=N_REQS, token_budget=32,
                             max_len=cfg.max_seq_len, pallas=pallas,
                             pallas_ffn=pallas_ffn)
    _drain(eng, _submit_all(eng, prompts, sampled))   # warm + seed prefix cache
    builds_warm = eng.stats["step_builds"]
    hits0 = eng.blocks.stats["prefix_hit_tokens"]
    best_tps, ttft_ms, outputs = 0.0, None, None
    for _ in range(TIMED_REPEATS):
        t0 = time.perf_counter()
        rids = _submit_all(eng, prompts, sampled)
        ttft = None
        while ttft is None and eng.has_work():
            if any(e.token >= 0 for e in eng.step()):
                ttft = time.perf_counter() - t0
        outputs = _drain(eng, rids)
        wall = time.perf_counter() - t0
        best_tps = max(best_tps, N_REQS * NEW_TOKENS / wall)
        if ttft is not None:
            ttft_ms = (ttft * 1e3 if ttft_ms is None
                       else min(ttft_ms, ttft * 1e3))
    return (outputs, best_tps, ttft_ms,
            eng.stats["step_builds"] - builds_warm,
            eng.blocks.stats["prefix_hit_tokens"] - hits0,
            eng.stats)


def run() -> dict:
    import jax

    from paddle_tpu import observability as obs
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=96, dtype=np.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _trace(cfg.vocab_size)

    dense_out, dense_tps, dense_ttft_ms = _run_dense(cfg, params, prompts)
    (paged_out, paged_tps, paged_ttft_ms,
     builds_timed, prefix_hit_tokens, _) = _run_paged(cfg, params, prompts)

    # pallas leg: forced through the paged-attention kernel (interpret
    # mode on CPU, real kernel on TPU). Token parity is gated everywhere;
    # the throughput ratio only REDs where the flag would actually enable
    # the kernel (available() == real TPU) — interpret-mode timing on CPU
    # is an emulation artifact, reported for trend only.
    from paddle_tpu.ops.pallas import paged_attention as PA
    (pallas_out, pallas_tps, _, pallas_builds_timed, _,
     pallas_stats) = _run_paged(cfg, params, prompts, pallas=True)
    pallas_ratio = pallas_tps / paged_tps if paged_tps else None

    # fused decode tick: paged attention + fused FFN + one-launch sampler
    # prep. Greedy leg gates bit-exact token parity vs the stock paged
    # engine; the sampled legs re-run the trace with mixed greedy/sampled
    # rows (fixed per-request seeds) on BOTH engines and gate bit-exact
    # parity there too — the fused sampler's masking math must match
    # `_sample_rows` to the bit. Launch budget: the fused-tick executable's
    # distinct traced Pallas launches must stay within 3·layers + 1.
    (fused_out, fused_tps, _, fused_builds_timed, _,
     fused_stats) = _run_paged(cfg, params, prompts, pallas=True,
                               pallas_ffn=True)
    fused_ratio = fused_tps / paged_tps if paged_tps else None
    launch_budget = 3 * cfg.num_layers + 1
    tick_launches = fused_stats["tick_pallas_launches"]
    (sampled_stock, *_rest) = _run_paged(cfg, params, prompts, sampled=True)
    (sampled_fused, _, _, sampled_builds_timed, _,
     _) = _run_paged(cfg, params, prompts, pallas=True, pallas_ffn=True,
                     sampled=True)

    serving = obs.summary().get("serving", {})
    checks = {
        "parity": paged_out == dense_out,
        "throughput_paged_ge_dense": bool(paged_tps >= dense_tps),
        "zero_retraces_steady_state": builds_timed == 0,
        "prefix_cache_served": prefix_hit_tokens > 0,
        "pallas_parity": pallas_out == paged_out,
        "pallas_zero_retraces": pallas_builds_timed == 0,
        "pallas_not_slower_when_enabled": bool(
            not PA.available() or (pallas_ratio or 0.0) >= 1.0),
        "fused_parity": fused_out == paged_out,
        "fused_sampled_parity": sampled_fused == sampled_stock,
        "fused_zero_retraces": (fused_builds_timed == 0
                                and sampled_builds_timed == 0),
        "fused_ticks_ran": fused_stats["fused_ticks"] > 0,
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
        "paged_tokens_per_s": round(paged_tps, 1),
        "dense_tokens_per_s": round(dense_tps, 1),
        "throughput_ratio": round(paged_tps / dense_tps, 3)
        if dense_tps else None,
        "paged_ttft_ms": round(paged_ttft_ms, 2)
        if paged_ttft_ms is not None else None,
        "dense_ttft_ms": round(dense_ttft_ms, 2)
        if dense_ttft_ms is not None else None,
        "prefix_hit_tokens_timed": prefix_hit_tokens,
        "step_builds_timed": builds_timed,
        "pallas_tokens_per_s": round(pallas_tps, 1),
        "pallas_throughput_ratio": round(pallas_ratio, 3)
        if pallas_ratio is not None else None,
        "pallas_available": PA.available(),
        "pallas_steps": pallas_stats["pallas_steps"],
        "pallas_decode_fast_steps": pallas_stats["decode_fast_steps"],
        "fused_tokens_per_s": round(fused_tps, 1),
        "fused_throughput_ratio": round(fused_ratio, 3)
        if fused_ratio is not None else None,
        "fused_ticks": fused_stats["fused_ticks"],
        "ffn_steps": fused_stats["ffn_steps"],
        "tick_pallas_launches": tick_launches,
        "tick_launch_budget": launch_budget,
        "ttft_p50_s": serving.get("ttft_p50_s"),
        "tpot_p50_s": serving.get("tpot_p50_s"),
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
