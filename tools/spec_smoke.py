"""Speculative-decoding + LoRA-adapter smoke: the bit-exactness gate
for the multi-tenant serving tentpole. Prints ONE JSON line; exit 0
iff ok.

The drill for the spec/adapter
subsystem:

- spec parity: greedy outputs with a (different, smaller) draft model
  attached must equal plain greedy decode token-for-token — a wrong
  draft costs acceptance rate, never correctness;
- parity survives preemption: under a starved block pool the scheduler
  preempts and recomputes mid-stream; the epoch-guarded draft catch-up
  must keep the stream bit-exact (and at least one preemption must
  actually fire, or the drill proved nothing);
- parity survives failover: a 2-replica router with spec-enabled
  engines, replica 0 chaos-killed mid-decode — exactly one failover
  wave, zero replay mismatches, outputs equal the single-engine
  reference;
- adapter hot-swap under traffic with ZERO steady-state retraces:
  after one warm submit per rank class, alternating adapters (and a
  chaos mid-stream device evict) must add no step-executable builds —
  adapter routing is data, not a trace key;
- chaos adapter evict is invisible: the forcibly evicted adapter
  reloads (counted as a swap) and the stream completes bit-exact;
- acceptance_rate is reported and must be > 0 with a trained-enough
  draft (here: the target's own weights on the shared layer prefix);
  tokens/s speculated-vs-plain is reported as INFORMATIONAL (CPU
  interpret-mode hosts pay per-launch overhead a TPU doesn't).
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

N_REQS = 8
PROMPT_LEN = 8
NEW_TOKENS = 10
SPEC_K = 3
ENGINE_KW = dict(num_blocks=96, block_size=8, max_batch=8, token_budget=32)
STARVED_KW = dict(num_blocks=10, block_size=8, max_batch=8, token_budget=32)
KILL_CALL = 5


def _trace(vocab: int, seed: int = 0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, vocab, size=PROMPT_LEN).tolist()
            for _ in range(N_REQS)]


def _run(eng, prompts, adapters=None, max_new=NEW_TOKENS):
    rids = []
    for i, p in enumerate(prompts):
        kw = {}
        if adapters is not None and adapters[i] is not None:
            kw["adapter"] = adapters[i]
        rids.append(eng.submit(p, max_new_tokens=max_new, **kw))
    t0 = time.perf_counter()
    done = {c.rid: c.output_tokens for c in eng.run()}
    dt = time.perf_counter() - t0
    return [done.get(r) for r in rids], dt


def run() -> dict:
    import jax

    from paddle_tpu.distributed.fault_tolerance import chaos
    from paddle_tpu.inference.serving import (DraftModel,
                                              PagedServingEngine,
                                              ServingRouter, make_adapter)
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=96, dtype=np.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    # draft: half the layers of the TARGET's own weights — cheap enough
    # to matter, correlated enough that acceptance is well above zero
    dcfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                         intermediate_size=64, num_layers=1, num_heads=4,
                         num_kv_heads=2, max_seq_len=96, dtype=np.float32)
    dparams = {"embed": params["embed"],
               "final_norm": params["final_norm"],
               "lm_head": params["lm_head"],
               "blocks": jax.tree.map(lambda a: a[:1], params["blocks"])}
    prompts = _trace(cfg.vocab_size)

    def build(spec=False, **over):
        kw = dict(ENGINE_KW, **over)
        if spec:
            kw.update(draft=DraftModel(dcfg, dparams), spec_k=SPEC_K)
        return PagedServingEngine(cfg, params, max_len=cfg.max_seq_len,
                                  **kw)

    # -- plain parity + informational throughput --------------------------
    base = build()
    base_out, _ = _run(base, prompts)          # warm + compile
    base_out2, base_dt = _run(base, prompts)
    assert base_out == base_out2
    spec = build(spec=True)
    spec_out, _ = _run(spec, prompts)
    spec_out2, spec_dt = _run(spec, prompts)
    acceptance = spec.spec.acceptance_rate
    spec_ticks = spec.stats["spec_ticks"]

    # -- parity under forced preemption -----------------------------------
    sb = build(**STARVED_KW)
    sb_out, _ = _run(sb, prompts)
    ss = build(spec=True, **STARVED_KW)
    ss_out, _ = _run(ss, prompts)
    preemptions = ss.scheduler.stats["preemptions"]

    # -- adapter hot-swap + chaos evict, zero steady-state retraces -------
    ad_a = make_adapter(cfg, "tenant-a", rank=4, alpha=8.0, seed=3)
    ad_b = make_adapter(cfg, "tenant-b", rank=4, alpha=8.0, seed=4)
    eng = build(spec=True, adapter_slots=2)
    eng.adapters.register(ad_a)
    eng.adapters.register(ad_b)
    sel_a = ["tenant-a"] * N_REQS
    sel_ab = [("tenant-a" if i % 2 else "tenant-b")
              for i in range(N_REQS)]
    ref_a, _ = _run(eng, prompts, adapters=sel_a)     # warm: loads both
    ref_ab, _ = _run(eng, prompts, adapters=sel_ab)   # classes + packs
    builds0 = eng.stats["step_builds"]
    hot_a, _ = _run(eng, prompts, adapters=sel_a)
    hot_ab, _ = _run(eng, prompts, adapters=sel_ab)
    swap_builds = eng.stats["step_builds"] - builds0
    swaps0 = eng.adapters.stats["swaps"]
    chaos.reconfigure("adapter:evict@op=use;call=2")
    try:
        chaos_ab, _ = _run(eng, prompts, adapters=sel_ab)
    finally:
        chaos.reconfigure("")
    evict_swaps = eng.adapters.stats["swaps"] - swaps0
    chaos_builds = eng.stats["step_builds"] - builds0

    # -- failover mid-spec: replica kill, bit-exact continuation ----------
    chaos.reconfigure(f"replica:kill@victim=0;call={KILL_CALL}")
    try:
        router = ServingRouter(lambda: build(spec=True), num_replicas=2,
                               probation_s=1e9,
                               tenant_weights={"default": N_REQS})
        rids = [router.submit(p, max_new_tokens=NEW_TOKENS)
                for p in prompts]
        done = {c.rid: c for c in router.run()}
    finally:
        chaos.reconfigure("")
    fo_out = [done[r].output_tokens if r in done else None for r in rids]

    checks = {
        "spec_parity": spec_out == base_out and spec_out2 == base_out,
        "spec_actually_ran": spec_ticks > 0,
        "acceptance_rate_positive": acceptance > 0.0,
        "preemption_parity": ss_out == sb_out,
        "preemption_happened": preemptions >= 1,
        "hot_swap_parity": hot_a == ref_a and hot_ab == ref_ab,
        "hot_swap_zero_retrace": swap_builds == 0,
        "chaos_evict_bit_exact": chaos_ab == ref_ab,
        "chaos_evict_reloaded": evict_swaps >= 1,
        "chaos_evict_zero_retrace": chaos_builds == 0,
        "failover_parity": fo_out == base_out,
        "exactly_one_failover": router.stats["failovers"] == 1,
        "zero_replay_mismatches": router.stats["mismatches"] == 0,
        "nothing_shed": router.stats["shed"] == 0,
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "requests": N_REQS,
        "spec_k": SPEC_K,
        "acceptance_rate": acceptance,
        "spec_ticks": spec_ticks,
        "preemptions": preemptions,
        "adapter_swaps_on_evict": evict_swaps,
        "failovers": router.stats["failovers"],
        # informational only: CPU interpret hosts pay per-launch overhead
        # the TPU doesn't, so this ratio is NOT gated
        "tokens_per_s_ratio_spec_vs_plain": round(base_dt / spec_dt, 3)
        if spec_dt else None,
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
