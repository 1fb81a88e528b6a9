"""Resilient-serving smoke: the multi-replica router under a chaos
replica kill. Prints ONE JSON line; exit 0 iff ok.

The drill for the router subsystem:
- zero dropped streams: every admitted stream completes even though one
  of the two replicas is chaos-killed mid-trace
- failover parity: the merged outputs (streamed prefix on the dead
  replica + replayed continuation on the survivor) must match a single
  replica-shaped engine running the same trace token-for-token
- mid-stream failover actually happened: at least one stream had
  already emitted tokens when its replica died (the replay-and-confirm
  path ran, with zero confirm mismatches)
- survivor zero-retrace: the surviving replica absorbs the failed-over
  streams without a single new step-executable build
- nothing shed: the kill must not push any stream into the shed path
- throughput: the 2-replica router on the full trace stays >= 0.9x the
  single-replica-SUM baseline — one replica-shaped engine serving its
  half-trace share (replicas step serially on one host here, so the
  fleet can at best match the sum of its parts; the gate pins the
  router's bookkeeping, placement and harvest tax under 10%)

All greedy (seeded determinism is what failover correctness rests on,
and greedy is its strictest form: any divergence is a wrong token, not
a resampled one).
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

N_REQS = 12
SHARED_LEN = 16      # shared prompt prefix (2 full 8-token pages)
UNIQ_LEN = 4
NEW_TOKENS = 8
KILL_CALL = 7        # replica 0's 8th own step: its streams are decoding
ENGINE_KW = dict(num_blocks=96, block_size=8, max_batch=8, token_budget=32)


def _trace(vocab: int, seed: int = 0):
    rs = np.random.RandomState(seed)
    shared = rs.randint(1, vocab, size=SHARED_LEN).tolist()
    return [shared + rs.randint(1, vocab, size=UNIQ_LEN).tolist()
            for _ in range(N_REQS)]


def _factory(cfg, params):
    from paddle_tpu.inference.serving import PagedServingEngine

    def build():
        return PagedServingEngine(cfg, params, max_len=cfg.max_seq_len,
                                  **ENGINE_KW)

    return build


def _run_single(factory, prompts):
    """One replica-shaped engine: full-trace pass for the parity
    reference, half-trace pass for the single-replica-sum throughput
    baseline (one replica serving the share the router would hand it)."""
    eng = factory()

    def one_pass(batch):
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in batch]
        done = {c.rid: c.output_tokens for c in eng.run()}
        dt = time.perf_counter() - t0
        return [done[r] for r in rids], len(batch) * NEW_TOKENS / dt

    one_pass(prompts)                             # warm + compile
    outputs, full_tps = one_pass(prompts)
    # best-of-2: the first cached-prefix repeat may still compile the
    # COW page-copy executable
    share_tps = max(one_pass(prompts[:N_REQS // 2])[1] for _ in range(2))
    return outputs, full_tps, share_tps


def _run_router_drill(factory, prompts):
    """2-replica router with replica 0 chaos-killed mid-decode."""
    from paddle_tpu.distributed.fault_tolerance import chaos
    from paddle_tpu.inference.serving import ServingRouter

    chaos.reconfigure(f"replica:kill@victim=0;call={KILL_CALL}")
    try:
        router = ServingRouter(factory, num_replicas=2, probation_s=1e9,
                               tenant_weights={"default": N_REQS})
        rids = [router.submit(p, max_new_tokens=NEW_TOKENS)
                for p in prompts]
        done = {c.rid: c for c in router.run()}
    finally:
        chaos.reconfigure("")
    outputs = [done[r].output_tokens if r in done else None for r in rids]
    reasons = [done[r].finish_reason if r in done else "MISSING"
               for r in rids]
    confirmed = sum(router._reqs[r].confirm_target for r in rids)
    return {
        "outputs": outputs,
        "all_length_finish": all(r == "length" for r in reasons),
        "completed": len(done),
        "failovers": router.stats["failovers"],
        "mismatches": router.stats["mismatches"],
        "shed": router.stats["shed"],
        "tokens_confirmed_on_replay": confirmed,
        "dead_replica_state": router.replicas[0].state,
        "survivor_step_builds": (
            router.replicas[1].engine.stats["step_builds"]
            if router.replicas[1].engine is not None else None),
    }


def _run_router_timed(factory, prompts):
    """2-replica router, no chaos: warm pass then timed pass."""
    from paddle_tpu.inference.serving import ServingRouter

    router = ServingRouter(factory, num_replicas=2,
                           tenant_weights={"default": N_REQS})

    def one_pass():
        t0 = time.perf_counter()
        rids = [router.submit(p, max_new_tokens=NEW_TOKENS)
                for p in prompts]
        done = {c.rid: c.output_tokens for c in router.run()}
        dt = time.perf_counter() - t0
        return [done[r] for r in rids], N_REQS * NEW_TOKENS / dt

    one_pass()                                    # warm both replicas
    best_out, best_tps = None, 0.0
    for _ in range(2):     # best-of-2 (see _run_single's COW note)
        out, tps = one_pass()
        if tps > best_tps:
            best_out, best_tps = out, tps
    return best_out, best_tps


def run() -> dict:
    import jax

    from paddle_tpu import observability as obs
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=96, dtype=np.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _trace(cfg.vocab_size)
    factory = _factory(cfg, params)

    single_out, single_tps, share_tps = _run_single(factory, prompts)
    drill = _run_router_drill(factory, prompts)
    router_out, router_tps = _run_router_timed(factory, prompts)

    fleet = obs.summary().get("router", {})
    checks = {
        "zero_dropped_streams": (drill["completed"] == N_REQS
                                 and drill["all_length_finish"]),
        "failover_parity": drill["outputs"] == single_out,
        "failover_happened": drill["failovers"] >= 1,
        "midstream_replay_confirmed": (
            drill["tokens_confirmed_on_replay"] > 0
            and drill["mismatches"] == 0),
        "nothing_shed": drill["shed"] == 0,
        "survivor_zero_retrace": drill["survivor_step_builds"] == 1,
        "steady_parity": router_out == single_out,
        "throughput_router_ge_0p9x_share": bool(
            router_tps >= 0.9 * share_tps),
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "requests": N_REQS,
        "prompt_len": SHARED_LEN + UNIQ_LEN,
        "new_tokens": NEW_TOKENS,
        "failovers": drill["failovers"],
        "tokens_confirmed_on_replay": drill["tokens_confirmed_on_replay"],
        "dead_replica_state": drill["dead_replica_state"],
        "router_tokens_per_s": round(router_tps, 1),
        "single_full_tokens_per_s": round(single_tps, 1),
        "single_share_tokens_per_s": round(share_tps, 1),
        "throughput_ratio_vs_share": round(router_tps / share_tps, 3)
        if share_tps else None,
        "ttft_p50_s": fleet.get("ttft_p50_s"),
        "tpot_p50_s": fleet.get("tpot_p50_s"),
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
