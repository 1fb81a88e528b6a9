"""Distributed-tracing smoke: the span/fleet plane end to end. Prints
ONE JSON line; exit 0 iff ok.

The drill for the tracing subsystem:

- TTFT decomposition: one traced request through the serving router;
  the queue.wait + prefill.chunk spans must sum to the observed
  wall-clock TTFT within tolerance (never exceeding it — spans are
  measured sub-intervals, not estimates), and decode ticks must count
  one span per post-first token
- failover visibility: a chaos replica:kill mid-stream must leave ONE
  merged chrome trace where the replay shows up as a failover.replay
  span on the survivor under the request's ORIGINAL trace_id
- chrome export: the merged multi-rank document must survive a JSON
  round trip with timestamps sorted on the shared axis
- fleet percentiles: a registry snapshot published over the TCPStore
  and merged back must report TTFT/TPOT percentiles bit-for-bit equal
  to the local histogram's own percentile() — the merge is the same
  algorithm, not an approximation
- overhead: the emit choke point must stay within the ci_op_benchmark
  budget with the span plane ON
- zero-retrace: the traced request must not add a single step-executable
  build to a warmed engine (trace context never reaches a jitted
  signature)
"""
from __future__ import annotations

import json
import os
import socket
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

PROMPT_LEN = 6
NEW_TOKENS = 8
DRILL_TOKENS = 12
KILL_CALL = 3
TTFT_COVER_LO = 0.15   # decomposition must explain >=15% of wall TTFT
TTFT_COVER_HI = 1.05   # and never exceed it (timer-skew guard)
ENGINE_KW = dict(num_blocks=64, block_size=8, max_batch=4, token_budget=32)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _prompt(vocab: int, seed: int):
    return np.random.RandomState(seed).randint(
        1, vocab, PROMPT_LEN).tolist()


def run() -> dict:
    import jax

    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.fault_tolerance import chaos
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.inference.serving import (PagedServingEngine,
                                              ServingRouter)
    from paddle_tpu.models import llama as L
    from paddle_tpu.observability import fleet, tracing

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=96, dtype=np.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))

    def factory():
        return PagedServingEngine(cfg, params, max_len=cfg.max_seq_len,
                                  **ENGINE_KW)

    # -- 1) TTFT decomposition on a warmed single-replica router --------
    router = ServingRouter(factory, num_replicas=1)
    warm = router.submit(_prompt(cfg.vocab_size, 1),
                         max_new_tokens=NEW_TOKENS)
    list(router.stream(warm))                     # compile outside the clock
    builds_before = router.replicas[0].engine.stats["step_builds"]
    obs.reset()                                   # judged window starts clean

    t0 = time.perf_counter()
    rid = router.submit(_prompt(cfg.vocab_size, 2),
                        max_new_tokens=NEW_TOKENS)
    tid = router._reqs[rid].trace_id
    first_at = None
    n_tokens = 0
    for _tok in router.stream(rid):
        if first_at is None:
            first_at = time.perf_counter()
        n_tokens += 1
    wall_ttft = (first_at - t0) if first_at else 0.0
    builds_after = router.replicas[0].engine.stats["step_builds"]

    spans = tracing.finished_spans(trace_id=tid)
    qw_s = sum(d["dur_s"] for d in spans if d["name"] == "queue.wait")
    prefill_s = sum(d["dur_s"] for d in spans
                    if d["name"] == "prefill.chunk")
    decode = [d for d in spans if d["name"] == "decode.tick"]
    decomposed = qw_s + prefill_s
    cover = decomposed / wall_ttft if wall_ttft > 0 else 0.0

    # -- 2) chaos kill drill: replay visible in ONE merged trace --------
    chaos.reconfigure(f"replica:kill@victim=0;call={KILL_CALL}")
    try:
        drill = ServingRouter(factory, num_replicas=2, probation_s=1e9)
        drid = drill.submit(_prompt(cfg.vocab_size, 3),
                            max_new_tokens=DRILL_TOKENS)
        dtid = drill._reqs[drid].trace_id
        dtoks = list(drill.stream(drid))
    finally:
        chaos.reconfigure("")
    replays = [d for d in tracing.finished_spans(trace_id=dtid)
               if d["name"] == "failover.replay"]
    failover_ok = (len(dtoks) == DRILL_TOKENS
                   and drill._reqs[drid].trace_id == dtid
                   and len(replays) == 1
                   and replays[0]["parent_id"] == dtid
                   and replays[0]["fields"].get("replica") == 1)

    doc = tracing.to_chrome_trace()
    merged = tracing.merge_chrome_traces(
        [doc, (tracing.to_chrome_trace(), int(5e8), "rank1")])
    merged = json.loads(json.dumps(merged))       # the file format survives
    ts = [e["ts"] for e in merged["traceEvents"]]
    drill_names = {e["name"] for e in merged["traceEvents"]
                   if e["args"].get("trace_id") == dtid}
    chrome_ok = (bool(merged["traceEvents"]) and ts == sorted(ts)
                 and {"request", "failover.replay"} <= drill_names)

    # -- 3) fleet percentiles over the store, bit-for-bit ---------------
    store = TCPStore("127.0.0.1", _free_port(), is_master=True,
                     world_size=1)
    try:
        tracing.clock_handshake(store, 0)
        fleet.publish(store, 0)
        summ = fleet.fleet_summary(store=store, ranks=[0])
    finally:
        store.stop()
    reg = obs.registry()
    h_ttft = reg.get("paddle_serving_ttft_seconds")
    h_tpot = reg.get("paddle_serving_tpot_seconds")
    percentiles_present = all(
        isinstance(summ.get(k), float)
        for k in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
                  "shed_rate"))
    bitexact = (summ["ttft_p50_s"] == round(h_ttft.percentile(50), 9)
                and summ["ttft_p99_s"] == round(h_ttft.percentile(99), 9)
                and summ["tpot_p50_s"] == round(h_tpot.percentile(50), 9)
                and summ["tpot_p99_s"] == round(h_tpot.percentile(99), 9))

    # -- 4) emit overhead with the span plane ON ------------------------
    from ci_op_benchmark import measure_observability_overhead

    over = measure_observability_overhead(batch=1000, rounds=5)

    checks = {
        "ttft_decomposition_within_tolerance": bool(
            TTFT_COVER_LO <= cover <= TTFT_COVER_HI),
        "decode_tick_per_post_first_token": (
            len(decode) == NEW_TOKENS - 1),
        "stream_complete": n_tokens == NEW_TOKENS,
        "traced_request_zero_retrace": builds_after == builds_before,
        "failover_replay_on_survivor_same_trace": failover_ok,
        "merged_chrome_trace_loads_sorted": chrome_ok,
        "fleet_percentiles_present": percentiles_present,
        "fleet_percentiles_bitexact": bitexact,
        "overhead_within_budget": not over["exceeded"],
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "wall_ttft_s": round(wall_ttft, 6),
        "queue_wait_s": round(qw_s, 6),
        "prefill_s": round(prefill_s, 6),
        "ttft_cover": round(cover, 4),
        "decode_ticks": len(decode),
        "drill_failovers": drill.stats["failovers"],
        "replay_confirmed": (replays[0]["fields"].get("confirmed")
                             if replays else None),
        "merged_events": len(merged["traceEvents"]),
        "fleet_ttft_p50_s": summ["ttft_p50_s"],
        "fleet_tpot_p50_s": summ["tpot_p50_s"],
        "overhead_pct": round(over["overhead_pct"], 3),
        "overhead_us": round(over["overhead_us"], 4),
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
