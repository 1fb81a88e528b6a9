"""Autotuner smoke: the cost model's ranking claim on a 3-candidate toy
space, end to end. Prints ONE JSON line; exit 0 iff ok.

The drill for the tuner subsystem:
- FRESH op measurements (not the pinned baseline — a stale pin would
  let the model agree with itself) feed the analytic cost model, three
  serving candidates are predicted, and every one is measured with
  real warm decode ticks: the analytic top-1 must equal the measured
  top-1 — the whole point of a cost model is that its cheapest
  candidate is the one you'd pick by measuring;
- the predicted-vs-measured gap of the winner stays under GAP_BUDGET
  (the model may be off, but bounded — an unbounded gap means the
  pruning margin no longer protects the measured winner);
- pruning at FLAGS_tune_prune_ratio never discards the measured
  winner on this space;
- the winner round-trips through the tuned-profile manifest (save ->
  load -> CRC ok -> topology ok -> apply) and an engine built under the
  applied profile serves a full trace with ZERO new step-executable
  builds after its two warmup steps — profiles are a pure flag
  assignment made before tracing, so the steady state never retraces.

The candidates differ along the axes the cost model actually ranks on
CPU: step geometry (max_batch) and the fused-vs-stock FFN choice.
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

# measured-vs-predicted tolerance for the winner: the model composes
# microsecond op pins into a whole-tick estimate, so 2.5x covers host
# jitter without letting the model drift into uselessness
GAP_BUDGET = 2.5
MEASURE_REPS = 8


def _candidates():
    from paddle_tpu.tuner import Candidate

    return [
        Candidate(),                                   # stock, hand-picked
        Candidate(max_batch=16),                       # bigger step
        Candidate(pallas_ffn=True),                    # the fused FFN
    ]


def run() -> dict:
    import jax

    from paddle_tpu import tuner
    from paddle_tpu.core import flags
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=np.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))

    # fresh measurements for exactly the anchor entries the serving cost
    # model composes — the smoke must hold on today's machine state, not
    # on whatever the pinned baseline remembers
    costs = tuner.OpCosts()
    costs.refresh(["decode_tick_stock", "block_mha_decode_stock",
                   "ffn_fwd_stock", "ffn_fwd_pallas"], reps=MEASURE_REPS)
    model = tuner.CostModel(costs=costs)
    workload = tuner.Workload("tune_smoke_serving", kind="serving",
                              tick_layers=cfg.num_layers)

    engines = {}

    def _engine(c):
        eng = PagedServingEngine(
            cfg, params, block_size=8, max_batch=c.max_batch,
            token_budget=c.token_budget, max_len=cfg.max_seq_len,
            pallas_ffn=c.pallas_ffn)
        rs = np.random.RandomState(7)
        for _ in range(c.max_batch):
            eng.submit(rs.randint(1, cfg.vocab_size, 12).tolist(),
                       max_new_tokens=64)
        eng.step()   # prefill executable
        eng.step()   # decode executable — steady state from here
        return eng

    def runner(c):
        eng = engines.get(c)
        if eng is None:
            eng = engines[c] = _engine(c)
        t0 = time.perf_counter()
        eng.step()
        return (time.perf_counter() - t0) / c.max_batch

    cands = _candidates()
    ranked = tuner.search(model, workload, cands, topk=len(cands),
                          prune_ratio=1e9)   # rank all 3, no pruning yet
    analytic_top1 = ranked[0].candidate
    measured = tuner.validate_candidates(
        [tuner.Ranked(r.candidate, r.predicted) for r in ranked], runner)
    measured_top1 = measured[0].candidate
    winner = measured[0]
    gap = (winner.measured_s / winner.cost) if winner.cost > 0 else 0.0
    if gap < 1.0 and gap > 0:
        gap = 1.0 / gap

    # pruning at the shipped ratio must keep the measured winner
    pruned = tuner.search(model, workload, cands, topk=len(cands))
    pruned_keeps_winner = any(r.candidate == measured_top1 for r in pruned)

    # manifest round-trip + zero-retrace application
    prof = tuner.TunedProfile(
        workload=workload.name, topology=tuner.topology_signature(),
        flags=measured_top1.to_flags(), predicted_cost=winner.cost,
        measured_s=winner.measured_s, source_key=costs.key,
        candidates_considered=len(cands))
    import tempfile

    path = os.path.join(tempfile.mkdtemp(prefix="tune_smoke_"),
                        "profile.json")
    tuner.save_profile(prof, path)
    loaded = tuner.load_profile(path)
    roundtrip_ok = (loaded.flags == prof.flags
                    and loaded.candidate() == measured_top1)
    flags.set_flags({"tuned_profile": path})
    try:
        eng = PagedServingEngine(cfg, params, block_size=8,
                                 max_len=cfg.max_seq_len)
        profile_geometry_ok = (eng.max_batch == measured_top1.max_batch
                               and eng.token_budget
                               == measured_top1.token_budget)
        rs = np.random.RandomState(11)
        for _ in range(eng.max_batch):
            eng.submit(rs.randint(1, cfg.vocab_size, 10).tolist(),
                       max_new_tokens=12)
        eng.step()
        eng.step()
        builds_warm = eng.stats["step_builds"]
        done = eng.run()
        retraces = eng.stats["step_builds"] - builds_warm
        served_ok = len(done) == eng.max_batch
    finally:
        flags.set_flags({"tuned_profile": ""})

    checks = {
        "analytic_top1_matches_measured": analytic_top1 == measured_top1,
        "gap_within_budget": 0 < gap <= GAP_BUDGET,
        "pruning_keeps_measured_winner": pruned_keeps_winner,
        "profile_roundtrip": roundtrip_ok,
        "profile_sets_geometry": profile_geometry_ok,
        "zero_steady_state_retraces": retraces == 0,
        "served_under_profile": served_ok,
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "analytic_top1": analytic_top1.describe(),
        "measured_top1": measured_top1.describe(),
        "winner_predicted_us_per_tok": round(winner.cost * 1e6, 2),
        "winner_measured_us_per_tok": round(winner.measured_s * 1e6, 2),
        "gap_ratio": round(gap, 3),
        "gap_budget": GAP_BUDGET,
        "candidates": [r.candidate.describe() for r in measured],
        "steady_state_retraces": retraces,
        "source_key": costs.key,
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
