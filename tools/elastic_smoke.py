"""Elastic smoke: the kill-one-rank drill as a CI gate.

Runs the acceptance scenario from tests/test_elastic_runtime.py::
test_rank_dead_drill_reconfigures_once_and_training_continues on the
CPU mesh — a short sharded-DP training loop where chaos kills rank 3
mid-collective — and checks the elastic invariants:

- exactly ONE reconfiguration happened (asserted from the metrics
  registry, not assumed from control flow)
- training resumed at N-1 on the surviving ranks and every loss is
  finite
- the post-shrink losses match an uninterrupted N-1 run of the same
  seeds within tolerance (the ZeRO-1 reshard preserved optimizer state)
- zero steady-state retraces: after the first post-shrink step
  compiles for the new mesh, later steps add no fused-update
  executables

Prints ONE json line and exits non-zero on any violation, so CI can gate
on it::

    python tools/elastic_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["PADDLE_TRAINERS_NUM"] = "4"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SPEC = "collective:rank_dead@victim=3;count=1"
WARM_STEPS = 2       # steps at the full world before the kill
POST_STEPS = 4       # steps that must land after the shrink


def _build(group=None):
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu.distributed.fault_tolerance import CheckpointManager

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(8, 16)
            self.l2 = nn.Linear(16, 4)

        def forward(self, x):
            import paddle_tpu.nn.functional as F

            return self.l2(F.relu(self.l1(x)))

    paddle.seed(7)
    m = dist.DataParallel(MLP(), group=group) if group is not None \
        else dist.DataParallel(MLP())
    inner = popt.Adam(parameters=m.parameters(), learning_rate=0.01)
    sopt = dist.sharded_update(inner, m)
    cm = CheckpointManager(model=m, optimizer=inner, interval=0)
    return m, sopt, cm


def _step(m, sopt, cm, seed):
    import numpy as np

    import paddle_tpu as paddle

    rng = np.random.RandomState(seed)
    x = paddle.to_tensor(rng.rand(4, 8).astype("float32"))
    loss = (m(x) ** 2).mean()
    loss.backward()
    sopt.step()
    sopt.clear_grad()
    cm.on_step(loss)
    return float(loss.numpy())


def run() -> dict:
    import numpy as np

    import paddle_tpu.distributed as dist
    from paddle_tpu import observability
    from paddle_tpu.core import flags
    from paddle_tpu.distributed import collective as coll
    from paddle_tpu.distributed.elastic import (ElasticRuntime,
                                                EpochChangedError)
    from paddle_tpu.distributed.elastic import epoch as ep
    from paddle_tpu.distributed.fault_tolerance import chaos

    t0 = time.perf_counter()
    reg = observability.registry()
    dist.init_parallel_env()
    flags.set_flags({"dp_shard_update": True})

    m, sopt, cm = _build()
    rt = ElasticRuntime(model=m, optimizer=sopt, checkpoint_manager=cm,
                        group=coll.get_group(0))
    rt.start()
    rc0 = reg.value("paddle_elastic_events_total", {"kind": "reconfigure"})
    rd0 = reg.value("paddle_elastic_events_total", {"kind": "rank_dead"})
    try:
        for i in range(WARM_STEPS):
            _step(m, sopt, cm, seed=i)
        chaos.reconfigure(SPEC)
        retried = 0
        post = []
        for i in range(WARM_STEPS, WARM_STEPS + POST_STEPS):
            while True:
                try:
                    post.append(_step(m, sopt, cm, seed=i))
                    break
                except EpochChangedError:
                    sopt.clear_grad()
                    retried += 1
                    if retried > 3:
                        raise RuntimeError("reconfigure loop did not settle")
            if len(post) == 2:
                # post-shrink warmup takes two steps (eager warmup on the
                # new accumulator shapes, then the fused build); nothing
                # after that may add an executable
                builds_after_warm = len(sopt.inner._fused_cache)
        builds_final = len(sopt.inner._fused_cache)
        chaos.reconfigure("")
        world = rt.group.nranks
        survivors = list(rt.group.ranks)
    finally:
        rt.stop()

    reconfigures = reg.value("paddle_elastic_events_total",
                             {"kind": "reconfigure"}) - rc0
    rank_deaths = reg.value("paddle_elastic_events_total",
                            {"kind": "rank_dead"}) - rd0
    world_gauge = reg.value("paddle_elastic_world_size")

    # reference: an uninterrupted run on the survivor world from step 0
    # (single-controller AVG collectives are world-size invariant, so the
    # drill's post-shrink losses must match these seeds exactly)
    ep._reset_for_tests()
    dist.collective.destroy_process_group()
    dist.init_parallel_env()
    m2, sopt2, cm2 = _build(group=coll.new_group(survivors))
    ref = [_step(m2, sopt2, cm2, seed=i)
           for i in range(WARM_STEPS + POST_STEPS)]
    loss_gap = max(abs(a - b) / max(abs(b), 1e-8)
                   for a, b in zip(post, ref[WARM_STEPS:]))

    checks = {
        "one_reconfigure": reconfigures == 1,
        "one_rank_death": rank_deaths == 1,
        "resumed_at_n_minus_1": world == 3 and survivors == [0, 1, 2]
        and world_gauge == 3,
        "losses_finite": all(np.isfinite(l) for l in post),
        "loss_matches_uninterrupted": loss_gap < 1e-4,
        "zero_steady_state_retraces": builds_final == builds_after_warm,
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "spec": SPEC,
        "retried_steps": retried,
        "reconfigures": reconfigures,
        "world": world,
        "survivors": survivors,
        "loss_gap": round(loss_gap, 8),
        "fused_builds_steady_state": builds_final - builds_after_warm,
        "post_losses": [round(l, 6) for l in post],
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def main() -> int:
    try:
        result = run()
    except Exception as e:  # noqa: BLE001 — the gate must report, not crash
        result = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    print(json.dumps(result), flush=True)
    return 0 if result.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
