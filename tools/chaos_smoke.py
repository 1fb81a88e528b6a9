"""Chaos smoke: the fault-tolerance acceptance drill as a CI runner.

Runs the same scenario as tests/test_fault_tolerance.py::
test_e2e_chaos_training_loop — a short CPU training loop with one
injected NaN step and one injected collective timeout — and checks the
recovery invariants:

- every recorded loss is finite and the model actually trained
- exactly one rollback and one collective retry appear in the metrics
  registry (recovery is *observed*, not assumed)
- the final checkpoint publishes and loads back with CRC verification

Prints ONE json line and exits non-zero on any violation, so CI can gate
on it::

    python tools/chaos_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SPEC = ("dispatch:nan@op=mean;step=3;count=1, "
        "collective:timeout@op=all_reduce;count=1")
STEPS = 8


def run() -> dict:
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    from paddle_tpu import observability
    from paddle_tpu.distributed.fault_tolerance import (CheckpointManager,
                                                        chaos)

    t0 = time.perf_counter()
    reg = observability.registry()
    rb0 = reg.value("paddle_ckpt_rollbacks_total")
    cr0 = reg.value("paddle_collective_retries_total", {"op": "all_reduce"})

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=model.parameters())
    tmpdir = tempfile.mkdtemp(prefix="chaos_smoke_")
    cm = CheckpointManager(directory=tmpdir, model=model, optimizer=opt,
                           interval=2, async_save=False)
    chaos.reconfigure(SPEC)

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(16, 4).astype(np.float32))
    y = paddle.to_tensor(rng.randn(16, 4).astype(np.float32))
    losses = []
    guard = 0
    while len(losses) < STEPS:
        guard += 1
        if guard > STEPS * 5:
            raise RuntimeError("rollback loop did not converge")
        out = model(x)
        loss = ((out - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        sync = paddle.to_tensor(np.ones(2, np.float32))
        dist.all_reduce(sync)
        if cm.on_step(loss):
            continue  # poisoned step rolled back: re-run it
        losses.append(float(loss))
    chaos.reconfigure("")

    rollbacks = reg.value("paddle_ckpt_rollbacks_total") - rb0
    retries = reg.value("paddle_collective_retries_total",
                        {"op": "all_reduce"}) - cr0
    injections = reg.value("paddle_chaos_injections_total")

    model2 = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 4))
    opt2 = paddle.optimizer.SGD(learning_rate=0.05,
                                parameters=model2.parameters())
    cm2 = CheckpointManager(directory=tmpdir, model=model2, optimizer=opt2,
                            interval=2, async_save=False)
    loaded_step = cm2.load_latest()
    reload_ok = loaded_step == STEPS and all(
        bool(np.allclose(v.numpy(), model.state_dict()[k].numpy(),
                         rtol=1e-6))
        for k, v in model2.state_dict().items())

    checks = {
        "losses_finite": all(np.isfinite(l) for l in losses),
        "trained": losses[-1] < losses[0],
        "one_rollback": rollbacks == 1,
        "one_collective_retry": retries == 1,
        "checkpoint_reloads": reload_ok,
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "spec": SPEC,
        "steps": STEPS,
        "rollbacks": rollbacks,
        "collective_retries": retries,
        "chaos_injections_total": injections,
        "first_loss": round(losses[0], 6),
        "final_loss": round(losses[-1], 6),
        "loaded_step": loaded_step,
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
