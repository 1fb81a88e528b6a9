"""Pipeline-parallel smoke: 1F1B on 4 virtual CPU devices vs pp=1.

The drill for the MPMD pipeline subsystem
(distributed.pipeline). Prints ONE JSON line; exit 0 iff ok. Gates:

- parity: pp=2 1F1B with 8 microbatches trains within float32-ulp
  tolerance of the pp=1 engine run (same microbatch accumulation order)
- bubble: the engine's simulated bubble fraction equals the closed form
  (pp-1)/(m+pp-1) within EPS — the schedule the engine executes is the
  one the math describes
- retraces: paddle_pp_stage_builds_total is constant after the warmup
  batch (signature-keyed executable cache; zero steady-state retraces)

Step times (naive-sequential GPipe vs 1F1B) are reported for trend
logging only — virtual CPU devices share one threadpool, so wall-clock
overlap is not gated here.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

N_DEV = 4
os.environ["JAX_PLATFORMS"] = "cpu"
flag = f"--xla_force_host_platform_device_count={N_DEV}"
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + flag).strip()

import numpy as np  # noqa: E402

EPS = 1e-9          # the simulation reproduces the closed form exactly
PARITY_TOL = 1e-5   # float32 ulp-level: stage-split XLA fusion may flip
                    # the last bit vs the single-kernel pp=1 run
Q_TOL = 0.25        # int8 handoffs round every stage boundary and SGD
                    # lr=0.1 amplifies the trajectory drift (observed
                    # ~0.12 on CPU); the gate is a blowup/NaN tripwire,
                    # with quantized_p2p_trains guarding the direction
Q_RATIO_FLOOR = 3.0  # int8 payload + one f32 scale per (clamped) block
PP, M = 2, 8
D_IN, D_HID, D_OUT = 16, 32, 4


def run() -> dict:
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers import (
        pp_layers)
    from paddle_tpu.distributed.pipeline import (
        PipelineEngine, closed_form_bubble)

    def _mse(out, label):
        return ((out - label) ** 2).mean()

    def _descs():
        return [pp_layers.LayerDesc(nn.Linear, D_IN, D_HID),
                pp_layers.LayerDesc(nn.ReLU),
                pp_layers.LayerDesc(nn.Linear, D_HID, D_HID),
                pp_layers.LayerDesc(nn.ReLU),
                pp_layers.LayerDesc(nn.Linear, D_HID, D_HID),
                pp_layers.LayerDesc(nn.ReLU),
                pp_layers.LayerDesc(nn.Linear, D_HID, D_OUT)]

    def _seed(model):
        rs = np.random.RandomState(0)
        for p in model.parameters():
            p.set_value(paddle.to_tensor(
                rs.normal(scale=0.3, size=p.shape).astype(np.float32)))

    rs = np.random.RandomState(1)
    x = paddle.to_tensor(rs.normal(size=(M, D_IN)).astype(np.float32))
    y = paddle.to_tensor(rs.normal(size=(M, D_OUT)).astype(np.float32))

    def train(pp, schedule="1F1B", steps=4):
        model = pp_layers.PipelineLayer(layers=_descs(), loss_fn=_mse,
                                        num_stages=pp)
        _seed(model)
        engine = PipelineEngine(model, accumulate_steps=M,
                                schedule=schedule)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        losses, times = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            loss = engine.run(x, y, train=True)
            opt.step()
            opt.clear_grad()
            times.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(loss._data)))
        return (losses, [p.numpy().copy() for p in model.parameters()],
                statistics.median(times[1:]) * 1e3, engine)

    ref_losses, ref_w, _, _ = train(1)
    losses, w, f1b_ms, engine = train(PP)
    _, _, gpipe_ms, _ = train(PP, schedule="gpipe")

    bubble = engine.schedule_stats["bubble_fraction"]
    bound = closed_form_bubble(PP, M)

    builds_after_warmup = None
    builds_now = obs.registry().value("paddle_pp_stage_builds_total")
    # steady state established above (4 steps): two more runs must not build
    for p in engine.model.parameters():
        p._grad = None
    engine.run(x, y, train=True)
    builds_after_warmup = obs.registry().value(
        "paddle_pp_stage_builds_total")

    loss_err = max(abs(a - b) for a, b in zip(losses, ref_losses))
    w_err = max(float(np.max(np.abs(a - b))) for a, b in zip(w, ref_w))

    # quantized-P2P leg: same pp=2 run with int8 stage handoffs
    # (FLAGS_pp_p2p_comm_dtype); gates on loss parity vs pp=1 at the
    # looser int8 tolerance plus the wire-bytes ratio from the metrics
    from paddle_tpu.core import flags
    obs.reset()  # isolate the pp wire counters to the quantized run
    flags.set_flags({"pp_p2p_comm_dtype": "int8"})
    try:
        q_losses, _, _, _ = train(PP)
    finally:
        flags.set_flags({"pp_p2p_comm_dtype": ""})
    q_loss_err = max(abs(a - b) for a, b in zip(q_losses, ref_losses))
    q_wire = obs.summary()["pipeline"]

    checks = {
        "loss_parity_vs_pp1": bool(loss_err <= PARITY_TOL),
        "weight_parity_vs_pp1": bool(w_err <= PARITY_TOL),
        "bubble_matches_closed_form": bool(abs(bubble - bound) <= EPS),
        "zero_steady_state_retraces": bool(builds_after_warmup
                                           == builds_now),
        "quantized_p2p_loss_parity": bool(q_loss_err <= Q_TOL),
        "quantized_p2p_trains": bool(q_losses[-1] < q_losses[0]),
        "quantized_p2p_wire_ratio": bool(
            q_wire["wire_compression_ratio"] >= Q_RATIO_FLOOR),
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "pp": PP,
        "microbatches": M,
        "bubble_fraction": round(bubble, 6),
        "closed_form_bound": round(bound, 6),
        "loss_err": loss_err,
        "weight_err": w_err,
        "quantized_loss_err": q_loss_err,
        "quantized_wire_ratio": q_wire["wire_compression_ratio"],
        "quantized_wire_bytes": q_wire["wire_bytes"],
        "f1b_ms": round(f1b_ms, 3),
        "gpipe_ms": round(gpipe_ms, 3),
        "stage_builds": int(builds_now),
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
