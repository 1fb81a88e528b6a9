"""DP overlap/sharding smoke: barrier vs overlap vs sharded step time on the
8-virtual-device CPU mesh. Prints ONE JSON line; exit 0 iff ok.

The drill for the data-parallel hot path:
- parity: overlapped and sharded updates must match the barrier baseline
- overlap: grad collectives issue from backward hooks (Task handles
  outstanding before the drain) and the overlap-efficiency gauge holds
- sharding: optimizer state is 1/N per device under FLAGS_dp_shard_update

Timing ratios on a CPU host are noisy, so `ok` gates on correctness and the
efficiency floor; the ms numbers are reported for trend logging only.
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

N_DEV = 8
os.environ["JAX_PLATFORMS"] = "cpu"
flag = f"--xla_force_host_platform_device_count={N_DEV}"
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + flag).strip()

import numpy as np  # noqa: E402

EFFICIENCY_FLOOR = 0.5  # CPU fallback collectives are cheap; a healthy
                        # overlap drain hides nearly all of the wait
WIRE_RATIO_FLOOR = 3.5  # int8 + per-block f32 scale vs the fp32 wire
                        # (4x minus scale overhead; block 256 -> 3.94x)
INT8_CURVE_TOL = 0.01   # max per-step loss drift of the int8+error-feedback
                        # curve vs fp32 after CURVE_STEPS steps
CURVE_STEPS = 8


def _median_step_ms(d, so, steps=6):
    import paddle_tpu as paddle

    times = []
    for i in range(steps):
        x = paddle.to_tensor(
            np.random.RandomState(i).randn(16, 64).astype(np.float32))
        t0 = time.perf_counter()
        d(x).mean().backward()
        so.step()
        so.clear_grad()
        times.append(time.perf_counter() - t0)
    return statistics.median(times[1:]) * 1e3


def run() -> dict:
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu import observability as obs
    from paddle_tpu.core import flags

    os.environ["PADDLE_TRAINERS_NUM"] = str(N_DEV)
    dist.init_parallel_env()
    g = dist.get_group(0)
    assert g is not None and g.nranks == N_DEV, "8-rank group unavailable"

    def build():
        paddle.seed(0)
        return nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                             nn.Linear(128, 64), nn.ReLU(),
                             nn.Linear(64, 8))

    def train(overlap, shard):
        flags.set_flags({"dp_overlap": overlap, "dp_shard_update": shard})
        m = build()
        d = dist.DataParallel(m, group=g)
        o = opt.Adam(learning_rate=1e-3, parameters=m.parameters())
        so = dist.sharded_update(o, d) if shard else o
        ms = _median_step_ms(d, so)
        w = [np.asarray(p._data) for p in m.parameters()]
        return ms, w, d, so

    barrier_ms, w_barrier, _, _ = train(False, False)
    overlap_ms, w_overlap, d_ov, _ = train(True, False)
    # hook issue evidence: one extra backward with no drain yet
    d_ov(paddle.to_tensor(np.ones((4, 64), np.float32))).mean().backward()
    issued_in_backward = bool(d_ov._reducer._outstanding)
    d_ov.sync_gradients()
    shard_ms, w_shard, _, so = train(True, True)
    opt_bytes = so.optimizer_state_bytes_per_device()
    eff = obs.summary().get("dp_overlap_efficiency", 0.0)
    flags.set_flags({"dp_overlap": True, "dp_shard_update": False})

    parity_overlap = all(np.array_equal(a, b)
                         for a, b in zip(w_barrier, w_overlap))
    parity_shard = all(np.array_equal(a, b)
                       for a, b in zip(w_barrier, w_shard))

    # ---- int8 wire leg (quant_comm block codec + error feedback) -------
    def grads_once(dtype):
        flags.set_flags({"dp_overlap": True, "dp_shard_update": False,
                         "dp_grad_comm_dtype": dtype})
        m = build()
        d = dist.DataParallel(m, group=g)
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(16, 64).astype(np.float32))
        d(x).mean().backward()
        d.sync_gradients()
        return [np.asarray(p._grad) for p in m.parameters()]

    def curve(dtype):
        flags.set_flags({"dp_overlap": True, "dp_shard_update": False,
                         "dp_grad_comm_dtype": dtype})
        m = build()
        d = dist.DataParallel(m, group=g)
        o = opt.Adam(learning_rate=1e-3, parameters=m.parameters())
        losses = []
        for i in range(CURVE_STEPS):
            x = paddle.to_tensor(
                np.random.RandomState(i).randn(16, 64).astype(np.float32))
            loss = d(x).mean()
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(np.asarray(loss._data)))
        return losses, d

    g_ref = grads_once("")
    g_q8 = grads_once("int8")
    # per-block error is bounded by blockwise absmax/254; gate at 1% of
    # the global grad magnitude (a ~2.5x margin over the bound)
    grad_tol = max(1e-6, max(float(np.max(np.abs(a))) for a in g_ref) / 100)
    int8_grad_err = max(float(np.max(np.abs(a - b)))
                        for a, b in zip(g_ref, g_q8))

    curve_ref, _ = curve("")
    obs.reset()  # isolate the wire-bytes counters to the int8 run
    curve_q8, d_q8 = curve("int8")
    int8_curve_err = max(abs(a - b) for a, b in zip(curve_ref, curve_q8))
    wire = obs.summary()["dp"]
    # steady state: two more steps must not build new pack executables
    builds_now = obs.registry().value("paddle_dp_flat_pack_calls_total")
    trace_now = obs.registry().value("paddle_dp_flat_pack_builds_total")
    o_q8 = opt.Adam(learning_rate=1e-3,
                    parameters=d_q8._layers.parameters())
    for i in range(2):
        x = paddle.to_tensor(
            np.random.RandomState(i).randn(16, 64).astype(np.float32))
        d_q8(x).mean().backward()
        o_q8.step()
        o_q8.clear_grad()
    int8_zero_retraces = bool(
        obs.registry().value("paddle_dp_flat_pack_builds_total")
        == trace_now
        and obs.registry().value("paddle_dp_flat_pack_calls_total")
        > builds_now)
    flags.set_flags({"dp_grad_comm_dtype": ""})
    full_bytes = sum(
        int(getattr(a, "nbytes", 0))
        for store in so.inner._accumulators.values()
        for a in store.values())
    checks = {
        "parity_overlap": parity_overlap,
        "parity_shard": parity_shard,
        "hooks_issue_in_backward": issued_in_backward,
        "overlap_efficiency_floor": bool(eff >= EFFICIENCY_FLOOR),
        "opt_state_sharded": bool(0 < opt_bytes < full_bytes),
        "int8_grad_parity": bool(int8_grad_err <= grad_tol),
        "int8_loss_curve": bool(int8_curve_err <= INT8_CURVE_TOL),
        "int8_wire_ratio": bool(
            wire["wire_compression_ratio"] >= WIRE_RATIO_FLOOR),
        "int8_zero_retraces": int8_zero_retraces,
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "barrier_ms": round(barrier_ms, 3),
        "overlap_ms": round(overlap_ms, 3),
        "shard_ms": round(shard_ms, 3),
        "ratio": round(overlap_ms / barrier_ms, 3) if barrier_ms else None,
        "overlap_efficiency": eff,
        "opt_state_bytes_per_dev": opt_bytes,
        "int8_grad_err": int8_grad_err,
        "int8_curve_err": int8_curve_err,
        "int8_wire_ratio": wire["wire_compression_ratio"],
        "int8_wire_bytes": wire["wire_bytes"],
        "devices": len(jax.devices()),
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
