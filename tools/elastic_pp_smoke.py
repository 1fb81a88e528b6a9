"""Elastic-pipeline smoke: the kill-one-stage drill as a CI gate.

The pp-axis sibling of tools/elastic_smoke.py. A 4-stage 1F1B pipeline
(8 homogeneous blocks, 8 microbatches, Adam) trains on the CPU mesh;
chaos drops stage 2 dead mid-microbatch (``pipeline:rank_dead``), and the
``FLAGS_elastic_pp`` runtime must fence the run, reshard the layer stack
to pp=2 bitwise, replay the aborted accumulation window, and keep
training. Gates:

- exactly ONE pipeline reconfiguration and ONE stage death, asserted
  from the metrics registry (paddle_elastic_events_total), not assumed
  from control flow
- the survivors resume at pp=2 and every post-death loss is finite
- loss_gap == 0.0 EXACTLY: the drill's post-death losses are bit-equal
  to an uninterrupted run that performed a *planned* downscale
  (``reshard_to(2)``) at the same step boundary — abort + bitwise
  reshard + window replay is indistinguishable from never having
  crashed at the new degree
- zero steady-state retraces: after the replay step compiles the pp=2
  stages, later steps add no stage executables
  (paddle_pp_stage_builds_total is constant)

Prints ONE json line; exit 0 iff ok::

    python tools/elastic_pp_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

N_DEV = 4
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flag = f"--xla_force_host_platform_device_count={N_DEV}"
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + flag).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SPEC = "pipeline:rank_dead@stage=2;count=1"
PP, NEW_PP, L, H, M = 4, 2, 8, 16, 8
WARM_STEPS = 2       # steps at pp=4 before the kill
POST_STEPS = 4       # steps that must land after the shrink


def _make_factory():
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers import (
        pp_layers)
    from paddle_tpu.distributed.pipeline import PipelineEngine

    def _mse(out, label):
        return ((out - label) ** 2).mean()

    def factory(pp):
        descs = []
        for _ in range(L):
            descs.append(pp_layers.LayerDesc(nn.Linear, H, H))
            descs.append(pp_layers.LayerDesc(nn.ReLU))
        model = pp_layers.PipelineLayer(layers=descs, loss_fn=_mse,
                                        num_stages=pp)
        rs = np.random.RandomState(0)
        for p in model.parameters():
            p.set_value(paddle.to_tensor(
                rs.normal(scale=0.2, size=p.shape).astype(np.float32)))
        engine = PipelineEngine(model, accumulate_steps=M)
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=model.parameters())
        return engine, opt

    return factory


def _batch(seed):
    import numpy as np

    import paddle_tpu as paddle

    rs = np.random.RandomState(seed)
    x = paddle.to_tensor(rs.normal(size=(M, H)).astype(np.float32))
    y = paddle.to_tensor(rs.normal(size=(M, H)).astype(np.float32))
    return x, y


def _step(ert, seed):
    import numpy as np

    x, y = _batch(seed)
    loss = ert.run(x, y, train=True)
    ert.optimizer.step()          # the reconfigure swaps the optimizer:
    ert.optimizer.clear_grad()    # always read it through the runtime
    return float(np.asarray(loss._data))


def run() -> dict:
    import numpy as np

    from paddle_tpu import observability
    from paddle_tpu.core import flags
    from paddle_tpu.distributed.elastic import maybe_start_pp
    from paddle_tpu.distributed.elastic import epoch as ep
    from paddle_tpu.distributed.fault_tolerance import chaos

    t0 = time.perf_counter()
    reg = observability.registry()
    factory = _make_factory()

    flags.set_flags({"elastic_pp": True})
    ert = maybe_start_pp(factory, PP)
    assert ert is not None, "FLAGS_elastic_pp opt-in did not start"
    rc0 = reg.value("paddle_elastic_events_total", {"kind": "reconfigure"})
    sd0 = reg.value("paddle_elastic_events_total", {"kind": "stage_dead"})
    rp0 = reg.value("paddle_elastic_events_total", {"kind": "pp_replay"})
    try:
        drill = [_step(ert, seed=i) for i in range(WARM_STEPS)]
        chaos.reconfigure(SPEC)
        builds_after_replay = None
        for i in range(WARM_STEPS, WARM_STEPS + POST_STEPS):
            drill.append(_step(ert, seed=i))
            if builds_after_replay is None:
                # the replay step compiled the pp=2 stages; nothing after
                # it may add an executable
                builds_after_replay = reg.value(
                    "paddle_pp_stage_builds_total")
        builds_final = reg.value("paddle_pp_stage_builds_total")
        chaos.reconfigure("")
        new_world = ert.engine.P_phys
        reconfigures = reg.value("paddle_elastic_events_total",
                                 {"kind": "reconfigure"}) - rc0
        stage_deaths = reg.value("paddle_elastic_events_total",
                                 {"kind": "stage_dead"}) - sd0
        replays = reg.value("paddle_elastic_events_total",
                            {"kind": "pp_replay"}) - rp0
        world_gauge = reg.value("paddle_elastic_world_size")
    finally:
        chaos.reconfigure("")
        ert.stop()
        flags.set_flags({"elastic_pp": False})

    # reference: the same seeds, same warm steps at pp=4, then a PLANNED
    # epoch-fenced downscale to pp=2 at the very step boundary the drill
    # aborted to, then the same post steps. The drill must be bit-equal:
    # same migration (reshard_pp is pure restacks), same engine, same
    # RNG stream (the replay rewound it), same microbatch order.
    ep._reset_for_tests()
    ert2 = None
    try:
        from paddle_tpu.distributed.elastic import ElasticPipelineRuntime

        ert2 = ElasticPipelineRuntime(factory, PP).start()
        ref = [_step(ert2, seed=i) for i in range(WARM_STEPS)]
        ert2.reshard_to(NEW_PP)
        ref += [_step(ert2, seed=i)
                for i in range(WARM_STEPS, WARM_STEPS + POST_STEPS)]
    finally:
        if ert2 is not None:
            ert2.stop()
        ep._reset_for_tests()

    loss_gap = max(abs(a - b) for a, b in zip(drill, ref))
    warm_gap = max(abs(a - b)
                   for a, b in zip(drill[:WARM_STEPS], ref[:WARM_STEPS]))

    checks = {
        "one_reconfigure": reconfigures == 1,
        "one_stage_death": stage_deaths == 1,
        "window_replayed": replays >= 1,
        "resumed_at_new_degree": new_world == NEW_PP
        and world_gauge == NEW_PP,
        "losses_finite": all(np.isfinite(l) for l in drill),
        "warm_steps_bitwise": warm_gap == 0.0,
        "loss_gap_zero_vs_planned_downscale": loss_gap == 0.0,
        "zero_steady_state_retraces": builds_final == builds_after_replay,
    }
    return {
        "ok": all(checks.values()),
        "checks": checks,
        "spec": SPEC,
        "pp": PP,
        "new_pp": new_world,
        "microbatches": M,
        "reconfigures": reconfigures,
        "stage_deaths": stage_deaths,
        "replays": replays,
        "loss_gap": loss_gap,
        "stage_builds_steady_state": builds_final - builds_after_replay,
        "drill_losses": [round(l, 6) for l in drill],
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def main() -> int:
    try:
        result = run()
    except Exception as e:  # noqa: BLE001 — the gate must report, not crash
        result = {"ok": False, "error": f"{type(e).__name__}: {e}",
                  "trace": traceback.format_exc()[-1200:]}
    print(json.dumps(result), flush=True)
    return 0 if result.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
