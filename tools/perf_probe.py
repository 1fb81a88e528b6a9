"""One-off flagship perf probe: try batch-size x remat variants on the real
chip to find a higher-MFU operating point for bench.py's flagship config.

Each variant compiles once and times a few steps; OOM/compile failures are
caught and reported as such so an over-HBM variant costs nothing but its
compile attempt.

Usage: python tools/perf_probe.py [--steps 3] [--warmup 2]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def probe(B, remat, steps, warmup, M=1):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import llama as L
    from paddle_tpu.distributed import hybrid as H
    import bench

    cfg = L.LlamaConfig(vocab_size=32000, hidden_size=1536,
                        intermediate_size=4096, num_layers=12,
                        num_heads=12, num_kv_heads=12, max_seq_len=2048)
    T = 2048
    mesh = H.build_mesh(dp=1, pp=1, tp=1)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    sp = H.shard_params(params, mesh, cfg)
    opt = H.init_opt_state(sp)
    step = H.make_train_step(cfg, mesh, num_microbatches=M,
                             hp=H.AdamWConfig(lr=1e-4), attn_impl="auto",
                             remat=remat)
    k = jax.random.PRNGKey(1)
    tokens = jax.random.randint(k, (B, T), 0, cfg.vocab_size, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    loss = None
    for _ in range(warmup):
        sp, opt, loss = step(sp, opt, tokens, targets)
    if loss is not None:
        jax.block_until_ready(loss)
    from paddle_tpu import observability
    from paddle_tpu.core import async_engine
    from paddle_tpu.ops import dispatch

    observability.reset()  # also zeroes the async/dispatch stats views
    t0 = time.perf_counter()
    for i in range(steps):
        sp, opt, loss = step(sp, opt, tokens, targets)
        a_s = async_engine.stats()
        c_s = dispatch.dispatch_cache_stats()
        print(f"  step {i}: in_flight={a_s['in_flight']}/{a_s['depth']} "
              f"cache_hit_rate={c_s['hit_rate']}", flush=True)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tps = B * T * steps / dt
    mfu = cfg.flops_per_token() * tps / bench.chip_peak_flops(jax.devices()[0])
    a_s = async_engine.stats()
    c_s = dispatch.dispatch_cache_stats()
    obs = observability.summary()
    print(f"  obs: hit_rate={obs['dispatch_hit_rate']} "
          f"retraces={obs['retraces_total']} "
          f"stall_p50={obs['fetch_stall_p50_s']}s "
          f"p99={obs['fetch_stall_p99_s']}s", flush=True)
    return {"tokens_per_s": round(tps, 1), "mfu": round(mfu, 4),
            "step_s": round(dt / steps, 4), "loss": float(loss),
            "async": {"depth": a_s["depth"],
                      "max_in_flight": a_s["max_depth_seen"],
                      "backpressure_waits": a_s["backpressure_waits"],
                      "sync_fetches": a_s["sync_fetches"]},
            "dispatch_cache": {"hit_rate": c_s["hit_rate"],
                               "traces": c_s["traces"]},
            "observability": obs}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--variants", type=str,
                    default="4:dots,4:none,8:dots,8:none,16:dots")
    args = ap.parse_args()

    import jax
    d = jax.devices()[0]
    print(f"device: {d.platform} {getattr(d, 'device_kind', '')}",
          flush=True)
    if d.platform == "cpu":
        print("cpu backend; aborting probe", flush=True)
        return 1
    results = {}
    for spec in args.variants.split(","):
        parts = spec.split(":")
        bs, rs = parts[0], parts[1]
        M = int(parts[2]) if len(parts) > 2 else 1
        remat = {"dots": "dots", "none": False, "full": True}[rs]
        key = f"B{bs}_{rs}" + (f"_M{M}" if M > 1 else "")
        t0 = time.perf_counter()
        try:
            results[key] = probe(int(bs), remat, args.steps, args.warmup,
                                 M=M)
            results[key]["wall_s"] = round(time.perf_counter() - t0, 1)
        except Exception as e:  # noqa: BLE001 — OOM variants report+continue
            results[key] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print(json.dumps({key: results[key]}), flush=True)
    print("FINAL " + json.dumps(results), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
