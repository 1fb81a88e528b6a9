"""Benchmark: all BASELINE.md configs on the available chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} for the
flagship (LLaMA hybrid train), with every other config's number + its own
vs_baseline under details.configs (BASELINE.md configs 1-4; config 5's
detection/OCR models are exercised in tests, not timed here yet).

The reference publishes no in-tree numbers (BASELINE.md — `"published": {}`),
so baselines are self-measured: BENCH_BASELINE.json stores one number per
config the first time each runs on real hardware; vs_baseline is the ratio
against that pin. Throughput is measured with the framework's own
ips/reader_cost/batch_cost timer (paddle_tpu.profiler.benchmark(), the
analog of `python/paddle/profiler/timer.py:332`).

Runs on a TPU only: without one it exits non-zero before any config
starts, a config that raises ends the run with its traceback, and a
`device_kind` missing from the peak table is an error. Smoke-level: the
benchmark grid that replaces this file measures per-cell on the chip.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

BASE_PATH = os.path.join(os.path.dirname(__file__), "BENCH_BASELINE.json")


def chip_peak_flops(dev) -> float:
    """Per-chip bf16 peak from the device kind; an unknown kind raises."""
    kind_l = dev.device_kind.lower()
    table = [
        ("v6", 918e12),           # Trillium
        ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
        ("v5p", 459e12), ("v5", 459e12),
        ("v4", 275e12),
        ("v3", 123e12),
        ("v2", 46e12),
    ]
    for pat, peak in table:
        if pat in kind_l:
            return peak
    raise ValueError(f"no bf16 peak on record for device kind "
                     f"{dev.device_kind!r} ({dev.platform})")


# ---------------------------------------------------------------------------
# Config 4 (flagship): LLaMA hybrid-parallel train step
# ---------------------------------------------------------------------------

def _llama_config():
    from paddle_tpu.models import llama as L

    # ~440M-param LLaMA slice sized for one chip's HBM (f32 master params
    # + AdamW m/v ~= 5.3G of the ~16G budget); bf16 compute.
    cfg = L.LlamaConfig(vocab_size=32000, hidden_size=1536,
                        intermediate_size=4096, num_layers=12,
                        num_heads=12, num_kv_heads=12, max_seq_len=2048)
    return cfg, 4, 2048, 1, 5, 2


def _llama_build(cfg, B, T, M, warmup, attn_impl, remat, ffn_impl="stock"):
    from paddle_tpu.models import llama as L
    from paddle_tpu.distributed import hybrid as H

    mesh = H.build_mesh(dp=1, pp=1, tp=1)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    sp = H.shard_params(params, mesh, cfg)
    opt = H.init_opt_state(sp)
    step = H.make_train_step(cfg, mesh, num_microbatches=M,
                             hp=H.AdamWConfig(lr=1e-4), attn_impl=attn_impl,
                             remat=remat, ffn_impl=ffn_impl)
    k = jax.random.PRNGKey(1)
    tokens = jax.random.randint(k, (B, T), 0, cfg.vocab_size, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    # the first warmup call compiles (Mosaic included) before timing starts
    loss = None
    for _ in range(warmup):
        sp, opt, loss = step(sp, opt, tokens, targets)
    jax.block_until_ready(loss)
    return step, sp, opt, tokens, targets


def bench_llama():
    cfg, B, T, M, steps, warmup = _llama_config()
    # one build, kernels forced: an unsupported shape or a Mosaic failure
    # raises. (Probes on a v5e-class chip, pre-PR-1: flash+dots-remat 0.353
    # MFU, flash+full-remat 0.291, xla attention ~0.20.)
    ffn_impl, flash = "pallas", "on (dots remat + pallas ffn)"
    built = _llama_build(cfg, B, T, M, warmup, "flash", "dots", ffn_impl)
    step, sp, opt, tokens, targets = built
    t0 = time.perf_counter()
    for _ in range(steps):
        sp, opt, loss = step(sp, opt, tokens, targets)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tps = B * T * steps / dt
    dev = jax.devices()[0]
    mfu = cfg.flops_per_token() * tps / chip_peak_flops(dev)
    return {
        "value": round(tps, 2), "unit": "tokens/s/chip",
        "details": {"mfu": round(mfu, 4),
                    "step_time_s": round(dt / steps, 4),
                    "loss": float(loss), "params": cfg.num_params(),
                    "batch": B, "seq": T, "flash": flash,
                    "ffn": ffn_impl},
    }


# ---------------------------------------------------------------------------
# Config 1: MNIST LeNet, dygraph
# ---------------------------------------------------------------------------

def bench_mnist_lenet():
    import paddle_tpu as paddle
    from paddle_tpu import profiler

    B = 64
    steps, warmup = 5, 2
    model = paddle.vision.models.LeNet()
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    parameters=model.parameters())
    rs = np.random.RandomState(0)
    batches = [(paddle.to_tensor(rs.randn(B, 1, 28, 28).astype(np.float32)),
                paddle.to_tensor(rs.randint(0, 10, (B,))))
               for _ in range(4)]

    def one_step(i):
        x, y = batches[i % len(batches)]
        loss = paddle.nn.functional.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for i in range(warmup):
        loss = one_step(i)
    float(loss.numpy())
    # Pipelined timed loop: the loss fetched each step is the one from
    # `depth` steps ago, so the host keeps >=2 steps in flight and the D2H
    # sync never serializes dispatch (the final drain IS inside the clock —
    # throughput counts only fully-materialized steps).
    from collections import deque
    from paddle_tpu.core import async_engine
    from paddle_tpu.ops import dispatch as _dispatch

    async_engine.reset_stats()
    _dispatch.reset_dispatch_cache_stats()
    depth = async_engine.depth()
    pending: deque = deque()
    tm = profiler.benchmark()
    tm.reset()
    tm.begin()
    t0 = time.perf_counter()
    for i in range(steps):
        tm.before_reader()
        _ = batches[i % len(batches)]
        tm.after_reader()
        loss = one_step(i)
        pending.append(loss)
        if len(pending) > depth:
            float(pending.popleft().numpy())  # lagged sync point
        tm.step(num_samples=B)
    last = 0.0
    while pending:
        last = float(pending.popleft().numpy())
    dt = time.perf_counter() - t0
    reader_cost = sum(tm._reader_costs) / max(len(tm._reader_costs), 1)
    tm.end()
    cache = _dispatch.dispatch_cache_stats()
    return {
        "value": round(B * steps / dt, 2), "unit": "samples/s",
        "details": {"mode": "dygraph (pipelined)", "batch": B,
                    "batch_cost_s": round(dt / steps, 5),
                    "reader_cost_s": round(reader_cost, 6),
                    "async_depth": depth,
                    "dispatch_cache_hit_rate": cache["hit_rate"],
                    "loss": last},
    }


# ---------------------------------------------------------------------------
# Config 2: ResNet-50, static (to_static) + AMP bf16
# ---------------------------------------------------------------------------

def bench_resnet50_amp():
    import paddle_tpu as paddle
    from paddle_tpu import profiler

    B = 64
    # warmup=2: step 1 compiles fwd/bwd, step 2 compiles the grad-ACCUMULATE
    # variants (grad None -> set vs add) + BN stat updates; timing anything
    # earlier charges one-off compiles to throughput.
    steps, warmup = 3, 2
    model = paddle.vision.models.resnet50(num_classes=100)

    class TrainNet(paddle.nn.Layer):
        """Forward + cast + loss captured as ONE static program so the
        autograd boundary is the scalar loss (autocast casts are baked into
        the trace; mixing an eager cast with a captured bf16 output breaks
        the VJP dtype contract)."""

        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, x, y):
            with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
                logits = self.m(x)
            return paddle.nn.functional.cross_entropy(
                logits.astype("float32"), y)

    net = TrainNet(model)
    paddle.jit.to_static(net)  # static-graph mode: one XLA program
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    parameters=model.parameters())
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(B, 3, 224, 224).astype(np.float32))
    y = paddle.to_tensor(rs.randint(0, 100, (B,)))

    def one_step():
        loss = net(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(warmup):
        loss = one_step()
    float(loss.numpy())
    tm = profiler.benchmark()
    tm.reset()
    tm.begin()
    for _ in range(steps):
        loss = one_step()
        float(loss.numpy())  # sync inside the timed step (async dispatch)
        tm.step(num_samples=B)
    batch_cost = sum(tm._batch_costs) / len(tm._batch_costs)
    ips = tm.ips
    tm.end()
    return {
        "value": round(ips, 2), "unit": "images/s/chip",
        "details": {"mode": "to_static + amp bf16", "batch": B,
                    "batch_cost_s": round(batch_cost, 5),
                    "loss": float(loss.numpy())},
    }


# ---------------------------------------------------------------------------
# Config 3: BERT-style pretrain step, fleet DP + sharding
# ---------------------------------------------------------------------------

def bench_bert_dp_sharding():
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.distributed import fleet

    B, T, V, D, L = (16, 128, 8192, 256, 4)
    steps, warmup = 5, 3

    class Bert(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = paddle.nn.Embedding(V, D)
            self.pos = paddle.nn.Embedding(T, D)
            layer = paddle.nn.TransformerEncoderLayer(D, 8, 4 * D,
                                                      dropout=0.0)
            self.encoder = paddle.nn.TransformerEncoder(layer, L)
            self.head = paddle.nn.Linear(D, V)

        def forward(self, tokens, positions):
            x = self.tok(tokens) + self.pos(positions)
            return self.head(self.encoder(x))

    model = Bert()
    paddle.jit.to_static(model)
    fleet_mode = "fleet dp+sharding (world=1)"
    strategy = fleet.DistributedStrategy()
    fleet.init(is_collective=True, strategy=strategy)
    model = fleet.distributed_model(model)
    inner = paddle.optimizer.AdamW(learning_rate=1e-4,
                                   parameters=model.parameters())
    opt = fleet.distributed_optimizer(inner)
    rs = np.random.RandomState(0)
    tokens = paddle.to_tensor(rs.randint(0, V, (B, T)))
    positions = paddle.to_tensor(np.arange(T))
    labels = paddle.to_tensor(rs.randint(0, V, (B * T,)))

    def one_step():
        logits = model(tokens, positions)
        loss = paddle.nn.functional.cross_entropy(
            logits.reshape([-1, V]), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(warmup):
        loss = one_step()
    float(loss.numpy())
    # Pipelined timed loop (see bench_mnist_lenet): loss fetch lags by the
    # async depth; the drain stays inside the clock.
    from collections import deque
    from paddle_tpu.core import async_engine

    depth = async_engine.depth()
    pending: deque = deque()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = one_step()
        pending.append(loss)
        if len(pending) > depth:
            float(pending.popleft().numpy())
    last = 0.0
    while pending:
        last = float(pending.popleft().numpy())
    dt = time.perf_counter() - t0
    return {
        "value": round(B * T * steps / dt, 2), "unit": "tokens/s/chip",
        "details": {"mode": fleet_mode + " (pipelined)", "batch": B, "seq": T,
                    "layers": L, "d_model": D,
                    "batch_cost_s": round(dt / steps, 5),
                    "async_depth": depth,
                    "loss": last,
                    "dp_overlap": _dp_overlap_details()},
    }


def _dp_overlap_details():
    """Sub-config: eager DataParallel grad-sync step time, barrier vs
    hook-overlapped vs ZeRO-1 sharded (FLAGS_dp_overlap /
    FLAGS_dp_shard_update), over a group spanning every reachable device.
    red_signal fires when overlap fails to beat the barrier baseline on a
    multi-device platform — the acceptance line for the overlapped path."""
    import statistics

    import paddle_tpu as paddle
    from paddle_tpu import distributed as dist
    from paddle_tpu import observability as obs
    from paddle_tpu.core import flags

    ndev = min(8, len(jax.devices()))
    dist.init_parallel_env()
    g = (dist.new_group(list(range(ndev)), devices=jax.devices()[:ndev])
         if ndev > 1 else dist.get_group(0))

    def train(overlap, shard, steps=5, wire=""):
        flags.set_flags({"dp_overlap": overlap,
                         "dp_shard_update": shard,
                         "dp_grad_comm_dtype": wire})
        paddle.seed(0)
        m = paddle.nn.Sequential(paddle.nn.Linear(256, 512),
                                 paddle.nn.ReLU(),
                                 paddle.nn.Linear(512, 256))
        d = dist.DataParallel(m, group=g)
        o = paddle.optimizer.Adam(learning_rate=1e-3,
                                  parameters=m.parameters())
        so = dist.sharded_update(o, d) if shard else o
        times = []
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(32, 256).astype(np.float32))
        for _ in range(steps):
            t0 = time.perf_counter()
            d(x).mean().backward()
            so.step()
            so.clear_grad()
            times.append(time.perf_counter() - t0)
        return statistics.median(times[1:]) * 1e3, so

    barrier_ms, _ = train(False, False)
    overlap_ms, _ = train(True, False)
    shard_ms, so = train(True, True)
    opt_bytes = so.optimizer_state_bytes_per_device()
    eff = obs.summary().get("dp_overlap_efficiency", 0.0)
    # same trio with the block-scaled int8 wire (quant_comm codec);
    # the wire ratio comes from the actual-vs-reference byte counter
    # deltas (no obs.reset() — the enclosing config owns that window)
    w0 = obs.registry().value("paddle_dp_wire_bytes_total",
                              {"dtype": "int8"})
    r0 = obs.registry().value("paddle_dp_wire_bytes_ref_total")
    overlap_int8_ms, _ = train(True, False, wire="int8")
    shard_int8_ms, _ = train(True, True, wire="int8")
    dw = obs.registry().value("paddle_dp_wire_bytes_total",
                              {"dtype": "int8"}) - w0
    dr = obs.registry().value("paddle_dp_wire_bytes_ref_total") - r0
    flags.set_flags({"dp_overlap": True, "dp_shard_update": False,
                     "dp_grad_comm_dtype": ""})
    return {
        "world": getattr(g, "nranks", 1),
        "barrier_ms": round(barrier_ms, 3),
        "overlap_ms": round(overlap_ms, 3),
        "shard_ms": round(shard_ms, 3),
        "overlap_int8_ms": round(overlap_int8_ms, 3),
        "shard_int8_ms": round(shard_int8_ms, 3),
        "int8_wire_ratio": round(dr / dw, 4) if dw else 0.0,
        "overlap_efficiency": eff,
        "opt_state_bytes_per_dev": opt_bytes,
        "red_signal": bool(getattr(g, "nranks", 1) > 1
                           and overlap_ms >= barrier_ms),
    }


# ---------------------------------------------------------------------------
# Config 5: PP-YOLOE-style detector inference (BASELINE config 5 analog)
# ---------------------------------------------------------------------------

def bench_detection_infer():
    """Single-chip detector inference ips: CSP-ish conv backbone + 3-scale
    head + in-graph yolo_box decode, bf16 under to_static; the
    data-dependent NMS runs on host AFTER the timed graph (reference deploy
    pipelines post-process outside the engine too)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, profiler
    import paddle_tpu.vision.ops as vops

    B = 8
    S = 640
    steps, warmup = 5, 2

    class ConvBN(nn.Layer):
        def __init__(self, cin, cout, k=3, s=1):
            super().__init__()
            self.conv = nn.Conv2D(cin, cout, k, stride=s, padding=k // 2,
                                  bias_attr=False)
            self.bn = nn.BatchNorm2D(cout)
            self.act = nn.Silu()

        def forward(self, x):
            return self.act(self.bn(self.conv(x)))

    class Detector(nn.Layer):
        """3 downsample stages -> P3/P4/P5 heads (na=1, 80 classes)."""

        def __init__(self, nc=80, w=32):
            super().__init__()
            self.stem = ConvBN(3, w, 3, 2)
            self.s1 = nn.Sequential(ConvBN(w, 2 * w, 3, 2),
                                    ConvBN(2 * w, 2 * w))
            self.s2 = nn.Sequential(ConvBN(2 * w, 4 * w, 3, 2),
                                    ConvBN(4 * w, 4 * w))
            self.s3 = nn.Sequential(ConvBN(4 * w, 8 * w, 3, 2),
                                    ConvBN(8 * w, 8 * w))
            self.s4 = nn.Sequential(ConvBN(8 * w, 16 * w, 3, 2),
                                    ConvBN(16 * w, 16 * w))
            out_c = 5 + nc
            self.h3 = nn.Conv2D(4 * w, out_c, 1)
            self.h4 = nn.Conv2D(8 * w, out_c, 1)
            self.h5 = nn.Conv2D(16 * w, out_c, 1)
            self.nc = nc

        def forward(self, x):
            x = self.stem(x)
            p2 = self.s1(x)
            p3 = self.s2(p2)
            p4 = self.s3(p3)
            p5 = self.s4(p4)
            return self.h3(p3), self.h4(p4), self.h5(p5)

    net = Detector()
    net.eval()

    class Infer(nn.Layer):
        def __init__(self, m, img_size):
            super().__init__()
            self.m = m
            self.img_size = img_size

        def forward(self, x, img_shape):
            with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
                heads = self.m(x)
            outs = []
            for hm, stride, anchor in zip(
                    heads, (8, 16, 32), ([8, 8], [16, 16], [32, 32])):
                boxes, scores = vops.yolo_box(
                    hm.astype("float32"), img_shape, anchor, self.m.nc,
                    conf_thresh=0.005,
                    downsample_ratio=stride)
                outs.append((boxes, scores))
            return outs

    infer = Infer(net, S)
    paddle.jit.to_static(infer)
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.rand(B, 3, S, S).astype(np.float32))
    img_shape = paddle.to_tensor(
        np.tile(np.asarray([[S, S]], np.int32), (B, 1)))

    def one_pass():
        outs = infer(x, img_shape)
        # force completion of every head
        return float(outs[-1][0].numpy().ravel()[0])

    for _ in range(warmup):
        one_pass()
    tm = profiler.benchmark()
    tm.reset()
    tm.begin()
    for _ in range(steps):
        one_pass()
        tm.step(num_samples=B)
    ips = tm.ips
    tm.end()
    # validity: host-side NMS on the decoded boxes of one image
    outs = infer(x, img_shape)
    boxes = np.concatenate([np.asarray(b.numpy())[0] for b, _ in outs])
    scores = np.concatenate(
        [np.asarray(s.numpy())[0].max(-1) for _, s in outs])
    keep = vops.nms(paddle.to_tensor(boxes), iou_threshold=0.5,
                    scores=paddle.to_tensor(scores), top_k=100)
    return {
        "value": round(ips, 2), "unit": "images/s/chip",
        "details": {"mode": "to_static bf16 + yolo_box in-graph",
                    "batch": B, "img": S,
                    "nms_kept": int(np.asarray(keep.numpy()).shape[0])},
    }


# ---------------------------------------------------------------------------
# Config 6: LLaMA KV-cached greedy decode (serving path)
# ---------------------------------------------------------------------------

def _serving_paged_details():
    """Sub-config: the paged continuous-batching engine vs the dense slot
    engine on one shared-prefix request trace (both warmed, prefix cache
    seeded — serving steady state). red_signal fires when paged throughput
    falls below the dense baseline — the acceptance line for the paged
    serving subsystem (tools/serving_smoke.py is the full gate)."""
    from paddle_tpu.inference.serving import PagedServingEngine, ServingEngine
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=256, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=4, max_seq_len=96, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    n_req, new = 24, 6
    rs = np.random.RandomState(0)
    shared = rs.randint(1, cfg.vocab_size, size=48).tolist()
    prompts = [shared + rs.randint(1, cfg.vocab_size, size=4).tolist()
               for _ in range(n_req)]

    def timed(eng):
        [eng.submit(p, max_new_tokens=new) for p in prompts]
        eng.run()                       # warm pass (+ prefix cache seed)
        best, outs = 0.0, None
        for _ in range(2):              # first repeat may still compile
            t0 = time.perf_counter()    # (e.g. the paged COW page copy)
            rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
            out = {c.rid: c.output_tokens for c in eng.run()}
            dt = time.perf_counter() - t0
            best, outs = max(best, n_req * new / dt), [out[r]
                                                       for r in rids]
        return outs, best

    dense_out, dense_tps = timed(
        ServingEngine(cfg, params, num_slots=4, max_len=cfg.max_seq_len,
                      chunk=new))
    paged = PagedServingEngine(cfg, params, num_blocks=224, block_size=8,
                               max_batch=n_req, token_budget=32,
                               max_len=cfg.max_seq_len)
    paged_out, paged_tps = timed(paged)
    return {
        "requests": n_req, "new_tokens": new,
        "paged_tokens_per_s": round(paged_tps, 1),
        "dense_tokens_per_s": round(dense_tps, 1),
        "ratio": round(paged_tps / dense_tps, 3) if dense_tps else None,
        "parity": paged_out == dense_out,
        "prefix_hit_tokens": paged.blocks.stats["prefix_hit_tokens"],
        "step_builds": paged.stats["step_builds"],
        "red_signal": bool(paged_out != dense_out
                           or paged_tps < dense_tps),
    }


def _serving_router_details():
    """Sub-config: the multi-replica router under a chaos replica kill —
    one of two replicas dies mid-decode, every stream must fail over and
    finish bit-exact vs a single replica-shaped engine on the same trace.
    red_signal fires on a dropped stream, a replay-confirm divergence, or
    a survivor retrace (tools/router_smoke.py is the full gate with the
    throughput floor)."""
    from paddle_tpu.distributed.fault_tolerance import chaos
    from paddle_tpu.inference.serving import (PagedServingEngine,
                                              ServingRouter)
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=256, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=4, max_seq_len=96, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    n_req, new = 8, 8
    rs = np.random.RandomState(0)
    shared = rs.randint(1, cfg.vocab_size, size=16).tolist()
    prompts = [shared + rs.randint(1, cfg.vocab_size, size=4).tolist()
               for _ in range(n_req)]

    def factory():
        return PagedServingEngine(cfg, params, num_blocks=96,
                                  block_size=8, max_batch=8,
                                  token_budget=32,
                                  max_len=cfg.max_seq_len)

    eng = factory()
    rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
    ref = {c.rid: c.output_tokens for c in eng.run()}
    single_out = [ref[r] for r in rids]

    chaos.reconfigure("replica:kill@victim=0;call=5")
    try:
        t0 = time.perf_counter()
        router = ServingRouter(factory, num_replicas=2,
                               probation_s=1e9,
                               tenant_weights={"default": n_req})
        rids = [router.submit(p, max_new_tokens=new) for p in prompts]
        done = {c.rid: c for c in router.run()}
        wall = time.perf_counter() - t0
    finally:
        chaos.reconfigure("")
    outs = [done[r].output_tokens if r in done else None for r in rids]
    dropped = sum(1 for r in rids
                  if r not in done or done[r].finish_reason != "length")
    survivor = router.replicas[1].engine
    return {
        "requests": n_req, "new_tokens": new,
        "parity_through_failover": outs == single_out,
        "dropped_streams": dropped,
        "failovers": router.stats["failovers"],
        "mismatches": router.stats["mismatches"],
        "survivor_step_builds": (survivor.stats["step_builds"]
                                 if survivor is not None else None),
        "drill_tokens_per_s": round(n_req * new / wall, 1),
        "red_signal": bool(outs != single_out or dropped
                           or router.stats["mismatches"]
                           or (survivor is not None
                               and survivor.stats["step_builds"] != 1)),
    }


def _serving_quant_details():
    """Sub-config: w8 weights + int8 paged KV vs the fp paged engine on
    one shared-prefix trace (both warmed). red_signal fires when greedy
    token agreement drops below 90%, the effective KV capacity ratio
    falls under 1.8x, or the quant engine retraces in steady state
    (tools/quant_smoke.py is the full gate with logit parity and the
    preemption bit-exactness drill)."""
    from paddle_tpu.inference import quant as Q
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=256, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=4, max_seq_len=96, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    n_req, new = 16, 6
    rs = np.random.RandomState(0)
    shared = rs.randint(1, cfg.vocab_size, size=40).tolist()
    prompts = [shared + rs.randint(1, cfg.vocab_size, size=4).tolist()
               for _ in range(n_req)]
    manifest = Q.calibrate(
        cfg, params,
        [rs.randint(1, cfg.vocab_size, (2, 16)) for _ in range(2)])

    def timed(eng):
        [eng.submit(p, max_new_tokens=new) for p in prompts]
        eng.run()                       # warm pass (+ prefix cache seed)
        best, outs = 0.0, None
        for _ in range(2):
            t0 = time.perf_counter()
            rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
            out = {c.rid: c.output_tokens for c in eng.run()}
            dt = time.perf_counter() - t0
            best, outs = max(best, n_req * new / dt), [out[r]
                                                       for r in rids]
        return outs, best

    def make(**kw):
        return PagedServingEngine(cfg, params, num_blocks=160,
                                  block_size=8, max_batch=n_req,
                                  token_budget=32,
                                  max_len=cfg.max_seq_len, **kw)

    fp_eng = make()
    fp_out, fp_tps = timed(fp_eng)
    q_eng = make(quant_mode="w8", quant_kv=True,
                 quant_manifest=manifest)
    builds0 = None
    q_out, q_tps = timed(q_eng)
    builds0 = q_eng.stats["step_builds"]
    pairs = [(x, y) for a, b in zip(q_out, fp_out)
             for x, y in zip(a, b)]
    agreement = (sum(x == y for x, y in pairs) / max(len(pairs), 1))
    capacity = fp_eng.kv_page_bytes / q_eng.kv_page_bytes
    return {
        "requests": n_req, "new_tokens": new,
        "quant_tokens_per_s": round(q_tps, 1),
        "fp_tokens_per_s": round(fp_tps, 1),
        "token_agreement": round(agreement, 4),
        "kv_capacity_ratio": round(capacity, 3),
        "quant_page_bytes": q_eng.kv_page_bytes,
        "fp_page_bytes": fp_eng.kv_page_bytes,
        "step_builds": builds0,
        "red_signal": bool(agreement < 0.9 or capacity < 1.8
                           or builds0 != 1),
    }


def _serving_spec_details():
    """Sub-config: speculative decoding (half-depth draft sharing the
    target's own layer-prefix weights) vs the plain paged engine on the
    same trace. red_signal fires on a greedy parity break, a dead
    acceptance rate, or a steady-state retrace; tokens/s spec-vs-plain
    is reported but NOT gated (tools/spec_smoke.py is the full gate with
    preemption and failover drills)."""
    from paddle_tpu.inference.serving import DraftModel, PagedServingEngine
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=256, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=4, max_seq_len=96, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    dcfg = L.LlamaConfig(vocab_size=256, hidden_size=64,
                         intermediate_size=128, num_layers=1,
                         num_heads=4, num_kv_heads=4, max_seq_len=96,
                         dtype=jnp.float32)
    dparams = {"embed": params["embed"],
               "final_norm": params["final_norm"],
               "lm_head": params["lm_head"],
               "blocks": jax.tree.map(lambda a: a[:1], params["blocks"])}
    n_req, new = 8, 8
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, cfg.vocab_size, size=12).tolist()
               for _ in range(n_req)]

    def timed(eng):
        [eng.submit(p, max_new_tokens=new) for p in prompts]
        eng.run()                       # warm pass
        best, outs = 0.0, None
        for _ in range(2):
            t0 = time.perf_counter()
            rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
            out = {c.rid: c.output_tokens for c in eng.run()}
            dt = time.perf_counter() - t0
            best, outs = max(best, n_req * new / dt), [out[r]
                                                       for r in rids]
        return outs, best

    def make(**kw):
        return PagedServingEngine(cfg, params, num_blocks=96,
                                  block_size=8, max_batch=8,
                                  token_budget=32,
                                  max_len=cfg.max_seq_len, **kw)

    plain_out, plain_tps = timed(make())
    spec = make(draft=DraftModel(dcfg, dparams), spec_k=3)
    spec_out, spec_tps = timed(spec)
    builds0 = spec.stats["step_builds"]
    spec_out2, _ = timed(spec)
    retraces = spec.stats["step_builds"] - builds0
    acceptance = spec.spec.acceptance_rate
    return {
        "requests": n_req, "new_tokens": new, "spec_k": 3,
        "spec_tokens_per_s": round(spec_tps, 1),
        "plain_tokens_per_s": round(plain_tps, 1),
        "ratio": round(spec_tps / plain_tps, 3) if plain_tps else None,
        "parity": spec_out == plain_out and spec_out2 == plain_out,
        "acceptance_rate": acceptance,
        "spec_ticks": spec.stats["spec_ticks"],
        "steady_state_retraces": retraces,
        "red_signal": bool(spec_out != plain_out
                           or spec_out2 != plain_out
                           or acceptance <= 0.0 or retraces),
    }


def _serving_adapters_details():
    """Sub-config: multi-tenant LoRA hot-swap under the paged engine —
    a mixed batch (base + two adapters of one rank class, more tenants
    than needed to prove slot reuse) vs per-tenant reference runs.
    red_signal fires when a base-row stream in the mixed batch is not
    bit-identical to the adapter-off engine, when repeating the mixed
    trace retraces the steady-state step, or when no swap was exercised
    (tools/spec_smoke.py carries the chaos-evict drill)."""
    from paddle_tpu.inference.serving import PagedServingEngine, make_adapter
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=256, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=4, max_seq_len=96, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    n_req, new = 9, 8
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, cfg.vocab_size, size=12).tolist()
               for _ in range(n_req)]
    tenants = [None, "tenant-a", "tenant-b"] * (n_req // 3)

    def make(**kw):
        return PagedServingEngine(cfg, params, num_blocks=96,
                                  block_size=8, max_batch=n_req,
                                  token_budget=48,
                                  max_len=cfg.max_seq_len, **kw)

    base = make()
    rids = [base.submit(p, max_new_tokens=new) for p in prompts]
    ref = {c.rid: c.output_tokens for c in base.run()}
    base_out = [ref[r] for r in rids]

    eng = make(adapter_slots=2)
    for name, seed in (("tenant-a", 3), ("tenant-b", 4)):
        # scale up from the default 0.02: the delta must be strong
        # enough to move every stream's greedy argmax, or the
        # rows-diverge sanity check below is vacuous
        eng.adapters.register(make_adapter(cfg, name, rank=4,
                                           alpha=8.0, seed=seed,
                                           scale=0.3))

    def mixed():
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=new,
                           **({"adapter": t} if t else {}))
                for p, t in zip(prompts, tenants)]
        out = {c.rid: c.output_tokens for c in eng.run()}
        return [out[r] for r in rids], time.perf_counter() - t0

    mix1, _ = mixed()               # warm: loads, traces the ad_sig step
    builds0 = eng.stats["step_builds"]
    mix2, wall = mixed()
    retraces = eng.stats["step_builds"] - builds0
    base_rows_equal = all(
        m == b for m, b, t in zip(mix2, base_out, tenants) if t is None)
    adapter_rows_differ = all(
        m != b for m, b, t in zip(mix2, base_out, tenants)
        if t is not None)
    return {
        "requests": n_req, "new_tokens": new, "tenants": 2,
        "adapter_slots": 2,
        "mixed_tokens_per_s": round(n_req * new / wall, 1),
        "base_row_parity": base_rows_equal,
        "adapter_rows_diverge": adapter_rows_differ,
        "deterministic": mix1 == mix2,
        "loads": eng.adapters.stats["loads"],
        "hits": eng.adapters.stats["hits"],
        "adapter_bytes_in_use": eng.adapters.bytes_in_use(),
        "steady_state_retraces": retraces,
        "red_signal": bool(not base_rows_equal
                           or not adapter_rows_differ
                           or mix1 != mix2 or retraces),
    }


def bench_llama_decode():
    """tokens/s of the jitted cached decode step (inference/llm.py) — the
    serving-path analog of the reference's block/masked-MHA decode loop."""
    from paddle_tpu.models import llama as L
    from paddle_tpu.inference.llm import LLMPredictor

    cfg = L.LlamaConfig(vocab_size=32000, hidden_size=1536,
                        intermediate_size=4096, num_layers=12,
                        num_heads=12, num_kv_heads=12, max_seq_len=2048)
    # warm_new=32 so the warmup compiles the same C=32 on-device decode
    # loop the timed run uses (128 = 4 chunks of 32, zero new compiles)
    B, T, new, warm_new = 8, 128, 128, 32
    weight_dtype = jnp.bfloat16   # serving deploys bf16 weights
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    pred = LLMPredictor(cfg, params, max_len=T + new + warm_new + 1,
                        weight_dtype=weight_dtype)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size, jnp.int32)
    seq = pred.generate(prompt, max_new_tokens=warm_new)   # compile both steps
    jax.block_until_ready(seq)
    t0 = time.perf_counter()
    seq = pred.generate(prompt, max_new_tokens=new)
    jax.block_until_ready(seq)
    dt = time.perf_counter() - t0
    tps = B * new / dt
    details = {"batch": B, "prompt": T, "new_tokens": new,
               "ms_per_token": round(1e3 * dt / new, 3),
               "weights": str(np.dtype(weight_dtype).name),
               "decode_loop": "on-device scan, 32 tokens/dispatch"}
    # serving-throughput point: decode is HBM-bandwidth-bound (one full
    # bf16 weight read per step), so a bigger batch amortizes the read
    # over more sequences — report B=32 alongside the pinned B=8 config
    B2 = 32
    prompt2 = jnp.tile(prompt, (B2 // B, 1))
    seq = pred.generate(prompt2, max_new_tokens=warm_new)
    jax.block_until_ready(seq)
    t0 = time.perf_counter()
    seq = pred.generate(prompt2, max_new_tokens=new)
    jax.block_until_ready(seq)
    dt2 = time.perf_counter() - t0
    details["throughput_b32"] = {
        "decode_tokens_per_s": round(B2 * new / dt2, 2),
        "ms_per_step": round(1e3 * dt2 / new, 3)}
    details["llama_serving_paged"] = _serving_paged_details()
    details["llama_serving_router"] = _serving_router_details()
    details["llama_serving_quant"] = _serving_quant_details()
    details["llama_serving_spec"] = _serving_spec_details()
    details["llama_serving_adapters"] = _serving_adapters_details()
    return {
        "value": round(tps, 2), "unit": "decode_tokens/s/chip",
        "details": details,
    }


# ---------------------------------------------------------------------------
# Config 7: MPMD pipeline schedules (distributed.pipeline)
# ---------------------------------------------------------------------------

def bench_pipeline_schedules():
    """Pipeline-engine step time: naive-sequential (pp=1 microbatch
    accumulation, no pipelining) vs 1F1B (pp=2) vs interleaved (pp=2, two
    virtual chunks per group). Wall-clock overlap only manifests with
    genuinely parallel stage devices, so the headline value is 1F1B
    steps/s and the details carry the trio plus the simulated bubble
    fractions (which ARE platform-independent: the closed forms
    (pp-1)/(m+pp-1) and (pp-1)/(v*m+pp-1))."""
    import statistics

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers import (
        pp_layers)
    from paddle_tpu.distributed.pipeline import (
        PipelineEngine, closed_form_bubble)

    M, D = 8, 256

    def _mse(out, label):
        return ((out - label) ** 2).mean()

    def _descs():
        return [pp_layers.LayerDesc(nn.Linear, D, D),
                pp_layers.LayerDesc(nn.ReLU),
                pp_layers.LayerDesc(nn.Linear, D, D),
                pp_layers.LayerDesc(nn.ReLU),
                pp_layers.LayerDesc(nn.Linear, D, D),
                pp_layers.LayerDesc(nn.ReLU),
                pp_layers.LayerDesc(nn.Linear, D, D)]

    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(M * 4, D).astype(np.float32))
    y = paddle.to_tensor(rs.randn(M * 4, D).astype(np.float32))

    def timed(pp, schedule, v=1, steps=5):
        model = pp_layers.PipelineLayer(layers=_descs(), loss_fn=_mse,
                                        num_stages=pp,
                                        num_virtual_pipeline_stages=v)
        engine = PipelineEngine(model, accumulate_steps=M,
                                schedule=schedule)
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            loss = engine.run(x, y, train=True)
            jax.block_until_ready(loss._data)
            times.append(time.perf_counter() - t0)
            for p in model.parameters():
                p._grad = None
        return statistics.median(times[1:]) * 1e3, engine

    seq_ms, _ = timed(1, "gpipe")  # one stage: a plain accumulation loop
    f1b_ms, eng = timed(2, "1F1B")
    il_ms, eng_il = timed(2, "interleave", v=2)
    bubble = eng.schedule_stats["bubble_fraction"]
    bubble_il = eng_il.schedule_stats["bubble_fraction"]
    return {
        "value": round(1e3 / f1b_ms, 2), "unit": "1f1b_steps/s",
        "details": {
            "microbatches": M,
            "sequential_ms": round(seq_ms, 3),
            "f1b_ms": round(f1b_ms, 3),
            "interleave_ms": round(il_ms, 3),
            "bubble_1f1b": round(bubble, 6),
            "bubble_interleave": round(bubble_il, 6),
            "red_signal": bool(
                abs(bubble - closed_form_bubble(2, M)) > 1e-9
                or abs(bubble_il - closed_form_bubble(2, M, 2)) > 1e-9),
        },
    }


# ---------------------------------------------------------------------------
# Config 8: raw eager dispatch latency (the hot path itself)
# ---------------------------------------------------------------------------

def bench_eager_dispatch_add():
    """ops/s of a bare `a + b` dispatch after cache warmup — the direct
    measure of the signature-keyed dispatch cache (a host-side cost)."""
    import paddle_tpu as paddle
    from paddle_tpu.ops import dispatch as _dispatch

    a = paddle.to_tensor(np.random.rand(256, 256).astype(np.float32))
    b = paddle.to_tensor(np.random.rand(256, 256).astype(np.float32))
    for _ in range(8):  # warmup: miss -> compile -> steady-state hits
        c = a + b
    float(c.sum().numpy())
    _dispatch.reset_dispatch_cache_stats()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        c = a + b
    float(c.sum().numpy())
    dt = time.perf_counter() - t0
    cache = _dispatch.dispatch_cache_stats()
    return {
        "value": round(n / dt, 2), "unit": "dispatches/s",
        "details": {"us_per_dispatch": round(1e6 * dt / n, 2),
                    "cache_hit_rate": cache["hit_rate"],
                    "retraces_in_window": cache["traces"]},
    }


def bench_tuned_serving():
    """The offline autotuner end-to-end over the serving flag space:
    analytic search (op-bench costs + geometry scaling) picks finalists,
    each finalist runs real warm decode ticks, the measured winner is
    pinned as a tuned profile under tuned_profiles/. The headline value
    is the tuned config's decode throughput; details carry the proof
    obligation — measured speedup vs the hand-picked incumbent
    (Candidate() IS the repo's default config) and whether the analytic
    top-1 agreed with the measured top-1."""
    from paddle_tpu import tuner
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L

    # same tiny geometry the op-bench decode_tick_* pins were measured
    # on, so the cost model's anchor entries transfer exactly
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=np.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    engines = {}

    def _engine(c):
        eng = PagedServingEngine(
            cfg, params, block_size=8, max_batch=c.max_batch,
            token_budget=c.token_budget, max_len=cfg.max_seq_len,
            pallas=c.pallas_attention, pallas_ffn=c.pallas_ffn)
        rs = np.random.RandomState(7)
        for _ in range(c.max_batch):
            eng.submit(rs.randint(1, cfg.vocab_size, 12).tolist(),
                       max_new_tokens=64)
        eng.step()   # prefill executable
        eng.step()   # decode executable — steady state from here
        return eng

    def runner(c):
        # one warm decode tick, in the cost model's unit (sec/token)
        eng = engines.get(c)
        if eng is None:
            eng = engines[c] = _engine(c)
        t0 = time.perf_counter()
        eng.step()
        return (time.perf_counter() - t0) / c.max_batch

    model = tuner.CostModel()
    workload = tuner.Workload("serving_llama_tiny", kind="serving",
                              tick_layers=cfg.num_layers)
    axes = {"pallas_attention": [False, True],
            "pallas_ffn": [False, True],
            "max_batch": [4, 8, 16],
            "token_budget": [64, 128]}
    platform = jax.devices()[0].platform
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tuned_profiles",
                            f"{workload.name}_{platform}.json")
    prof = tuner.tune(model, workload, axes, runner, out_path=out_path)

    winner_eng = engines.get(prof.candidate())
    builds_before = winner_eng.stats["step_builds"] if winner_eng else 0
    if winner_eng is not None:
        runner(prof.candidate())   # one more tick under the winner
    retraces = ((winner_eng.stats["step_builds"] - builds_before)
                if winner_eng else -1)
    # analytic top-1 (cheapest prediction over the full space) vs the
    # measured winner — the agreement claim tune_smoke gates in CI
    preds = tuner.search(model, workload, tuner.enumerate_space(axes),
                         topk=1, prune_ratio=1e9)
    analytic_top1 = preds[0].candidate
    speedup = (prof.baseline_measured_s / prof.measured_s
               if prof.measured_s > 0 and prof.baseline_measured_s > 0
               else 0.0)
    return {
        "value": round(1.0 / prof.measured_s, 2)
        if prof.measured_s > 0 else 0.0,
        "unit": "tokens/s",
        "details": {
            "winner": prof.candidate().describe(),
            "tuned_us_per_tok": round(prof.measured_s * 1e6, 2),
            "handpicked_us_per_tok": round(
                prof.baseline_measured_s * 1e6, 2),
            "speedup_vs_handpicked": round(speedup, 4),
            "analytic_top1": analytic_top1.describe(),
            "analytic_matches_measured": analytic_top1
            == prof.candidate(),
            "candidates_considered": prof.candidates_considered,
            "steady_state_retraces": retraces,
            "profile": os.path.relpath(
                out_path, os.path.dirname(os.path.abspath(__file__))),
            "source_key": prof.source_key,
        },
    }


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

CONFIGS = [
    ("llama_train_tokens_per_sec_per_chip", bench_llama),
    ("mnist_lenet_dygraph", bench_mnist_lenet),
    ("resnet50_static_amp", bench_resnet50_amp),
    ("bert_dp_sharding", bench_bert_dp_sharding),
    ("ppyoloe_style_detector_infer", bench_detection_infer),
    ("llama_decode_serving", bench_llama_decode),
    ("pipeline_1f1b", bench_pipeline_schedules),
    ("eager_dispatch_add", bench_eager_dispatch_add),
    ("serving_autotuned", bench_tuned_serving),
]


def _read_base():
    if not os.path.exists(BASE_PATH):
        return None
    try:
        with open(BASE_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _load_baselines(platform):
    base = _read_base()
    if base is None or base.get("platform") != platform:
        return {}
    configs = dict(base.get("configs") or {})
    # legacy round-1/2 format: single llama number under "value"
    if "llama_train_tokens_per_sec_per_chip" not in configs and base.get("value"):
        configs["llama_train_tokens_per_sec_per_chip"] = float(base["value"])
    return configs


REGRESSION_POLICY = (
    "pins are REGRESSION FLOORS, not aspirations: any config whose "
    "vs_baseline drops below 1.0 against an existing pin for the CURRENT "
    "platform is a red build signal (details.red_signals).")


def _save_baselines(platform, configs):
    with open(BASE_PATH, "w") as f:
        json.dump({"platform": platform, "configs": configs,
                   "policy": REGRESSION_POLICY,
                   # keep the legacy key so older tooling still reads it
                   "value": configs.get(
                       "llama_train_tokens_per_sec_per_chip"),
                   "unit": "tokens/s/chip"}, f, indent=1)


def _emit(results: dict, note: dict) -> None:
    """Print the ONE JSON line."""
    primary_name = CONFIGS[0][0]
    primary = results[primary_name]
    details = {**note, **primary.get("details", {}),
               "configs": {n: results[n] for n, _ in CONFIGS[1:]}}
    print(json.dumps({
        "metric": primary_name,
        "value": primary["value"],
        "unit": primary["unit"],
        "vs_baseline": primary["vs_baseline"],
        "details": details,
    }), flush=True)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures on a TPU; JAX reports "
                         f"{dev.platform!r}")
    chip_peak_flops(dev)   # an unknown device kind fails before any config
    from paddle_tpu.core import compile_cache

    platform = dev.platform
    note = {"platform": platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "compile_cache": compile_cache.configure()}
    # FLAGS_tuned_profile: apply a pinned tuner manifest before any
    # config builds executables (fail-loud on CRC/topology mismatch)
    from paddle_tpu import tuner as _tuner

    prof = _tuner.maybe_apply_flagged()
    if prof is not None:
        note["tuned_profile"] = {
            "workload": prof.workload,
            "flags": prof.flags,
            "measured_s": prof.measured_s}
    baselines = _load_baselines(platform)
    new_baselines = dict(baselines)
    results = {}
    for name, fn in CONFIGS:
        t_cfg = time.perf_counter()
        print(f"[bench] running {name}...", file=sys.stderr, flush=True)
        # per-config observability window: the snapshot embedded below
        # covers exactly this config's dispatches/stalls/retraces
        from paddle_tpu import observability as _obs

        _obs.reset()
        r = fn()
        r.setdefault("details", {})["observability"] = _obs.summary()
        pinned = baselines.get(name)
        if pinned:
            r["vs_baseline"] = round(r["value"] / pinned, 4)
            if r["vs_baseline"] < 1.0:
                # pinned-platform regression: RED build signal (policy
                # in BENCH_BASELINE.json); a missing pin never flags
                r["red_signal"] = True
                note.setdefault("red_signals", []).append(name)
                print(f"[bench] RED: {name} vs_baseline="
                      f"{r['vs_baseline']} < 1.0 (pin {pinned})",
                      file=sys.stderr, flush=True)
        else:
            r["vs_baseline"] = 1.0  # first TPU run pins the baseline
        if name not in new_baselines:
            new_baselines[name] = r["value"]
        # MFU red-line: the flagship's MFU is pinned as its own floor
        # ("llama_train_mfu_floor") — dropping below it REDs even when
        # raw tokens/s stays above the throughput pin (e.g. a kernel
        # regression masked by a faster host).
        mfu = r["details"].get("mfu")
        if name == "llama_train_tokens_per_sec_per_chip" and mfu:
            floor = baselines.get("llama_train_mfu_floor")
            r["details"]["mfu_floor"] = floor or round(mfu, 4)
            if floor and mfu < floor:
                r["red_signal"] = True
                note.setdefault("red_signals", []).append("llama_train_mfu")
                print(f"[bench] RED: pallas-ffn mfu={mfu} below "
                      f"pinned floor {floor}", file=sys.stderr,
                      flush=True)
            if "llama_train_mfu_floor" not in new_baselines:
                new_baselines["llama_train_mfu_floor"] = round(mfu, 4)
        r["details"]["config_wall_s"] = round(time.perf_counter() - t_cfg, 1)
        print(f"[bench] {name}: {r['value']} {r.get('unit')} "
              f"({r['details']['config_wall_s']}s)", file=sys.stderr, flush=True)
        results[name] = r
    if new_baselines != baselines:
        _save_baselines(platform, new_baselines)
    _emit(results, note)


if __name__ == "__main__":
    main()
