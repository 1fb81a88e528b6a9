"""Op microbenchmark regression gate.

Reference: tools/ci_op_benchmark.sh:128 — CI times a basket of ops on the
PR branch and diffs against develop, failing on regressions. Here the
baseline is a pinned JSON per platform (op_bench_baseline.json next to
this script): run with --update to (re)pin, run bare to compare; exit 1
when any op is slower than threshold x its pinned time.

Usage:
    python tools/ci_op_benchmark.py --update      # pin current timings
    python tools/ci_op_benchmark.py               # gate (default 1.5x)
    python tools/ci_op_benchmark.py --threshold 2.0

The basket covers the op families whose regressions have bitten before:
matmul epilogues, conv, norm/softmax fusions, attention, scatter/gather,
reductions. Kernel entries time the JITTED raw kernel (steady-state,
after warmup — compiled-code regressions); the eager_dispatch_* entries
go through the PUBLIC op api on Tensors, so call_op / tape bookkeeping
regressions (the eager hot path) are gated too.

Baselines are keyed by platform + cpu count: absolute microsecond pins
only gate the machine class that produced them; an unmatched key is
reported and skipped, never failed.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import jax
import jax.numpy as jnp

BASE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "op_bench_baseline.json")

RS = np.random.RandomState(0)

# observability must stay cheap enough to leave always-on: the recorder+
# metrics path on a cache-hit eager dispatch is budgeted at 3% (or, on
# machines where 3% of a dispatch is below timer noise, 1.5us absolute)
OBS_OVERHEAD_BUDGET_PCT = 3.0
OBS_OVERHEAD_FLOOR_US = 1.5

# noise-aware gating: the RED threshold for an op widens by the measured
# dispersion of BOTH sides of the comparison (the pin's rel-IQR recorded
# at --update time plus the current run's), so an op that is simply noisy
# on this machine class doesn't trip the gate at a fixed ratio while a
# genuinely regressed quiet op still does. The widened threshold is
# capped: past 4x even a noisy op is a real regression.
NOISE_WIDEN_K = 2.0
NOISE_WIDEN_CAP = 4.0


def entry_time(entry):
    """Pinned/measured seconds from either baseline format: the legacy
    flat float or the {"t": ..., "noise": ...} dict."""
    if isinstance(entry, (int, float)):
        return float(entry)
    if isinstance(entry, dict) and "t" in entry:
        return float(entry["t"])
    return None


def entry_noise(entry) -> float:
    if isinstance(entry, dict):
        return float(entry.get("noise", 0.0))
    return 0.0


def effective_threshold(base: float, pin_entry, cur_entry) -> float:
    widened = base + NOISE_WIDEN_K * (entry_noise(pin_entry)
                                      + entry_noise(cur_entry))
    return min(widened, max(base, NOISE_WIDEN_CAP))


def measure_observability_overhead(batch: int = 2000, rounds: int = 7,
                                   attempts: int = 3):
    """Eager-dispatch cost with metrics sampling on vs off.

    Returns {"on_us", "off_us", "overhead_pct", "overhead_us",
    "budget_pct", "attempts_used", "exceeded"}.

    Paired median-of-k sampling: each round times one batch with sampling
    ON immediately followed by one with sampling OFF, so clock-frequency
    drift and allocator phase land on both sides of a pair equally; the
    reported overhead is the MEDIAN per-pair difference — one noisy round
    cannot flip the gate the way the old min-of-phase comparison could
    (the two phases ran seconds apart and compared noise floors measured
    under different machine states). A measurement still over budget is
    re-run up to ``attempts`` times, keeping the best, so the gate fires
    only on reproducible overhead, never one scheduler hiccup.
    """
    import paddle_tpu  # noqa: F401
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.dispatch import OPS

    tiny = jnp.asarray(RS.randn(32).astype(np.float32))
    t = Tensor._from_data(tiny)
    add = OPS["add"]

    def _batch(sampling: int) -> float:
        _flags.set_flags({"metrics_sampling": sampling})
        t0 = time.perf_counter()
        for _ in range(batch):
            add(t, t)
        return (time.perf_counter() - t0) / batch

    def _over(on, off, overhead):
        pct = 100.0 * overhead / off if off > 0 else 0.0
        return bool(pct > OBS_OVERHEAD_BUDGET_PCT
                    and overhead * 1e6 > OBS_OVERHEAD_FLOOR_US)

    def _attempt():
        try:
            for sampling in (1, 0):   # warm both configs' caches
                _flags.set_flags({"metrics_sampling": sampling})
                for _ in range(200):
                    add(t, t)
            pairs = [(_batch(1), _batch(0)) for _ in range(rounds)]
        finally:
            _flags.set_flags({"metrics_sampling": 1})
        on = min(p[0] for p in pairs)
        off = min(p[1] for p in pairs)
        overhead = statistics.median(p[0] - p[1] for p in pairs)
        return on, off, overhead

    best = None
    used = 0
    for _ in range(max(1, attempts)):
        used += 1
        cand = _attempt()
        if best is None or cand[2] < best[2]:
            best = cand
        if not _over(*best):
            break
    on, off, overhead = best
    pct = 100.0 * overhead / off if off > 0 else 0.0
    return {
        "on_us": on * 1e6,
        "off_us": off * 1e6,
        "overhead_us": overhead * 1e6,
        "overhead_pct": pct,
        "budget_pct": OBS_OVERHEAD_BUDGET_PCT,
        "attempts_used": used,
        "exceeded": _over(on, off, overhead),
    }


def _basket():
    import paddle_tpu  # noqa: F401  (registers ops)
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.dispatch import OPS

    a = jnp.asarray(RS.randn(256, 256).astype(np.float32))
    b = jnp.asarray(RS.randn(256, 256).astype(np.float32))
    img = jnp.asarray(RS.randn(8, 32, 32, 32).astype(np.float32))
    nchw = jnp.asarray(RS.randn(8, 16, 32, 32).astype(np.float32))
    w = jnp.asarray(RS.randn(16, 16, 3, 3).astype(np.float32))
    qkv = jnp.asarray(RS.randn(4, 128, 4, 32).astype(np.float32))
    tiny = jnp.asarray(RS.randn(32).astype(np.float32))
    seg_x = jnp.asarray(RS.randn(1024, 64).astype(np.float32))
    seg_id = jnp.asarray(RS.randint(0, 64, 1024).astype(np.int32))

    K = {name: OPS[name]._kernel for name in OPS}
    t_tiny = Tensor._from_data(tiny)
    t_tiny_g = Tensor._from_data(tiny)
    t_tiny_g.stop_gradient = False

    from paddle_tpu.core import flags as _flags
    from paddle_tpu.ops import dispatch as _dispatch

    def _add_uncached():
        # the pre-cache dispatch cost: flag off forces the jax.vjp-every-call
        # path, which is what every dispatch paid before the signature cache
        _flags.set_flags({"eager_dispatch_cache": False})
        try:
            return OPS["add"](t_tiny_g, t_tiny_g)._data
        finally:
            _flags.set_flags({"eager_dispatch_cache": True})

    # DP flat-pack: the reducer's cached jitted pack executable (steady
    # state) vs tracing a fresh one every call (what each step paid before
    # the signature-keyed plan cache)
    from paddle_tpu.core.tensor import Parameter
    from paddle_tpu.distributed import parallel as _par

    pack_ps = [Parameter.from_tensor(
        Tensor(jnp.asarray(RS.randn(64, 64).astype(np.float32))),
        name=f"_ci_pack_{i}") for i in range(4)]
    pack_bucket = _par._Bucket(0, pack_ps, nranks=1, comm_dtype=None)
    pack_bucket.pack = _par._make_pack(pack_bucket)
    pack_arrs = [p._data for p in pack_ps]
    pack_bucket.pack(pack_arrs)  # trace once outside the clock

    def _pack_uncached():
        b = _par._Bucket(0, pack_ps, nranks=1, comm_dtype=None)
        return _par._make_pack(b)(pack_arrs)

    # int8 wire codec (quant_comm): the error-feedback fused pack and the
    # gather-decode, cached vs uncached, plus the bf16 cast pack — the
    # codec's overhead vs the plain compressed wire
    from paddle_tpu.distributed import quant_comm as _qcomm

    q8_bucket = _par._Bucket(0, pack_ps, nranks=1, comm_dtype="int8")
    q8_bucket.qpack = _qcomm.make_pack_q8(q8_bucket)
    q8_bucket.qdecode = _qcomm.make_decode_q8(q8_bucket)
    q8_res = _qcomm.zeros_residual(q8_bucket)
    q8_wire = q8_bucket.qpack(pack_arrs, q8_res)[0]
    q8_gathered = jnp.stack([q8_wire])
    q8_bucket.qdecode(q8_gathered)  # trace once outside the clock

    def _q8_pack_uncached():
        b = _par._Bucket(0, pack_ps, nranks=1, comm_dtype="int8")
        return _qcomm.make_pack_q8(b)(pack_arrs, q8_res)[0]

    bf16_bucket = _par._Bucket(0, pack_ps, nranks=1, comm_dtype="bfloat16")
    bf16_bucket.pack = _par._make_pack(bf16_bucket)
    bf16_bucket.pack(pack_arrs)  # trace once outside the clock

    # pallas-vs-stock paged attention (fusion-paper methodology: measure
    # what XLA already does before owning a kernel). Fixed tiny serving
    # shapes — B=4 slots, 2 kv heads x group 2, hd=32, 16-token pages.
    # Pallas entries run interpret mode on CPU (keyed per-platform, so the
    # CPU pin gates interpret overhead and a TPU pin gates the real
    # kernel); decode uses the max_q=1 specialized launch.
    def _blk_mha(this, past, quant=False, use_pallas=False):
        KVh, G, hd, bs, mb, nb = 2, 2, 32, 16, 4, 24
        H = KVh * G
        Bb = len(this)
        tok = sum(this)
        cu = np.zeros(Bb + 1, np.int32)
        cu[1:] = np.cumsum(this)
        tables = np.full((Bb, mb), -1, np.int32)
        used = 0
        for i in range(Bb):
            for p_ in range(-(-(past[i] + this[i]) // bs)):
                tables[i, p_] = used
                used += 1
        qkv_in = jnp.asarray(RS.randn(tok, (H + 2 * KVh) * hd)
                             .astype(np.float32))
        if quant:
            kc = jnp.asarray(RS.randint(-127, 128, (nb, KVh, bs, hd))
                             .astype(np.int8))
            vc = jnp.asarray(RS.randint(-127, 128, (nb, KVh, bs, hd))
                             .astype(np.int8))
            kq = jnp.full((KVh,), 42.3, jnp.float32)
            vq = jnp.full((KVh,), 37.1, jnp.float32)
            scales = dict(cache_k_quant_scales=kq, cache_v_quant_scales=vq,
                          cache_k_dequant_scales=jnp.broadcast_to(
                              1.0 / kq, (nb, KVh)),
                          cache_v_dequant_scales=jnp.broadcast_to(
                              1.0 / vq, (nb, KVh)))
        else:
            kc = jnp.asarray(RS.randn(nb, KVh, bs, hd).astype(np.float32))
            vc = jnp.asarray(RS.randn(nb, KVh, bs, hd).astype(np.float32))
            scales = {}
        fixed = dict(cu_seqlens_q=jnp.asarray(cu),
                     block_tables=jnp.asarray(tables), block_size=bs,
                     use_pallas=use_pallas, **scales)
        zb = jnp.zeros(Bb, jnp.int32)
        past_a = jnp.asarray(past, np.int32)
        this_a = jnp.asarray(this, np.int32)
        blk = K["block_multihead_attention_"]
        return lambda: blk(qkv_in, kc, vc, zb, past_a, this_a, **fixed)

    PRE, DEC = ([16, 16, 16, 16], [0, 0, 0, 0]), ([1, 1, 1, 1], [31, 17, 9, 40])
    MIX = ([16, 1, 1, 8], [0, 12, 30, 16])
    blk_entries = {
        "block_mha_prefill_stock": _blk_mha(*PRE),
        "block_mha_prefill_pallas": _blk_mha(*PRE, use_pallas=True),
        "block_mha_decode_stock": _blk_mha(*DEC),
        "block_mha_decode_pallas": _blk_mha(*DEC, use_pallas="decode"),
        "block_mha_mixed_stock": _blk_mha(*MIX),
        "block_mha_mixed_pallas": _blk_mha(*MIX, use_pallas=True),
        "block_mha_int8_stock": _blk_mha(*DEC, quant=True),
        "block_mha_int8_pallas": _blk_mha(*DEC, quant=True,
                                          use_pallas="decode"),
    }

    # fused SwiGLU FFN vs the stock three-matmul chain: fwd, bwd (through
    # the custom_vjp — two Pallas launches), and the weight-only int8
    # dequant variant. Pallas entries run interpret mode on CPU (same
    # per-platform-pin policy as the block_mha entries: the CPU pin gates
    # interpret overhead, a TPU pin gates the real kernel). Decode-ish
    # tile: 128 rows, d=128, d_ff=256.
    from paddle_tpu.ops.pallas import fused_ffn as FF

    fx = jnp.asarray(RS.randn(128, 128).astype(np.float32))
    fw1 = jnp.asarray(RS.randn(128, 256).astype(np.float32))
    fw3 = jnp.asarray(RS.randn(128, 256).astype(np.float32))
    fw2 = jnp.asarray(RS.randn(256, 128).astype(np.float32))

    def _stock_ffn(x, w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def _absmax_q8(w):
        s = jnp.max(jnp.abs(w), axis=0, keepdims=True)
        return jnp.round(w / s * 127.0).astype(jnp.int8), s

    fw1_q, fw1_s = _absmax_q8(fw1)
    fw3_q, fw3_s = _absmax_q8(fw3)
    fw2_q, fw2_s = _absmax_q8(fw2)

    def _stock_ffn_w8(x):
        # the stock w8 path: int8 matmul in f32, per-out-channel scale
        # applied post-matmul (matmul_param dequant order)
        u = (x @ fw1_q.astype(jnp.float32)) * (fw1_s / 127.0)
        v = (x @ fw3_q.astype(jnp.float32)) * (fw3_s / 127.0)
        return ((jax.nn.silu(u) * v)
                @ fw2_q.astype(jnp.float32)) * (fw2_s / 127.0)

    _stock_bwd = jax.grad(lambda args: jnp.sum(_stock_ffn(*args)))
    _pallas_bwd = jax.grad(lambda args: jnp.sum(FF.fused_ffn(*args)))
    ffn_entries = {
        "ffn_fwd_stock": lambda: _stock_ffn(fx, fw1, fw3, fw2),
        "ffn_fwd_pallas": lambda: FF.fused_ffn(fx, fw1, fw3, fw2),
        "ffn_bwd_stock": lambda: _stock_bwd((fx, fw1, fw3, fw2)),
        "ffn_bwd_pallas": lambda: _pallas_bwd((fx, fw1, fw3, fw2)),
        "ffn_int8_stock": lambda: _stock_ffn_w8(fx),
        "ffn_int8_pallas": lambda: FF.fused_ffn_w8(
            fx, fw1_q, fw1_s, fw3_q, fw3_s, fw2_q, fw2_s),
    }

    # whole decode tick through the paged serving engine, stock
    # vs the fused tick (paged-attention + fused FFN + fused sampler
    # prep). Eager entries: eng.step() is host orchestration around one
    # cached executable — the number being gated is the end-to-end tick,
    # exactly what serving latency is made of. Engines are pre-warmed
    # (prefill + first decode tick compile outside the clock) and seeded
    # with enough queued generation to cover warmup + reps ticks.
    def _tick_engine(params_cfg, pallas=None, pallas_ffn=None):
        from paddle_tpu.inference.serving import PagedServingEngine

        cfg, params = params_cfg
        eng = PagedServingEngine(cfg, params, num_blocks=64, block_size=8,
                                 max_batch=4, token_budget=64,
                                 max_len=cfg.max_seq_len, pallas=pallas,
                                 pallas_ffn=pallas_ffn)
        rs = np.random.RandomState(5)
        for _ in range(4):
            eng.submit(rs.randint(1, cfg.vocab_size, 16).tolist(),
                       max_new_tokens=72)
        eng.step()   # prefill executable
        eng.step()   # decode executable — steady state from here
        return eng

    from paddle_tpu.models import llama as _L

    _tick_cfg = _L.LlamaConfig(vocab_size=97, hidden_size=32,
                               intermediate_size=64, num_layers=2,
                               num_heads=4, num_kv_heads=2, max_seq_len=96,
                               dtype=np.float32)
    _tick_pc = (_tick_cfg, _L.init_params(_tick_cfg, jax.random.PRNGKey(0)))
    tick_stock = _tick_engine(_tick_pc)
    tick_fused = _tick_engine(_tick_pc, pallas=True, pallas_ffn=True)

    # eager entries run the PUBLIC api (dispatch + tape), not raw kernels;
    # they are marked so measure() skips jitting them
    eager = {
        "eager_dispatch_add": lambda: OPS["add"](t_tiny, t_tiny)._data,
        "eager_dispatch_add_grad": lambda: OPS["add"](
            t_tiny_g, t_tiny_g)._data,
        "eager_dispatch_add_uncached": _add_uncached,
        "dp_flat_pack_cached": lambda: pack_bucket.pack(pack_arrs),
        "dp_flat_pack_uncached": _pack_uncached,
        "dp_flat_pack_bf16_cached": lambda: bf16_bucket.pack(pack_arrs),
        "dp_q8_pack_cached": lambda: q8_bucket.qpack(pack_arrs, q8_res)[0],
        "dp_q8_pack_uncached": _q8_pack_uncached,
        "dp_q8_decode_cached": lambda: q8_bucket.qdecode(q8_gathered),
        "decode_tick_stock": tick_stock.step,
        "decode_tick_fused": tick_fused.step,
    }
    jitted = {
        "matmul_256": lambda: K["matmul"](a, b),
        "fc_gelu": lambda: K["fc"](a, b, None, activation_type="gelu"),
        "conv2d_3x3": lambda: K["conv2d"](nchw, w, None, 1, 1, 1, 1,
                                          "NCHW"),
        "layer_norm": lambda: K["layer_norm"](img, None, None, 1e-5, -1),
        "softmax": lambda: K["softmax"](a, -1),
        "flash_attn_or_sdpa": lambda: K["flash_attn"](qkv, qkv, qkv,
                                                      causal=True),
        "segment_sum": lambda: K["segment_pool"](seg_x, seg_id, "SUM", 64),
        "reduce_sum": lambda: K["sum"](img),
        "topk": lambda: K["topk"](a, 8),
        **blk_entries,
        **ffn_entries,
    }
    return eager, jitted


def _rel_iqr(times) -> float:
    """Measurement dispersion as (q75 - q25) / median — scale-free, so
    a 3us op and a 3ms tick report comparable noise, and robust to the
    one-outlier reps that a shared-CI box produces."""
    med = statistics.median(times)
    if med <= 0 or len(times) < 4:
        return 0.0
    q = statistics.quantiles(times, n=4)
    return max(0.0, (q[2] - q[0]) / med)


def measure(reps: int = 20, warmup: int = 3, only=None, detail: bool = False):
    """Median seconds per basket entry ({name: float}); broken entries
    report {"error": ...}. detail=True returns {"t": median, "noise":
    rel_IQR} per entry instead, so callers (the gate's --update path,
    the tuner's OpCosts.refresh) can persist dispersion next to the pin."""
    out = {}
    eager, jitted = _basket()
    from paddle_tpu.ops import dispatch as _dispatch

    _dispatch.reset_dispatch_cache_stats()
    entries = [(n, f, False) for n, f in eager.items()] + \
        [(n, f, True) for n, f in jitted.items()]
    if only is not None:
        entries = [e for e in entries if e[0] in only]
    for name, fn, do_jit in entries:
        jfn = jax.jit(fn) if do_jit else fn
        try:
            for _ in range(warmup):
                jax.tree.map(
                    lambda x: x.block_until_ready() if hasattr(
                        x, "block_until_ready") else x, jfn())
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.tree.map(
                    lambda x: x.block_until_ready() if hasattr(
                        x, "block_until_ready") else x, jfn())
                times.append(time.perf_counter() - t0)
            med = statistics.median(times)
            out[name] = ({"t": med, "noise": _rel_iqr(times)}
                         if detail else med)
        except Exception as e:  # basket op broken counts as a failure too
            out[name] = {"error": f"{type(e).__name__}: {str(e)[:120]}"}
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--update", action="store_true",
                   help="pin current timings as the baseline")
    p.add_argument("--threshold", type=float, default=1.5,
                   help="fail when median time > threshold * baseline")
    p.add_argument("--reps", type=int, default=20)
    args = p.parse_args()

    platform = jax.devices()[0].platform
    # absolute-time pins only gate the machine class that produced them;
    # affinity-aware count so a cgroup-limited container keys correctly
    try:
        ncpu = len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        ncpu = os.cpu_count()
    key = f"{platform}/{ncpu}cpu"
    current = measure(args.reps, detail=True)
    from paddle_tpu.ops.dispatch import dispatch_cache_stats

    cache = dispatch_cache_stats()
    obs = measure_observability_overhead()
    print(json.dumps({"key": key, "timings": current,
                      "observability_overhead": obs,
                      "dispatch_cache": {"hit_rate": cache["hit_rate"],
                                         "traces": cache["traces"],
                                         "entries": cache["entries"]}},
                     indent=1))

    if args.update:
        broken = {n: t for n, t in current.items()
                  if isinstance(t, dict) and "error" in t}
        if broken:
            print(f"[op-bench] refusing to pin a broken baseline: "
                  f"{sorted(broken)}", file=sys.stderr)
            return 1
        data = {}
        if os.path.exists(BASE_PATH):
            with open(BASE_PATH) as f:
                data = json.load(f)
        data[key] = current
        with open(BASE_PATH, "w") as f:
            json.dump(data, f, indent=1)
        print(f"[op-bench] baseline pinned for {key!r}", file=sys.stderr)
        return 0

    if not os.path.exists(BASE_PATH):
        print("[op-bench] no baseline; run with --update first",
              file=sys.stderr)
        return 0
    with open(BASE_PATH) as f:
        base = json.load(f).get(key)
    if not base:
        print(f"[op-bench] no baseline for machine key {key!r}; "
              f"run --update on this machine class first", file=sys.stderr)
        return 0

    failures = []
    print(f"[op-bench] observability overhead: {obs['overhead_pct']:.2f}% "
          f"({obs['on_us']:.2f}us on vs {obs['off_us']:.2f}us off, "
          f"budget {OBS_OVERHEAD_BUDGET_PCT:.0f}%)", file=sys.stderr)
    if obs["exceeded"]:
        failures.append(
            f"observability_overhead: {obs['overhead_pct']:.2f}% "
            f"> {OBS_OVERHEAD_BUDGET_PCT:.0f}% budget")
    # per-op (current seconds, pinned seconds, effective threshold): the
    # threshold widens by the recorded dispersion of the pin plus the
    # current run, so "this op is noisy on this box" is structural state
    # in the baseline, not a one-off --threshold bump someone hand-tunes
    ratios = {}
    for name, cur in current.items():
        pinned = base.get(name)
        if isinstance(cur, dict) and "error" in cur:
            failures.append(f"{name}: {cur['error']}")
            continue
        t, p = entry_time(cur), entry_time(pinned)
        if t is None or p is None:
            continue
        ratios[name] = (t, p, effective_threshold(args.threshold,
                                                  pinned, cur))
    over = sorted(n for n, (t, p, th) in ratios.items() if t / p > th)
    if over:
        # outlier tolerance: one shared-CI scheduler hiccup lands on one
        # measurement, a real regression lands on every one — re-measure
        # just the over-threshold ops and keep the better median, so the
        # gate fails only on reproducible slowdowns
        print(f"[op-bench] re-measuring {len(over)} over-threshold op(s) "
              f"to rule out one-shot noise: {over}", file=sys.stderr)
        retry = measure(args.reps, only=set(over), detail=True)
        for name in over:
            t2 = entry_time(retry.get(name))
            if t2 is not None:
                t, p, th = ratios[name]
                ratios[name] = (min(t, t2), p, th)
    for name, (t, pinned, th) in sorted(ratios.items()):
        ratio = t / pinned
        flag = " <-- REGRESSION" if ratio > th else ""
        widened = f", gate x{th:.2f}" if th != args.threshold else ""
        print(f"[op-bench] {name}: {t * 1e6:.0f}us vs pinned "
              f"{pinned * 1e6:.0f}us (x{ratio:.2f}{widened}){flag}",
              file=sys.stderr)
        if ratio > th:
            failures.append(f"{name}: x{ratio:.2f} slower "
                            f"(noise-widened gate x{th:.2f})")
    if failures:
        print("[op-bench] FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    print("[op-bench] all ops within threshold", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
