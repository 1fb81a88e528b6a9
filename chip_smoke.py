"""Start the two hot paths on the TPU and check what comes out.

    python chip_smoke.py [--seed N]      one chip: trainer, then server
    python chip_smoke.py --chips 4       four chips: the sharded trainer only

One process, which is the only one that touches JAX. Exits non-zero, and
prints no result line, without a TPU, when a phase raises, or when a
check fails. Weights, prompts and the batch come from --seed; nothing
outside the checkout is read and the network is not used. The last line
of standard output is the result the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Both phases run `CONFIGS["llama-7b"]` at its published widths (d 4096,
d_ff 11008, 32 heads of 128, vocab 32000) with random weights and cut
depth. Times printed here are smoke timings of single cold steps — not a
benchmark, and never a rate or a utilization.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core import compile_cache, native
from paddle_tpu.distributed import hybrid as H
from paddle_tpu.inference import quant as Q
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import fused_ffn as FF
from paddle_tpu.ops.pallas import fused_sample as FS
from paddle_tpu.ops.pallas import paged_attention as PA

BASE = L.CONFIGS["llama-7b"]
SEQ = 2048
# f32 params + f32 AdamW m/v + f32 grads are 16 bytes/param: two layers
# plus embed/lm_head (667M params) compile to 15.3 of the chip's 15.75 GiB
TRAIN_DEPTH = 2
TRAIN_STEPS = 5
SERVE_DEPTH = 8
# engine geometry: prompts up to 1024 + 64 new tokens in 16-token pages
SERVE = dict(block_size=16, max_batch=8, token_budget=128, max_len=1152)
# the stock path gathers every token's pages into a dense f32 view: a
# 128-token tick would need 19 GB of it, a 16-token tick compiles to 6 GiB
STOCK_TOKEN_BUDGET = 16
PROMPT_LENS = (64, 128, 256, 384, 512, 640, 768, 1024)
NEW_TOKENS = 64
# a token counts as a bf16 tie-break when the f32 reference puts it within
# four bf16 ulps (2^-7 each, relative to the row's largest logit) of the
# best logit: on the chip each bf16 engine sits up to 1.2 x two ulps from
# the reference, and two engines differ by twice that. int8 pages quantize
# K/V about four times as coarsely, and the reference does not quantize
TIE_TOL = 4 * 2.0 ** -7
INT8_TIE_TOL = 4 * TIE_TOL
MIN_AGREEMENT = 0.99


class CompileLog:
    """Counts from jax.monitoring. `made` is every executable JAX asked
    its backend for: the event spans the persistent-cache lookup, so a
    cache hit counts there as much as a compile."""

    def __init__(self):
        self.made = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.made += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")


def device_bytes(stat: str) -> list:
    return [d.memory_stats()[stat] for d in jax.devices()]


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def train(mesh_shape, batch: int, seed: int, log: CompileLog,
          microbatches: int = 1):
    """TRAIN_STEPS steps of the hybrid train step on a dp x pp x tp mesh,
    one fixed batch. Returns the per-step losses and the sharded state."""
    dp, pp, tp = mesh_shape
    cfg = dataclasses.replace(BASE, num_layers=TRAIN_DEPTH)
    rows = batch // dp // microbatches * SEQ
    f_local = cfg.intermediate_size // tp
    check(FF.supported(rows, cfg.hidden_size, f_local),
          f"fused FFN supports rows={rows} d={cfg.hidden_size} f={f_local}")
    mesh = H.build_mesh(dp, pp, tp)
    params = H.shard_params(L.init_params(cfg, jax.random.PRNGKey(seed)),
                            mesh, cfg)
    opt = H.init_opt_state(params)
    # flash / pallas are forced: an unsupported shape raises, where "auto"
    # would choose the XLA path
    step = H.make_train_step(cfg, mesh, num_microbatches=microbatches,
                             attn_impl="flash", ffn_impl="pallas")
    rs = np.random.RandomState(seed)
    data = rs.randint(0, cfg.vocab_size, (batch, SEQ + 1)).astype(np.int32)
    sharding = NamedSharding(mesh, P("dp", None))
    tokens = jax.device_put(data[:, :-1], sharding)
    targets = jax.device_put(data[:, 1:], sharding)

    losses, made, secs = [], [], []
    launches0 = FA.trace_launches()
    for _ in range(TRAIN_STEPS):
        made0, t0 = log.made, time.perf_counter()
        params, opt, loss = step(params, opt, tokens, targets)
        losses.append(float(jax.block_until_ready(loss)))
        secs.append(time.perf_counter() - t0)
        made.append(log.made - made0)
    launches = FA.trace_launches() - launches0
    print(f"trainer mesh dp{dp} pp{pp} tp{tp}: depth {TRAIN_DEPTH} of "
          f"{BASE.num_layers}, d {cfg.hidden_size}, d_ff "
          f"{cfg.intermediate_size}, seq {SEQ}, batch {batch}, "
          f"{cfg.num_params():,} params, bf16 compute, f32 AdamW")
    print(f"trainer losses {[round(x, 4) for x in losses]}")
    print(f"trainer executables made per step {made}; smoke timing of each "
          f"step, first one compiling, seconds "
          f"{[round(s, 2) for s in secs]}")
    print(f"trainer kernels: flash attention (attn_impl=flash) and fused "
          f"FFN (ffn_impl=pallas) forced; {launches} Pallas launches traced "
          f"into the step")
    print(f"trainer bytes_in_use per device {device_bytes('bytes_in_use')}, "
          f"peak {device_bytes('peak_bytes_in_use')}")
    check(all(np.isfinite(losses)), "every loss is finite")
    check(losses[-1] < losses[0], "loss fell from step 1 to step 5")
    check(made[0] >= 1 and not any(made[1:]),
          f"the step compiled in step 1 and in no later step (got {made})")
    # fwd + two backward launches of each kernel, at least
    check(launches >= 6, "flash and fused-FFN launches are in the step")
    return losses, params, opt


def spread(tree, n_devices: int) -> float:
    """Largest share of a sharded pytree's bytes held by one device."""
    per_device = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (per_device.get(shard.device.id, 0)
                                           + shard.data.nbytes)
    check(len(per_device) == n_devices,
          f"state lives on {n_devices} devices (found {len(per_device)})")
    return max(per_device.values()) / sum(per_device.values())


def run_four_chips(seed: int, log: CompileLog) -> None:
    """The sharded trainer against the one-chip step on the same batch."""
    batch = 2
    one, params, opt = train((1, 1, 1), batch, seed, log)
    del params, opt
    for mesh_shape, microbatches in (((2, 1, 2), 1), ((1, 2, 2), 2)):
        losses, params, opt = train(mesh_shape, batch, seed, log,
                                    microbatches)
        share = spread((params, opt), 4)
        print(f"mesh {mesh_shape}: first loss {losses[0]:.4f} vs one chip "
              f"{one[0]:.4f}; largest per-device share of params+optimizer "
              f"bytes {share:.3f}")
        # dp replicates and tp/pp split, so no device holds it all
        check(share <= 0.5, "params and optimizer state are spread, not on "
                            "one device")
        check(abs(losses[0] - one[0]) <= 1e-2 * abs(one[0]),
              "first-step loss matches the one-chip step within bf16 "
              "tolerance")
        del params, opt


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def serve(cfg, params, prompts, new_tokens, seed: int, log: CompileLog,
          **engine_kw):
    """Build an engine, warm both of its executables (mixed prefill+decode
    and the decode tick) with two throwaway requests, then run `prompts`
    through submit/step. Returns (tokens per prompt, engine stats)."""
    eng = PagedServingEngine(cfg, params, **{**SERVE, **engine_kw})
    rs = np.random.RandomState(seed + 2)
    budget = eng.token_budget
    for n in (budget // 3, budget * 3 // 2):   # the longer one is chunked
        eng.submit(rs.randint(1, cfg.vocab_size, n), max_new_tokens=4)
    while eng.has_work():
        eng.step()
    eng.run()                # drop the warm-up completions

    made0, launches0, t0 = log.made, FA.trace_launches(), time.perf_counter()
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    ticks = 0
    while eng.has_work():
        eng.step()
        ticks += 1
    done = {c.rid: c.output_tokens for c in eng.run()}
    secs = time.perf_counter() - t0
    check(set(done) == set(rids), "every request completed")
    check(all(len(done[r]) == new_tokens for r in rids),
          f"every request produced {new_tokens} tokens")
    check(log.made == made0 and FA.trace_launches() == launches0,
          "no compile and no retrace after the warm-up ticks")
    stats = dict(eng.stats, ticks=ticks, smoke_secs=round(secs, 2))
    return [done[r] for r in rids], stats


_REFERENCE = jax.jit(
    lambda params, tokens, cfg: L.forward(params, tokens, cfg,
                                          attn_impl="xla", ffn_impl="stock"),
    static_argnums=2)


def reference_logits(cfg, params, prompt, generated):
    """Teacher-forced f32 logits of the plain model (XLA attention, stock
    FFN, highest matmul precision) at each generated position."""
    seq = np.zeros((1, SERVE["max_len"]), np.int32)
    full = list(prompt) + list(generated)
    seq[0, :len(full)] = full
    with jax.default_matmul_precision("highest"):
        logits = _REFERENCE(params, jnp.asarray(seq), cfg)
    first = len(prompt) - 1
    return np.asarray(logits[0, first:first + len(generated)])


def agreement(cfg, params, prompts, ours, stock, label: str,
              tie_tol: float) -> None:
    """Token agreement of the Pallas engine with the stock engine and with
    the f32 reference. Engines are compared up to each request's first
    mismatch (after it they condition on different text); a mismatch where
    the reference holds both tokens within `tie_tol` is a tie-break and
    is set aside."""
    ref_cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    same = ties = wrong = off_reference = total = 0
    worst, mismatches = 0.0, []
    for prompt, a, b in zip(prompts, ours, stock):
        logits = reference_logits(ref_cfg, params, prompt, a)
        tol = tie_tol * np.abs(logits).max(axis=-1)
        gaps = logits.max(axis=-1) - logits[np.arange(len(a)), a]
        off_reference += int((gaps > tol).sum())
        worst = max(worst, float((gaps / tol).max()))
        total += len(a)
        for t, (x, y) in enumerate(zip(a, b)):
            if x == y:
                same += 1
                continue
            gap = abs(logits[t, x] - logits[t, y]) / tol[t]
            mismatches.append((len(prompt), t, round(float(gap), 2)))
            if gap <= 1.0:
                ties += 1
            else:
                wrong += 1
            break
    vs_stock = same / max(same + wrong, 1)
    vs_reference = 1.0 - off_reference / total
    print(f"{label}: agreement with the stock engine {vs_stock:.4f} "
          f"({same} equal, {wrong} different, {ties} bf16 tie-breaks set "
          f"aside, {total - same - wrong - ties} positions after a "
          f"tie-break not compared); agreement with the f32 reference, "
          f"teacher-forced, {vs_reference:.4f} over {total} tokens (largest "
          f"gap {worst:.2f} of the tie tolerance); first mismatches as "
          f"(prompt length, position, reference gap / tolerance) "
          f"{mismatches}")
    check(vs_stock >= MIN_AGREEMENT,
          f"{label}: stock-engine agreement >= {MIN_AGREEMENT}")
    check(vs_reference >= MIN_AGREEMENT,
          f"{label}: reference agreement >= {MIN_AGREEMENT}")


def run_server(seed: int, log: CompileLog) -> None:
    cfg = dataclasses.replace(BASE, num_layers=SERVE_DEPTH,
                              param_dtype=jnp.bfloat16)
    params = L.init_params(cfg, jax.random.PRNGKey(seed + 1))
    rs = np.random.RandomState(seed + 1)
    prompts = [rs.randint(1, cfg.vocab_size, n).tolist() for n in PROMPT_LENS]
    rows = max(SERVE["token_budget"], SERVE["max_batch"])
    check(FF.supported(rows, cfg.hidden_size, cfg.intermediate_size)
          and PA.supported(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                           SERVE["block_size"])
          and FS.supported(SERVE["max_batch"], cfg.vocab_size),
          "the engine geometry is supported by all three kernels")
    print(f"server: depth {SERVE_DEPTH} of {BASE.num_layers}, bf16 weights, "
          f"{SERVE}, {len(prompts)} greedy requests, prompts {PROMPT_LENS}, "
          f"{NEW_TOKENS} new tokens each")

    def both(label, prompts, new_tokens, tie_tol, **kw):
        ours, stats = serve(cfg, params, prompts, new_tokens, seed, log,
                            pallas=True, pallas_ffn=True, **kw)
        print(f"{label} pallas engine: {stats}")
        check(stats["pallas_steps"] == stats["steps"]
              and stats["ffn_steps"] == stats["steps"],
              "paged attention and the fused FFN ran in every tick")
        # the fused decode tick traces the page write, paged attention and
        # the fused FFN (once each, in the layer scan) + the sampler prep
        check(stats["fused_ticks"] > 0
              and stats["tick_pallas_launches"] == 4,
              "the fused decode tick holds the four kernels")
        stock, stats = serve(cfg, params, prompts, new_tokens, seed, log,
                             pallas=False, pallas_ffn=False,
                             token_budget=STOCK_TOKEN_BUDGET, **kw)
        print(f"{label} stock engine: {stats}")
        check(stats["pallas_steps"] == 0 and stats["ffn_steps"] == 0,
              "the stock engine ran no kernel")
        agreement(cfg, params, prompts, ours, stock, label, tie_tol)

    both("server", prompts, NEW_TOKENS, TIE_TOL)
    # int8 pages: calibrate K/V scales on one prompt, then a short pass
    manifest = Q.calibrate(cfg, params, [prompts[1]])
    both("server int8 pages", prompts[:4], 16, INT8_TIE_TOL, quant_kv=True,
         quant_manifest=manifest)
    print(f"server peak_bytes_in_use {device_bytes('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s), JAX reports "
              f"{device}", file=sys.stderr)
        return 1
    cache_dir = compile_cache.configure()
    log = CompileLog()
    print(f"device {device}; jax {jax.__version__}; seed {args.seed}")
    print(f"compile cache directory {cache_dir}")
    print(f"core/native in use: "
          f"{'g++-built .so' if native.available() else 'Python fallback'}")

    if args.chips == 4:
        run_four_chips(args.seed, log)
    else:
        train((1, 1, 1), 1, args.seed, log)
        run_server(args.seed, log)
    print(f"compile cache {cache_dir}: {log.made} executables made, "
          f"{log.hits} of them cache hits, {log.misses} entries written")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
