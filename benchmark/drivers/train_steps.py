"""Training steps through `distributed.hybrid.make_train_step` on the mesh
the configuration names: f32 master weights and AdamW, bf16 compute, and
`remat`, `attn_impl` and `ffn_impl` left at the function's defaults (the
path the program picks by itself).

Set-up (all of it counted in `setup_s`): weights made on the device, in
their sharding, from the seed in one jitted call; the optimizer state
likewise; the batch maker and the step compiled and run twice. Every
measured step draws a fresh seeded batch on the device.

The rate is tokens over the time from the start of the first measured
step to the `block_until_ready` end of the last one: a window holds some
tens of steps of some hundreds of milliseconds, and dividing by
`--seconds` would move the rate by one step in that many.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.distributed import hybrid as H
from paddle_tpu.models import llama as L

from ..lib.harness import (Context, Record, Spans, memory_peak_bytes,
                           seed_key, traced_window)
from ..lib.program import DTYPES, llama_config

# With weights drawn from N(0, 0.02^2) the logits of a position are about
# N(0, s^2) with s = 0.02 * sqrt(hidden_size) (the final norm makes the
# hidden state unit RMS), so the first loss is near ln(vocab) + s^2/2:
# 11.22 at these widths (ln 32768 = 10.40, s = 1.28). PR 22 read 11.18
# against 11.19 so predicted at llama widths. A first loss outside
# [ln V, ln V + s^2] means the forward pass, the targets or the loss are
# wrong, not that the draw was unlucky.
def first_loss_band(cfg: dict):
    ln_v = math.log(cfg["vocab_size"])
    s2 = (0.02 ** 2) * cfg["hidden_size"]
    return ln_v, ln_v + s2


def run(ctx: Context) -> Record:
    cfg, tr = ctx.config, ctx.traffic
    t = cfg["trainer"]
    spans = Spans()
    phases = {"imports_s": time.perf_counter() - ctx.process_start_s}
    t_build = time.perf_counter()
    lcfg = llama_config(cfg, DTYPES[t["param_dtype"]])
    dp, pp, tp = (t["mesh"][a] for a in ("dp", "pp", "tp"))
    mesh = H.build_mesh(dp, pp, tp)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             H.param_specs(lcfg),
                             is_leaf=lambda x: isinstance(x, P))
    key = seed_key(ctx.seed)
    params = jax.jit(
        lambda k: H.stack_pipeline(L.init_params(lcfg, k), pp),
        out_shardings=shardings)(key)
    opt = jax.jit(H.init_opt_state, out_shardings={
        "m": shardings, "v": shardings,
        "step": NamedSharding(mesh, P())})(params)
    step = H.make_train_step(lcfg, mesh, num_microbatches=tr["microbatches"])

    batch, seq = tr["global_batch"], tr["seq_len"]
    rows = NamedSharding(mesh, P("dp", None))

    def draw(k, i):
        data = jax.random.randint(jax.random.fold_in(k, i), (batch, seq + 1),
                                  0, cfg["vocab_size"], jnp.int32)
        return data[:, :-1], data[:, 1:]

    make_batch = jax.jit(draw, out_shardings=(rows, rows))
    data_key = jax.random.fold_in(key, 7)
    losses, step_ms = [], []
    n_steps = 0

    def one_step():
        nonlocal params, opt, n_steps
        t0 = time.perf_counter()
        with spans.span("bench.make_batch"):
            tokens, targets = make_batch(data_key, n_steps)
        with spans.span("bench.train_step"):
            params, opt, loss = step(params, opt, tokens, targets)
        with spans.span("bench.fetch_loss"):
            jax.block_until_ready(loss)
        t1 = time.perf_counter()
        n_steps += 1
        losses.append(loss)
        step_ms.append((t1 - t0) * 1e3)
        return t0, t1

    jax.block_until_ready(opt)
    phases["weights_and_step_s"] = time.perf_counter() - t_build
    t_warm = time.perf_counter()
    for _ in range(tr["warm_steps"]):
        one_step()
    warm = len(losses)
    phases["warm_steps_s"] = time.perf_counter() - t_warm

    gc.collect()
    gc.freeze()
    made0 = ctx.compile_log.made
    setup_s = time.perf_counter() - ctx.process_start_s
    deadline = time.perf_counter() + ctx.seconds
    first_start_s = last_end_s = None
    while time.perf_counter() < deadline:
        t0, last_end_s = one_step()
        if first_start_s is None:
            first_start_s = t0
    measured = len(losses) - warm
    counters = {
        "elapsed_s": last_end_s - first_start_s,
        "steps": measured, "tokens": measured * batch * seq, "seq_len": seq,
        "chips": dp * pp * tp,
        "compiles_in_window": ctx.compile_log.made - made0,
    }

    trace = trace_counters = None
    notes = {"setup_phases": phases}
    if ctx.trace:
        with traced_window(ctx.workload["name"]) as traced:
            for _ in range(tr["trace_steps"]):
                one_step()
        trace = traced["reduced"]
        trace_counters = {"steps": tr["trace_steps"],
                          "tokens": tr["trace_steps"] * batch * seq}
        notes["trace_file"] = traced["path"]

    values = [float(x) for x in losses]
    lo, hi = first_loss_band(cfg)
    finite = [math.isfinite(v) for v in values]
    correct = all(finite) and lo <= values[0] <= hi
    notes.update(first_loss=values[0], last_loss=values[-1],
                 first_loss_band=[lo, hi])
    return Record(
        correct=correct, attempted=measured,
        failed=sum(not f for f in finite[warm:warm + measured]),
        setup_s=setup_s, samples={"step_ms": step_ms[warm:warm + measured]},
        counters=counters, spans=spans, trace=trace,
        trace_counters=trace_counters, notes=notes, context=ctx,
        memory_peak_bytes=memory_peak_bytes())
