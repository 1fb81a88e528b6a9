"""The set-up and the measured window of a closed-loop serving cell, for
drivers that bring their own configuration object, correctness check and
loop: `closed_loop_serve.run` with those three handed in (that driver keeps
its own copy: a file the benchmark already has is not edited)."""
from __future__ import annotations

import gc
import time

import jax

from paddle_tpu.models import llama as L

from ..drivers.closed_loop_serve import build_engine
from . import machine_pauses
from .harness import (Context, Record, Spans, memory_peak_bytes, seed_key,
                      traced_window)
from .program import DTYPES


def run(ctx: Context, make_config, check, make_loop) -> Record:
    """`make_config(cfg, param_dtype)` gives the program's config object,
    `check(eng, cfg, params, lcfg, seed)` gives (correct, notes),
    `make_loop(eng, ctx, spans)` a `closed_loop_serve.Loop`. Everything
    before the window is counted in `setup_s`. The window's books and
    samples are those of the ticks and waits that no pause of the whole
    machine fell into (`machine_pauses`); the notes count what was left
    out, and `tokens_out_raw` over `elapsed_raw_s` is the rate with it."""
    cfg, tr = ctx.config, ctx.traffic
    spans = Spans()
    phases = {"imports_s": time.perf_counter() - ctx.process_start_s}
    t0 = time.perf_counter()
    lcfg = make_config(cfg, DTYPES[cfg["engine"]["param_dtype"]])
    params = jax.block_until_ready(
        jax.jit(lambda key: L.init_params(lcfg, key))(seed_key(ctx.seed)))
    eng = build_engine(cfg, params, lcfg)
    phases["weights_and_engine_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    correct, notes = check(eng, cfg, params, lcfg, ctx.seed)
    phases["correctness_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    loop = make_loop(eng, ctx, spans)
    for client in loop.clients:
        loop.submit(client)
    while any(c.j == 0 and c.got == 0 for c in loop.clients):
        loop.tick()
    phases["first_tokens_s"] = time.perf_counter() - t0
    notes.update(warm_ticks=len(loop.tick_ms), setup_phases=phases)

    # the window: whole ticks from here until --seconds have passed, with
    # a second process watching for pauses of the whole machine; the books
    # are read after every tick so that a tick can be taken out of them
    gc.collect()
    gc.freeze()
    observer = machine_pauses.Observer()
    loop.reset_books()
    made0 = ctx.compile_log.made
    setup_s = time.perf_counter() - ctx.process_start_s
    deadline = time.perf_counter() + ctx.seconds
    books, made = [], []
    while time.perf_counter() < deadline:
        loop.tick()
        books.append(loop.counters())
        made.append({"gap_ms": len(loop.gap_ms), "ttft_ms": len(loop.ttft_ms),
                     "tick_ms": len(loop.tick_ms)})
    pauses = observer.stop()
    inside = [p for p in pauses or []
              if p[0] < loop.last_end_s and p[1] > loop.first_start_s]
    raw = books[-1]
    counters, samples, left_out = machine_pauses.books_outside(
        inside, loop.first_start_s, books,
        {"gap_ms": loop.gap_ms, "ttft_ms": loop.ttft_ms,
         "tick_ms": loop.tick_ms}, made)
    counters.update(tokens_out_raw=raw["tokens_out"],
                    elapsed_raw_s=raw["elapsed_s"])
    notes["machine_pauses"] = {
        "observer": observer.why or "ok", "count": len(inside),
        "paused_s": sum(p1 - p0 for p0, p1 in inside), "left_out": left_out,
        "ttft_mean_raw_ms": (sum(loop.ttft_ms) / len(loop.ttft_ms)
                             if loop.ttft_ms else None)}
    counters["compiles_in_window"] = ctx.compile_log.made - made0
    record = Record(
        correct=correct, attempted=loop.completed, failed=loop.failed,
        setup_s=setup_s, samples=samples, counters=counters, spans=spans,
        notes=notes, context=ctx)

    if ctx.trace:
        loop.reset_books()
        with traced_window(ctx.workload["name"]) as traced:
            for _ in range(tr["trace_ticks"]):
                loop.tick()
        record.trace = traced["reduced"]
        record.trace_counters = loop.counters()
        record.notes["trace_file"] = traced["path"]
    record.memory_peak_bytes = memory_peak_bytes()
    return record
