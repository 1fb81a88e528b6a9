"""Closed-loop serving through `PagedServingEngine`: K clients, greedy
sampling, no end-of-sequence token, a fixed number of new tokens. A client
submits its next request in the host iteration that harvests its last
token, i.e. on a tick boundary, so given the seed the scheduler sees the
same sequence of states in every run and only tick durations vary.

Set-up (all of it counted in `setup_s`): weights made on the device from
the seed in one jitted call, the engine built as the configuration says,
a few requests run to completion (this compiles both of the engine's
executables) and their tokens judged against the plain reference, then
every client's first request submitted and ticked until each has its first
token. The measured window starts there, on a tick boundary.

From the program the driver takes `submit`, `step` and its events, and
`stats`; lengths, chunking and positions are kept in its own books.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L

from ..lib import agreement, reference, traffic as T
from ..lib.harness import (Context, Record, Spans, memory_peak_bytes,
                           seed_key, traced_window)
from ..lib.program import DTYPES, llama_config

def build_engine(cfg: dict, params, lcfg) -> PagedServingEngine:
    e = cfg["engine"]
    return PagedServingEngine(
        lcfg, params, num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], token_budget=e["token_budget"],
        max_len=e["max_len"], pallas=e["pallas"], pallas_ffn=e["pallas_ffn"])


def check_against_reference(eng, cfg: dict, params, seed: int):
    """Run the configuration's correctness requests through the engine
    (prefill in chunks, then decode through the pages) and judge every
    generated token against the reference's teacher-forced logits."""
    c = cfg["correctness"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    prompts = [rng.integers(1, cfg["vocab_size"], n, dtype=np.int32)
               for n in c["prompt_lens"]]
    rids = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    width = c["reference_len"]
    agreed, worst, judged = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for rid, prompt in zip(rids, prompts):
            out = np.asarray(done[rid], np.int32)
            if len(out) != c["new_tokens"]:
                return False, {"why": f"request {rid} returned {len(out)} "
                                      f"tokens, not {c['new_tokens']}"}
            seq = np.zeros((width,), np.int32)
            seq[:len(prompt)] = prompt
            seq[len(prompt):len(prompt) + len(out)] = out
            at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
            logits = reference.logits_at(
                params, jnp.asarray(seq), jnp.asarray(at),
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"])
            share, gap = agreement.judge(np.asarray(logits), out)
            agreed += share * len(out)
            judged += len(out)
            worst = max(worst, gap)
    share = agreed / judged
    return share >= agreement.MIN_AGREEMENT, {
        "positions_judged": judged, "agreement": share,
        "largest_gap_over_tolerance": worst}


class Client:
    """One caller of the closed loop, with the books the metrics need."""

    def __init__(self, index: int):
        self.index = index
        self.j = -1                # index of the request in flight
        self.rid = None
        self.prompt_len = 0
        self.want = 0
        self.got = 0
        self.submitted_s = 0.0
        self.last_token_s = 0.0


class Loop:
    """The closed loop and its books. `tick()` is one host iteration:
    engine step, harvest, submit."""

    def __init__(self, eng, ctx: Context, spans: Spans):
        self.eng, self.ctx, self.spans = eng, ctx, spans
        self.clients = [Client(i) for i in range(ctx.traffic["clients"])]
        self.by_rid = {}
        self.reset_books()

    def reset_books(self):
        self.gap_ms, self.ttft_ms, self.tick_ms = [], [], []
        self.tokens_out = self.completed = self.failed = 0
        self.prompt_tokens_done = 0     # prompts whose first token came
        self.attended_keys = 0          # see model_math.tick_flops
        self.context_read = 0           # cached positions decode rows read
        self.positions_written = 0
        self.stats0 = dict(self.eng.stats)
        self.first_start_s = self.last_end_s = None

    def submit(self, client: Client):
        client.j += 1
        tr, seed = self.ctx.traffic, self.ctx.seed
        tokens = T.request_tokens(tr, seed, client.index, client.j,
                                  self.ctx.config["vocab_size"])
        client.prompt_len, client.got = len(tokens), 0
        client.want = T.new_tokens(tr, client.index, client.j)
        client.submitted_s = time.perf_counter()
        client.rid = self.eng.submit(tokens, max_new_tokens=client.want,
                                     eos_token_id=None)
        self.by_rid[client.rid] = client

    def tick(self):
        t0 = time.perf_counter()
        if self.first_start_s is None:
            self.first_start_s = t0
        with self.spans.span("bench.tick"):
            events = self.eng.step()    # returns after the step's one sync
        t1 = time.perf_counter()
        self.last_end_s = t1
        self.tick_ms.append((t1 - t0) * 1e3)
        finished = []
        with self.spans.span("bench.harvest"):
            for ev in events:
                client = self.by_rid.get(ev.rid)
                if client is None:
                    continue
                if ev.token >= 0:
                    client.got += 1
                    self.tokens_out += 1
                    if client.got == 1:
                        self.ttft_ms.append((t1 - client.submitted_s) * 1e3)
                        p = client.prompt_len
                        self.prompt_tokens_done += p
                        self.attended_keys += p * (p + 1) // 2
                        self.positions_written += p
                    else:
                        self.gap_ms.append((t1 - client.last_token_s) * 1e3)
                        # the row computed was position prompt+got-2: it
                        # read that many cached positions and attended to
                        # one more
                        pos = client.prompt_len + client.got - 2
                        self.context_read += pos
                        self.attended_keys += pos + 1
                        self.positions_written += 1
                    client.last_token_s = t1
                if ev.finished:
                    del self.by_rid[ev.rid]
                    self.completed += 1
                    if ev.reason != "length" or client.got != client.want:
                        self.failed += 1
                    finished.append(client)
        with self.spans.span("bench.submit"):
            for client in finished:
                self.submit(client)

    def counters(self) -> dict:
        stats = self.eng.stats
        return {
            "elapsed_s": self.last_end_s - self.first_start_s,
            "ticks": len(self.tick_ms),
            "tokens_out": self.tokens_out,
            "requests_completed": self.completed,
            "prompt_tokens_done": self.prompt_tokens_done,
            "attended_keys": self.attended_keys,
            "context_read": self.context_read,
            "positions_written": self.positions_written,
            "engine_steps": stats["steps"] - self.stats0["steps"],
            "engine_tokens_computed": (stats["tokens_computed"]
                                       - self.stats0["tokens_computed"]),
        }


def run(ctx: Context) -> Record:
    cfg, tr = ctx.config, ctx.traffic
    spans = Spans()
    phases = {"imports_s": time.perf_counter() - ctx.process_start_s}
    t0 = time.perf_counter()
    pdt = DTYPES[cfg["engine"]["param_dtype"]]
    lcfg = llama_config(cfg, pdt)
    params = jax.block_until_ready(
        jax.jit(lambda key: L.init_params(lcfg, key))(seed_key(ctx.seed)))
    eng = build_engine(cfg, params, lcfg)
    phases["weights_and_engine_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    correct, notes = check_against_reference(eng, cfg, params, ctx.seed)
    phases["correctness_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    loop = Loop(eng, ctx, spans)
    for client in loop.clients:
        loop.submit(client)
    while any(c.j == 0 and c.got == 0 for c in loop.clients):
        loop.tick()
    phases["first_tokens_s"] = time.perf_counter() - t0
    notes.update(warm_ticks=len(loop.tick_ms), setup_phases=phases)

    # the window: whole ticks from here until --seconds have passed
    gc.collect()
    gc.freeze()
    loop.reset_books()
    made0 = ctx.compile_log.made
    setup_s = time.perf_counter() - ctx.process_start_s
    deadline = time.perf_counter() + ctx.seconds
    while time.perf_counter() < deadline:
        loop.tick()
    counters = loop.counters()
    counters["compiles_in_window"] = ctx.compile_log.made - made0
    record = Record(
        correct=correct, attempted=loop.completed, failed=loop.failed,
        setup_s=setup_s,
        samples={"gap_ms": loop.gap_ms, "ttft_ms": loop.ttft_ms,
                 "tick_ms": loop.tick_ms},
        counters=counters, spans=spans, notes=notes, context=ctx)

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
