"""The two drivers PR 27 adds, on tiny fixtures on the CPU (counts only):
the routed-expert closed loop, and the closed loop of chat sessions."""
import dataclasses
import math

import numpy as np
import pytest

from benchmark.drivers import (closed_loop_serve_moe as moe,
                               closed_loop_sessions as sessions)
from benchmark.end_to_end import decode_tokens_per_s, gap_p90_ms, ttft_mean_ms
from benchmark.layer_metrics import (cow_copies_per_tick,
                                     moe_experts_hit_share,
                                     moe_experts_roofline,
                                     moe_load_max_over_mean, prefix_hit_share,
                                     tick_moe_overhead_share, tick_moe_share)
from benchmark.lib import (machine_pauses, moe_scopes, program_trace,
                           serve_window)
from benchmark.lib.harness import Spans
from benchmark.tests.helpers import context, fixture
from benchmark.tests.test_closed_loop import FakeEngine


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(serve_window, "memory_peak_bytes", lambda: 0)


# ---- closed_loop_serve_moe -------------------------------------------------

def test_moe_config_carries_the_keys_program_llama_config_drops():
    import jax.numpy as jnp

    cfg = fixture("configs", "tiny-olmoe")
    lcfg = moe.moe_config(cfg, jnp.bfloat16)
    assert (lcfg.num_experts, lcfg.top_k, lcfg.qk_norm,
            lcfg.norm_topk_prob) == (8, 2, True, False)


def test_a_program_without_the_new_keys_fails_at_once(monkeypatch):
    """The parent of PR 27 has no `LlamaConfig.qk_norm`: the driver raises
    before any weight is made, and run.py exits non-zero."""
    from paddle_tpu.models import llama as L

    fields = [(f.name, f.type, f) for f in dataclasses.fields(L.LlamaConfig)
              if f.name not in ("qk_norm", "norm_topk_prob")]
    Old = dataclasses.make_dataclass("LlamaConfig", fields, frozen=True)
    monkeypatch.setattr(L, "LlamaConfig", Old)
    ctx = context("tiny-olmoe", "tiny_moe_closed", seed=1)
    with pytest.raises(TypeError, match="qk_norm"):
        moe.run(ctx)


def test_moe_driver_rehearsal():
    ctx = context("tiny-olmoe", "tiny_moe_closed", seed=2**31 + 5,
                  seconds=1.0)
    rec = moe.run(ctx)
    assert rec.correct, rec.notes
    assert rec.notes["positions_judged"] == 18
    assert rec.notes["experts"] == "dense_einsum"        # on the CPU
    for rows in (32, 4):
        note = rec.notes[f"layer_rows_{rows}"]
        assert note["padding_rows_zero"]
        assert note["largest_error_over_tolerance"] < 1.0
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["compiles_in_window"] == 0
    pauses = rec.notes["machine_pauses"]
    assert pauses["observer"] == "ok" and pauses["count"] >= 0
    assert bool(pauses["left_out"]["ticks"]) == bool(pauses["count"])
    if not pauses["count"]:
        assert (c["tokens_out"], c["elapsed_s"]) == (c["tokens_out_raw"],
                                                     c["elapsed_raw_s"])
        assert len(rec.samples["tick_ms"]) == c["ticks"]
    assert c["moe_pairs"] == 2 * c["engine_tokens_computed"]
    assert (2 * 2 * c["engine_steps"] <= c["moe_experts_hit"]
            <= 8 * 2 * c["engine_steps"])
    share = moe_experts_hit_share.read(rec)
    assert share == 100.0 * c["moe_experts_hit"] / (c["engine_steps"] * 16)
    for reader in (decode_tokens_per_s, gap_p90_ms):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) > 0
    # no trace: the trace readers find nothing and do not raise
    for reader in (tick_moe_share, tick_moe_overhead_share,
                   moe_experts_roofline, moe_load_max_over_mean):
        assert reader.read(rec) is None


def test_the_observer_notes_a_stop_of_its_process_and_nothing_else():
    """Nothing here can stop the machine; stopping the observer alone looks
    the same to it."""
    import signal
    import time

    obs = machine_pauses.Observer()
    time.sleep(0.05)
    t0 = time.perf_counter()
    obs.child.send_signal(signal.SIGSTOP)
    time.sleep(0.08)
    obs.child.send_signal(signal.SIGCONT)
    t1 = time.perf_counter()
    time.sleep(0.05)
    pauses = obs.stop()
    assert obs.why is None and obs.child.returncode == 0
    long = [p for p in pauses if p[1] - p[0] >= 0.06]
    assert len(long) == 1, pauses
    (p0, p1), = long
    assert t0 - 0.01 <= p0 <= t0 + 0.02 and t1 <= p1 <= t1 + 0.02
    ticks = [(t0 - 0.2, t0 - 0.1), (t0 - 0.1, t0 + 0.03), (t0 + 0.03, t1),
             (t1 + 0.05, t1 + 0.1)]
    assert machine_pauses.overlapping(ticks, long) == [1, 2]
    assert machine_pauses.overlapping(ticks, []) == []


def test_books_outside_the_pauses_on_a_hand_counted_window():
    """Five ticks of 10 ms and 16 tokens from 1.000 s on, the third held up
    for 110 ms by a pause: 64 tokens in 40 ms are left of 80 in 150. Each
    tick makes one gap as long as itself; the fourth also a first token
    after 135 ms, which had waited through the pause, and the fifth one
    after 12 ms."""
    ends = [1.010, 1.020, 1.140, 1.150, 1.160]
    books = [{"elapsed_s": e - 1.0, "tokens_out": 16 * (i + 1), "ticks": i + 1}
             for i, e in enumerate(ends)]
    samples = {"gap_ms": [10.0, 10.0, 120.0, 10.0, 10.0],
               "ttft_ms": [135.0, 12.0]}
    made = [{"gap_ms": i + 1, "ttft_ms": max(0, i - 2)} for i in range(5)]
    c, kept, left = machine_pauses.books_outside(
        [(1.025, 1.135)], 1.0, books, samples, made)
    assert (c["tokens_out"], c["ticks"]) == (64, 4)
    assert c["elapsed_s"] == pytest.approx(0.040)
    assert kept == {"gap_ms": [10.0] * 4, "ttft_ms": [12.0]}
    assert left == {"ticks": 1, "gap_ms": 1, "ttft_ms": 1}
    assert books[-1] == {"elapsed_s": pytest.approx(0.160), "tokens_out": 80,
                         "ticks": 5}                      # not written into
    # a pause while the client harvests tick 1 takes tick 2, which it held up
    c, kept, left = machine_pauses.books_outside(
        [(1.0201, 1.0205)], 1.0, books, samples, made)
    assert (c["tokens_out"], left["ticks"], left["gap_ms"]) == (64, 1, 1)
    assert c["elapsed_s"] == pytest.approx(0.040)
    # no pause: the books of the last tick, every sample
    c, kept, left = machine_pauses.books_outside([], 1.0, books, samples, made)
    assert c == books[-1] and kept == samples
    assert left == {"ticks": 0, "gap_ms": 0, "ttft_ms": 0}


def test_the_moe_scopes_reach_scope_of_only_once_registered(monkeypatch):
    name = "jit(step_fn)/layers/while/body/closed_call/moe/experts/pallas_call"
    monkeypatch.setattr(program_trace, "SCOPES", frozenset(
        program_trace.SCOPES - {moe_scopes.MOE, *moe_scopes.INNER}))
    assert program_trace.scope_of(name) == "layers"
    moe_scopes.register()
    assert program_trace.scope_of(name) == "experts"
    assert program_trace.scope_of(name.replace("/experts/pallas_call",
                                               "/mul")) == "moe"
    assert program_trace.scope_of(
        "jit(step_fn)/layers/while/body/closed_call/ffn/dot") == "ffn"


def test_moe_trace_readers_on_a_recorded_tick(tmp_path):
    """The scope shares, the roofline and the load ratio, by hand, on a
    two-operation trace in program_trace's own layout."""
    import json
    import types

    moe_scopes.register()
    ms = 1_000_000
    trace = {
        "device": {"/device:TPU:0": [["gmm.1", 0, 3 * ms],
                                     ["fusion.2", 3 * ms, 1 * ms],
                                     ["paged_attention.3", 4 * ms, 4 * ms]]},
        "device_scopes": {"/device:TPU:0": ["experts", "router",
                                            "paged_attention"]},
        "host": [["bench.tick", 0, 10 * ms]],
        "program_spans": [
            ["ptpu.serve.step", 0, 10 * ms,
             {"batch": 16, "moe_pairs": 128, "moe_max_load": 7}],
            ["ptpu.serve.step", 10 * ms, ms, {"tick": 9}]],
    }
    path = tmp_path / "tick.json"
    path.write_text(json.dumps(trace))
    cfg = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
           "num_experts_per_tok": 8, "num_hidden_layers": 16}
    from benchmark.lib.peaks import PEAKS
    rec = types.SimpleNamespace(
        trace={"busy_s": 0.008}, notes={"trace_file": str(path)},
        trace_counters={"moe_experts_hit": 56 * 16, "moe_pairs": 128},
        counters={}, context=types.SimpleNamespace(
            config=cfg, peaks=PEAKS["TPU v5 lite"]))
    assert tick_moe_share.read(rec) == pytest.approx(50.0)
    assert tick_moe_overhead_share.read(rec) == pytest.approx(12.5)
    # 56 x 16 experts of 12.58 MB over 819 GB/s = 13.76 ms, over 3 ms
    least = 56 * 16 * 3 * 2048 * 1024 * 2 / 819e9
    assert moe_experts_roofline.read(rec) == pytest.approx(
        100 * least / 0.003)
    assert moe_load_max_over_mean.read(rec) == 3.5


# ---- closed_loop_sessions --------------------------------------------------

class StreamingFake(FakeEngine):
    """FakeEngine that also streams what it produced (token 7s) and has a
    block manager's and an engine's counters."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.stats["cow_block_copies"] = 0
        self.blocks = type("B", (), {"stats": {"prefix_hit_tokens": 0}})()
        self.prompts, self.outputs = [], {}

    def submit(self, tokens, max_new_tokens, eos_token_id=None):
        rid = super().submit(tokens, max_new_tokens, eos_token_id)
        self.prompts.append(np.asarray(tokens))
        self.outputs[rid] = [7] * max_new_tokens
        return rid

    def stream(self, rid):
        return iter(self.outputs[rid])


def test_a_turns_prompt_extends_its_history():
    ctx = context("tiny-sessions", "tiny_sessions", seed=5)
    eng = StreamingFake(budget=64, tick_s=0.0)
    log = {0: [], 1: []}                # client -> its requests, in order

    class Logged(sessions.SessionLoop):
        def submit(self, client):
            super().submit(client)
            log[client.index].append((eng.prompts[-1], client.want))

    loop = Logged(eng, ctx, Spans())
    for c in loop.clients:
        loop.submit(c)
    for _ in range(60):
        loop.tick()
    turns, prefix = ctx.traffic["turns"], loop.prefix
    assert len(prefix) == 48
    for requests in log.values():
        assert len(requests) > turns            # a second session began
        for j, (prompt, _) in enumerate(requests):
            if j % turns == 0:                  # a session's first turn
                head = prefix
            else:                               # history, answer, new part
                before, want = requests[j - 1]
                head = np.concatenate([before, [7] * want])
            assert np.array_equal(prompt[:len(head)], head)
            assert len(prompt) - len(head) in (8, 20)
    # the books: every submitted prompt token is counted
    assert loop.counters()["prompt_tokens_submitted"] == sum(
        len(p) for requests in log.values() for p, _ in requests)


def test_prefix_hit_share_on_a_hand_counted_case():
    ctx = context("tiny-sessions", "tiny_sessions", seed=5)
    eng = StreamingFake(budget=64, tick_s=0.0)
    loop = sessions.SessionLoop(eng, ctx, Spans())
    loop.reset_books()
    loop.prompt_tokens_submitted = 400
    eng.blocks.stats["prefix_hit_tokens"] += 356
    eng.stats["cow_block_copies"] += 2
    loop.first_start_s, loop.last_end_s = 0.0, 1.0
    eng.stats["steps"] += 8
    import types
    rec = types.SimpleNamespace(counters=loop.counters())
    assert prefix_hit_share.read(rec) == 89.0
    assert cow_copies_per_tick.read(rec) == 0.25


@pytest.mark.parametrize("agreed, found, correct", [
    (48.0, True, True), (47.0, True, True),     # one near tie in 48 is borne
    (40.0, True, False),                        # (288 + 40) / 336 = 0.976
    (48.0, False, False)])                      # no hit or no copy
def test_the_share_is_taken_over_the_cached_turns_and_the_six_together(
        monkeypatch, agreed, found, correct):
    monkeypatch.setattr(
        sessions, "check_against_reference",
        lambda *a: (True, {"positions_judged": 288, "agreement": 1.0}))
    monkeypatch.setattr(
        sessions, "check_cached_turn",
        lambda *a: (agreed, 48, found, {"cached_turn_agreement": agreed / 48}))
    ok, notes = sessions.check(None, {}, None, None, 1)
    assert ok is correct
    assert notes["agreement_with_cached_turns"] == (288 + agreed) / 336


def test_sessions_driver_rehearsal_and_the_second_sessions_prefix_hits():
    """Through the real engine: `correct` covers a cached turn and a page
    copy; in the window a turn's prompt is served from cached pages, the
    next session's prefix included."""
    ctx = context("tiny-sessions", "tiny_sessions", seed=2**31 + 5,
                  seconds=3.0)
    rec = sessions.run(ctx)
    assert rec.correct, rec.notes
    assert rec.notes["cached_turn_hit_tokens"] >= 2 * 288 + 8
    assert rec.notes["cached_turn_copies"] >= 1
    assert rec.failed == 0 and rec.attempted >= 4     # however slow the host
    c = rec.counters
    assert c["compiles_in_window"] == 0
    # every prompt of the window starts with the 3 cached prefix pages
    assert c["prefix_hit_tokens"] >= 48 * rec.attempted * 0.9
    assert 40.0 < prefix_hit_share.read(rec) < 100.0
    assert cow_copies_per_tick.read(rec) >= 0.0
    assert math.isfinite(ttft_mean_ms.read(rec)) and ttft_mean_ms.read(rec) > 0
