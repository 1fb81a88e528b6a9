"""The closed loop's books, against an engine that is a few lines of
Python: the submit order follows from the tick outcomes alone, and the
rate is work over the work's own elapsed time, whatever `--seconds` was."""
import time
from dataclasses import dataclass

import pytest

from benchmark.drivers import closed_loop_serve as D
from benchmark.end_to_end import decode_tokens_per_s, gap_p90_ms, ttft_mean_ms
from benchmark.layer_metrics import batch_occupancy, prefill_tokens_per_s
from benchmark.lib.harness import Record, Spans
from benchmark.tests.helpers import context


@dataclass
class Event:
    rid: int
    token: int
    finished: bool
    reason: str = None


class FakeEngine:
    """Prefills `budget` prompt tokens a tick, oldest request first, then
    gives every prefilled request one token a tick."""

    def __init__(self, budget=32, tick_s=0.002):
        self.budget, self.tick_s = budget, tick_s
        self.live, self.next_rid, self.submitted = {}, 0, []
        self.stats = {"steps": 0, "tokens_computed": 0}

    def submit(self, tokens, max_new_tokens, eos_token_id=None):
        rid, self.next_rid = self.next_rid, self.next_rid + 1
        self.live[rid] = {"left": len(tokens), "want": max_new_tokens,
                          "got": 0}
        self.submitted.append((rid, len(tokens), max_new_tokens))
        return rid

    def step(self):
        time.sleep(self.tick_s)
        budget, events, computed = self.budget, [], 0
        for rid, r in list(self.live.items()):
            if r["left"] > 0:
                n = min(r["left"], budget)
                r["left"] -= n
                budget -= n
                computed += n
                if r["left"] > 0 or n == 0:
                    continue
            else:
                computed += 1
            r["got"] += 1
            done = r["got"] == r["want"]
            events.append(Event(rid, 7, done, "length" if done else None))
            if done:
                del self.live[rid]
        self.stats["steps"] += 1
        self.stats["tokens_computed"] += computed
        return events


def drive(seed, ticks, **kw):
    ctx = context("tiny-serve", "tiny_closed", seed=seed)
    eng = FakeEngine(**kw)
    loop = D.Loop(eng, ctx, Spans())
    for c in loop.clients:
        loop.submit(c)
    for _ in range(ticks):
        loop.tick()
    return loop, eng, ctx


def test_submit_order_follows_from_the_tick_outcomes_alone():
    a = drive(3, 40, tick_s=0.0)[1].submitted
    b = drive(3, 40, tick_s=0.003)[1].submitted      # slower ticks
    assert a == b and len(a) > 10
    # another seed: other token ids, the same sizes in the same order
    assert a == drive(4, 40, tick_s=0.0)[1].submitted


def test_books_balance():
    loop, eng, _ = drive(3, 60)
    c = loop.counters()
    assert c["ticks"] == 60 == c["engine_steps"]
    assert c["tokens_out"] == len(loop.gap_ms) + len(loop.ttft_ms)
    assert loop.failed == 0 and loop.completed == c["requests_completed"] > 0
    done = [s for s in eng.submitted if s[0] not in eng.live]
    assert loop.completed == len(done)
    # every prompt whose first token came is booked once, whole
    firsts = len(loop.ttft_ms)
    assert c["prompt_tokens_done"] == sum(n for _, n, _ in
                                          eng.submitted[:firsts])
    assert c["positions_written"] == c["prompt_tokens_done"] + len(loop.gap_ms)


@pytest.mark.parametrize("seconds", [0.05, 5.0, 500.0])
def test_rates_ignore_the_seconds_asked_for(seconds):
    loop, _, ctx = drive(3, 50)
    ctx.seconds = seconds
    c = loop.counters()
    rec = Record(correct=True, attempted=0, failed=0, setup_s=0.0,
                 samples={"gap_ms": loop.gap_ms, "ttft_ms": loop.ttft_ms,
                          "tick_ms": loop.tick_ms},
                 counters=c, spans=loop.spans, context=ctx)
    elapsed = loop.last_end_s - loop.first_start_s
    assert c["elapsed_s"] == elapsed
    assert decode_tokens_per_s.read(rec) == c["tokens_out"] / elapsed
    assert prefill_tokens_per_s.read(rec) == c["prompt_tokens_done"] / elapsed
    assert batch_occupancy.read(rec) == (c["engine_tokens_computed"]
                                         / c["engine_steps"])
    # a gap is one tick of the fake engine; a first token takes 1..3
    assert 2.0 <= gap_p90_ms.read(rec) < 50.0
    assert ttft_mean_ms.read(rec) >= 2.0


def test_a_request_that_ends_early_is_a_failure():
    ctx = context("tiny-serve", "tiny_closed", seed=3)

    class Short(FakeEngine):
        def step(self):
            events = super().step()
            for ev in events:
                if ev.rid == 1 and not ev.finished:
                    ev.finished, ev.reason = True, "deadline"
                    del self.live[ev.rid]
            return events

    loop = D.Loop(Short(), ctx, Spans())
    for c in loop.clients:
        loop.submit(c)
    for _ in range(30):
        loop.tick()
    assert loop.failed == 1
