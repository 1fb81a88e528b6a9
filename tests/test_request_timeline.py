"""A request's time to its first token, as the engine writes it on the span
clock (`Sequence.submit_ns` / `admit_ns`, the `ptpu.serve.first_token`
mark, the `serving.token` event's `queue_s`, `host_s`, `device_s`) and
every `ptpu.serve.step`'s `perf_ns`, which lays those stamps over a
profile's axis.

CPU, tiny models. What is held here:

- the stamps are monotone and the three parts add up to the first token's
  `ttft_s` to the nanosecond, and `device_s` is what of the time since the
  request's first dispatch lay inside a tick's interval, its own or
  another's, for a one-tick prompt, a three-chunk prompt, a prefix hit, a
  preempted and re-admitted request, a block-diffusion request, a request
  queued behind a full batch, one admitted behind a tick in flight and one
  whose chunks wait a tick for another's;
- inside a `jax.profiler` session every step carries `perf_ns` and a request
  has exactly one mark, inside `ptpu.serve.harvest`, with the sequence's own
  numbers; a tick harvested outside `step()` writes its mark outside any;
- with `FLAGS_trace_spans` off all of it is still written and no ring span
  appears; token streams and `stats["step_builds"]` are what they are
  without: no stamp reaches an executable's cache key.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.core import flags
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from paddle_tpu.observability import tracing
from tests.test_tracing import _profiled


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def tiny():
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=96, dtype=jnp.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny_blockdiff():
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=96, block_length=4, mask_token_id=96,
                        dtype=jnp.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


class Watched(PagedServingEngine):
    """An engine that keeps what a test reckons `device_s` from: every
    tick it launched, with the interval its harvest gave it."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.ticks = []                 # [tick, start_ns, end_ns or None]

    def _launch(self, prev):
        tick = super()._launch(prev)
        if tick.batch is not None:
            self.ticks.append([tick, None, None])
        return tick

    def _harvest(self, cur, span):
        free = self._device_free_ns
        events = super()._harvest(cur, span)
        for rec in self.ticks:
            if rec[0] is cur:
                rec[1:] = max(cur.t0, free), self._device_free_ns
        return events

    def carried(self, rid):
        """The ticks that carried rows of `rid`, in order."""
        return [rec for rec in self.ticks
                if any(seq.rid == rid for seq, _ in rec[0].batch.items)]

    def busy_between(self, lo: int, hi: int) -> int:
        """What of [lo, hi] lay inside a tick's interval."""
        return sum(max(0, min(end, hi) - max(start, lo))
                   for _, start, end in self.ticks if end is not None)


def engine(model, **kw):
    cfg, params = model
    kw = {**dict(num_blocks=32, block_size=4, max_batch=2, token_budget=16),
          **kw}
    return Watched(cfg, params, **kw)


def prompt(n, seed=3):
    return np.random.RandomState(seed).randint(0, 96, (n,)).tolist()


def first_tokens():
    """{rid: (record time ns, fields)} of the flight recorder's
    `serving.token` events with first=True."""
    return {f["rid"]: (ts, f) for _, ts, kind, _, f in
            obs.recorder().events()
            if kind == "serving.token" and f["first"]}


def ns(seconds: float) -> int:
    return round(seconds * 1e9)


def holds(eng, rid, t_before: int):
    """What every request's first token must satisfy; returns its sequence
    and the event's fields."""
    seq = eng.scheduler.get(rid)
    recorded_ns, f = first_tokens()[rid]
    # monotone, on one clock: before submit <= submit <= admit <= the
    # first token (the recorder stamps the event as it takes it)
    ttft_ns = ns(f["ttft_s"])
    assert t_before <= seq.submit_ns <= seq.admit_ns
    assert seq.admit_ns <= seq.submit_ns + ttft_ns <= recorded_ns
    # the event's parts are the sequence's stamps, and add up
    assert ns(f["queue_s"]) == seq.admit_ns - seq.submit_ns
    assert ns(f["queue_s"] + f["host_s"] + f["device_s"]) == ttft_ns
    assert f["host_s"] >= 0 and f["tpot_s"] is None
    # the device's part, reckoned again from the ticks' own intervals: from
    # the request's first dispatch to its first token, whatever lay inside
    # a tick, its own or another's
    dispatch = eng.carried(rid)[0][0].t0
    assert seq.admit_ns <= dispatch
    assert ns(f["device_s"]) == eng.busy_between(
        dispatch, seq.submit_ns + ttft_ns) > 0
    return seq, f


def ticks_to_first_token(eng, rid) -> int:
    """How many ticks carried rows of `rid` up to its first token."""
    _, f = first_tokens()[rid]
    seq = eng.scheduler.get(rid)
    at = seq.submit_ns + ns(f["ttft_s"])
    return sum(end <= at for _, _, end in eng.carried(rid))


# ---------------------------------------------------------------------------
# the split, case by case
# ---------------------------------------------------------------------------

def one_tick(models):
    eng = engine(models["tiny"])
    t = time.perf_counter_ns()
    rid = eng.submit(prompt(9), max_new_tokens=3)
    eng.run()
    holds(eng, rid, t)
    assert ticks_to_first_token(eng, rid) == 1


def three_chunks(models):
    eng = engine(models["tiny"], prefill_chunk=8)
    t = time.perf_counter_ns()
    rid = eng.submit(prompt(20), max_new_tokens=3)
    eng.run()
    _, f = holds(eng, rid, t)
    assert ticks_to_first_token(eng, rid) == 3     # 8 + 8 + 4 tokens
    # the count stops at the first token: the two decode ticks behind it
    # are in the odometer and not in `device_s`
    assert eng.stats["steps"] == 5
    assert ns(f["device_s"]) < eng._device_busy_ns


def prefix_hit(models):
    eng = engine(models["tiny"])
    shared = prompt(13)
    eng.submit(shared, max_new_tokens=2)
    eng.run()
    t = time.perf_counter_ns()
    rid = eng.submit(shared + prompt(3, seed=8), max_new_tokens=2)
    eng.run()
    holds(eng, rid, t)
    assert eng.blocks.stats["prefix_hit_tokens"] == 12
    (tick, _, _), = eng.carried(rid)[:1]
    assert dict((s.rid, n) for s, n in tick.batch.items)[rid] == 4


def preempted(models):
    """A starved pool evicts the lowest priority before its first token is
    out or after; whoever was preempted keeps its first `admit_ns`."""
    eng = engine(models["tiny"], num_blocks=6, max_batch=3)
    t = time.perf_counter_ns()
    rids = [eng.submit(p, max_new_tokens=10, priority=i)
            for i, p in enumerate([prompt(6), prompt(4, 5), prompt(3, 6)])]
    admits = {}
    while eng.has_work():
        eng.step()
        for rid in rids:
            seq = eng.scheduler.get(rid)
            if seq.admit_ns:
                admits.setdefault(rid, seq.admit_ns)
    assert eng.scheduler.stats["preemptions"] >= 1
    victims = [r for r in rids if eng.scheduler.get(r).preemptions]
    assert victims
    for rid in rids:
        seq, _ = holds(eng, rid, t)
        assert seq.admit_ns == admits[rid]    # the first admission's


def blockdiff(models):
    """One mark a request, at its first committed block: the denoise
    forwards before it are ticks that carried its rows."""
    eng = engine(models["tiny_blockdiff"], token_budget=16, max_batch=2)
    t = time.perf_counter_ns()
    rid = eng.submit(prompt(6), max_new_tokens=8, denoising_steps=2)
    eng.run()
    holds(eng, rid, t)
    # 6 tokens: one whole block prefilled; the open block holds the
    # prompt's last two and two masked rows, which one denoise forward
    # unmasks (2 a forward at 2 steps a block of 4); then its commit
    assert ticks_to_first_token(eng, rid) == 1 + 1 + 1
    assert len(first_tokens()) == 1
    assert eng.stats["diff_blocks_committed"] >= 2


def queued_behind_a_full_batch(models):
    eng = engine(models["tiny"], max_batch=2)
    t = time.perf_counter_ns()
    rids = [eng.submit(prompt(5, seed=s), max_new_tokens=4)
            for s in (1, 2, 3)]
    eng.run()
    waited, f = holds(eng, rids[2], t)
    for rid in rids[:2]:
        holds(eng, rid, t)
    # the third is admitted only when a slot is free: it queued for at
    # least a whole tick of the others (their device interval), they for
    # none
    others = [eng.scheduler.get(r) for r in rids[:2]]
    _, first_start, first_end = eng.ticks[0]
    assert ns(f["queue_s"]) >= first_end - first_start
    assert all(s.admit_ns < waited.admit_ns for s in others)
    assert ticks_to_first_token(eng, rids[2]) == 1


def admitted_behind_a_tick_in_flight(models):
    """A request that `schedule()` admits into a tick launched behind one
    in flight waits for the device while that one runs: the rest of the
    tick ahead is `device_s`, not `host_s`."""
    eng = engine(models["tiny"], max_batch=3)
    eng.submit(prompt(5), max_new_tokens=12)
    eng.step()
    eng.step()
    ahead = eng._in_flight
    assert ahead is not None and ahead.ahead
    t = time.perf_counter_ns()
    rid = eng.submit(prompt(7, seed=4), max_new_tokens=3)
    eng.step()            # launches its tick behind `ahead`, reads `ahead`
    (mine, _, _), = eng.carried(rid)
    assert mine.ahead and mine is eng._in_flight
    assert first_tokens().get(rid) is None
    eng.run()
    _, f = holds(eng, rid, t)
    # its own tick's interval opens where the tick ahead ends, after its
    # dispatch: what lies between is the device at work ahead of it
    (_, a0, a1), = [r for r in eng.ticks if r[0] is ahead]
    (_, m0, m1), = eng.carried(rid)[:1]
    assert a0 < mine.t0 < a1 == m0
    seq = eng.scheduler.get(rid)
    at = seq.submit_ns + ns(f["ttft_s"])
    assert ns(f["device_s"]) >= (a1 - mine.t0) + (m1 - m0)
    assert ns(f["host_s"]) <= (mine.t0 - seq.admit_ns) + (at - m1)


def a_chunk_waits_for_anothers_tick(models):
    """Two prompts of three chunks under a budget of one chunk a tick:
    the second's chunks alternate with none of its own, and the ticks that
    carried only the first's rows are still the device at work."""
    eng = engine(models["tiny"], prefill_chunk=8, token_budget=8)
    t = time.perf_counter_ns()
    rids = [eng.submit(prompt(20, seed=s), max_new_tokens=2) for s in (1, 2)]
    eng.run()
    for rid in rids:
        holds(eng, rid, t)
    seq = eng.scheduler.get(rids[1])
    _, f = first_tokens()[rids[1]]
    at = seq.submit_ns + ns(f["ttft_s"])
    mine = {id(r[0]) for r in eng.carried(rids[1])}
    dispatch = eng.carried(rids[1])[0][0].t0
    others = [r for r in eng.ticks
              if id(r[0]) not in mine and dispatch < r[2] <= at]
    assert others
    own = sum(end - start for _, start, end in eng.carried(rids[1])
              if end <= at)
    assert ns(f["device_s"]) >= own + sum(
        end - max(start, dispatch) for _, start, end in others)


CASES = [one_tick, three_chunks, prefix_hit, preempted, blockdiff,
         queued_behind_a_full_batch, admitted_behind_a_tick_in_flight,
         a_chunk_waits_for_anothers_tick]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_queue_host_device_add_up_to_ttft(case, tiny, tiny_blockdiff):
    case({"tiny": tiny, "tiny_blockdiff": tiny_blockdiff})


def test_first_token_that_is_an_eos_surfaces_no_mark(tiny):
    """The mark is for the first SURFACED token: an end-of-sequence id as
    the first token finishes the request with neither mark nor event."""
    eng = engine(tiny)
    rid = eng.submit(prompt(9), max_new_tokens=3)
    first = [c.output_tokens for c in eng.run()][0][0]
    obs.reset()
    rid = eng.submit(prompt(9), max_new_tokens=3, eos_token_id=first)
    (done,) = [c for c in eng.run() if c.rid == rid]
    assert done.finish_reason == "stop" and done.output_tokens == []
    assert first_tokens() == {}


# ---------------------------------------------------------------------------
# in a profile
# ---------------------------------------------------------------------------

def profiled(out_dir, fn):
    """`fn` inside a jax.profiler session: the session's ptpu.* events
    [(name, start_ns, dur_ns, stats)] in order."""
    return _profiled(out_dir, fn)[0]


def inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


@pytest.fixture(scope="module")
def profile(tiny, tmp_path_factory):
    """Three requests (one of three chunks) through a warm engine inside a
    session; returns the spans, the sequences and the clock readings
    around the drive."""
    obs.reset()
    eng = engine(tiny, prefill_chunk=8)
    eng.submit(prompt(12, seed=9), max_new_tokens=3)
    eng.run()                              # both executables built
    obs.reset()
    out = {}

    def drive():
        out["t0"] = time.perf_counter_ns()
        rids = [eng.submit(prompt(n, seed=70 + n), max_new_tokens=4)
                for n in (12, 20, 5)]
        while eng.has_work():
            eng.step()
        out["t1"] = time.perf_counter_ns()
        out["seqs"] = {r: eng.scheduler.get(r) for r in rids}

    out["spans"] = profiled(str(tmp_path_factory.mktemp("profile")), drive)
    out["events"] = first_tokens()
    obs.reset()
    return out


def test_every_step_carries_perf_ns_and_all_give_one_offset(profile):
    """A step's start on the profile's axis less its `perf_ns` is the
    offset from the process's perf_counter_ns to that axis: every step
    gives the same one, to what lies between the reading and the
    annotation's own start."""
    steps = [s for s in profile["spans"] if s[0] == "ptpu.serve.step"]
    assert len(steps) > 5
    offsets = [s[1] - s[3]["perf_ns"] for s in steps]
    slack = 5_000_000
    assert max(offsets) - min(offsets) < slack
    for s in steps:
        assert profile["t0"] <= s[3]["perf_ns"] <= profile["t1"]


def test_perf_ns_lays_the_request_stamps_over_the_profile(profile):
    """The mark's `submit_ns`, moved by the enclosing step's offset, falls
    inside that request's `ptpu.serve.submit`; its `admit_ns` inside a
    `ptpu.serve.schedule`; and both lie before the mark."""
    spans = profile["spans"]
    steps = [s for s in spans if s[0] == "ptpu.serve.step"]
    submits = [s for s in spans if s[0] == "ptpu.serve.submit"]
    schedules = [s for s in spans if s[0] == "ptpu.serve.schedule"]
    marks = [s for s in spans if s[0] == "ptpu.serve.first_token"]
    slack = 5_000_000
    assert len(submits) == len(marks) == 3
    for mark, submit in zip(sorted(marks, key=lambda m: m[3]["rid"]),
                            submits):
        (step,) = [s for s in steps if inside(mark, s)]
        offset = step[1] - step[3]["perf_ns"]
        t_submit = mark[3]["submit_ns"] + offset
        t_admit = mark[3]["admit_ns"] + offset
        assert submit[1] - slack <= t_submit <= submit[1] + submit[2] + slack
        assert any(s[1] - slack <= t_admit <= s[1] + s[2] + slack
                   for s in schedules)
        assert t_submit <= t_admit <= mark[1] + slack


def test_exactly_one_mark_a_request_inside_harvest(profile):
    spans = profile["spans"]
    marks = [s for s in spans if s[0] == "ptpu.serve.first_token"]
    harvests = [s for s in spans if s[0] == "ptpu.serve.harvest"]
    assert sorted(m[3]["rid"] for m in marks) == sorted(profile["seqs"])
    for mark in marks:
        assert sum(inside(mark, h) for h in harvests) == 1
        assert mark[2] < 5_000_000             # a mark, not a stretch


def test_the_marks_fields_are_the_sequences_stamps(profile):
    marks = {m[3]["rid"]: m[3] for m in profile["spans"]
             if m[0] == "ptpu.serve.first_token"}
    for rid, seq in profile["seqs"].items():
        assert marks[rid] == {"rid": rid, "submit_ns": seq.submit_ns,
                              "admit_ns": seq.admit_ns}
        assert all(isinstance(v, int) for v in marks[rid].values())


def test_the_event_and_the_mark_tell_one_story(profile):
    """The flight recorder's event of a request and its mark in the
    profile hold the same stamps, and the event's three parts add up."""
    marks = {m[3]["rid"]: m[3] for m in profile["spans"]
             if m[0] == "ptpu.serve.first_token"}
    for rid, (_, f) in profile["events"].items():
        m = marks[rid]
        assert ns(f["queue_s"]) == m["admit_ns"] - m["submit_ns"]
        assert ns(f["queue_s"] + f["host_s"] + f["device_s"]) \
            == ns(f["ttft_s"])


def test_a_tick_settled_outside_step_marks_outside_any_step(tiny, tmp_path):
    """`engine_stats` harvests the tick in flight: a first token found
    there is marked with no `ptpu.serve.step` around it (the benchmark's
    readers skip such a mark), and its events come with the next step."""
    eng = engine(tiny)
    eng.submit(prompt(6), max_new_tokens=3)
    eng.run()

    def drive():
        eng.submit(prompt(7, seed=4), max_new_tokens=6)
        eng.submit(prompt(20, seed=5), max_new_tokens=6)
        eng.step()               # 7 + 9 tokens; the next tick launched ahead
        assert eng._in_flight is not None
        eng.engine_stats         # settles it: the second's first token
        while eng.has_work():
            eng.step()

    spans = profiled(str(tmp_path), drive)
    steps = [s for s in spans if s[0] == "ptpu.serve.step"]
    marks = [s for s in spans if s[0] == "ptpu.serve.first_token"]
    assert len(marks) == 2
    enclosed = [sum(inside(m, s) for s in steps) for m in marks]
    assert enclosed == [1, 0]


# ---------------------------------------------------------------------------
# off is off, and nothing reaches an executable
# ---------------------------------------------------------------------------

def drive_three(model):
    eng = engine(model, prefill_chunk=8)
    rids = [eng.submit(prompt(n, seed=50 + n), max_new_tokens=6)
            for n in (4, 20, 9)]
    done = {c.rid: c.output_tokens for c in eng.run()}
    return eng, rids, [done[r] for r in rids]


def test_with_the_span_plane_off_stamps_mark_and_event_stay(tiny, tmp_path):
    """They are phases and an event, not ring spans: `FLAGS_trace_spans`
    off changes none of them, and no ring span appears."""
    flags.set_flags({"trace_spans": False})
    try:
        t = time.perf_counter_ns()
        out = {}
        spans = profiled(str(tmp_path),
                         lambda: out.update(run=drive_three(tiny)))
        eng, rids, _ = out["run"]
        assert tracing.finished_spans() == []
        for rid in rids:
            holds(eng, rid, t)
    finally:
        flags.set_flags({"trace_spans": True})
    marks = [s for s in spans if s[0] == "ptpu.serve.first_token"]
    assert sorted(m[3]["rid"] for m in marks) == rids
    steps = [s for s in spans if s[0] == "ptpu.serve.step"]
    assert steps and all("perf_ns" in s[3] for s in steps)


def test_no_stamp_reaches_an_executable_or_a_token(tiny, tmp_path):
    """Token streams and `step_builds` are the same outside a session,
    inside one, and with the span plane off: the stamps are host ints on
    the sequence and metadata on annotations."""
    eng, _, tokens = drive_three(tiny)
    builds = eng.stats["step_builds"]
    assert builds <= 2
    out = {}
    profiled(str(tmp_path), lambda: out.update(run=drive_three(tiny)))
    assert out["run"][2] == tokens
    assert out["run"][0].stats["step_builds"] == builds
    flags.set_flags({"trace_spans": False})
    try:
        eng_off, _, tokens_off = drive_three(tiny)
    finally:
        flags.set_flags({"trace_spans": True})
    assert tokens_off == tokens
    assert eng_off.stats["step_builds"] == builds


def test_a_routed_request_closes_queue_wait_on_the_admit_stamp(tiny):
    """Where there is a ring span, the reading that sets `admit_ns` closes
    it: `queue.wait` ends at `admit_ns` to the nanosecond."""
    eng = engine(tiny)
    root = tracing.new_trace("request", rid=0)
    rid = eng.submit(prompt(9), max_new_tokens=2,
                     trace=(root.trace_id, root.span_id))
    eng.run()
    (wait,) = tracing.finished_spans(trace_id=root.trace_id,
                                     name="queue.wait")
    seq = eng.scheduler.get(rid)
    assert wait["end_ns"] == seq.admit_ns
    assert seq.submit_ns <= wait["start_ns"] <= seq.admit_ns
