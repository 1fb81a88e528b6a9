"""The engine's record of its ticks: one finished span `serve.tick` a
harvested tick in the ring of `observability/tracing.py`, kept for the
run (`PagedServingEngine._harvest`).

Contract: a record a tick that ran a batch, and none for a call that ran
none; the fields of the tick's `ptpu.serve.step` span, with `prompt_rows`,
`tick`, `launch_ns`, `gap_ns` and `replica`; intervals that do not overlap and,
with the gaps, tile the engine's time exactly; the tick's one event
(`serving.step`) carries the same fields (the tick's `kind` as
`tick_kind`: in a dump an event's `kind` is its name) and feeds the registry's
counters by kind; nothing of it changes what the engine computes or
builds, and `FLAGS_trace_spans` off writes nothing. Counts and orderings
only: no time is asserted.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.core import flags
from paddle_tpu.inference.serving import DraftModel, PagedServingEngine
from paddle_tpu.models import llama as L
from paddle_tpu.observability import tracing
from tests.test_tracing import _profiled

ADDED = {"prompt_rows", "tick", "launch_ns", "gap_ns", "replica"}


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def tiny():
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=jnp.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny_blocks():
    cfg = L.LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=32, max_seq_len=128,
        block_length=4, mask_token_id=511, dtype=jnp.float32,
        param_dtype=jnp.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


def _engine(tiny, **kw):
    kw = {**dict(num_blocks=48, block_size=4, max_batch=4, token_budget=8),
          **kw}
    return PagedServingEngine(*tiny, **kw)


def _prompt(cfg, n, seed):
    return np.random.RandomState(seed).randint(
        1, cfg.vocab_size, (n,)).tolist()


def _ticks(eng=None):
    """The ring's `serve.tick` spans, oldest first (of one engine)."""
    return [s for s in tracing.finished_spans(name="serve.tick")
            if eng is None or s["trace_id"] == eng._trace_id]


def _submit_mixed(eng, cfg):
    """Prompts longer than the budget (chunked), more requests than slots
    (a queue), and a sampled row."""
    return [eng.submit(_prompt(cfg, n, seed=100 + i), max_new_tokens=new,
                       temperature=temp or None, seed=3)
            for i, (n, new, temp) in enumerate(
                [(19, 7, 0.0), (3, 12, 0.0), (11, 5, 0.8), (6, 9, 0.0),
                 (23, 4, 0.0), (2, 10, 0.0)])]


# ---------------------------------------------------------------------------
# one record a tick
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "kernel"])
def test_one_record_a_harvested_tick(tiny, pallas):
    eng = _engine(tiny, pallas=pallas)
    _submit_mixed(eng, tiny[0])
    calls = 0
    while eng.has_work():
        eng.step()
        calls += 1
    ticks = _ticks(eng)
    assert len(ticks) == eng.stats["steps"] > 10
    assert calls >= len(ticks)
    assert [t["fields"]["tick"] for t in ticks] == list(range(len(ticks)))
    assert all(t["parent_id"] == 0 and t["trace_id"] == eng._trace_id
               for t in ticks)
    # the sums of the records are the engine's books
    f = [t["fields"] for t in ticks]
    assert sum(x["tokens"] for x in f) == eng.stats["tokens_computed"]
    assert sum(x["ahead"] for x in f) == eng.stats["ticks_ahead"] > 0
    assert sum(x["sampled_rows"] for x in f) == eng.stats["sampled_rows"] > 0
    assert sum(x["sampled_rows"] > 0 for x in f) == eng.stats["ticks_sampled"]
    assert sum(x["void_rows"] for x in f) == eng.stats["ahead_void_rows"]
    kinds = {x["kind"] for x in f}
    assert kinds == ({"decode", "mixed"} if pallas else {"mixed"})
    assert (sum(x["kind"] == "decode" for x in f)
            == eng.stats["decode_fast_steps"])
    assert all(x["replica"] is None for x in f)
    # every row of the six prompts is a prompt row once, the last chunk's
    # too, which yields a token and so is no `prefill_tokens`
    assert sum(x["prompt_rows"] for x in f) == 19 + 3 + 11 + 6 + 23 + 2
    assert all(x["prompt_rows"] >= x["prefill_tokens"] for x in f)
    assert sum(x["prefill_tokens"] for x in f) < 64
    assert any(x["prompt_rows"] == 0 for x in f)


def test_a_call_that_ran_no_batch_writes_none(tiny):
    eng = _engine(tiny)
    assert eng.step() == [] and not _ticks()
    eng.submit(_prompt(tiny[0], 5, seed=1), max_new_tokens=3)
    eng.run()
    n = len(_ticks(eng))
    assert n == eng.stats["steps"]
    assert eng.step() == [] and eng.step() == []
    assert len(_ticks(eng)) == n


def test_the_replica_is_the_routers(tiny):
    eng = _engine(tiny)
    eng._trace_replica = 3          # as ReplicaHandle sets it
    eng.submit(_prompt(tiny[0], 5, seed=1), max_new_tokens=2)
    eng.run()
    assert {t["fields"]["replica"] for t in _ticks(eng)} == {3}


def test_two_engines_two_traces(tiny):
    a, b = _engine(tiny), _engine(tiny)
    assert a._trace_id != b._trace_id
    for eng in (a, b):
        eng.submit(_prompt(tiny[0], 5, seed=1), max_new_tokens=3)
    while a.has_work() or b.has_work():
        a.step()
        b.step()
    for eng in (a, b):
        assert len(_ticks(eng)) == eng.stats["steps"] > 0
    # the id is a bare one: no root span stands open for an engine's life
    assert tracing.active_spans() == []
    assert tracing.active_tree()["in_flight_spans"] == 0


# ---------------------------------------------------------------------------
# the step span's fields, inside a profiler session
# ---------------------------------------------------------------------------

def test_fields_are_the_step_spans(tiny, tmp_path):
    eng = _engine(tiny, pallas=True)
    eng.submit(_prompt(tiny[0], 6, seed=2), max_new_tokens=2)
    eng.run()                              # both executables built
    before = len(_ticks(eng))

    def drive():
        eng.submit(_prompt(tiny[0], 13, seed=5), max_new_tokens=4)
        eng.submit(_prompt(tiny[0], 3, seed=6), max_new_tokens=6)
        while eng.has_work():
            eng.step()

    spans, _ = _profiled(str(tmp_path), drive)
    steps = [s[3] for s in spans
             if s[0] == "ptpu.serve.step" and "batch" in s[3]]
    ticks = _ticks(eng)[before:]
    assert len(steps) == len(ticks) > 4
    for step, tick in zip(steps, ticks):
        fields = dict(tick["fields"])
        assert ADDED <= set(fields)
        assert fields["tick"] == step["tick"]
        assert fields["launch_ns"] >= step["perf_ns"] or fields["ahead"]
        for name in ADDED:
            del fields[name]
        assert fields == {k: v for k, v in step.items()
                          if k not in ("tick", "perf_ns")}


# ---------------------------------------------------------------------------
# intervals and gaps
# ---------------------------------------------------------------------------

def test_intervals_and_gaps_tile_the_time_exactly(tiny):
    eng = _engine(tiny, pallas=True)
    _submit_mixed(eng, tiny[0])
    while eng.has_work():
        eng.step()
    ticks = _ticks(eng)
    assert ticks[0]["fields"]["gap_ns"] == 0       # nothing lay before it
    for a, b in zip(ticks, ticks[1:]):
        f = b["fields"]
        assert a["start_ns"] < a["end_ns"] <= b["start_ns"]
        # to the nanosecond: the device interval starts where the tick
        # before ended or, behind a gap, at this tick's call
        assert a["end_ns"] + f["gap_ns"] == b["start_ns"]
        assert b["start_ns"] == max(f["launch_ns"], a["end_ns"])
        if f["ahead"]:
            assert f["gap_ns"] == 0 and f["launch_ns"] < a["end_ns"]
        else:
            assert f["gap_ns"] > 0
    elapsed = ticks[-1]["end_ns"] - ticks[0]["start_ns"]
    assert elapsed == (sum(t["end_ns"] - t["start_ns"] for t in ticks)
                       + sum(t["fields"]["gap_ns"] for t in ticks[1:]))
    assert any(t["fields"]["ahead"] for t in ticks)
    assert not all(t["fields"]["ahead"] for t in ticks[1:])


def test_a_gap_behind_an_empty_engine_counts_from_the_empty_step(tiny):
    """An engine with no request does not charge its emptiness to the
    host: the gap of the tick behind it starts at the end of the last
    `step()` that found nothing to schedule, a reading of the span clock
    taken after the last tick's end."""
    eng = _engine(tiny)
    eng.submit(_prompt(tiny[0], 5, seed=1), max_new_tokens=3)
    eng.run()
    last = _ticks(eng)[-1]
    readings, clock = [], time.perf_counter_ns

    def reading():
        readings.append(clock())
        return readings[-1]

    time.perf_counter_ns = reading
    try:
        assert eng.step() == []
        first = len(readings)
        assert eng.step() == []
        second = readings[first:]
        eng.submit(_prompt(tiny[0], 5, seed=2), max_new_tokens=2)
        eng.step()
    finally:
        time.perf_counter_ns = clock
    tick = _ticks(eng)[-1]       # the one tick that call harvested
    f = tick["fields"]
    assert f["tick"] == last["fields"]["tick"] + 1 and not f["ahead"]
    # counted from a reading the SECOND empty call took, not from the
    # last tick's end
    assert f["launch_ns"] - f["gap_ns"] in second
    assert f["launch_ns"] - f["gap_ns"] > last["end_ns"]
    assert f["gap_ns"] > 0 and tick["start_ns"] == f["launch_ns"]


@pytest.mark.parametrize("how", ["cancel", "engine_stats", "extract_pages"])
def test_a_settled_tick_is_recorded_once(tiny, how):
    eng = _engine(tiny)
    rid = eng.submit(_prompt(tiny[0], 5, seed=51), max_new_tokens=20)
    eng.submit(_prompt(tiny[0], 7, seed=52), max_new_tokens=9)
    for _ in range(4):
        eng.step()
    assert eng._in_flight is not None and eng._in_flight.batch is not None
    before = len(_ticks(eng))
    assert before == eng.stats["steps"]
    if how == "cancel":
        assert eng.cancel(rid)
    elif how == "engine_stats":
        assert eng.engine_stats["steps"] == before + 1
    else:
        eng.extract_pages(_prompt(tiny[0], 5, seed=51))
    assert eng._in_flight is None
    assert len(_ticks(eng)) == before + 1 == eng.stats["steps"]
    eng.step()                  # the settled tick's events: no new record
    assert len(_ticks(eng)) in (before + 1, before + 2)
    eng.run()
    ticks = _ticks(eng)
    assert len(ticks) == eng.stats["steps"]
    assert [t["fields"]["tick"] for t in ticks] == list(range(len(ticks)))
    for a, b in zip(ticks, ticks[1:]):
        assert a["end_ns"] + b["fields"]["gap_ns"] == b["start_ns"]


def test_block_diffusion_writes_kind_block(tiny_blocks):
    cfg, params = tiny_blocks
    eng = PagedServingEngine(cfg, params, num_blocks=48, block_size=8,
                             max_batch=4, token_budget=32, max_len=128,
                             pallas=False)
    eng.submit(_prompt(cfg, 9, seed=4), max_new_tokens=8, denoising_steps=2)
    eng.submit(_prompt(cfg, 12, seed=5), max_new_tokens=8, denoising_steps=2)
    eng.run()
    f = [t["fields"] for t in _ticks(eng)]
    assert len(f) == eng.stats["steps"]
    assert {x["kind"] for x in f} == {"mixed", "block"}
    # a prompt's rows are `prefill_tokens`, and a tick of blocks alone
    # holds none; a block is `block_length` rows a sequence
    assert all((x["prefill_tokens"] > 0) == (x["kind"] == "mixed")
               for x in f)
    # an open block's rows are no prompt's, with or without a token out
    assert all(x["prompt_rows"] == x["prefill_tokens"] for x in f)
    assert all(x["tokens"] == 4 * x["batch"] for x in f
               if x["kind"] == "block")
    assert sum(x["ahead"] for x in f) == eng.stats["ticks_ahead"] > 0


# ---------------------------------------------------------------------------
# the ring, the flag, the export
# ---------------------------------------------------------------------------

def _streams(tiny):
    eng = _engine(tiny, pallas=True)
    rids = _submit_mixed(eng, tiny[0])
    done = {c.rid: (c.output_tokens, c.finish_reason) for c in eng.run()}
    return eng, [done[r] for r in rids]


def test_the_flag_off_writes_nothing_and_changes_nothing(tiny):
    on, streams_on = _streams(tiny)
    assert len(_ticks(on)) == on.stats["steps"]
    tracing.reset()
    flags.set_flags({"trace_spans": False})
    try:
        off, streams_off = _streams(tiny)
        assert tracing.finished_spans() == []
    finally:
        flags.set_flags({"trace_spans": True})
    assert streams_on == streams_off
    for name in ("steps", "step_builds", "tokens_computed", "ticks_ahead"):
        assert on.stats[name] == off.stats[name], name
    # the tick's event is written whatever the span flag says
    steps = [e for e in obs.recorder().events() if e[2] == "serving.step"]
    assert len(steps) == on.stats["steps"] + off.stats["steps"]
    # an engine built with the flag off records once it is on again
    off.submit(_prompt(tiny[0], 4, seed=9), max_new_tokens=2)
    off.run()
    assert 0 < len(_ticks(off)) == off.stats["steps"] - on.stats["steps"]


def test_the_ring_keeps_a_windows_ticks():
    """`serve_decode`'s judged window is some 4,100 ticks behind a check
    and a warm-up, `serve_blockdiff_decode`'s 4,450."""
    assert flags.flag_value("trace_buffer_size") >= 4 * 8192
    tid = tracing.new_id()
    for i in range(8192 + 100):
        tracing.record_span("serve.tick", tid, 0, 1000 * i, 5e-7,
                            event=False, tick=i)
    got = tracing.finished_spans(name="serve.tick")
    assert [s["fields"]["tick"] for s in got] == list(range(8192 + 100))


def test_a_recorded_span_ends_on_the_later_reading():
    """`dur_s` is a difference of two clock readings times 1e-9: the end
    comes out as the later one, whatever the float made of it."""
    tid = tracing.new_id()
    rng = np.random.RandomState(0)
    for _ in range(2000):
        start = int(rng.randint(1, 2**62 // 10**6))
        length = int(rng.randint(1, 10**12))
        sp = tracing.record_span("x", tid, 0, start, length * 1e-9)
        assert sp.end_ns == start + length


def test_the_tick_span_feeds_no_trace_span_event(tiny):
    eng = _engine(tiny)
    eng.submit(_prompt(tiny[0], 5, seed=1), max_new_tokens=3)
    eng.run()
    kinds = [e[2] for e in obs.recorder().events()]
    assert kinds.count("serving.step") == eng.stats["steps"] > 0
    assert "trace.span" not in kinds       # an untraced request: no span
    snap = obs.metrics_snapshot()["paddle_trace_spans_total"]["values"]
    assert not any("serve.tick" in k for k in snap)


def test_chrome_export_holds_the_ticks_under_the_engines_trace(tiny):
    eng = _engine(tiny)
    root = tracing.new_trace("request", rid=0)
    eng.submit(_prompt(tiny[0], 9, seed=1), max_new_tokens=4,
               trace=(root.trace_id, root.span_id))
    eng.run()
    tracing.end_span(root)
    events = tracing.to_chrome_trace(offset_ns=-5)["traceEvents"]
    row = [e for e in events if e["tid"] == f"trace-{eng._trace_id}"]
    ticks = _ticks(eng)
    assert [e["name"] for e in row] == ["serve.tick"] * len(ticks)
    assert len(row) == eng.stats["steps"] > 0
    for e, t in zip(row, ticks):
        assert e["args"]["tick"] == t["fields"]["tick"]
        assert e["args"]["kind"] == t["fields"]["kind"]
        assert e["ts"] == (t["start_ns"] - 5) / 1e3
    # the request's own spans stand on its row, over the same intervals
    mine = [e for e in events if e["tid"] == f"trace-{root.trace_id}"
            and e["name"] in ("prefill.chunk", "decode.tick")]
    assert sorted(e["ts"] for e in mine) == sorted(e["ts"] for e in row)


# ---------------------------------------------------------------------------
# one event a tick, and the registry's counters by kind
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "kernel"])
def test_one_event_a_tick_with_the_records_fields(tiny, pallas):
    eng = _engine(tiny, pallas=pallas)
    eng.submit(_prompt(tiny[0], 11, seed=1), max_new_tokens=4)
    eng.submit(_prompt(tiny[0], 3, seed=2), max_new_tokens=6)
    eng.run()
    events = obs.recorder().events()
    steps = [e for e in events if e[2] == "serving.step"]
    ticks = _ticks(eng)
    assert len(steps) == len(ticks) == eng.stats["steps"]
    for (_, _, _, dur_s, fields), tick in zip(steps, ticks):
        want = dict(tick["fields"], pallas=pallas, ffn=False)
        want["tick_kind"] = want.pop("kind")
        assert fields == want
        assert round(dur_s * 1e9) == tick["end_ns"] - tick["start_ns"]
    assert not any(e[2] in ("serving.pallas_step", "pallas_ffn.step")
                   for e in events)
    s = obs.summary()["serving"]
    assert s["steps_total"] == eng.stats["steps"]
    assert s["pallas_steps"] == eng.stats["pallas_steps"]
    assert (s["pallas_steps"] > 0) == pallas
    by_kind = obs.metrics_snapshot()[
        "paddle_serving_pallas_steps_total"]["values"]
    if pallas:
        assert by_kind['{kind="decode"}'] == eng.stats["decode_fast_steps"]
        assert (by_kind['{kind="decode"}'] + by_kind['{kind="mixed"}']
                == eng.stats["steps"])
    else:
        assert by_kind == {"": 0}


def test_the_fused_ffn_counters_ride_the_same_event(tiny):
    eng = _engine(tiny, pallas=True, pallas_ffn=True)
    eng.submit(_prompt(tiny[0], 6, seed=1), max_new_tokens=5)
    eng.run()
    s = obs.summary()["serving"]
    assert s["ffn_steps"] == eng.stats["ffn_steps"] == eng.stats["steps"] > 0
    assert s["fused_ticks"] == eng.stats["fused_ticks"] > 0
    steps = [e[4] for e in obs.recorder().events() if e[2] == "serving.step"]
    assert all(f["ffn"] and f["pallas"] for f in steps)
    assert (sum(f["tick_kind"] == "decode" for f in steps)
            == eng.stats["fused_ticks"])


def test_a_dump_names_the_ticks_event(tiny, tmp_path):
    """In the distress dump an event's fields lie beside its envelope:
    the tick's kind must not stand where the event's name does."""
    import json

    eng = _engine(tiny, pallas=True)
    eng.submit(_prompt(tiny[0], 11, seed=1), max_new_tokens=4)
    eng.run()
    with open(obs.distress.dump("test", path=str(tmp_path / "d.json"))) as f:
        events = json.load(f)["events"]
    steps = [e for e in events if e["kind"] == "serving.step"]
    assert len(steps) == eng.stats["steps"] > 2
    assert [e["tick"] for e in steps] == list(range(len(steps)))
    assert {e["tick_kind"] for e in steps} == {"decode", "mixed"}
    assert not {e["kind"] for e in events} & {"decode", "mixed", "block"}


def test_a_speculative_tick_runs_no_prompt_rows(tiny):
    """A draft's rows ride a decode tick: more rows than sequences, and
    none of a prompt."""
    cfg, params = tiny
    eng = _engine(tiny, draft=DraftModel(cfg, params), spec_k=3)
    eng.submit(_prompt(cfg, 6, seed=1), max_new_tokens=9)
    eng.submit(_prompt(cfg, 5, seed=2), max_new_tokens=9)
    eng.run()
    f = [t["fields"] for t in _ticks(eng)]
    assert sum(x["prompt_rows"] for x in f) == 11
    spec = [x for x in f if x["tokens"] > x["batch"] and not x["prompt_rows"]]
    assert spec and eng.stats["spec_ticks"] > 0
    assert all(x["prefill_tokens"] == 0 for x in spec)
