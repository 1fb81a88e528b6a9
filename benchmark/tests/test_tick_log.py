"""`benchmark/lib/tick_log.py` and the five readers of the judged window's
ticks, on hand-made records: the ring of `paddle_tpu.observability.tracing`
filled with `serve.tick` spans as the engine writes them, and a record whose
`bench.tick` spans mark the warm-up, the window and a traced stretch."""
import json
import os
import types

import pytest

from benchmark.layer_metrics import (plain_tick_p50_ms, prefill_tick_p50_ms,
                                     window_host_gap_share,
                                     window_prefill_tick_share,
                                     window_ticks_ahead_share)
from benchmark.lib import tick_log
from benchmark.lib.harness import Spans
from benchmark.tests.helpers import ROOT_DIR
from paddle_tpu.observability import tracing

MS = 1_000_000
READERS = {"window_prefill_tick_share": window_prefill_tick_share,
           "plain_tick_p50_ms": plain_tick_p50_ms,
           "prefill_tick_p50_ms": prefill_tick_p50_ms,
           "window_host_gap_share": window_host_gap_share,
           "window_ticks_ahead_share": window_ticks_ahead_share}


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset()
    yield
    tracing.reset()


def tick(number, start_ms, end_ms, gap_ms=0.0, ahead=1, kind="decode",
         batch=4, tokens=4, prefill_tokens=0, prompt_rows=0):
    """One `serve.tick` span as `engine._harvest` writes it."""
    start = int(start_ms * MS)
    tracing.record_span(
        "serve.tick", 1, 0, start, (int(end_ms * MS) - start) * 1e-9,
        event=False, batch=batch, tokens=tokens,
        prefill_tokens=prefill_tokens, kind=kind, ahead=ahead, void_rows=0,
        sampled_rows=0, prompt_rows=prompt_rows, tick=number,
        launch_ns=start,
        gap_ns=int(gap_ms * MS), replica=None)


def record(bench_ticks_ms, warm, trace=False, trace_ticks=2):
    """A record whose `bench.tick` spans are (start, end) in ms, with a
    harvest span between them as the loop writes one."""
    spans = Spans()
    for a, b in bench_ticks_ms:
        spans.records.append(("bench.tick", a * 1e-3, b * 1e-3))
        spans.records.append(("bench.harvest", b * 1e-3, b * 1e-3))
    ctx = types.SimpleNamespace(trace=trace,
                                traffic={"trace_ticks": trace_ticks})
    return types.SimpleNamespace(spans=spans, notes={"warm_ticks": warm},
                                 context=ctx, trace={} if trace else None)


def the_window():
    """Two warm ticks, then a window of 3 decode ticks and 1 chunk tick
    with one gap, then two traced ticks:

        tick 2  decode  100..110  launched ahead
        tick 3  decode  110..120  launched ahead
        tick 4  mixed   122..162  behind a gap of 2 ms, 40 rows of a prompt
        tick 5  decode  162..172  launched ahead
    """
    tick(0, 50, 80, kind="mixed", tokens=30, prefill_tokens=26,
         prompt_rows=26, ahead=0)
    tick(1, 80, 100, kind="mixed", tokens=12, prompt_rows=9, ahead=1)
    tick(2, 100, 110)
    tick(3, 110, 120)
    tick(4, 122, 162, gap_ms=2, ahead=0, kind="mixed", tokens=43,
         prompt_rows=40)
    tick(5, 162, 172)
    tick(6, 172, 182)
    tick(7, 182, 192)
    bench = [(49, 81), (81, 101), (101, 111), (111, 121), (121, 163),
             (163, 173), (173, 183), (183, 193)]
    return bench


def test_window_is_found_from_warm_and_trace_ticks():
    bench = the_window()
    # an untraced run: the window runs to the record's last tick
    rec = record(bench[:6], warm=2)
    got = tick_log.window(rec)
    assert [t["fields"]["tick"] for t in got] == [2, 3, 4, 5]
    # a traced run: the traffic's trace_ticks lie behind the window
    rec = record(bench, warm=2, trace=True)
    got = tick_log.window(rec)
    assert [t["fields"]["tick"] for t in got] == [2, 3, 4, 5]
    assert rec.notes["tick_log"] == {
        "ticks": 4, "bench_ticks": 4, "most_ends_in_a_bench_tick": 1,
        "by_kind": {"decode": 3, "mixed": 1}, "with_prompt_rows": 1,
        "ahead": 3, "first_tick": 2, "elapsed_s": pytest.approx(0.072),
        "intervals_and_gaps_over_elapsed": 1.0}
    assert "tick_log_wrapped" not in rec.notes
    # the same record read as untraced takes the traced ticks in
    rec = record(bench, warm=2)
    assert len(tick_log.window(rec)) == 6


@pytest.mark.parametrize("name, want", [
    # 40 of 70 ms of tick time ran rows of a prompt
    ("window_prefill_tick_share", 100 * 40 / 70),
    ("plain_tick_p50_ms", 10.0),
    ("prefill_tick_p50_ms", 40.0),
    # 2 ms of the 72 from tick 2's start to tick 5's end
    ("window_host_gap_share", 100 * 2 / 72),
    ("window_ticks_ahead_share", 75.0),
])
def test_each_reader_by_hand(name, want):
    rec = record(the_window(), warm=2, trace=True)
    assert READERS[name].read(rec) == pytest.approx(want, rel=1e-12)


def test_the_first_ticks_gap_lies_before_the_window():
    tick(0, 0, 10)
    tick(1, 15, 25, gap_ms=5, ahead=0)
    tick(2, 25, 35)
    rec = record([(0, 11), (14, 26), (26, 36)], warm=1)
    assert window_host_gap_share.read(rec) == 0.0
    assert rec.notes["tick_log"]["intervals_and_gaps_over_elapsed"] == 1.0


def test_a_window_of_decode_ticks_has_no_prefill_median():
    for i in range(5):
        tick(i, 10 * i, 10 * i + 10)
    rec = record([(10 * i + 0.5, 10 * i + 10.5) for i in range(5)], warm=1)
    assert prefill_tick_p50_ms.read(rec) is None
    assert window_prefill_tick_share.read(rec) == 0.0
    assert plain_tick_p50_ms.read(rec) == 10.0


@pytest.mark.parametrize("fields, want", [
    (dict(kind="decode", batch=16, tokens=16, prefill_tokens=0,
          prompt_rows=0), False),
    # a chunk that yields no token
    (dict(kind="mixed", batch=17, tokens=976, prefill_tokens=960,
          prompt_rows=960), True),
    # a prompt's last chunk, a turn's new part: the rows yield a token,
    # so `prefill_tokens` does not hold them
    (dict(kind="mixed", batch=16, tokens=143, prefill_tokens=0,
          prompt_rows=128), True),
    # block diffusion: a block is `block_length` rows a sequence
    (dict(kind="block", batch=16, tokens=64, prefill_tokens=0,
          prompt_rows=0), False),
    (dict(kind="mixed", batch=16, tokens=124, prefill_tokens=64,
          prompt_rows=64), True),
    # the stock path calls every tick mixed: one row a sequence is plain
    (dict(kind="mixed", batch=4, tokens=4, prefill_tokens=0,
          prompt_rows=0), False),
    # a speculative tick verifies a draft's rows beside each sequence's
    # own: more rows than sequences, and none of a prompt
    (dict(kind="mixed", batch=4, tokens=20, prefill_tokens=0,
          prompt_rows=0), False),
])
def test_which_ticks_run_rows_of_a_prompt(fields, want):
    """The engine's own count decides, not the tick's other fields."""
    assert tick_log.runs_prompt_rows({"fields": fields}) == want


def test_the_five_readers_share_one_window(monkeypatch):
    rec = record(the_window(), warm=2, trace=True)
    calls = []
    real = tracing.finished_spans
    monkeypatch.setattr(tracing, "finished_spans",
                        lambda **kw: calls.append(kw) or real(**kw))
    for reader in READERS.values():
        assert reader.read(rec) is not None
    assert calls == [{"name": "serve.tick"}]


def test_a_call_that_ran_no_batch_holds_no_record():
    tick(0, 0, 10)
    tick(1, 10, 20)
    tick(2, 40, 50, gap_ms=8, ahead=0)      # behind an empty call at 32
    rec = record([(0, 11), (11, 21), (21, 32), (32, 51)], warm=1)
    got = tick_log.window(rec)
    assert [t["fields"]["tick"] for t in got] == [1, 2]
    assert rec.notes["tick_log"]["bench_ticks"] == 3
    assert rec.notes["tick_log"]["most_ends_in_a_bench_tick"] == 1
    # the empty engine's time is charged to nobody
    assert rec.notes["tick_log"][
        "intervals_and_gaps_over_elapsed"] == pytest.approx(28 / 40)


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_to_read_without_tick_spans(name):
    """The parent's program writes no `serve.tick`: the reader is left
    out, and nothing is noted."""
    tracing.record_span("decode.tick", 1, 0, 100 * MS, 0.01, rid=0)
    rec = record([(49, 81), (99, 111), (111, 121)], warm=1)
    assert READERS[name].read(rec) is None
    assert rec.notes == {"warm_ticks": 1}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_wrapped_ring_reports_no_partial_window(name):
    bench = the_window()
    spans = tracing.finished_spans(name="serve.tick")
    tracing.reset()
    for s in spans[3:]:         # the ring lost ticks 0..2: the window's first
        tracing.record_span("serve.tick", 1, 0, s["start_ns"],
                            s["dur_s"], event=False, **s["fields"])
    rec = record(bench, warm=2, trace=True)
    assert READERS[name].read(rec) is None
    assert rec.notes["tick_log_wrapped"] == {
        "ring_first_tick": 3, "window_first_tick": 3, "window_ticks": 3}
    assert "tick_log" not in rec.notes


def test_a_ring_that_ends_before_the_window_is_whole():
    """Tick 1 ended before the window began: the window's first tick is
    the ring's second, so nothing of the window was dropped."""
    bench = the_window()
    spans = tracing.finished_spans(name="serve.tick")
    tracing.reset()
    for s in spans[1:]:
        tracing.record_span("serve.tick", 1, 0, s["start_ns"],
                            s["dur_s"], event=False, **s["fields"])
    rec = record(bench, warm=2, trace=True)
    assert len(tick_log.window(rec)) == 4


def test_no_window_without_the_drivers_note():
    the_window()
    rec = record([(99, 111)], warm=0)
    del rec.notes["warm_ticks"]
    assert tick_log.window(rec) is None


def test_the_five_entries_by_name():
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    judged = next(m for m in bench["end_to_end"]
                  if m["name"] == "decode_tokens_per_s")["workloads"]
    assert len(judged) == 7
    by_name = {m["name"]: m for m in bench["per_layer"]}
    units = {"window_prefill_tick_share": ("%", "lower"),
             "plain_tick_p50_ms": ("ms", "lower"),
             "prefill_tick_p50_ms": ("ms", "lower"),
             "window_host_gap_share": ("%", "lower"),
             "window_ticks_ahead_share": ("%", "higher")}
    assert set(units) == set(READERS)
    for name, (unit, better) in units.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_span", "layer": "server",
            "moves": "decode_tokens_per_s", "workloads": judged}, name
        assert os.path.exists(os.path.join(
            ROOT_DIR, "benchmark", "layer_metrics", name + ".py"))
