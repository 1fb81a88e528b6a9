"""A request's time to first token out of a traced window
(benchmark/lib/request_timeline.py; the readers `ttft_queue_ms`,
`ttft_host_ms`, `ttft_device_ms`), and the two readers of the step spans'
`attn_*` fields (`attn_live_page_share`, `attn_tile_occupancy`): on traces
written out by hand in program_trace's layout, on the ticks recorded on the
chip for this PR, on PR 25's older fixtures and on a program without names.
"""
import importlib
import json
import os
import types

import pytest

from benchmark.lib import program_trace as pt, request_timeline as rt, xplane
from benchmark.tests.helpers import ROOT_DIR

TESTDATA = os.path.join(ROOT_DIR, "benchmark", "lib", "testdata")
FIXTURE = os.path.join(TESTDATA, "serve_prefix_sessions_turn_v5e.json")
PR25_PROGRAM = os.path.join(TESTDATA, "serve_decode_2ticks_v5e_program.json")
PR24_PLAIN = os.path.join(TESTDATA, "serve_decode_2ticks_v5e.json")
TTFT = ["ttft_queue_ms", "ttft_host_ms", "ttft_device_ms"]
ATTN = ["attn_live_page_share", "attn_tile_occupancy"]
MS = 1_000_000
# the process's perf_counter_ns lies this far from the profile's axis
OFFSET = -7_000_000 * MS


def read(name, record):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(record)


def record_of(path):
    return types.SimpleNamespace(trace={"busy_s": 1.0},
                                 notes={"trace_file": str(path)})


def written(tmp_path, trace):
    path = tmp_path / "window.json"
    path.write_text(json.dumps(trace))
    return record_of(path)


def mark(at, rid, submit, admit, **fields):
    """A first-token mark at `at` whose stamps are given on the profile's
    axis and stored on the process's clock, as the engine writes them."""
    f = dict(rid=rid, submit_ns=submit - OFFSET, admit_ns=admit - OFFSET)
    return [rt.MARK, at, 1000, {**f, **fields}]


def step(start, dur, **fields):
    return [pt.STEP, start, dur, dict(perf_ns=start - OFFSET, batch=4,
                                      **fields)]


def hand_written():
    """Six ticks of 10 ms from 0 to 60 ms; the device runs [1, 9] ms of
    each but the third, where it starts late, [24, 29]. Three requests:

    - rid 1: submitted at 9.5 (the client's loop after tick 0), admitted
      at 10.2 in tick 1, first token returned at the step's end 19.8:
      queue 0.7; [10.2, 19.8] holds idle [10.2, 11] and [19, 19.8]: host
      1.6, device 8.0;
    - rid 2: submitted at 19.9, admitted at 20.5, two ticks (chunks),
      returned at 39.8: queue 0.6; idle [20.5, 24], [29, 31], [39, 39.8]:
      host 6.3, device 13.0;
    - rid 3: submitted at 30.1 while tick 3 ran, admitted at 40.4,
      returned at 49.8: queue 10.3; idle [40.4, 41], [49, 49.8]: host 1.4,
      device 8.0.

    And two that do not count: rid 0 was submitted before the window
    opened, rid 9's mark lies in no step (a tick harvested by `cancel`)."""
    busy = [(1, 9), (11, 19), (24, 29), (31, 39), (41, 49), (51, 59)]
    steps = [step(i * 10 * MS + 0.1 * MS, 9.7 * MS) for i in range(6)]
    marks = [mark(8.5 * MS, 0, -3 * MS, -2 * MS),
             mark(19.5 * MS, 1, 9.5 * MS, 10.2 * MS),
             mark(39.5 * MS, 2, 19.9 * MS, 20.5 * MS),
             mark(49.5 * MS, 3, 30.1 * MS, 40.4 * MS),
             mark(59.9 * MS, 9, 50.0 * MS, 50.1 * MS)]
    return {
        "device": {"/device:TPU:0": [["fusion.1", a * MS, (b - a) * MS]
                                     for a, b in busy]},
        "device_scopes": {"/device:TPU:0": ["ffn"] * len(busy)},
        "host": [["bench.tick", i * 10 * MS, 9.9 * MS] for i in range(6)],
        "program_spans": sorted(steps + marks, key=lambda e: e[1])}


def test_requests_of_a_window_written_out_by_hand():
    reqs = rt.requests(hand_written())
    assert [r["rid"] for r in reqs] == [1, 2, 3]
    got = [[round(r[k + "_ns"] / MS, 6) for k in rt.PARTS] for r in reqs]
    assert got == [[0.7, 1.6, 8.0], [0.6, 6.3, 13.0], [10.3, 1.4, 8.0]]
    for r in reqs:
        # the three add up to T2 - T0
        assert r["queue_ns"] + r["host_ns"] + r["device_ns"] \
            == pytest.approx(r["end"] - r["submit"])


def test_the_three_readers_are_the_means_and_add_up(tmp_path):
    rec = written(tmp_path, hand_written())
    got = {name: read(name, rec) for name in TTFT}
    assert got == {"ttft_queue_ms": pytest.approx((0.7 + 0.6 + 10.3) / 3),
                   "ttft_host_ms": pytest.approx((1.6 + 6.3 + 1.4) / 3),
                   "ttft_device_ms": pytest.approx((8.0 + 13.0 + 8.0) / 3)}
    # (19.8 - 9.5) + (39.8 - 19.9) + (49.8 - 30.1) over 3
    assert sum(got.values()) == pytest.approx((10.3 + 19.9 + 19.7) / 3)
    # the count goes into the run's notes, which run.py prints
    assert rec.notes["ttft_split_first_tokens"] == 3


@pytest.mark.parametrize("change, why", [
    (lambda t: t["program_spans"].__delitem__(slice(-4, None)),
     "two first tokens in the window: fewer than three are no mean"),
    (lambda t: [e[3].pop("perf_ns", None) for e in t["program_spans"]],
     "the parent's steps carry no perf_ns"),
    (lambda t: t.update(program_spans=[
        e for e in t["program_spans"] if e[0] != rt.MARK]),
     "the parent writes no mark"),
    (lambda t: t.update(program_spans=[]), "a program without names"),
])
def test_nothing_to_read_is_none_not_an_error(tmp_path, change, why):
    trace = hand_written()
    change(trace)
    rec = written(tmp_path, trace)
    for name in TTFT:
        assert read(name, rec) is None, why


def test_none_on_the_older_fixtures_and_without_a_trace():
    """PR 25's recorded ticks hold steps without `perf_ns` and no mark,
    PR 24's no name of the program at all; an untraced run has no file."""
    no_trace = types.SimpleNamespace(trace=None, notes={})
    for name in TTFT:
        assert read(name, record_of(PR25_PROGRAM)) is None
        assert read(name, record_of(PR24_PLAIN)) is None
        assert read(name, no_trace) is None
    # their steps carry no attn_* field either (PR 28 added them)
    for name in ATTN:
        assert read(name, record_of(PR25_PROGRAM)) is None
        assert read(name, record_of(PR24_PLAIN)) is None
        assert read(name, no_trace) is None


# ---- the turn recorded on the chip ---------------------------------------------

def busy_ns(events, lo, hi):
    """Nanoseconds of [lo, hi] covered by the events' intervals: the
    plain way, a sweep over them sorted by start."""
    total, reach = 0.0, lo
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        a, b = max(start, reach), min(start + dur, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def test_the_recorded_turn_reckoned_by_hand():
    """Request 488 of the fixture (README_request.txt). Its step opened at
    1,361,350 ns of the cut with `perf_ns` 163,401,476,593, so the
    process's clock lies 163,400,115,243 ns ahead of the cut's axis:

    - `submit_ns` 163,401,216,283 is 1,101,040: inside `ptpu.serve.submit`
      [1,097,030, 1,345,610];
    - `admit_ns` 163,401,499,313 is 1,384,070: 20.6 us after the start of
      the step's first `ptpu.serve.schedule` [1,363,519, 2,085,979], whose
      other 0.70 ms hash the prompt's pages;
    - the step ends at 1,361,350 + 28,681,697 = 30,043,047.

    Queue 283,030 ns. Device 0's first operation of the mixed tick starts
    at 2,648,965.75, 0.465 ms into `ptpu.serve.dispatch`, so the host's
    part is 1,264,895.75 ns of hashing, `prepare` and launch, and 65 us
    between the operations up to the step's end."""
    trace = pt.load_json(FIXTURE)
    (req,) = rt.requests(trace)
    assert req["rid"] == 488
    assert (req["submit"], req["admit"], req["end"]) == (
        1101040.0, 1384070.0, 30043047.0)
    assert req["queue_ns"] == 283030.0
    events = trace["device"]["/device:TPU:0"]
    first = min(e[1] for e in events if e[1] + e[2] > req["admit"])
    assert first == 2648965.75
    busy = busy_ns(events, req["admit"], req["end"])
    assert req["device_ns"] == pytest.approx(busy, abs=1.0)
    assert req["host_ns"] == pytest.approx(
        req["end"] - req["admit"] - busy, abs=1.0)
    assert req["host_ns"] == pytest.approx(1330285.04, abs=1.0)
    assert req["host_ns"] - (first - req["admit"]) == pytest.approx(
        65389.29, abs=1.0)
    assert req["device_ns"] == pytest.approx(27328691.96, abs=1.0)


def test_the_readers_on_the_recorded_turn(monkeypatch):
    rec = record_of(FIXTURE)
    # one first token is no mean ...
    for name in TTFT:
        assert read(name, rec) is None
    assert rec.notes["ttft_split_first_tokens"] == 1
    # ... unless one is asked for: the readers give the request's own parts
    monkeypatch.setattr(rt, "MIN_MARKS", 1)
    assert read("ttft_queue_ms", rec) == pytest.approx(0.28303)
    assert read("ttft_host_ms", rec) == pytest.approx(1.33028504, abs=1e-6)
    assert read("ttft_device_ms", rec) == pytest.approx(27.32869196,
                                                        abs=1e-6)
    # the steps' fields: 413 of 432 pages in the decode tick, 421 of 608
    # in the mixed one, whose 131 query rows ran on tiles of 152
    assert read("attn_live_page_share", rec) == pytest.approx(
        100.0 * (413 + 421) / (432 + 608))
    assert read("attn_tile_occupancy", rec) == pytest.approx(
        100.0 * 131 / 152)
    # and the older reductions read the new fixture as they read PR 25's
    assert sum(pt.idle_shares(pt.load_json(FIXTURE)).values()) \
        == pytest.approx(xplane.idle_share_percent(
            xplane.reduce(pt.load_json(FIXTURE))), abs=0.05)


# ---- the step spans' attn_* fields --------------------------------------------

DECODE = dict(kind="decode", attn_pages_live=280, attn_pages_fetched=344)
MIXED = dict(kind="mixed", attn_pages_live=290, attn_pages_fetched=832,
             attn_rows_live=217, attn_rows_packed=408)


@pytest.mark.parametrize("ticks, pages, rows", [
    # serve_decode's cycle: 15 decode launches and the mixed tick
    ([DECODE] * 15 + [MIXED],
     100.0 * (15 * 280 + 290) / (15 * 344 + 832), 100.0 * 217 / 408),
    ([DECODE] * 3, 100.0 * 280 / 344, None),      # no traced tick is mixed
    ([MIXED, MIXED], 100.0 * 290 / 832, 100.0 * 217 / 408),
    # a call that ran no batch, and a tick off the whole-page walks
    ([{"tick": 3}, dict(kind="decode")], None, None),
    ([], None, None),
])
def test_attn_readers_sum_the_fields_over_the_steps(tmp_path, ticks, pages,
                                                    rows):
    trace = hand_written()
    trace["program_spans"] = [step(i * 10 * MS, 9 * MS, **f)
                              for i, f in enumerate(ticks)]
    rec = written(tmp_path, trace)
    assert read("attn_live_page_share", rec) == (
        None if pages is None else pytest.approx(pages))
    assert read("attn_tile_occupancy", rec) == (
        None if rows is None else pytest.approx(rows))


# ---- the five entries ----------------------------------------------------------

def test_the_five_entries_are_the_last_five_and_say_what_the_issue_says():
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    last = bench["per_layer"][-5:]
    assert [m["name"] for m in last] == TTFT + ATTN
    for m in last[:3]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "server",
                     "moves": "ttft_mean_ms",
                     "workloads": ["serve_longprompt",
                                   "serve_prefix_sessions"]}
    for m in last[3:]:
        assert m == {"name": m["name"], "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "kernels",
                     "moves": "gap_p90_ms",
                     "workloads": ["serve_decode", "serve_longprompt",
                                   "serve_moe_decode"]}
    # each lists only cells that report the end-to-end metric it moves
    for m in last:
        (moved,) = [e for e in bench["end_to_end"] if e["name"] == m["moves"]]
        assert set(m["workloads"]) <= set(moved["workloads"])
