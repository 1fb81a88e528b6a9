"""The judged window's ticks, from the program's own record.

The engine keeps one finished span `serve.tick` a harvested tick in the
ring of `paddle_tpu.observability.tracing`, for the whole run: the tick's
device interval as the host sees it (`start_ns`: its call, or the end of
the tick before it where it had been launched ahead; `end_ns`: the end of
its read-back), the fields of its `ptpu.serve.step` span (`batch`,
`tokens`, `prefill_tokens`, `kind`, `ahead`, ...) and `prompt_rows` (its
rows of sequences with no token out yet: the engine's own count), `tick`,
`launch_ns`, `gap_ns` (what the device had nothing queued before the call,
as far as the host sees it). The per-layer shares of a serve cell are read from the
`trace_ticks` profiled BEHIND the judged window, which in the cells with
long prompts hold another mix of ticks than the window; these readers
take the window itself, the untraced one the end-to-end metric is judged
on.

`window(record)` is the RAW window: the ticks a pause of the whole machine
fell into stay in (`machine_pauses.books_outside` is the harness's filter
of its own books, and the notes say what it left out).
"""
from __future__ import annotations

from typing import List, Optional

from paddle_tpu.observability import tracing

from .stats import percentile

NAME = "serve.tick"


def bench_ticks(record) -> Optional[list]:
    """The judged window's `bench.tick` spans, (name, start_s, end_s) on
    `time.perf_counter`: of the record's, `notes["warm_ticks"]` lie before
    the window and, in a traced run, the traffic's `trace_ticks` behind
    it. None where the driver notes no `warm_ticks`."""
    ticks = [r for r in record.spans.records if r[0] == "bench.tick"]
    warm = (record.notes or {}).get("warm_ticks")
    if warm is None:
        return None
    behind = (record.context.traffic["trace_ticks"]
              if record.context.trace else 0)
    return ticks[warm:len(ticks) - behind] or None


def window(record) -> Optional[List[dict]]:
    """`_window(record)`, computed once a record: the five readers share
    one copy of the ring and one note."""
    if not hasattr(record, "_tick_window"):
        record._tick_window = _window(record)
    return record._tick_window


def _window(record) -> Optional[List[dict]]:
    """The `serve.tick` spans (`Span.to_dict()`) whose `end_ns` lies in the
    judged window, oldest first. None on a program that writes no such
    span, and None, with `notes["tick_log_wrapped"]`, where the ring no
    longer holds a tick from before the window or the ticks' numbers have
    a hole: a partial window is never reported. What the window held, and
    how the record agrees with the harness's own spans, goes into
    `notes["tick_log"]`."""
    bench = bench_ticks(record)
    ring = tracing.finished_spans(name=NAME)
    if bench is None or not ring:
        return None
    lo, hi = bench[0][1] * 1e9, bench[-1][2] * 1e9
    ticks = [t for t in ring if lo <= t["end_ns"] <= hi]
    if not ticks:
        return None
    numbers = [t["fields"]["tick"] for t in ticks]
    if (ring[0]["end_ns"] >= lo
            or numbers != list(range(numbers[0], numbers[0] + len(ticks)))):
        record.notes["tick_log_wrapped"] = {
            "ring_first_tick": ring[0]["fields"]["tick"],
            "window_first_tick": numbers[0], "window_ticks": len(ticks)}
        return None
    kinds = [t["fields"]["kind"] for t in ticks]
    # every `bench.tick` span holds the end of one record, or of none
    # where the call ran no batch
    ends = sorted(t["end_ns"] for t in ticks)
    most, at = 0, 0
    for _, start, end in bench:
        n = 0
        while at < len(ends) and ends[at] <= end * 1e9:
            n += ends[at] >= start * 1e9
            at += 1
        most = max(most, n)
    elapsed = ticks[-1]["end_ns"] - ticks[0]["start_ns"]
    covered = (sum(interval_ns(t) for t in ticks)
               + sum(t["fields"]["gap_ns"] for t in ticks[1:]))
    record.notes["tick_log"] = {
        "ticks": len(ticks), "bench_ticks": len(bench),
        "most_ends_in_a_bench_tick": most,
        "by_kind": {k: kinds.count(k) for k in sorted(set(kinds))},
        "with_prompt_rows": sum(map(runs_prompt_rows, ticks)),
        "ahead": sum(t["fields"]["ahead"] for t in ticks),
        "first_tick": numbers[0], "elapsed_s": elapsed * 1e-9,
        "intervals_and_gaps_over_elapsed": covered / elapsed}
    return ticks


def interval_ns(tick: dict) -> int:
    return tick["end_ns"] - tick["start_ns"]


def runs_prompt_rows(tick: dict) -> bool:
    """Whether the tick ran rows of a prompt, by the engine's own count
    (`prompt_rows`: a chunk's rows, the last chunk's too though it yields
    a token, and a turn's new part over cached pages; `prefill_tokens`
    holds only the rows that yield no token). On the chip that is the
    tick whose `kind` is `mixed`."""
    return tick["fields"]["prompt_rows"] > 0


def tick_p50_ms(record, prompt_rows: bool) -> Optional[float]:
    """Median interval of the window's ticks with, or without, rows of a
    prompt; None where the window holds none such."""
    ticks = window(record)
    if ticks is None:
        return None
    ms = [interval_ns(t) * 1e-6 for t in ticks
          if runs_prompt_rows(t) == prompt_rows]
    return percentile(ms, 50) if ms else None
