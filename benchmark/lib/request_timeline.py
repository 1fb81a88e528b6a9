"""A request's time to its first token inside a traced window, from the
program's own stamps (paddle_tpu/inference/serving/engine.py, PR 39):

- every `ptpu.serve.step` carries `perf_ns`, the `time.perf_counter_ns()`
  reading taken as the span opens, so `start - perf_ns` is the offset from
  the process's perf_counter_ns to the profile's axis;
- a request's first surfaced token is the mark `ptpu.serve.first_token`
  inside the step's `harvest`, with `rid`, `submit_ns` (entry of
  `engine.submit`) and `admit_ns` (the admission attempt of
  `Scheduler.schedule` that first took it, before it hashes the prompt's
  pages).

For every mark of the window that lies inside a step and whose submit,
moved onto the profile's axis by that step's offset, lies inside the
window: T0 = submit, T1 = admit, T2 = the end of the enclosing step, where
`step()` hands the events to its caller.

    queue  = T1 - T0                 submitted and not yet planned: in a
                                     closed loop `submit`'s own work and
                                     the client's code up to `step`
    host   = the time inside [T1, T2] in which device 0 ran no operation
             (`program_trace.idle_intervals`, clipped): the prompt's
             hashing, launch, read-back and harvest of the request's
             synchronous ticks
    device = (T2 - T1) - host        the device at work, on this request's
                                     chunks and on whatever shared or
                                     preceded its ticks

The three add up to T2 - T0. A mark with no step around it (a tick
harvested by `cancel`, a page hand-off or `engine_stats`) is skipped, and
so is a request submitted before the window opened. A program whose steps
carry no `perf_ns` (before PR 39) gives nothing to read.

The means are over the first tokens of a traced window of 48 ticks: 6 in
`serve_prefix_sessions`, 9 or 10 in `serve_longprompt`, whose prompts of
512 to 1,712 tokens make the device's mean follow the draw (PERF.md PR 39).
Readings of a handful of requests, then, not judged numbers.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.lib import program_trace

MARK = "ptpu.serve.first_token"
PARTS = ("queue", "host", "device")
# fewer first tokens than this in a window are no mean
MIN_MARKS = 3


def requests(trace: dict) -> List[Dict[str, float]]:
    """One dict for each first token of the window, in order: `rid`, T0,
    T1, T2 as `submit`, `admit`, `end` (ns on the profile's axis),
    `queue_ns`, `host_ns`, `device_ns`."""
    steps = [e for e in trace["program_spans"]
             if e[0] == program_trace.STEP and "perf_ns" in e[3]]
    marks = [e for e in trace["program_spans"] if e[0] == MARK]
    if not steps or not marks:
        return []
    lo, _ = program_trace.window_of(trace)
    idle = program_trace.idle_intervals(trace)
    out = []
    for _, at, _, f in marks:
        step = next((s for s in steps if s[1] <= at < s[1] + s[2]), None)
        if step is None:
            continue
        offset = step[1] - float(step[3]["perf_ns"])
        t0, t1 = float(f["submit_ns"]) + offset, float(f["admit_ns"]) + offset
        t2 = step[1] + step[2]
        if t0 < lo:
            continue
        host = sum(min(e, t2) - max(s, t1) for s, e in idle
                   if e > t1 and s < t2)
        out.append({
            "rid": int(f["rid"]), "submit": t0, "admit": t1, "end": t2,
            "queue_ns": t1 - t0, "host_ns": host,
            "device_ns": (t2 - t1) - host})
    return out


def means_ms(reqs: List[Dict[str, float]]) -> Optional[Dict[str, float]]:
    """{"queue": ms, "host": ms, "device": ms}, the means over a window's
    first tokens (`requests`); None with fewer than MIN_MARKS of them."""
    if len(reqs) < MIN_MARKS:
        return None
    return {p: sum(r[p + "_ns"] for r in reqs) / len(reqs) * 1e-6
            for p in PARTS}


def mean_ms(record, part: str) -> Optional[float]:
    """What the readers in benchmark/layer_metrics call."""
    trace = program_trace.of_record(record)
    if trace is None:
        return None
    reqs = requests(trace)
    if isinstance(record.notes, dict):
        # for a reader of the log: what the three means are taken over
        record.notes["ttft_split_first_tokens"] = len(reqs)
    means = means_ms(reqs)
    return None if means is None else means[part]
