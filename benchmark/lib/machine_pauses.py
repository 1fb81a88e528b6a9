"""An observer of pauses of the whole machine, for the serving windows.

On the one-chip machines the benchmark runs on, every process stops for
about 110 ms a few times a minute once a process holds gigabytes of the
chip's memory (PR 27, PERF.md §6: two observers pinned to different cores
and the engine's own loop lose the same 107-115 ms at the same instant,
whichever phase of a tick it falls in; no thread of the process runs
meanwhile; a loop of small kernels beside 14
GiB of resident arrays shows them, the same loop alone does not, nor an
idle machine; the train cells hide them behind asynchronous dispatch). A
serving tick is synchronous, so each pause costs a 51 s window about 0.2 %
of its tokens per second and a turn that waits for its first token up to
the whole pause, and their number, 0 to 7 a window, was most of the spread
that refused `serve_moe_decode` at first.

The observer is a second process that does nothing but sleep a millisecond
at a time and note every sleep that took `LEAST_S` or longer. It shares no
lock with the program under test and never touches the chip.
`books_outside()` then gives the window's books without the ticks a pause
fell into, tokens and time both, and its samples without the waits a pause
fell into: a random slice taken out of the window, which leaves every rate
and mean as it is without the pauses. What was left out is counted beside
what is left.
"""
from __future__ import annotations

import json
import subprocess
import sys
import time

PERIOD_S = 0.001
LEAST_S = 0.020     # twenty sleeps lost at once: no scheduler does that
                    # to an idle process on a machine with free cores

# `select` on stdin is the sleep, so closing the pipe ends the loop at once
_OBSERVER = r"""
import json, select, sys, time
period, least = float(sys.argv[1]), float(sys.argv[2])
print(time.perf_counter(), flush=True)
pauses, last = [], time.perf_counter()
while not select.select([sys.stdin], [], [], period)[0]:
    now = time.perf_counter()
    if now - last >= least:
        pauses.append([last, now])
    last = now
print(json.dumps(pauses), flush=True)
"""


class Observer:
    """`Observer()` starts the process and returns once it runs; `stop()`
    ends it and returns the pauses as (start, end) on this process's
    `time.perf_counter()` axis, or None with `why` set where the two
    processes' clocks cannot be laid over one another."""

    def __init__(self):
        self.why = None
        before = time.perf_counter()
        self.child = subprocess.Popen(
            [sys.executable, "-S", "-c", _OBSERVER, str(PERIOD_S),
             str(LEAST_S)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        theirs = float(self.child.stdout.readline())
        # Linux: perf_counter is CLOCK_MONOTONIC in every process
        if not before <= theirs <= time.perf_counter():
            self.why = "the observer's clock is not this process's"

    def stop(self):
        out, _ = self.child.communicate()    # closes stdin, reaps the child
        if self.why is not None:
            return None
        return [tuple(p) for p in json.loads(out)]


def overlapping(intervals, pauses) -> list:
    """Indices of the (start, end) intervals that a pause overlaps."""
    return [k for k, (a, b) in enumerate(intervals)
            if any(p0 < b and p1 > a for p0, p1 in pauses)]


def books_outside(pauses, first_start_s: float, books: list, samples: dict,
                  made: list):
    """`books[i]` are a loop's counters after the window's tick i, each a
    sum over ticks, with `elapsed_s` the time since `first_start_s`, so
    tick i lasted from the end of the one before to its own end, the
    client's work between two engine steps included. `samples[name]` are
    times in ms, each ending with the tick that made it; `made[i][name]`
    is how many there were after tick i. Returns the counters summed over
    the ticks that no pause overlaps, the samples whose own stretch no
    pause overlaps, and how many of each were left out."""
    ends = [first_start_s + b["elapsed_s"] for b in books]
    ticks = list(zip([first_start_s] + ends[:-1], ends))
    counters = dict(books[-1])
    hit = overlapping(ticks, pauses)
    for k in hit:
        before = books[k - 1] if k else dict.fromkeys(counters, 0)
        for name in counters:
            counters[name] -= books[k][name] - before[name]
    left_out = {"ticks": len(hit)}
    kept = {}
    for name, values in samples.items():
        spans, tick = [], 0
        for j, ms in enumerate(values):
            while made[tick][name] <= j:
                tick += 1
            spans.append((ends[tick] - ms * 1e-3, ends[tick]))
        gone = set(overlapping(spans, pauses))
        kept[name] = [ms for j, ms in enumerate(values) if j not in gone]
        left_out[name] = len(gone)
    return counters, kept, left_out
