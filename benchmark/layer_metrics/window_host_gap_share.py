"""Share of the judged window in which the device had nothing queued because
the host was still planning, as far as the host can see it: the sum of the
`serve.tick` spans' `gap_ns` (from the end of the tick before, or of a
`step()` that found nothing to schedule since, to the entry of the next
tick's `serve.dispatch`; 0 for a tick launched ahead) over the window's
elapsed time, the first interval's start to the last one's end (the first
tick's gap lies before that stretch and is left out). The executable's call
and the read-back's tail lie inside the interval, so this is a lower bound
of the profile's idle share. None on a program that writes no `serve.tick`
span (`benchmark/lib/tick_log.py`)."""
from benchmark.lib import tick_log


def read(record):
    ticks = tick_log.window(record)
    if ticks is None:
        return None
    gaps = sum(t["fields"]["gap_ns"] for t in ticks[1:])
    return 100.0 * gaps / (ticks[-1]["end_ns"] - ticks[0]["start_ns"])
