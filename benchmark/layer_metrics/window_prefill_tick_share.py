"""Share of the judged window's tick time spent in ticks that ran rows of a
prompt (a chunk, a turn's new part): the sum of the `serve.tick` intervals
of those ticks over the sum of all the window's (`benchmark/lib/tick_log.py`).
The weight a saving in such a tick has in the judged rate; the window's,
not the traced stretch's behind it. None on a program that writes no
`serve.tick` span."""
from benchmark.lib import tick_log


def read(record):
    ticks = tick_log.window(record)
    if ticks is None:
        return None
    with_rows = sum(tick_log.interval_ns(t) for t in ticks
                    if tick_log.runs_prompt_rows(t))
    return 100.0 * with_rows / sum(map(tick_log.interval_ns, ticks))
