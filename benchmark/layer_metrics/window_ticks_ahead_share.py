"""Share of the judged window's ticks that had been launched ahead (the
`serve.tick` spans with `ahead` = 1 over all of the window's): what
`tick_ahead_share` reads off the 48 profiled ticks, over the whole judged
window and for the cells that metric cannot list. None on a program that
writes no `serve.tick` span (`benchmark/lib/tick_log.py`)."""
from benchmark.lib import tick_log


def read(record):
    ticks = tick_log.window(record)
    if ticks is None:
        return None
    return 100.0 * sum(t["fields"]["ahead"] for t in ticks) / len(ticks)
