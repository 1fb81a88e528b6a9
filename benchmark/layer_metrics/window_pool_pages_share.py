"""Percent of the pages the window layers would hold without release that
their pool holds, over the window's ticks, from the engine's
`stats["window_pages_live"]` and `["full_pages_live"]` (pages allocated in
each pool when a tick is launched, summed over ticks): what giving a page
back 512 positions behind saves the window layers' pool."""
from benchmark.lib import window_math


def read(record):
    c = record.counters
    if not c.get("full_pages_live"):
        return None
    return window_math.pool_pages_share(c["window_pages_live"],
                                        c["full_pages_live"])
