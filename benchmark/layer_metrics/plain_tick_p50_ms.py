"""Median `serve.tick` interval of the judged window's ticks that ran no row
of a prompt (kind `decode`, or `block`): the tick's device interval as the
host sees it, from its call, or the end of the tick before where it had been
launched ahead, to the end of its read-back. A median, so that a pause of
the whole machine inside one tick does not move it. None on a program that
writes no `serve.tick` span (`benchmark/lib/tick_log.py`)."""
from benchmark.lib import tick_log


def read(record):
    return tick_log.tick_p50_ms(record, prompt_rows=False)
