"""Median `serve.tick` interval of the judged window's ticks that ran rows of
a prompt (a chunk, a turn's new part beside the decode rows). None where the
window holds none, and on a program that writes no `serve.tick` span
(`benchmark/lib/tick_log.py`)."""
from benchmark.lib import tick_log


def read(record):
    return tick_log.tick_p50_ms(record, prompt_rows=True)
