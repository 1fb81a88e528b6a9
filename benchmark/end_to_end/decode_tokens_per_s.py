"""Output tokens a replica emits per second: every token harvested in the
window over the time from the first measured tick's start to the last
one's end."""


def read(record):
    c = record.counters
    return c["tokens_out"] / c["elapsed_s"]
