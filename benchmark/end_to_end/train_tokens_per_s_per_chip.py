"""Training tokens per second and chip: the tokens of the steps completed
in the window over the time from the first one's start to the last one's
end, over the chips of the mesh."""


def read(record):
    c = record.counters
    return c["tokens"] / c["elapsed_s"] / c["chips"]
