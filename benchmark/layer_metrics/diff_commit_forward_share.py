"""Percent of the window's sequence-forwards (one sequence's block through
one tick) that were commit forwards: they keep a finished block's keys and
values and yield no token, from the engine's `stats`. A third with 2
denoise forwards a block."""


def read(record):
    c = record.counters
    forwards = (c.get("diff_denoise_forwards", 0)
                + c.get("diff_commit_forwards", 0))
    if not forwards:
        return None
    return 100.0 * c["diff_commit_forwards"] / forwards
