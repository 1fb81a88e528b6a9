"""Tokens a sequence gains per forward of its block, from the engine's
`stats`: masked rows that became tokens over denoise plus commit
sequence-forwards (one sequence's block through one tick) in the window.
Block length 4 in 2 denoise forwards and 1 commit forward reads 4/3; the
commit forward folded into the next block's first forward would read 2."""


def read(record):
    c = record.counters
    forwards = (c.get("diff_denoise_forwards", 0)
                + c.get("diff_commit_forwards", 0))
    if not forwards:
        return None
    return c["diff_tokens_unmasked"] / forwards
