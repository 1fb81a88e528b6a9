"""Seconds from the start of the process to the first measured step:
imports, weights, compilation or the compile cache's reads, warm-up and
the correctness check."""


def read(record):
    return record.setup_s
