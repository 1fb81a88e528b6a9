"""A new per-layer metric, added as a file: how many spans were recorded."""


def read(record):
    return len(record.spans.records)
