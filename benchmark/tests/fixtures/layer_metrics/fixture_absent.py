"""A reader that finds nothing to read: the harness leaves it out."""


def read(record):
    return None
