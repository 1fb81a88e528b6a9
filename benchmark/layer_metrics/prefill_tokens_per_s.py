"""Prompt tokens of the requests whose first token came in the window,
over the window's elapsed time."""


def read(record):
    c = record.counters
    return c["prompt_tokens_done"] / c["elapsed_s"]
