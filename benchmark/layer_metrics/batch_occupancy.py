"""Tokens the engine computed per step, from its own `stats`."""


def read(record):
    c = record.counters
    return c["engine_tokens_computed"] / c["engine_steps"]
