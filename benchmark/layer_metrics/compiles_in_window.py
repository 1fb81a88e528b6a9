"""Executables JAX asked its backend for during the measured window
(cache hits count too). Must read 0."""


def read(record):
    return record.counters["compiles_in_window"]
