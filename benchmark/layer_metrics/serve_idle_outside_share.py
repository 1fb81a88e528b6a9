"""Share of the traced window in which device 0 ran nothing and the host was in
none of the six `ptpu.serve.*` phases: the benchmark's client loop between
two `step()` calls, and the microseconds of `ptpu.serve.step` between its
phases. With the six phase shares it adds up to `serve_device_idle_share`."""
from benchmark.lib import program_trace


def read(record):
    return program_trace.serve_idle_share(record, "outside")
