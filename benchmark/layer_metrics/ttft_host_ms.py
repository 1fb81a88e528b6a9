"""Mean over the traced window's first tokens of the time between a
request's admission and the end of the `ptpu.serve.step` that returned its
first token in which device 0 ran no operation: the prompt's hashing,
launch, read-back and harvest of the request's synchronous ticks
(benchmark/lib/request_timeline.py). A reading of the 6 to 10 first tokens
a traced window holds, not a judged number. None on a program without the
marks or with fewer than three of them in the window."""
from benchmark.lib import request_timeline


def read(record):
    return request_timeline.mean_ms(record, "host")
