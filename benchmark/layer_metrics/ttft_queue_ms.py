"""Mean over the traced window's first tokens of the time between a
request's `engine.submit` and the admission attempt of `schedule()` that
took it (a tick in flight that was launched ahead, a full batch, a budget;
in a closed loop `submit`'s own work and the client's code up to `step`):
the mark `ptpu.serve.first_token`'s `admit_ns` - `submit_ns`, see
benchmark/lib/request_timeline.py. A reading of the 6 to 10 first tokens a
traced window holds, not a judged number. None on a program without the
marks or with fewer than three of them in the window."""
from benchmark.lib import request_timeline


def read(record):
    return request_timeline.mean_ms(record, "queue")
