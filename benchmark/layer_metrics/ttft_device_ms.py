"""Mean over the traced window's first tokens of the time between a
request's admission and the end of the `ptpu.serve.step` that returned its
first token in which device 0 was at work, on the request's chunks and on
whatever shared or preceded its ticks
(benchmark/lib/request_timeline.py). With `ttft_queue_ms` and
`ttft_host_ms` it adds up to the mean submit-to-first-token of those
requests. A reading of the 6 to 10 first tokens a traced window holds: in
`serve_longprompt`, whose prompts span 512 to 1,712 tokens, it follows the
draw of their lengths by about 5 %. None on a program without the marks or
with fewer than three of them in the window."""
from benchmark.lib import request_timeline


def read(record):
    return request_timeline.mean_ms(record, "device")
