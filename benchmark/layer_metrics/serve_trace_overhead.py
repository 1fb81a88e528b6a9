"""What a tick costs more while the profiler records: the 25th percentile of
the `ptpu.serve.step` durations in the traced window over the 25th
percentile of the tick times of the untraced window, less one. The lower
quartile lies inside the decode-tick mode in both serve cells (94 % and
about half of the ticks); the median of `serve_longprompt` sits on the
boundary between decode ticks and chunk-carrying ticks three times as
long, and flips between them from one window to the next."""
from benchmark.lib import program_trace
from benchmark.lib.stats import percentile


def read(record):
    trace = program_trace.of_record(record)
    steps = program_trace.step_durations_ms(trace) if trace else []
    if not steps:
        return None
    return 100.0 * (percentile(steps, 25)
                    / percentile(record.samples["tick_ms"], 25) - 1.0)
