"""The least time the chip could take for the traced ticks' work, over the
time it was busy in them. The floor is the larger of FLOPs over the bf16
peak and bytes over the HBM bandwidth, of the whole traced window (the
sum of per-tick floors would be no smaller, so this cannot flatter).
Work: the rows the engine computed (its `stats`) through every block, one
head row per token harvested, attention over the keys each row attended
to; bytes: the weights once per tick, the cached positions each decode
row read, the positions written. A prompt's attention and writes are
booked when its first token comes."""
from benchmark.lib import model_math


def read(record):
    if record.trace is None or record.trace_counters is None:
        return None
    c, ctx = record.trace_counters, record.context
    cfg = ctx.config
    flops = model_math.tick_flops(cfg, c["engine_tokens_computed"],
                                  c["tokens_out"], c["attended_keys"])
    bytes_moved = model_math.ticks_bytes(cfg, c["ticks"],
                                         c["positions_written"],
                                         c["context_read"])
    least, _ = model_math.least_seconds(flops, bytes_moved, ctx.peaks)
    return 100.0 * least / record.trace["busy_s"]
