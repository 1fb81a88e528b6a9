"""Model FLOP/s utilization: the FLOPs the forward and backward passes
require per token (no recomputation) times tokens per second, over chips
times the bf16 peak."""
from benchmark.lib import model_math


def read(record):
    c, ctx = record.counters, record.context
    per_token = model_math.train_flops_per_token(ctx.config, c["seq_len"])
    rate = c["tokens"] / c["elapsed_s"]
    return 100.0 * per_token * rate / (
        c["chips"] * ctx.peaks["bf16_flops_per_s"])
