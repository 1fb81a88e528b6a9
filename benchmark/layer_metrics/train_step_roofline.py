"""The least time the mesh could take for the traced steps, over the time
its devices were busy in them (mean over the devices). The floor is the
larger of the required FLOPs over chips times peak and the optimizer's
unavoidable bytes over chips times bandwidth."""
from benchmark.lib import model_math


def read(record):
    if record.trace is None or record.trace_counters is None:
        return None
    c, ctx = record.trace_counters, record.context
    cfg, chips = ctx.config, record.counters["chips"]
    flops = c["tokens"] * model_math.train_flops_per_token(
        cfg, record.counters["seq_len"])
    bytes_moved = c["steps"] * model_math.train_step_bytes(
        cfg, model_math.n_params(cfg))
    least, _ = model_math.least_seconds(flops, bytes_moved, ctx.peaks, chips)
    return 100.0 * least / record.trace["busy_s"]
