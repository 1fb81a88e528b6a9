"""The least time the chip could take for the traced steps' window
attention, over the self time of the operations under scope
`attention_window`: the windowed flash kernel's share of its roofline,
forward, dq and dk/dv. The floor is the larger of 3 x 4 x heads x head_dim
FLOPs a visible (query, key) pair over the bf16 peak and q, k, v, o and
their gradients moved once over the HBM bandwidth
(benchmark/lib/train_plan_math.py). The visible pairs are an exact
function of the static mask and the shapes, not a counter; the forward
launch that remat replays in the backward pass is time in the scope and
no FLOP in the floor."""
from benchmark.lib import program_trace, train_plan_math, train_plan_scopes


def read(record):
    c = record.trace_counters
    if record.trace is None or not c or "sequences" not in c:
        return None
    share = program_trace.scope_share(record, train_plan_scopes.WINDOW)
    if not share:
        return None
    ctx = record.context
    windows = [w for w in train_plan_math.layer_windows(ctx.config) if w]
    seq = record.counters["seq_len"]
    least, _ = train_plan_math.least_seconds(
        train_plan_math.attention_flops(ctx.config, seq, c["sequences"],
                                        windows),
        train_plan_math.attention_bytes(ctx.config, seq, c["sequences"],
                                        len(windows)),
        ctx.peaks)
    return 100.0 * least / (share / 100.0 * record.trace["busy_s"])
