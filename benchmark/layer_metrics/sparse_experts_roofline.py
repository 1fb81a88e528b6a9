"""`moe_experts_roofline` for a layer plan in which not every layer is
sparse (Laguna: layer 0 is dense): the least time the chip could take for
the traced ticks' expert matmuls (`moe_math.experts_least_seconds` with
`moe_intermediate_size` as the width of one expert and the count of SPARSE
layers for `num_hidden_layers`: the accepted readers would count every
layer) over the self time of the operations under scope `experts`. The
shared expert runs under its own scope and is in neither. The counts are
the engine's own over the traced ticks."""
from benchmark.lib import moe_math, program_trace


def sparse_config(cfg: dict) -> dict:
    return dict(cfg, intermediate_size=cfg["moe_intermediate_size"],
                num_hidden_layers=cfg["mlp_layer_types"].count("sparse"))


def read(record):
    c = record.trace_counters
    cfg = record.context.config
    if (record.trace is None or not c or "moe_experts_hit" not in c
            or "mlp_layer_types" not in cfg):
        return None
    share = program_trace.scope_share(record, "experts")
    if not share:
        return None
    least, _ = moe_math.experts_least_seconds(
        sparse_config(cfg), c["moe_experts_hit"], c["moe_pairs"],
        record.context.peaks)
    return 100.0 * least / (share / 100.0 * record.trace["busy_s"])
