"""The whole step's share of the chip's peak in a cell that trains one
chip's share of a routed model under a layer plan: the FLOPs the forward
and backward passes REQUIRE of this chip for the window's steps (q/k/v/o
and router of every layer, the visible keys by layer type, the head over
the vocabulary's slice: exact functions of the shapes; the pairs on the
experts held here: the steps' own count) over the window's seconds, over
the bf16 peak. No recomputation counted (benchmark/lib/train_plan_math.py)."""
from benchmark.lib import train_plan_math


def read(record):
    c, ctx = record.counters, record.context
    if "moe_pairs_held" not in c:
        return None
    flops = train_plan_math.step_flops(
        ctx.config, c["seq_len"], c["sequences"], c["moe_pairs_held"])
    return 100.0 * flops / c["elapsed_s"] / (
        c["chips"] * ctx.peaks["bf16_flops_per_s"])
