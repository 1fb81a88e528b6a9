"""`tick_attention_share` of a tick whose attention layers have heads of 64
values lying in 128 lanes of their page beside the kernel (PR 56: the
whole-page walks instead of the BlockSpec walk, which took 72 % of this
cell's tick): the share of device busy time that is self time under `qkv`,
`paged_attention` and `attn_out`, by the accepted reader; an entry of its
own because that reader moves `gap_p90_ms`, which the cell of the
state-space model does not report."""
from benchmark.layer_metrics import tick_attention_share

read = tick_attention_share.read
