"""`tick_head_sample_share` of a tick of the state-space model, whose head
is the embedding's own matrix (tied, `llama.head_logits`, the logits
divided; PR 56): the share of device busy time that is self time under
`head` and `sample`, by the accepted reader; an entry of its own because
that reader moves `gap_p90_ms`, which this cell does not report."""
from benchmark.layer_metrics import tick_head_sample_share

read = tick_head_sample_share.read
