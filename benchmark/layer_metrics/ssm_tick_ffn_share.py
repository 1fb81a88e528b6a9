"""`tick_ffn_share` of a tick of the state-space model, whose every layer
of either kind is followed by a dense SwiGLU (40 of them, two fifths of
the tick's weight floor with the tied head), its output through
`llama.residual`'s scale (PR 56): the share of device busy time that is
self time under `ffn`, by the accepted reader; an entry of its own because
that reader moves `gap_p90_ms`, which this cell does not report."""
from benchmark.layer_metrics import tick_ffn_share

read = tick_ffn_share.read
