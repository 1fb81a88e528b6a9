"""The named scopes of a trained layer plan's step (PR 47:
`attention_window` and `attention_full` around a layer's attention kernel
inside `attention`; `moe` inside `ffn`, and inside it what
`llama.routed_ffn_load` writes: `router`, `dispatch`, `experts`,
`combine`), made known to `program_trace` as `moe_scopes` makes the
routed-expert scopes known, and for the same reason: `program_trace.SCOPES`
is a literal in a file that only a `benchmark` PR may edit. The driver of
a cell whose model trains under a layer plan calls `register()` when it is
imported; cells of other drivers see the set as it was. With the inner
scopes registered `attention` and `ffn` name only what lies outside them
(the projections, norms and residual adds), which is why this cell is not
listed under `train_attention_share` and `train_ffn_share`. A `benchmark` PR
should move the names into the literal and delete this file.
"""
from __future__ import annotations

from . import moe_scopes, program_trace

WINDOW = "attention_window"
FULL = "attention_full"
MOE = (moe_scopes.MOE, *moe_scopes.INNER)
MOE_OVERHEAD = ("router", "dispatch", "combine")
EXPERTS = "experts"


def register() -> None:
    moe_scopes.register()
    program_trace.SCOPES = program_trace.SCOPES | {WINDOW, FULL}
