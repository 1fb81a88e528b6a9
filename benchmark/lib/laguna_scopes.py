"""The named scopes of a layer plan's tick (PR 34: `paged_attention_full`
and `paged_attention_window` inside `paged_attention`, `attn_gate`, and
`shared_expert` inside `moe`), made known to `program_trace` as
`moe_scopes` makes the routed-expert scopes known, and for the same reason:
`program_trace.SCOPES` is a literal in a file that only a `benchmark` PR
may edit. The driver of a cell whose model has a layer plan calls
`register()` when it is imported; cells of other drivers see the set as it
was. The new scopes are the innermost of their operations, so with them
registered `tick_attention_share` and `tick_moe_share` (which name the
outer scopes) no longer count what lies under them: `ATTENTION` and `MOE`
here are the whole sub-blocks. A `benchmark` PR should move the names into
the literal and delete this file.
"""
from __future__ import annotations

from . import moe_scopes, program_trace

FULL = "paged_attention_full"
WINDOW = "paged_attention_window"
GATE = "attn_gate"
SHARED = "shared_expert"
ATTENTION = ("qkv", "paged_attention", FULL, WINDOW, GATE, "attn_out")
MOE = (moe_scopes.MOE, *moe_scopes.INNER, SHARED)


def register() -> None:
    moe_scopes.register()
    program_trace.SCOPES = program_trace.SCOPES | {FULL, WINDOW, GATE,
                                                   SHARED}
