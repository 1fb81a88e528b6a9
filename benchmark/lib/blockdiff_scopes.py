"""The named scope of the block-diffusion tick (PR 32: `unmask`, inside
`sample`: softmax, confidence, transfer), made known to `program_trace`
as `moe_scopes` makes the routed-expert scopes known, and for the same
reason: `program_trace.SCOPES` is a literal in a file that only a
`benchmark` PR may edit. The driver of a block-diffusion cell calls
`register()` when it is imported; cells of other drivers see the set as it
was. A `benchmark` PR should move the name into the literal and delete
this file.
"""
from __future__ import annotations

from . import program_trace

UNMASK = "unmask"


def register() -> None:
    program_trace.SCOPES = program_trace.SCOPES | {UNMASK}
