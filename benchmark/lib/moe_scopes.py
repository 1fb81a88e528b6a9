"""The named scopes of the routed-expert tick (PR 27: `moe` and inside it
`router`, `dispatch`, `experts`, `combine`), made known to
`program_trace`.

`program_trace.SCOPES` is a literal in a file that only a `benchmark` PR
may edit, and `scope_of` finds no scope outside it: an operation under
`layers/.../moe/experts` would count as `layers`. A driver whose program
writes these scopes calls `register()` when it is imported, before any
reader loads a trace; cells of other drivers see the set as it was. A
`benchmark` PR should move the five names into the literal and delete this
file.
"""
from __future__ import annotations

from . import program_trace

MOE = "moe"
INNER = ("router", "dispatch", "experts", "combine")


def register() -> None:
    program_trace.SCOPES = program_trace.SCOPES | {MOE, *INNER}
