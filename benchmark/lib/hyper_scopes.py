"""The named scopes of a tick whose residual stream is lanes mixed by
hyper-connections (PR 54: `hyper` around a sub-block's mixing, with
`hyper_coeff` (the row norm, the projection, the Sinkhorn iteration),
`hyper_pre` (the lanes' sum a sub-block sees) and `hyper_post` (the lanes'
update behind it) inside), made known to `program_trace` as `latent_scopes`
makes a latent tick's known, and for the same reason: `program_trace.SCOPES`
is a literal in a file that only a `benchmark` PR may edit. The driver of a
cell whose model has such a stream calls `register()` when it is imported;
cells of other drivers see the set as it was. The three inner scopes are
the innermost of their operations (`hyper_post` lies inside `attn_out`,
`moe` or `ffn`, where the caller adds the sub-block's output), so with
them registered those outer shares no longer count the lanes' update. A
`benchmark` PR should move the names into the literal and delete this file.
"""
from __future__ import annotations

from . import latent_scopes, program_trace

ALL = "hyper"
COEFF = "hyper_coeff"
PRE = "hyper_pre"
POST = "hyper_post"
HYPER = (ALL, COEFF, PRE, POST)
ATTENTION = latent_scopes.ATTENTION
MOE = latent_scopes.MOE


def register() -> None:
    latent_scopes.register()
    program_trace.SCOPES = program_trace.SCOPES | set(HYPER)
