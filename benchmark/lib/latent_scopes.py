"""The named scopes of a latent-attention tick (PR 41: `latent_q` and
`latent_kv` inside `qkv` and around the absorption, `paged_attention_latent`
(the launches) inside `paged_attention`, `latent_out` around Wkvb's value
half and Wo), made known to
`program_trace` as `laguna_scopes` makes a layer plan's known, and for the
same reason: `program_trace.SCOPES` is a literal in a file that only a
`benchmark` PR may edit. The driver of a cell whose model has latent layers
calls `register()` when it is imported; cells of other drivers see the set
as it was. The new scopes are the innermost of their operations, so with
them registered `tick_attention_share` (which names the outer scopes) no
longer counts what lies under them: `ATTENTION` here is the whole
sub-block. A `benchmark` PR should move the names into the literal and
delete this file.
"""
from __future__ import annotations

from . import laguna_scopes, program_trace

Q = "latent_q"
KV = "latent_kv"
READ = "paged_attention_latent"
OUT = "latent_out"
LATENT = (Q, KV, READ, OUT)
ATTENTION = ("qkv", "paged_attention", "attn_out", *LATENT)
MOE = laguna_scopes.MOE


def register() -> None:
    laguna_scopes.register()        # moe, its inner scopes, shared_expert
    program_trace.SCOPES = program_trace.SCOPES | set(LATENT)
