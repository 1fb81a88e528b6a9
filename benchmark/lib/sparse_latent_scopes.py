"""The named scopes of a tick whose latent layers are of two kinds, one
under a learned sparse index and one under a window (PR 43, dots3-note):
`index_q` (the index queries and the heads' weights) and `index_k` (the
index key, its LayerNorm and rope) inside `qkv`; `index_scores` (every
visible key scored) and `index_select` (the exact selection and its
positions); `paged_attention_sparse` (the read over the selected cache
rows) and `paged_attention_latent_window` (the windowed walks) inside
`paged_attention`, beside `paged_attention_latent` (the dense walk of the
rows that had no selection to make). Made known to `program_trace` as
`latent_scopes` makes Kimi's four known, which this registers too, and for
the same reason: `program_trace.SCOPES` is a literal in a file that only a
`benchmark` PR may edit. The new scopes are the innermost of their
operations. A `benchmark` PR should move the names into the literal and
delete this file.
"""
from __future__ import annotations

from . import laguna_scopes, latent_scopes, program_trace

INDEX_Q = "index_q"
INDEX_K = "index_k"
SCORES = "index_scores"
SELECT = "index_select"
SPARSE = "paged_attention_sparse"
WINDOW = "paged_attention_latent_window"
INDEX = (INDEX_Q, INDEX_K, SCORES, SELECT)
NEW = (*INDEX, SPARSE, WINDOW)
ATTENTION = (*latent_scopes.ATTENTION, laguna_scopes.GATE, *NEW)
MOE = latent_scopes.MOE


def register() -> None:
    latent_scopes.register()    # Kimi's four, Laguna's gate, the experts'
    program_trace.SCOPES = program_trace.SCOPES | set(NEW)
